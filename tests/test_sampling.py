"""ops/sampling.py's per-row sampler: exact against the implementation it
replaced (kept verbatim below), against the static pipeline, and its
greedy short cut.

The per-row sampler skips the vocabulary-wide filter and draw for a batch
without a sampling row (one ``lax.cond``), and when a row samples it
carries values through its sorts instead of gathering them afterwards.
Neither may change a token or a log-probability: the replay, tenancy,
migration and speculative suites pin sampled streams bit for bit.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dla_tpu.generation.engine import GenerationConfig
from dla_tpu.models.config import get_model_config
from dla_tpu.models.transformer import Transformer
from dla_tpu.ops import sampling
from dla_tpu.ops.sampling import (NEG_INF, filter_logits_per_row,
                                  sample_token, sample_token_block,
                                  sample_token_per_row)
from dla_tpu.serving import SamplingParams, ServingConfig, ServingEngine
from dla_tpu.serving import server as serving_server


# ------------------------------------------------- the replaced sampler
# Verbatim from ops/sampling.py before the payload sorts and the cond:
# two argsorts, two take_along_axis gathers, filter and draw for every
# batch. The oracle of the bit-identity cases; do not tidy it.

def _old_filter_logits_per_row(logits, temps, top_ps, top_ks):
    x = logits.astype(jnp.float32) / jnp.maximum(temps, 1e-6)[:, None]
    v = x.shape[-1]
    sort_idx = jnp.argsort(x, axis=-1)[..., ::-1]
    sorted_x = jnp.take_along_axis(x, sort_idx, axis=-1)
    ranks = jnp.arange(v, dtype=jnp.int32)[None, :]
    keep_k = (ranks < top_ks[:, None]) | (top_ks[:, None] <= 0)
    sorted_probs = jax.nn.softmax(jnp.where(keep_k, sorted_x, NEG_INF),
                                  axis=-1)
    cum = jnp.cumsum(sorted_probs, axis=-1)
    keep_p = (cum - sorted_probs) < top_ps[:, None]
    keep_sorted = keep_p & keep_k
    inv = jnp.argsort(sort_idx, axis=-1)
    keep = jnp.take_along_axis(keep_sorted, inv, axis=-1)
    return jnp.where(keep, x, NEG_INF)


def _old_sample_token_per_row(seeds, positions, logits, temps, top_ps,
                              top_ks):
    raw = logits.astype(jnp.float32)
    logp_all = jax.nn.log_softmax(raw, axis=-1)
    filt = _old_filter_logits_per_row(raw, temps, top_ps, top_ks)

    def draw(seed, position, row):
        key = jax.random.fold_in(jax.random.PRNGKey(seed), position)
        return jax.random.categorical(key, row)

    sampled = jax.vmap(draw)(seeds, positions, filt)
    greedy = jnp.argmax(raw, axis=-1)
    tok = jnp.where(temps <= 0.0, greedy, sampled).astype(jnp.int32)
    logp = jnp.take_along_axis(logp_all, tok[:, None], axis=-1)[:, 0]
    return tok, logp


# ------------------------------------------------------------- the rows

#: (temperature, top_p, top_k) of each kind of row
KINDS = {
    "greedy": (0.0, 0.9, 5),
    "temperature": (0.7, 1.0, 0),
    "top_k": (1.0, 1.0, 7),
    "top_p": (1.3, 0.9, 0),
    "both": (0.8, 0.85, 40),
}
SHAPES = [(b, v) for v in (257, 32000) for b in (1, 3, 16)]


def _rows(b, first=0):
    """Per-row knobs cycling through KINDS from ``first``."""
    kinds = [list(KINDS.values())[(first + i) % len(KINDS)] for i in range(b)]
    temps, top_ps, top_ks = zip(*kinds)
    return (np.asarray(temps, np.float32), np.asarray(top_ps, np.float32),
            np.asarray(top_ks, np.int32))


def _tied_logits(rs, b, v, dtype=np.float32):
    """Random logits on a grid of 0.25, so every value has many equals,
    with ties planted where they decide something: the largest value
    twice (the arg-max and the head of the nucleus), and a run of equal
    values across the top-k cut."""
    x = (rs.randn(b, v) * 2.0).round(2)
    x = np.round(x * 4) / 4
    for row in x:
        order = np.argsort(-row, kind="stable")
        row[order[1]] = row[order[0]]
        row[order[4:11]] = row[order[6]]          # across k = 7
        row[order[36:45]] = row[order[39]]        # across k = 40
    return jnp.asarray(x, dtype)


def _seeds_positions(rs, b):
    return (rs.randint(0, 2 ** 32, (b,), dtype=np.uint64).astype(np.uint32),
            rs.randint(0, 2048, (b,)).astype(np.int32))


def _bits(x):
    return np.asarray(x, np.float32).view(np.int32)


# -------------------------------------- (a) against the replaced sampler

@pytest.mark.parametrize("b,v", SHAPES)
def test_filter_bit_identical_to_replaced(b, v):
    rs = np.random.RandomState(b * 1000 + v)
    old, new = jax.jit(_old_filter_logits_per_row), jax.jit(
        filter_logits_per_row)
    for trial in range(3):
        logits = _tied_logits(rs, b, v)
        knobs = _rows(b, first=trial + 1)
        np.testing.assert_array_equal(_bits(old(logits, *knobs)),
                                      _bits(new(logits, *knobs)))


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("b,v", SHAPES)
def test_sampler_bit_identical_to_replaced(b, v, dtype):
    rs = np.random.RandomState(b * 1000 + v + 1)
    old, new = jax.jit(_old_sample_token_per_row), jax.jit(
        sample_token_per_row)
    # b == 1: every kind in turn; else rows of mixed kinds
    for trial in range(len(KINDS) if b == 1 else 3):
        logits = _tied_logits(rs, b, v, jnp.dtype(dtype))
        knobs = _rows(b, first=trial)
        seeds, positions = _seeds_positions(rs, b)
        tok_old, lp_old = old(seeds, positions, logits, *knobs)
        tok_new, lp_new = new(seeds, positions, logits, *knobs)
        np.testing.assert_array_equal(np.asarray(tok_old),
                                      np.asarray(tok_new))
        np.testing.assert_array_equal(_bits(lp_old), _bits(lp_new))
        assert tok_new.dtype == jnp.int32 and lp_new.dtype == jnp.float32


def test_sampled_rows_do_draw():
    """The oracle comparison is not vacuous: sampling rows leave the
    arg-max, and a row's draw moves with its position."""
    rs = np.random.RandomState(5)
    logits = jnp.asarray(rs.randn(16, 257), jnp.float32)
    knobs = _rows(16, first=1)
    seeds, positions = _seeds_positions(rs, 16)
    tok, _ = sample_token_per_row(seeds, positions, logits, *knobs)
    tok2, _ = sample_token_per_row(seeds, positions + 1, logits, *knobs)
    greedy = np.asarray(jnp.argmax(logits, axis=-1))
    sampling_rows = knobs[0] > 0
    assert (np.asarray(tok) != greedy)[sampling_rows].sum() >= 4
    assert (np.asarray(tok) != np.asarray(tok2))[sampling_rows].sum() >= 4
    np.testing.assert_array_equal(np.asarray(tok)[~sampling_rows],
                                  greedy[~sampling_rows])


# ------------------------------------ (b) against the static pipeline

@pytest.mark.parametrize("kind", sorted(KINDS))
def test_per_row_equals_static_pipeline_row_by_row(kind):
    """Each row of a mixed batch equals ``sample_token`` with that row's
    knobs as static parameters and the row's own key (distinct logits:
    the static top-k keeps every tie at its cut-off, the per-row one
    exactly k)."""
    b, v = len(KINDS), 257
    rs = np.random.RandomState(11)
    logits = jnp.asarray(rs.permutation(b * v).reshape(b, v) / 97.0,
                         jnp.float32)
    knobs = _rows(b)
    seeds, positions = _seeds_positions(rs, b)
    tok, logp = sample_token_per_row(seeds, positions, logits, *knobs)
    i = list(KINDS).index(kind)
    temperature, top_p, top_k = KINDS[kind]
    key = jax.random.fold_in(jax.random.PRNGKey(seeds[i]), positions[i])
    want = sample_token(key, logits[i][None], temperature=temperature,
                        top_p=top_p, top_k=top_k,
                        do_sample=temperature > 0)[0]
    assert int(tok[i]) == int(want)
    assert float(logp[i]) == float(
        jax.nn.log_softmax(logits[i])[int(want)])
    if temperature > 0:
        static = sampling.top_p_mask(sampling.top_k_mask(
            sampling.apply_temperature(logits[i][None], temperature),
            top_k), top_p)
        ours = filter_logits_per_row(logits, *knobs)[i]
        np.testing.assert_array_equal(np.asarray(static[0] > NEG_INF / 2),
                                      np.asarray(ours > NEG_INF / 2))


# ---------------------------------------------- (c) the greedy short cut

def _eqns(jaxpr):
    """Every equation of a jaxpr, sub-jaxprs included."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _eqns(sub)


def _primitives(jaxpr):
    return {eqn.primitive.name for eqn in _eqns(jaxpr)}


def _sampler_cond(b=4, v=257):
    args = (np.zeros((b,), np.uint32), np.zeros((b,), np.int32),
            np.zeros((b, v), np.float32), *_rows(b))
    jaxpr = jax.make_jaxpr(sample_token_per_row)(*args).jaxpr
    conds = [e for e in jaxpr.eqns if e.primitive.name == "cond"]
    assert len(conds) == 1
    return jaxpr, conds[0]


def test_greedy_branch_holds_no_sort_and_no_draw():
    jaxpr, cond = _sampler_cond()
    greedy_branch, sampling_branch = cond.params["branches"]   # False, True
    heavy = {"sort", "cumsum", "random_bits", "threefry2x32", "gather",
             "exp"}
    assert not _primitives(greedy_branch.jaxpr) & heavy
    assert "sort" in _primitives(sampling_branch.jaxpr)
    # nothing vocabulary-wide is left outside the cond but the arg-max
    # and the log-softmax
    outside = {e.primitive.name for e in jaxpr.eqns}
    assert "sort" not in outside and "random_bits" not in outside


def test_sampling_branch_gathers_nothing_vocabulary_wide():
    """No [B, V] gather and one sort per direction: the replaced filter's
    two ``take_along_axis`` and its inverse ``argsort`` are gone."""
    _, cond = _sampler_cond()
    eqns = list(_eqns(cond.params["branches"][1].jaxpr))
    assert [e.primitive.name for e in eqns].count("sort") == 2
    for eqn in eqns:
        if eqn.primitive.name == "gather":
            assert eqn.outvars[0].aval.size < 257, eqn


@pytest.mark.parametrize("b,v", SHAPES)
def test_all_greedy_batch_is_argmax(b, v):
    rs = np.random.RandomState(b + v)
    logits = _tied_logits(rs, b, v)
    seeds, positions = _seeds_positions(rs, b)
    # top_p / top_k of a greedy row are ignored; a temperature under 0
    # is greedy too
    temps = np.where(np.arange(b) % 2 == 0, 0.0, -1.0).astype(np.float32)
    _, top_ps, top_ks = _rows(b, first=1)
    tok, logp = jax.jit(sample_token_per_row)(
        seeds, positions, logits, temps, top_ps, top_ks)
    want = np.asarray(jnp.argmax(logits, axis=-1))
    np.testing.assert_array_equal(np.asarray(tok), want)
    np.testing.assert_array_equal(_bits(logp), _bits(
        jax.nn.log_softmax(logits, axis=-1)[np.arange(b), want]))


# ------------------------- (d) a stale temperature on a slot not running

MAX_NEW = 12


@pytest.fixture(scope="module")
def model_and_params():
    model = Transformer(get_model_config("tiny"))
    return model, model.init(jax.random.key(7))


@pytest.mark.parametrize("speculative", [False, True],
                         ids=["plain", "speculative"])
def test_stale_temperature_of_a_free_slot_keeps_steps_greedy(
        model_and_params, monkeypatch, speculative):
    """A sampled request ends and leaves its temperature in its slot's
    row; the greedy request still running beside it must take the
    arg-max branch from then on: the decode program zeroes the rows of
    slots that are not running, and ``decode_steps_sampled`` counts the
    steps of the sampled request alone."""
    # per execution of a step program's sampler (first tokens go through
    # the engine's own jitted copy): did any row sample?
    seen = []

    def spy(inner):
        def wrapped(seeds, positions, logits, temps, top_ps, top_ks):
            jax.debug.callback(
                lambda t: seen.append(bool((t > 0).any())), temps)
            return inner(seeds, positions, logits, temps, top_ps, top_ks)
        return wrapped
    if speculative:     # the verify program's sampler decides the tokens
        monkeypatch.setattr(serving_server, "sample_token_block",
                            spy(sample_token_block))
    else:
        monkeypatch.setattr(serving_server, "sample_token_per_row",
                            spy(sample_token_per_row))
    model, params = model_and_params
    gen = GenerationConfig(max_new_tokens=MAX_NEW, do_sample=False,
                           eos_token_id=-1, pad_token_id=0)
    extra = ({"speculative": {"enabled": True, "k": 2, "draft": "self"}}
             if speculative else {})
    eng = ServingEngine(model, params, gen, ServingConfig(
        page_size=4, num_pages=32, num_slots=2, max_model_len=32, **extra))
    # admission takes one request a step: the sampled one needs more
    # tokens than its first step can commit (1 + k + 1), so that the
    # greedy one is admitted beside it and not into its freed slot
    sampled = eng.submit([5, 6, 7, 8], 6, sampling=SamplingParams(
        temperature=0.9, top_p=0.9, seed=3))
    greedy = eng.submit([9, 10, 11, 12], MAX_NEW)
    held = []        # per decode phase: was the sampled request running?
    name = "_spec_decode_step" if speculative else "_decode_step"
    decode = getattr(eng, name)

    def watched():
        held.append(any(r.rid == sampled
                        for r in eng.scheduler.running.values()))
        return decode()
    monkeypatch.setattr(eng, name, watched)
    eng.run_until_drained(max_steps=200)
    jax.effects_barrier()
    steps_with_sampled = sum(held)
    stale_slot = int(np.flatnonzero(eng.samp_temp > 0)[0])
    eng.close()

    assert len(eng.result(sampled).generated) == 6
    assert len(eng.result(greedy).generated) == MAX_NEW
    # the row still holds the finished request's temperature ...
    assert eng.samp_temp[stale_slot] == np.float32(0.9)
    # ... the host counted the sampled request's decode steps alone ...
    steps = int(eng.metrics.decode_steps.value)
    assert eng.metrics.decode_steps_sampled.value == steps_with_sampled
    assert 0 < steps_with_sampled < steps
    assert eng.metrics.snapshot()["serving/decode_steps_sampled"] == (
        steps_with_sampled)
    # ... and so did the device: the sampler saw a positive temperature
    # in exactly those steps, the first ones
    assert len(held) == steps and seen == held
    assert held == sorted(held, reverse=True)
    assert eng.decode_compiles == (0 if speculative else 1)


# ------------------------------------------------------- (e) block form

@pytest.mark.parametrize("all_greedy", [False, True],
                         ids=["mixed", "all_greedy"])
def test_block_equals_successive_single_calls(all_greedy):
    b, g, v = 3, 4, 257
    rs = np.random.RandomState(17)
    logits = jnp.stack([_tied_logits(rs, b, v) for _ in range(g)], axis=1)
    temps, top_ps, top_ks = _rows(b, first=3)
    if all_greedy:
        temps = np.zeros_like(temps)
    seeds, positions = _seeds_positions(rs, b)
    toks, logps = jax.jit(sample_token_block)(
        seeds, positions, logits, temps, top_ps, top_ks)
    assert toks.shape == logps.shape == (b, g)
    single = jax.jit(sample_token_per_row)
    for j in range(g):
        tok, logp = single(seeds, positions + j, logits[:, j], temps,
                           top_ps, top_ks)
        np.testing.assert_array_equal(np.asarray(toks[:, j]),
                                      np.asarray(tok))
        np.testing.assert_array_equal(_bits(logps[:, j]), _bits(logp))
