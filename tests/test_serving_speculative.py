"""Speculative serving tests: blockwise draft/verify speculation on the
paged KV pool must be an invisible optimisation. Greedy AND
per-request-seeded sampled outputs are bit-identical to the
non-speculative engine (emitted tokens are the target's own samples —
acceptance only decides how many commit per round), the draft and
verify compiles pin at one per engine build, eviction-recompute is
unchanged, and the spec counters stay monotone across supervisor
restarts."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from dla_tpu.generation.engine import GenerationConfig, build_generate_fn
from dla_tpu.generation.speculative import build_speculative_generate_fn
from dla_tpu.models.config import get_model_config
from dla_tpu.models.transformer import Transformer
from dla_tpu.serving import (
    RequestState,
    SamplingParams,
    ServingConfig,
    ServingEngine,
    Supervisor,
    SupervisorConfig,
)

MAX_NEW = 8
SPEC = {"enabled": True, "k": 3, "draft": "self"}


@pytest.fixture(scope="module")
def model_and_params():
    cfg = get_model_config("tiny")
    model = Transformer(cfg)
    return model, model.init(jax.random.key(7))


def _prompts(n=4, seed=3):
    rs = np.random.RandomState(seed)
    return [list(rs.randint(3, 500, (length,)))
            for length in rs.randint(4, 10, (n,))]


def _run(model, params, gen, prompts, sampling=None, **cfg_kw):
    """Run prompts to completion on a fresh engine; returns the engine
    (for counter assertions) and the per-prompt Request results."""
    kw = dict(page_size=4, num_pages=32, num_slots=2, max_model_len=32)
    kw.update(cfg_kw)
    eng = ServingEngine(model, params, gen, ServingConfig(**kw))
    sampling = sampling or [None] * len(prompts)
    rids = [eng.submit(p, MAX_NEW, sampling=sp)
            for p, sp in zip(prompts, sampling)]
    results = eng.run_until_drained(max_steps=500)
    eng.scheduler.assert_consistent()
    return eng, [results[r] for r in rids]


@pytest.mark.parametrize("draft", ["self", "int8"])
def test_spec_greedy_bit_identical_and_compiles_pinned(
        model_and_params, draft):
    """THE parity pin: the speculative engine's greedy stream is
    byte-for-byte the non-speculative engine's (tokens AND logprobs),
    for both the int8 self-draft and the full-precision sanity draft;
    draft/verify each compile exactly once."""
    model, params = model_and_params
    gen = GenerationConfig(max_new_tokens=MAX_NEW, do_sample=False,
                           eos_token_id=2, pad_token_id=0)
    prompts = _prompts()
    _, base = _run(model, params, gen, prompts)
    eng, spec = _run(model, params, gen, prompts,
                     speculative={"enabled": True, "k": 3, "draft": draft})
    for i, (b, s) in enumerate(zip(base, spec)):
        assert s.state is RequestState.FINISHED
        assert s.generated == b.generated, f"prompt {i} diverged"
        np.testing.assert_allclose(s.generated_logprobs,
                                   b.generated_logprobs, atol=1e-5, rtol=0)
    assert eng.spec_draft_compiles == 1
    assert eng.spec_verify_compiles == 1
    snap = eng.metrics.snapshot()
    assert snap["serving/spec/rounds"] > 0
    assert snap["serving/spec/proposed_tokens"] > 0
    assert 0.0 < snap["serving/spec/acceptance_rate"] <= 1.0
    if draft == "self":
        # self-draft proposes the target's own choices: full acceptance
        assert snap["serving/spec/acceptance_rate"] == 1.0
        assert snap["serving/spec/rollbacks"] == 0
    assert eng.cache.allocator.used_count == 0


def test_spec_sampled_matches_nonspec_per_request_seeds(model_and_params):
    """Sampled streams are a pure function of (seed, token index): the
    speculative engine reproduces the non-speculative engine bit-for-bit
    under per-request seeded sampling, for both draft kinds — rejected
    draft tails must never perturb the committed stream."""
    model, params = model_and_params
    gen = GenerationConfig(max_new_tokens=MAX_NEW, do_sample=True,
                           temperature=0.9, top_p=0.9, top_k=8,
                           eos_token_id=2, pad_token_id=0)
    prompts = _prompts(seed=5)
    sampling = [SamplingParams(temperature=0.9, top_p=0.9, top_k=8,
                               seed=70 + i, do_sample=True)
                for i in range(len(prompts))]
    _, base = _run(model, params, gen, prompts, sampling=sampling)
    for draft in ("self", "int8"):
        _, spec = _run(
            model, params, gen, prompts, sampling=sampling,
            speculative={"enabled": True, "k": 3, "draft": draft})
        for i, (b, s) in enumerate(zip(base, spec)):
            assert s.generated == b.generated, (draft, i)
            np.testing.assert_allclose(
                s.generated_logprobs, b.generated_logprobs,
                atol=1e-5, rtol=0)


def test_spec_matches_fixed_shape_speculative_engine(model_and_params):
    """Cross-engine pin: the paged speculative engine and the
    fixed-shape speculative generator (same target, self-draft, greedy)
    land on identical tokens — both must equal plain greedy decode."""
    model, params = model_and_params
    gen = GenerationConfig(max_new_tokens=MAX_NEW, do_sample=False,
                           eos_token_id=2, pad_token_id=0)
    prompts = _prompts(seed=7)
    width = max(len(p) for p in prompts)
    ids = np.zeros((len(prompts), width), np.int32)
    mask = np.zeros_like(ids)
    for i, p in enumerate(prompts):
        ids[i, :len(p)] = p
        mask[i, :len(p)] = 1
    fn = jax.jit(build_speculative_generate_fn(model, model, gen, gamma=4))
    out = fn(params, params, jnp.asarray(ids), jnp.asarray(mask),
             jax.random.key(0))
    resp = np.asarray(out["response_tokens"])
    rmask = np.asarray(out["response_mask"])
    ref = [[int(t) for t, m in zip(resp[i], rmask[i]) if m]
           for i in range(len(prompts))]
    _, spec = _run(model, params, gen, prompts, speculative=SPEC)
    for i, (r, s) in enumerate(zip(ref, spec)):
        assert s.generated == r, f"prompt {i} diverged"


def test_spec_eviction_recomputes_identically(model_and_params):
    """A pool sized to force mid-decode preemption under speculation:
    the evicted request re-prefills and still lands on the greedy
    reference — rollback bookkeeping must not corrupt recompute."""
    model, params = model_and_params
    rs = np.random.RandomState(11)
    use = [list(rs.randint(3, 500, (4,))) for _ in range(2)]
    gen = GenerationConfig(max_new_tokens=MAX_NEW, do_sample=False,
                           eos_token_id=2, pad_token_id=0)
    fn = jax.jit(build_generate_fn(model, gen))
    ids = np.asarray(use, np.int32)
    out = fn(params, jnp.asarray(ids), jnp.ones_like(jnp.asarray(ids)),
             jax.random.key(0))
    resp = np.asarray(out["response_tokens"])
    rmask = np.asarray(out["response_mask"])
    want = [[int(t) for t, m in zip(resp[i], rmask[i]) if m]
            for i in range(len(use))]
    # capacity 9 pages, one admission a step: the first request has
    # grown to its whole window (6 pages) when the second is admitted at
    # 3, and the second's first round wants a fourth -> it is preempted
    # mid-decode with one token out and recomputes behind the first
    eng = ServingEngine(model, params, gen,
                        ServingConfig(page_size=2, num_pages=10,
                                      num_slots=2, max_model_len=12,
                                      speculative=SPEC))
    rids = [eng.submit(p, MAX_NEW) for p in use]
    results = eng.run_until_drained(max_steps=500)
    assert eng.metrics.preemptions.value >= 1, (
        "config was meant to force at least one preemption")
    for rid, expect in zip(rids, want):
        req = results[rid]
        assert req.generated == expect, (
            f"eviction recompute diverged (evictions={req.evictions})")
    assert eng.cache.allocator.used_count == 0
    eng.scheduler.assert_consistent()


def test_spec_counters_monotone_across_supervisor_restart(
        model_and_params):
    """Satellite pin: serving/spec/* counters never reset across a
    supervisor rebuild — the final engine's panel equals the SUM of
    every build's own round accounting, and the acceptance-rate gauge
    re-seeds from the carried totals."""
    model, params = model_and_params
    gen = GenerationConfig(max_new_tokens=MAX_NEW, do_sample=False,
                           eos_token_id=-1, pad_token_id=0)
    prompts = _prompts(seed=9)
    engines = []

    def factory():
        eng = ServingEngine(model, params, gen, ServingConfig(
            page_size=4, num_pages=32, num_slots=2, max_model_len=32,
            speculative=SPEC,
            fault_plan="engine_step=3:device_error"))
        engines.append(eng)
        return eng

    sup = Supervisor(factory, SupervisorConfig(
        watchdog_timeout_s=0.05, watchdog_poll_s=0.01, max_restarts=2))
    rids = [sup.submit(p, MAX_NEW) for p in prompts]
    results = sup.run(max_steps=500)
    sup.close()
    assert sup.restarts == 1 and len(engines) == 2
    for rid in rids:
        assert results[rid].state is RequestState.FINISHED
    # the pre-restart engine did at least one spec round before dying
    assert engines[0]._spec_stats["rounds"] > 0
    final = engines[-1]
    for field, ctr in (("rounds", final.metrics.spec_rounds),
                      ("proposed", final.metrics.spec_proposed),
                      ("accepted", final.metrics.spec_accepted),
                      ("rollbacks", final.metrics.spec_rollbacks)):
        total = sum(e._spec_stats[field] for e in engines)
        assert ctr.value == total, (field, ctr.value, total)
        assert ctr.value >= engines[0]._spec_stats[field]  # monotone
    snap = final.metrics.snapshot()
    assert snap["serving/spec/acceptance_rate"] == 1.0  # self-draft
    assert [e.spec_draft_compiles for e in engines] == [1, 1]
    assert [e.spec_verify_compiles for e in engines] == [1, 1]


def test_spec_config_validation(model_and_params):
    model, params = model_and_params
    gen = GenerationConfig(max_new_tokens=4, do_sample=False,
                           eos_token_id=-1, pad_token_id=0)
    base = dict(page_size=4, num_pages=32, num_slots=2, max_model_len=32)
    for bad in ({"enabled": True, "k": 0},
                {"enabled": True, "draft": "bogus"},
                {"enabled": True, "gamma": 4}):
        with pytest.raises(ValueError):
            ServingEngine(model, params, gen,
                          ServingConfig(speculative=bad, **base))
    # disabled block is inert: no draft tree, no spec executables
    eng = ServingEngine(model, params, gen, ServingConfig(
        speculative={"enabled": False, "k": 9}, **base))
    assert eng.draft_params is None
    assert eng.spec_draft_compiles == 0
