"""A decoder of Mamba-1 layers (dt, B and C RMS-normed) beside plain
multi-query attention layers that each keep rows of their own
(``model_type: jamba``, the ``tiny-jamba`` preset), served by the paged
``ServingEngine`` and held, on logits, to the benchmark's plain float32
reference ``perfbench/reference/jamba_block.py``.

Tolerances. Float32 against float32 differ by the order of the sums alone
(a chunked scan against a sequential one, a softmax over cached + fresh
columns against one over the whole row): 1e-4 on a log-probability of size
6 is a hundred times what was read (2e-6) and far under the smallest fault
this file plants (the inner norms left out: 0.05 and more; another layer's
rows read: 1e-2 and more). A bfloat16 engine (bfloat16 pages and
activations, float32 state and weights) keeps 8 bits: read 0.03 at worst
over these sequences, three hundred times the float32 limit, which it
fails, and is itself held to 0.08.
"""
import dataclasses
import hashlib
import importlib.util
import json
import re
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dla_tpu.generation.engine import GenerationConfig
from dla_tpu.models.config import (
    LayerSpec,
    ModelConfig,
    get_model_config,
    jamba_layers,
)
from dla_tpu.models import hybrid
from dla_tpu.models.hf_import import hf_config_to_model_config
from dla_tpu.models.hybrid import Run, layer_runs
from dla_tpu.models.transformer import Transformer
from dla_tpu.ops.attention import block_decode_attention
from dla_tpu.serving import ServingConfig, ServingEngine
from dla_tpu.telemetry.xla_introspect import compiled_scopes
from dla_tpu.utils.profiling import DEVICE_SCOPES, SPANS

ROOT = Path(__file__).resolve().parents[1]
CATALOG = Path("/opt/skills/guides/model-configs/architectures.jsonl")
TOL_F32 = 1e-4
TOL_BF16 = 0.08
PAGE, CHUNK = 4, 8

#: the tiny model's Hugging Face keys, as the reference reads them
HF = dict(num_hidden_layers=12, hidden_size=64, num_attention_heads=4,
          num_key_value_heads=1, rms_norm_eps=1e-6, attn_layer_period=6,
          attn_layer_offset=2)


def _load(path, name):
    spec = importlib.util.spec_from_file_location(name, ROOT / path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.fixture(scope="module")
def ref():
    return _load("perfbench/reference/jamba_block.py", "jamba_ref")


def _moved(params, key=1, scale=0.05):
    """Every leaf moved off its initial value, so norms and biases count;
    the inner norms' weights are drawn well away from 1 (0.4 to 1.9) so
    that a missing norm shows."""
    leaves, tree = jax.tree_util.tree_flatten_with_path(params)
    keys = jax.random.split(jax.random.key(key), len(leaves))
    out = []
    for (path, leaf), k in zip(leaves, keys):
        name = str(path[-1])
        if re.search(r"(dt|b|c)_norm", name):
            out.append(jax.random.uniform(k, leaf.shape, leaf.dtype,
                                          0.4, 1.9))
        else:
            out.append(leaf + scale * jax.random.normal(k, leaf.shape))
    return jax.tree_util.tree_unflatten(tree, out)


@pytest.fixture(scope="module")
def model_and_params():
    model = Transformer(get_model_config("tiny-jamba"))
    return model, _moved(model.init(jax.random.key(0)))


def _reference_logprobs(ref, params, tokens, hf=HF):
    """[T, V] log-probabilities of the reference's forward over
    ``tokens``: row t is the distribution of token t + 1."""
    with jax.default_matmul_precision("highest"):
        hidden = ref.hidden_states(
            np.asarray(tokens), params["embed"]["embedding"],
            lambda l: ref.take_layer(params["layers"], l),
            params["final_norm"], hf)
        logits = ref.logits(hidden, params["embed"]["embedding"])
    return np.asarray(jax.nn.log_softmax(logits, axis=-1))


def _engine(model, params, **kw):
    cfg = dict(page_size=PAGE, num_pages=96, num_slots=3, max_model_len=64,
               prefill_chunk=CHUNK)
    cfg.update(kw)
    gen = GenerationConfig(max_new_tokens=48, do_sample=False,
                           eos_token_id=-1, pad_token_id=0)
    return ServingEngine(model, params, gen, ServingConfig(**cfg))


def _prompts(lengths, seed=0):
    rs = np.random.RandomState(seed)
    return [[int(t) for t in rs.randint(3, 500, (n,))] for n in lengths]


def _gaps(ref, params, prompt, result, hf=HF):
    """(largest |log-probability - reference's| over the answer's chosen
    tokens, largest gap by which a chosen token trails the reference's
    best) of one finished request."""
    seq = prompt + list(result.generated)
    logp = _reference_logprobs(ref, params, seq[:-1], hf)[len(prompt) - 1:]
    chosen = np.asarray(result.generated)
    picked = logp[np.arange(len(chosen)), chosen]
    return (float(np.abs(picked - np.asarray(
        result.generated_logprobs)).max()),
        float((logp.max(-1) - picked).max()))


def _drain(eng):
    while eng.has_work():
        eng.step()
        eng.scheduler.assert_consistent()


# ------------------------------------------------------- the model alone

def test_spec_runs_and_cache_spec():
    spec = jamba_layers(28, 14, 7)
    assert [l for l, s in enumerate(spec) if s.mixer == "attention"] \
        == [7, 21]
    assert spec[7] == LayerSpec("attention", "paged") \
        and spec[0] == LayerSpec("ssm", "state")
    # a period over MAX_PERIOD is not looked for: five runs
    assert layer_runs(spec) == (Run(0, 1, 7), Run(7, 1, 1), Run(8, 1, 13),
                                Run(21, 1, 1), Run(22, 1, 6))
    model = Transformer(get_model_config("tiny-jamba"))
    assert layer_runs(model.cfg.layer_spec) == (
        Run(0, 1, 2), Run(2, 1, 1), Run(3, 1, 5), Run(8, 1, 1),
        Run(9, 1, 3))
    # two paged layers with a [16] key and a [16] value a token each, at
    # indices 0 and 1 of the paged arrays; ten states
    assert [(a.kind, a.layers, a.shape) for a in model.cache_spec()] == [
        ("paged", 2, (16,)), ("paged", 2, (16,)),
        ("state", 10, (4, 128)), ("state", 10, (3, 128))]
    assert model.hybrid._cache_index[2] == (0, 1)
    assert model.hybrid._cache_index[8] == (1, 1)
    assert model.hybrid.shared_readers == 1


def test_apply_matches_the_reference(model_and_params, ref):
    model, params = model_and_params
    tokens = _prompts([40])[0]
    with jax.default_matmul_precision("highest"):
        logits = jax.jit(model.apply)(params, jnp.asarray(tokens)[None])[0]
    got = np.asarray(jax.nn.log_softmax(logits, axis=-1))
    assert np.abs(got - _reference_logprobs(ref, params, tokens)).max() \
        < TOL_F32
    # right padding moves nothing before it (nor the state under it)
    padded = jnp.asarray(tokens + [0] * 8)[None]
    mask = jnp.asarray([1] * 40 + [0] * 8)[None]
    with jax.default_matmul_precision("highest"):
        again = jax.jit(model.apply)(params, padded, mask)[0, :40]
    assert np.abs(np.asarray(again) - np.asarray(logits)).max() < TOL_F32


# --------------------------------------------------- through the engine

@pytest.fixture
def blocks_of(monkeypatch):
    """Steer the chunk program's attention walk to blocks of so many
    cached columns: a test's stand-in for a long window (the program
    works the size out from the shapes, and no option reaches it)."""
    def set_columns(columns):
        monkeypatch.setattr(hybrid, "CHUNK_ATTENTION_BLOCK_COLUMNS", columns)
    return set_columns


@pytest.mark.parametrize("block_columns", [None, CHUNK],
                         ids=["one_block_a_window", "blocks_of_a_chunk"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_chunked_prefill_and_paged_decode_match_the_reference(
        model_and_params, ref, dtype, block_columns, blocks_of):
    """Prompts of several chunks with a ragged last one, five requests of
    different lengths through three slots (so slots are freed and reused:
    a stale state or a stale row would show). A bfloat16 engine fails the
    float32 limit and meets its own. The chunks' attention layers walk
    their cached rows in blocks: the whole window in one (the shapes'
    own size here), or blocks of 8 columns, so that the 23-token prompt's
    chunks run 0, 1 and 2 of them."""
    model, params = model_and_params
    if block_columns:
        blocks_of(block_columns)
    if dtype == "bfloat16":
        model = Transformer(dataclasses.replace(model.cfg, dtype="bfloat16"))
    eng = _engine(model, params)
    prompts = _prompts([19, 5, 23, 11, 17])
    new = [30, 28, 12, 33, 9]
    rids = [eng.submit(p, n) for p, n in zip(prompts, new)]
    _drain(eng)
    assert eng.cache.allocator.used_count == 0
    worst = 0.0
    for rid, prompt, n in zip(rids, prompts, new):
        res = eng.result(rid)
        assert len(res.generated) == n
        err, deficit = _gaps(ref, params, prompt, res)
        worst = max(worst, err)
        tol = TOL_F32 if dtype == "float32" else TOL_BF16
        assert err < tol and deficit < tol, (rid, err, deficit)
    if dtype == "bfloat16":
        assert worst > 10 * TOL_F32      # bfloat16 compute fails float32's
    eng.close()


@pytest.mark.parametrize("layout", ["attention_inside_a_scan"])
def test_paged_layers_inside_a_repeating_stretch(ref, layout):
    """One attention layer in 4 from layer 1 over 8 layers is one run of
    period 4 repeated twice: the two paged layers ride a ``lax.scan`` and
    index the paged arrays by the repeat."""
    hf = dict(HF, num_hidden_layers=8, attn_layer_period=4,
              attn_layer_offset=1)
    cfg = dataclasses.replace(get_model_config("tiny-jamba"), num_layers=8,
                              layers=jamba_layers(8, 4, 1))
    assert layer_runs(cfg.layer_spec) == (Run(0, 4, 2),)
    model = Transformer(cfg)
    params = _moved(model.init(jax.random.key(2)))
    eng = _engine(model, params)
    prompts = _prompts([21, 9], seed=4)
    rids = [eng.submit(p, 14) for p in prompts]
    _drain(eng)
    for rid, prompt in zip(rids, prompts):
        err, deficit = _gaps(ref, params, prompt, eng.result(rid), hf)
        assert err < TOL_F32 and deficit < TOL_F32, (rid, err, deficit)
    eng.close()


def test_two_requests_in_flight_and_a_preemption_between(model_and_params,
                                                         ref):
    """Two requests of different length decode side by side; the pool runs
    out, the younger is preempted and recomputed by the chunk lane from
    prompt + generated tokens: both answers stay the reference's."""
    model, params = model_and_params
    prompts = _prompts([9, 14], seed=11)

    def run(num_pages):
        eng = _engine(model, params, num_slots=2, num_pages=num_pages,
                      max_model_len=48)
        rids = [eng.submit(p, 24) for p in prompts]
        _drain(eng)
        out = [eng.result(r) for r in rids]
        n = eng.metrics.preemptions.value
        eng.close()
        return out, n

    calm, none = run(64)
    tight, preempted = run(16)
    assert none == 0 and preempted >= 1
    assert any(r.evictions for r in tight)
    for a, b, prompt in zip(calm, tight, prompts):
        assert a.generated == b.generated
        err, deficit = _gaps(ref, params, prompt, b)
        assert err < TOL_F32 and deficit < TOL_F32, (err, deficit)


def test_leaving_out_the_inner_norms_is_seen(model_and_params, ref):
    """The same weights through a model that does not norm dt, B and C
    leave the reference by far more than any tolerance here."""
    model, params = model_and_params
    bare = Transformer(dataclasses.replace(model.cfg, ssm_inner_norms=False))
    prompt = _prompts([21], seed=5)[0]
    eng = _engine(bare, params, num_slots=1)
    rid = eng.submit(prompt, 20)
    eng.run_until_drained(max_steps=200)
    err, _ = _gaps(ref, params, prompt, eng.result(rid))
    assert err > 100 * TOL_F32
    eng.close()
    tokens = jnp.asarray(prompt)[None]
    with jax.default_matmul_precision("highest"):
        gap = np.abs(np.asarray(jax.jit(bare.apply)(params, tokens))
                     - np.asarray(jax.jit(model.apply)(params, tokens))).max()
    assert gap > 100 * TOL_F32


def test_each_paged_layer_reads_rows_of_its_own(model_and_params, ref,
                                                monkeypatch):
    """Layer 8 reading layer 2's rows (both at index 0 of the paged
    arrays) leaves the reference; and a change to one layer's cached rows
    reaches the logits through that layer alone."""
    model, params = model_and_params
    prompt = _prompts([18], seed=7)[0]
    wrong = Transformer(model.cfg)
    wrong.hybrid._cache_index = {**wrong.hybrid._cache_index, 8: (0, 1)}
    eng = _engine(wrong, params, num_slots=1)
    rid = eng.submit(prompt, 16)
    eng.run_until_drained(max_steps=200)
    err, _ = _gaps(ref, params, prompt, eng.result(rid))
    assert err > 100 * TOL_F32
    eng.close()

    eng = _engine(model, params, num_slots=1)
    rid = eng.submit(prompt, 6)
    while len(eng.result(rid).generated) < 3:
        eng.step()
    cache = eng.cache
    packed = eng._decode_layout.pack(
        1, block_tables=cache.block_tables, window_tables=cache.window_tables,
        lengths=cache.lengths, tokens=cache.tokens,
        active=np.ones((1,), bool), top_k=eng.samp_top_k,
        seed=eng.samp_seed, gen_pos=eng.gen_pos, temp=eng.samp_temp,
        top_p=eng.samp_top_p)
    view = {**eng._unpack_decode(jnp.asarray(packed), None)[0],
            "real": jnp.ones((1, 1), bool),
            "write_pages": jnp.zeros((1, 1), jnp.int32),
            "write_offs": jnp.zeros((1, 1), jnp.int32)}
    pools = [np.array(p) for p in cache.pools]
    step = jax.jit(lambda weights, arrays: model.decode_step_paged(
        weights, {**view, "pools": arrays}, jnp.asarray(cache.tokens))[0])

    def logits(weights, arrays):
        return np.asarray(step(weights, tuple(jnp.asarray(a)
                                              for a in arrays)))

    def cut(l):
        """Attention layer ``l``'s output projection zeroed."""
        key = f"{l:02d}s1_attention"
        layers = dict(params["layers"])
        layers[key] = {**layers[key],
                       "wo": jnp.zeros_like(layers[key]["wo"])}
        return {**params, "layers": layers}

    page = int(cache.block_tables[0, 0])
    for index, layer, other in ((0, 2, 8), (1, 8, 2)):
        moved = [a.copy() for a in pools]
        moved[1][index, page] += 0.5          # that layer's cached values
        assert np.abs(logits(params, moved)
                      - logits(params, pools)).max() > 1e-3
        assert np.abs(logits(cut(other), moved)
                      - logits(cut(other), pools)).max() > 1e-3
        # with the layer's own output cut, nobody reads them
        assert np.array_equal(logits(cut(layer), moved),
                              logits(cut(layer), pools))
    eng.close()


REFUSALS = {
    "prefix_cache": dict(prefix_cache=True),
    "speculative": dict(speculative={"enabled": True, "k": 2,
                                     "draft": "self"}),
    "kv_export_import_role": dict(role="prefill"),
}


@pytest.mark.parametrize("what", sorted(REFUSALS))
def test_engine_refuses_what_needs_a_state_snapshot(model_and_params, what):
    model, params = model_and_params
    with pytest.raises(ValueError, match="state snapshots"):
        _engine(model, params, **REFUSALS[what])


SPEC_REFUSALS = {
    "latent_attention_in_a_spec": (
        dict(layers=(("latent_attention", "paged"), ("ssm", "state"))),
        "homogeneous stack"),
    "attention_with_a_window": (
        dict(layers=(("attention", "paged_window", 8), ("ssm", "state"))),
        "mixer 'attention' with cache"),
    "attention_with_bias": (
        dict(layers=(("attention", "paged"), ("ssm", "state")),
             attention_bias=True), "plain form"),
    "cross_layer_over_two_paged_layers": (
        dict(layers=(("diff_attention", "paged"), ("diff_attention",
                                                   "paged"),
                     ("cross_diff_attention", "shared")), num_layers=3,
             num_kv_heads=2), "the one paged"),
    "odd_heads_under_differential_attention": (
        dict(layers=(("diff_attention", "paged"), ("ssm", "state")),
             num_kv_heads=1), "pairs heads"),
}


@pytest.mark.parametrize("what", sorted(SPEC_REFUSALS))
def test_config_refuses_by_name(what):
    extra, message = SPEC_REFUSALS[what]
    fields = dict(vocab_size=8, hidden_size=8, intermediate_size=8,
                  num_layers=2, num_heads=2, num_kv_heads=1)
    fields.update(extra)
    with pytest.raises(ValueError, match=message):
        ModelConfig(**fields)


# ------------------------------------------------- the published config

def _published_keys():
    if CATALOG.is_file():
        return next(json.loads(line) for line in open(CATALOG)
                    if '"AI21-Jamba2-3B"' in line)["config"]
    return json.loads((ROOT / "perfbench/configs/jamba2_3b_serve.json"
                       ).read_text())


def test_hf_config_gives_the_published_model():
    cfg = hf_config_to_model_config(_published_keys(), dtype="bfloat16",
                                    param_dtype="bfloat16")
    assert cfg.num_layers == 28
    assert [l for l, s in enumerate(cfg.layer_spec)
            if s.mixer == "attention"] == [7, 21]
    assert all(s == LayerSpec("ssm", "state")
               for l, s in enumerate(cfg.layer_spec) if l not in (7, 21))
    assert (cfg.arch, cfg.norm, cfg.tie_embeddings, cfg.ssm_inner_norms,
            cfg.rms_norm_eps) == ("llama", "rms", True, True, 1e-6)
    assert (cfg.ssm_inner_, cfg.ssm_state_size, cfg.ssm_conv_width,
            cfg.ssm_dt_rank_, cfg.head_dim_, cfg.num_heads,
            cfg.num_kv_heads) == (5120, 16, 4, 160, 128, 20, 1)
    model = Transformer(cfg)
    shapes = jax.eval_shape(model.init, jax.random.key(0))
    count = lambda tree: sum(int(np.prod(x.shape))  # noqa: E731
                             for x in jax.tree_util.tree_leaves(tree))
    assert "lm_head" not in shapes                    # tied
    assert count(shapes) == 3_029_337_472
    per_layer = {k: count(v) // next(iter(v.values())).shape[0]
                 for k, v in shapes["layers"].items()}
    mlp, norms = 3 * 2560 * 8192, 2 * 2560
    assert per_layer["00s1_ssm"] == 41_241_792 + mlp + norms
    assert per_layer["07s1_attention"] == 13_762_560 + mlp + norms
    # the benchmark's own count agrees with the program's, at the
    # published widths
    costs = _load("perfbench/lib/costs_ssm_attn.py", "costs_ssm_attn")
    keys = json.loads((ROOT / "perfbench/configs/jamba2_3b_serve.json"
                       ).read_text())
    assert costs.total_params(keys) == count(shapes)
    assert costs.mixer_params(keys) == {"ssm": 41_241_792,
                                        "attention": 13_762_560}
    # a cached token is 1,024 B over the whole model; a slot's state 8.9 MiB
    spec = model.cache_spec()
    assert [(a.kind, a.layers, a.shape) for a in spec] == [
        ("paged", 2, (128,)), ("paged", 2, (128,)),
        ("state", 26, (16, 5120)), ("state", 26, (3, 5120))]
    assert costs.kv_row_bytes(keys) * 2 == 1024
    assert costs.state_bytes_per_slot(keys) == 26 * 358_400
    # the configuration file carries the catalog row's keys unchanged
    if CATALOG.is_file():
        row = _published_keys()
        assert {k: keys[k] for k in row} == row


def test_routed_experts_refuse_by_name():
    with pytest.raises(ValueError, match="num_experts=16"):
        hf_config_to_model_config({**_published_keys(), "num_experts": 16,
                                   "num_experts_per_tok": 2})


# ------------------------------------- the chunk program's block walk

def _gathering(q, k_pool, v_pool, layer, tables, context, k_new, v_new, *,
               q_positions, block_pages, softmax_scale):
    """What the chunk program's attention layers did before the walk:
    ``block_decode_attention`` over the row's whole gathered window."""
    b = q.shape[0]
    window = tables.shape[1] * k_pool.shape[2]
    heads = (b, window) + k_new.shape[2:]
    cols = jnp.arange(window, dtype=jnp.int32)
    return block_decode_attention(
        q, k_pool[layer, tables].reshape(heads),
        v_pool[layer, tables].reshape(heads), k_new, v_new,
        kv_valid=cols[None] < context[:, None], q_positions=q_positions,
        kv_positions=jnp.broadcast_to(cols, (b, window)),
        softmax_scale=softmax_scale)


def _prefilled(model, params, prompt, new, monkeypatch, gather):
    """One request through an engine whose chunk program walks blocks of
    8 columns, or gathers: (the pools after the prompt's last chunk, the
    request's result)."""
    monkeypatch.setattr(hybrid, "CHUNK_ATTENTION_BLOCK_COLUMNS", CHUNK)
    if gather:
        monkeypatch.setattr(hybrid, "blockwise_paged_attention", _gathering)
    eng = _engine(model, params, num_slots=1)
    rid = eng.submit(prompt, new)
    while not eng.result(rid).generated:
        eng.step()
    pools = [np.array(p) for p in eng.cache.pools]
    _drain(eng)
    res = eng.result(rid)
    eng.close()
    monkeypatch.undo()
    return pools, res


def test_walked_chunks_write_the_pages_of_the_gathered_window(
        model_and_params, monkeypatch):
    """A prompt of four chunks (contexts 0, 8, 16, 24: 0 to 3 blocks)
    through the walk and through ``block_decode_attention`` over the
    gathered window: the same pages, the same state, and at float32 the
    same greedy tokens with the same log-probabilities."""
    model, params = model_and_params
    prompt = _prompts([29], seed=13)[0]
    walked, got = _prefilled(model, params, prompt, 24, monkeypatch, False)
    gathered, want = _prefilled(model, params, prompt, 24, monkeypatch, True)
    for a, b in zip(walked, gathered):
        assert np.abs(a - b).max() < 1e-5
    # the first attention layer's rows come from below every attention
    assert np.array_equal(walked[0][0], gathered[0][0])
    assert got.generated == want.generated
    assert np.abs(np.asarray(got.generated_logprobs)
                  - np.asarray(want.generated_logprobs)).max() < 1e-5


@pytest.mark.parametrize("program", ["walk", "gather"])
def test_chunk_program_holds_no_score_as_wide_as_the_window(
        model_and_params, monkeypatch, program):
    """The compiled chunk program of an engine with a 96-column window
    (no other size of the model is 96 or 104) walking blocks of 8: no
    float buffer is as wide as the window, or the window and the chunk.
    The gathered form, compiled the same way, holds both: the search
    finds what it looks for."""
    model, params = model_and_params
    monkeypatch.setattr(hybrid, "CHUNK_ATTENTION_BLOCK_COLUMNS", CHUNK)
    if program == "gather":
        monkeypatch.setattr(hybrid, "blockwise_paged_attention", _gathering)
    eng = _engine(model, params, num_slots=1, max_model_len=96,
                  num_pages=80)
    window = eng.cache.geom.slot_window
    assert window == 96
    text = jax.jit(eng._prefill_chunk_fn, donate_argnums=1).lower(
        params, eng.cache.pools,
        jnp.zeros((eng._chunk_layout.width,), jnp.int32)).compile().as_text()
    eng.close()
    wide = re.findall(
        rf"f32\[(?:\d+,)*(?:{window}|{window + CHUNK})(?:,\d+)*\]", text)
    if program == "walk":
        assert not wide, sorted(set(wide))
        assert re.search(rf"f32\[(?:\d+,)*{CHUNK},{CHUNK}\]", text)
    else:
        assert wide


@pytest.mark.parametrize("model_name, walks", [
    ("tiny-jamba", True), ("tiny-sambay", False), ("tiny", False)])
def test_attention_walk_counters(model_name, walks, blocks_of):
    """``serving/prefill/attn_read_tokens`` over ``attn_window_tokens``:
    the share of the window the chunks' block walk read, from the host's
    ``start``; 0 / 0 for a model whose chunk program gathers."""
    blocks_of(CHUNK)
    model = Transformer(get_model_config(model_name))
    params = model.init(jax.random.key(0))
    eng = _engine(model, params, num_slots=1)
    assert eng._attn_walk == ((CHUNK, 2) if walks else (0, 0))
    eng.submit(_prompts([29])[0], 2)
    eng.run_until_drained(max_steps=50)
    snap = eng.metrics.snapshot()
    assert snap["serving/prefill/chunks"] == 4
    if walks:
        # contexts 0, 8, 16, 24 in blocks of 8, two layers; window 64
        assert snap["serving/prefill/attn_read_tokens"] == 2 * (8 + 16 + 24)
        assert snap["serving/prefill/attn_window_tokens"] == 4 * 2 * 64
    else:
        assert snap["serving/prefill/attn_read_tokens"] == 0
        assert snap["serving/prefill/attn_window_tokens"] == 0
    eng.close()
    if model.hybrid:
        assert model.hybrid.chunk_attention_walk(PAGE, 16) == eng._attn_walk
    # blocks are whole pages: at least one, at most the table
    assert hybrid.chunk_attention_block_pages(16, 2112) == 1
    assert hybrid.chunk_attention_block_pages(PAGE, 1) == 1


# ---------------------------------------- spans, scopes and the programs

def test_scope_and_counters_are_in_the_compiled_steps(model_and_params):
    model, params = model_and_params
    eng = _engine(model, params, num_slots=2)
    eng.submit(_prompts([10])[0], 3)
    eng.run_until_drained(max_steps=50)
    want = {"ssm_mixer", "ssm_scan", "full_attention", "embed"}
    assert want <= set(DEVICE_SCOPES)
    assert "context" in SPANS["serve_prefill_chunk"][1]
    for program in (r"jit__decode_fn", r"jit__prefill_chunk_fn"):
        ops = " ".join(compiled_scopes(program).values())
        for scope in want:
            assert re.search(rf"[/(]{scope}[/)]", ops), (program, scope)
        # the recurrence sits inside the mixer's scope, and the plain
        # attention layers are not under the differential mixers' scopes
        assert re.search(r"ssm_mixer/ssm_scan[/)]", ops), program
        assert not re.search(r"[/(](swa|cross)_attention[/)]", ops), program
    snap = eng.metrics.snapshot()
    assert snap["serving/kv_paged_layers"] == 2
    assert snap["serving/kv_bytes_per_token"] == 2 * 2 * 16 * 4
    assert snap["serving/kv_shared_readers"] == 1
    assert snap["serving/state_bytes_per_slot"] == 10 * (
        4 * 128 * 4 + 3 * 128 * 4)
    # 10 real tokens in two chunks through ten state-space layers
    assert snap["serving/prefill/chunks"] == 2
    assert snap["serving/prefill/scan_tokens"] == 10 * 10
    eng.close()


def sambay_paged_steps():
    """``tiny-sambay``'s two paged programs, lowered, and their logits over
    a live cache: what tests/fixtures/sambay_paged_steps.json pins."""
    model = Transformer(get_model_config("tiny-sambay"))
    params = model.init(jax.random.key(0))
    leaves, tree = jax.tree_util.tree_flatten(params)
    keys = jax.random.split(jax.random.key(1), len(leaves))
    params = jax.tree_util.tree_unflatten(tree, [
        leaf + 0.05 * jax.random.normal(k, leaf.shape)
        for leaf, k in zip(leaves, keys)])
    gen = GenerationConfig(max_new_tokens=8, do_sample=False,
                           eos_token_id=-1, pad_token_id=0)
    eng = ServingEngine(model, params, gen, ServingConfig(
        page_size=4, num_pages=48, num_slots=2, max_model_len=48,
        prefill_chunk=8))
    prompt = _prompts([19])[0]
    rid = eng.submit(prompt, 8)
    while len(eng.result(rid).generated) < 3:
        eng.step()
    cache, slot = eng.cache, eng.result(rid).slot
    out = {}
    lowered = {
        "decode": (eng._decode_fn, jnp.zeros(
            (2, eng._decode_layout.width), jnp.int32)),
        "prefill_chunk": (eng._prefill_chunk_fn, jnp.zeros(
            (eng._chunk_layout.width,), jnp.int32))}
    for name, (fn, packed) in lowered.items():
        text = jax.jit(fn, donate_argnums=1).lower(
            params, cache.pools, packed).as_text()
        out[f"{name}/hlo_sha256"] = hashlib.sha256(text.encode()).hexdigest()
    # the decode step's logits over the live cache
    active = np.zeros((2,), bool)
    active[slot] = True
    packed = eng._decode_layout.pack(
        2, block_tables=cache.block_tables, window_tables=cache.window_tables,
        lengths=cache.lengths, tokens=cache.tokens, active=active,
        top_k=eng.samp_top_k, seed=eng.samp_seed, gen_pos=eng.gen_pos,
        temp=eng.samp_temp, top_p=eng.samp_top_p)
    view = {**eng._unpack_decode(jnp.asarray(packed), None)[0],
            "pools": cache.pools, "real": jnp.asarray(active)[:, None],
            "write_pages": jnp.zeros((2, 1), jnp.int32),
            "write_offs": jnp.zeros((2, 1), jnp.int32)}
    logits = jax.jit(lambda p, v, t: model.decode_step_paged(p, v, t)[0])(
        params, view, jnp.asarray(cache.tokens))
    out["decode/logits"] = np.asarray(logits[slot], np.float32)
    # the prompt's second chunk again, over the rows of the first
    chunk = eng._chunk_layout.pack(
        block_tables=cache.block_tables[slot],
        window_tables=cache.window_tables[slot],
        ids=np.asarray(prompt[8:16], np.int32), start=np.int32(8),
        nvalid=np.int32(8), adapter=eng.adapter_idx[slot],
        slot=np.int32(slot))
    _, logits = jax.jit(eng._prefill_chunk_fn)(
        params, cache.pools, jnp.asarray(chunk))
    out["prefill_chunk/logits"] = np.asarray(logits[0], np.float32)
    eng.close()
    return out


@pytest.mark.parametrize("program", ["decode", "prefill_chunk"])
def test_sambay_paged_steps_are_what_they_were(program):
    """``tiny-sambay`` through both paged steps, against what the commit
    before plain attention and further paged layers entered the spec'd
    stack gave (tests/fixtures/sambay_paged_steps.json): the same lowered
    program and bit-identical logits. A change that means to move them
    regenerates the fixture and says so."""
    want = json.loads((ROOT / "tests/fixtures/sambay_paged_steps.json"
                       ).read_text())
    got = sambay_paged_steps()
    assert got[f"{program}/hlo_sha256"] == want[f"{program}/hlo_sha256"], \
        f"{program}: another program text"
    assert hashlib.sha256(got[f"{program}/logits"].tobytes()).hexdigest() \
        == want[f"{program}/logits_sha256"], f"{program}: other logits"
