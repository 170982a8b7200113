"""A model whose layers are of several kinds (``ModelConfig.layers``: the
SambaY layout of state-space mixers, window and full differential
attention, gated memory units and cross-attention), served by the paged
``ServingEngine`` and held, on logits, to the benchmark's plain float32
reference ``perfbench/reference/phi4flash_block.py``.

Tolerances. Float32 against float32 differ by the order of the sums
alone (a chunked scan against a sequential one, a softmax over cached +
fresh columns against one over the whole row): 1e-4 on a log-probability
of size 6 is a hundred times what was read (1e-6) and a thousand times
under the smallest fault this file plants (a page dropped early: 1e-2 and
more). A bfloat16 engine (bfloat16 pages and activations, float32 state
and weights) keeps 8 bits: read 0.02 at worst over these sequences, held
to 0.08, which a state kept in bfloat16 as well does not change at this
size (that limit is the chip comparison's to hold, PERF.md section 6).
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

from dla_tpu.generation.engine import GenerationConfig, GenerationEngine
from dla_tpu.models.config import (
    LayerSpec,
    ModelConfig,
    get_model_config,
    sambay_layers,
)
from dla_tpu.models.hf_import import hf_config_to_model_config
from dla_tpu.models.hybrid import Run, layer_runs
from dla_tpu.models.transformer import Transformer
from dla_tpu.ops.selective_scan import (
    causal_conv_step,
    selective_scan_chunk,
    selective_scan_step,
)
from dla_tpu.serving import ServingConfig, ServingEngine
from dla_tpu.telemetry.xla_introspect import compiled_scopes
from dla_tpu.utils.profiling import DEVICE_SCOPES

ROOT = Path(__file__).resolve().parents[1]
TOL_F32 = 1e-4
TOL_BF16 = 0.08
WINDOW, PAGE, CHUNK = 8, 4, 8

#: the tiny model's Hugging Face keys, as the reference reads them
HF = dict(num_hidden_layers=12, hidden_size=64, num_attention_heads=4,
          num_key_value_heads=2, layer_norm_eps=1e-5,
          sliding_window=WINDOW, mb_per_layer=2)


def _load(path, name):
    spec = importlib.util.spec_from_file_location(name, ROOT / path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.fixture(scope="module")
def ref():
    return _load("perfbench/reference/phi4flash_block.py", "phi4flash_ref")


@pytest.fixture(scope="module")
def model_and_params():
    """The ``tiny-sambay`` preset with every leaf moved off its
    initial value, so biases, norms and lambdas all count."""
    model = Transformer(get_model_config("tiny-sambay"))
    params = model.init(jax.random.key(0))
    leaves, tree = jax.tree_util.tree_flatten(params)
    keys = jax.random.split(jax.random.key(1), len(leaves))
    return model, jax.tree_util.tree_unflatten(tree, [
        leaf + 0.05 * jax.random.normal(k, leaf.shape)
        for leaf, k in zip(leaves, keys)])


def _reference_logprobs(ref, params, tokens):
    """[T, V] log-probabilities of the reference's forward over
    ``tokens``: row t is the distribution of token t + 1."""
    with jax.default_matmul_precision("highest"):
        hidden = ref.hidden_states(
            np.asarray(tokens), params["embed"]["embedding"],
            lambda l: ref.take_layer(params["layers"], l),
            (params["final_norm"], params["final_norm_bias"]), HF)
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


def _gaps(ref, params, prompt, result):
    """(largest |log-probability - reference's| over the answer's chosen
    tokens, largest gap by which a chosen token trails the reference's
    best) of one finished request."""
    seq = prompt + list(result.generated)
    logp = _reference_logprobs(ref, params, seq[:-1])[len(prompt) - 1:]
    chosen = np.asarray(result.generated)
    picked = logp[np.arange(len(chosen)), chosen]
    return (float(np.abs(picked - np.asarray(
        result.generated_logprobs)).max()),
        float((logp.max(-1) - picked).max()))


# ------------------------------------------------------- the model alone

def test_layer_runs_and_spec():
    spec = sambay_layers(32, 512)
    kinds = [(s.mixer, s.cache, s.window) for s in spec]
    assert kinds[:2] == [("ssm", "state", None),
                         ("diff_attention", "paged_window", 512)]
    assert kinds[16] == ("ssm", "state", None)
    assert kinds[17] == ("diff_attention", "paged", None)
    assert kinds[18:20] == [("gmu", "none", None),
                            ("cross_diff_attention", "shared", None)]
    assert layer_runs(spec) == (Run(0, 2, 8), Run(16, 1, 1), Run(17, 1, 1),
                                Run(18, 2, 7))
    # a model of one kind of layer is one run; gemma-2's alternating
    # window is one run too (the window rides the scan as data)
    for name in ("tiny", "tiny-mla-moe", "gemma2-2b"):
        cfg = get_model_config(name)
        assert layer_runs(cfg.layer_spec) == (Run(0, 1, cfg.num_layers),)
    gemma = get_model_config("gemma2-2b").layer_spec
    assert [s.window for s in gemma[:4]] == [4096, None, 4096, None]
    assert get_model_config("mistral-7b").layer_spec[5] == LayerSpec(
        "attention", "paged", 4096)


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


def test_scan_forms_agree_and_pads_leave_the_state():
    rs = np.random.RandomState(3)
    b, t, d, n = 2, 24, 16, 4
    x = jnp.asarray(rs.randn(b, t, d), jnp.float32)
    dt = jnp.asarray(np.abs(rs.randn(b, t, d)) * 0.3, jnp.float32)
    a = -jnp.exp(jnp.asarray(rs.randn(n, d), jnp.float32))
    bm = jnp.asarray(rs.randn(b, t, n), jnp.float32)
    cm = jnp.asarray(rs.randn(b, t, n), jnp.float32)
    skip = jnp.asarray(rs.randn(d), jnp.float32)
    s0 = jnp.asarray(rs.randn(b, n, d), jnp.float32)
    y_chunk, s_chunk = selective_scan_chunk(x, dt, a, bm, cm, skip, s0)
    state, ys = s0, []
    for i in range(t):
        y, state = selective_scan_step(
            x[:, i], dt[:, i], a, bm[:, i], cm[:, i], skip, state)
        ys.append(y)
    assert np.allclose(y_chunk, jnp.stack(ys, 1), atol=1e-5)
    assert np.allclose(s_chunk, state, atol=1e-5)
    # two chunks from the carried state are the one long chunk; so is an
    # odd block size (the scan falls back to smaller blocks)
    y1, s1 = selective_scan_chunk(x[:, :8], dt[:, :8], a, bm[:, :8],
                                  cm[:, :8], skip, s0)
    y2, s2 = selective_scan_chunk(x[:, 8:], dt[:, 8:], a, bm[:, 8:],
                                  cm[:, 8:], skip, s1)
    assert np.allclose(jnp.concatenate([y1, y2], 1), y_chunk, atol=1e-5)
    assert np.allclose(s2, s_chunk, atol=1e-5)
    y3, s3 = selective_scan_chunk(x[:, :7], dt[:, :7], a, bm[:, :7],
                                  cm[:, :7], skip, s0)
    assert np.allclose(y3, y_chunk[:, :7], atol=1e-5)
    # pad rows (step size 0) leave the state bit for bit where it was
    # (24 tokens run in blocks of 8: the same blocks over the real 16)
    dt_pad = dt.at[:, 16:].set(0.0)
    _, s_pad = selective_scan_chunk(x, dt_pad, a, bm, cm, skip, s0)
    _, s_16 = selective_scan_chunk(x[:, :16], dt[:, :16], a, bm[:, :16],
                                   cm[:, :16], skip, s0, block=8)
    assert np.array_equal(s_pad, s_16)
    _, s_same = selective_scan_step(x[:, 0], jnp.zeros((b, d)), a,
                                    bm[:, 0], cm[:, 0], skip, s0)
    assert np.array_equal(s_same, s0)
    # the convolution's tail ends at the last real token
    tail = jnp.asarray(rs.randn(b, 3, d), jnp.float32)
    w = jnp.asarray(rs.randn(4, d), jnp.float32)
    out, new_tail = causal_conv_step(
        x, tail, w, skip, jnp.asarray([t, 5], jnp.int32))
    seq = jnp.concatenate([tail, x], 1)
    assert np.allclose(out[:, 0], sum(seq[:, i] * w[i] for i in range(4))
                       + skip, atol=1e-5)
    assert np.array_equal(new_tail[0], x[0, -3:])
    assert np.array_equal(new_tail[1], x[1, 2:5])
    _, kept = causal_conv_step(x[:, :1], tail, w, skip,
                               jnp.asarray([0, 1], jnp.int32))
    assert np.array_equal(kept[0], tail[0])
    assert np.array_equal(kept[1, -1], x[1, 0])


# --------------------------------------------------- through the engine

@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_chunked_prefill_and_paged_decode_match_the_reference(
        model_and_params, ref, dtype):
    """Prompts longer than two chunks, answers longer than three windows,
    five requests of different lengths through three slots (so slots are
    freed and reused: a stale state or a stale window page would show),
    window pages released and reallocated all the way."""
    model, params = model_and_params
    if dtype == "bfloat16":
        model = Transformer(dataclasses.replace(model.cfg, dtype="bfloat16"))
    eng = _engine(model, params)
    prompts = _prompts([19, 5, 23, 11, 17])
    new = [30, 28, 12, 33, 9]
    rids = [eng.submit(p, n) for p, n in zip(prompts, new)]
    ring, held = eng.cache.geom.window_ring, 0
    assert ring == -(-(WINDOW + CHUNK) // PAGE) + 1
    while eng.has_work():
        eng.step()
        eng.scheduler.assert_consistent()
        held = max(held, int((eng.cache.window_next
                              - eng.cache.window_first).max()))
    assert 0 < held <= ring
    assert eng.cache.window_pages_released > 0
    assert eng.cache.window_allocator.used_count == 0
    assert eng.cache.allocator.used_count == 0
    snap = eng.metrics.snapshot()
    assert snap["serving/window_pages_released"] \
        == eng.cache.window_pages_released
    assert 0 < snap["serving/window_page_occupancy_peak"] <= 1
    tol = TOL_F32 if dtype == "float32" else TOL_BF16
    for rid, prompt, n in zip(rids, prompts, new):
        res = eng.result(rid)
        assert len(res.generated) == n
        err, deficit = _gaps(ref, params, prompt, res)
        assert err < tol and deficit < tol, (rid, err, deficit)
    eng.close()


def test_state_is_float32_beside_activation_dtype_pages(model_and_params):
    model, params = model_and_params
    model = Transformer(dataclasses.replace(model.cfg, dtype="bfloat16"))
    eng = _engine(model, params)
    spec = model.cache_spec()
    assert [a.kind for a in spec] == ["paged", "paged", "paged_window",
                                     "paged_window", "state", "state"]
    # one paged layer (the cross layers own no pages), three window
    # layers, four state-space layers; a row is a token's pairs side by
    # side (1 pair of 2 x 16 here)
    assert [a.layers for a in spec] == [1, 1, 3, 3, 4, 4]
    assert spec[0].shape == (32,) and spec[4].shape == (4, 128)
    assert [p.dtype for p in eng.cache.pools] == [
        jnp.bfloat16] * 4 + [jnp.float32, jnp.bfloat16]
    geom = eng.cache.geom
    assert eng.cache.pools[2].shape == (3, geom.num_slots * geom.window_ring
                                        + 1, PAGE, 32)
    assert eng.cache.pools[4].shape == (4, geom.num_slots, 4, 128)
    snap = eng.metrics.snapshot()
    assert snap["serving/kv_bytes_per_token"] == 2 * 32 * 2
    assert snap["serving/window_bytes_per_token"] == 3 * 2 * 32 * 2
    assert snap["serving/state_bytes_per_slot"] == 4 * (
        4 * 128 * 4 + 3 * 128 * 2)
    assert snap["serving/kv_shared_readers"] == 3    # layer 7 and 9, 11
    eng.close()


def test_a_window_page_dropped_early_is_seen(model_and_params, ref,
                                             monkeypatch):
    """The release rule is exact: giving pages back one page sooner (the
    window counted a page short) drops rows a query still sees, and the
    logits leave the reference."""
    model, params = model_and_params
    prompt = _prompts([21], seed=5)[0]
    eng = _engine(model, params, num_slots=1)
    release = type(eng.cache)._release_window
    monkeypatch.setattr(
        type(eng.cache), "_release_window",
        lambda self, slot, nxt: release(self, slot, nxt + PAGE))
    rid = eng.submit(prompt, 30)
    eng.run_until_drained(max_steps=200)
    err, _ = _gaps(ref, params, prompt, eng.result(rid))
    assert err > 100 * TOL_F32
    eng.close()


def test_cross_layers_read_the_one_paged_layers_rows(model_and_params):
    """One pool, one writer (layer 7), its readers above it: changing the
    cached rows of that pool changes the step's logits through each
    cross layer alone, and through nothing else; no other layer owns
    request-long pages."""
    model, params = model_and_params
    assert sum(s.cache == "paged" for s in model.cfg.layer_spec) == 1
    eng = _engine(model, params, num_slots=1)
    rid = eng.submit(_prompts([14], seed=7)[0], 6)
    while len(eng.result(rid).generated) < 3:
        eng.step()
    cache = eng.cache
    slot = eng.result(rid).slot
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

    page = int(cache.block_tables[slot, 0])
    moved = [a.copy() for a in pools]
    moved[1][0, page] += 0.5              # the paged layer's cached values
    assert np.abs(logits(params, moved) - logits(params, pools)).max() > 1e-3

    def cut(keep):
        """Layer 7's own output projection and every cross layer's but
        ``keep``'s zeroed: the rows reach the logits through ``keep``."""
        layers = dict(params["layers"])
        own = layers["07s1_diff_attention"]
        layers["07s1_diff_attention"] = {
            **own, "wo": jnp.zeros_like(own["wo"])}
        cross = layers["09s2_cross_diff_attention"]
        on = jnp.arange(cross["wo"].shape[0]) == keep
        layers["09s2_cross_diff_attention"] = {
            **cross, "wo": cross["wo"] * on[:, None, None]}
        return {**params, "layers": layers}

    for keep in (0, 1):                   # layers 9 and 11, each alone
        assert np.abs(logits(cut(keep), moved)
                      - logits(cut(keep), pools)).max() > 1e-4
    # and no one else reads them
    assert np.array_equal(logits(cut(-1), moved), logits(cut(-1), pools))
    eng.close()


def test_preempted_request_resumes_to_the_same_tokens(model_and_params):
    """Page exhaustion mid-decode preempts the youngest request; on
    re-admission the chunk lane rebuilds its state and window pages from
    prompt + generated tokens, and its answer is the undisturbed one."""
    model, params = model_and_params
    prompts = _prompts([9, 10], seed=11)

    def run(num_pages):
        eng = _engine(model, params, num_slots=2, num_pages=num_pages,
                      max_model_len=48)
        rids = [eng.submit(p, 24) for p in prompts]
        while eng.has_work():
            eng.step()
            eng.scheduler.assert_consistent()
        out = [eng.result(r) for r in rids]
        n = eng.metrics.preemptions.value
        eng.close()
        return out, n

    calm, none = run(64)
    tight, preempted = run(15)
    assert none == 0 and preempted >= 1
    assert any(r.evictions for r in tight)
    for a, b in zip(calm, tight):
        assert a.generated == b.generated
        assert np.allclose(a.generated_logprobs, b.generated_logprobs,
                           atol=TOL_F32)


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


def test_kv_export_and_import_refuse(model_and_params):
    model, params = model_and_params
    eng = _engine(model, params)
    rid = eng.submit(_prompts([6])[0], 4)
    eng.step()
    with pytest.raises(ValueError, match="state snapshots"):
        eng.export_request(rid)
    with pytest.raises(ValueError, match="state snapshots"):
        eng.import_request(None)
    eng.close()


def test_contiguous_cache_path_refuses(model_and_params):
    model, params = model_and_params
    with pytest.raises(ValueError, match="paged cache manager"):
        model.init_cache(2, 16)
    with pytest.raises(ValueError, match="paged cache manager"):
        model.prefill_external(params, jnp.zeros((1, 8), jnp.int32),
                               jnp.ones((1, 8), jnp.int32))
    gen = GenerationConfig(max_new_tokens=4, do_sample=False,
                           eos_token_id=-1, pad_token_id=0)
    with pytest.raises(ValueError, match="paged cache manager"):
        GenerationEngine(model, None, gen)


def test_int8_kv_refuses():
    cfg = get_model_config("tiny-sambay")
    with pytest.raises(ValueError, match="int8 KV"):
        dataclasses.replace(cfg, kv_cache_dtype="int8")
    with pytest.raises(ValueError, match="per-layer spec"):
        ModelConfig(vocab_size=8, hidden_size=8, intermediate_size=8,
                    num_layers=2, num_heads=2, num_kv_heads=2, norm="layer")


# ------------------------------------------------- the published config

def test_hf_config_gives_the_published_model():
    row = next(json.loads(line) for line in open(
        "/opt/skills/guides/model-configs/architectures.jsonl")
        if '"Phi-4-mini-flash-reasoning"' in line) \
        if Path("/opt/skills/guides/model-configs/architectures.jsonl"
                ).is_file() else None
    hf = row["config"] if row else json.loads((
        ROOT / "perfbench/configs/phi4_mini_flash_serve.json").read_text())
    cfg = hf_config_to_model_config(hf, dtype="bfloat16",
                                    param_dtype="bfloat16")
    assert cfg.layer_spec == sambay_layers(32, 512)
    assert (cfg.arch, cfg.norm, cfg.tie_embeddings) == ("llama", "layer",
                                                        True)
    assert (cfg.ssm_inner_, cfg.ssm_state_size, cfg.ssm_conv_width,
            cfg.ssm_dt_rank_, cfg.head_dim_) == (5120, 16, 4, 160, 64)
    model = Transformer(cfg)
    shapes = jax.eval_shape(model.init, jax.random.key(0))
    count = lambda tree: sum(int(np.prod(x.shape))  # noqa: E731
                             for x in jax.tree_util.tree_leaves(tree))
    per_layer = {k: count(v) // next(iter(v.values())).shape[0]
                 for k, v in shapes["layers"].items()}
    mlp = 3 * 2560 * 10240
    norms = 4 * 2560
    # the issue's arithmetic: a Mamba mixer 41.2M, a self-attention
    # 19.7M, a GMU 26.2M, a cross-attention 13.1M, beside a 78.6M MLP
    mamba = (2560 * 10240 + 5120 * 192 + 160 * 5120 + 5120 * 2560
             + 4 * 5120 + 5120 + 5120 + 5120 * 16 + 5120)
    attn = 2560 * 5120 + 5120 + 2560 * 2560 + 2560 + 4 * 64 + 128
    cross = 2 * (2560 * 2560 + 2560) + 4 * 64 + 128
    assert per_layer == {
        "00s2_ssm": mlp + norms + mamba,
        "01s2_diff_attention": mlp + norms + attn,
        "16s1_ssm": mlp + norms + mamba,
        "17s1_diff_attention": mlp + norms + attn,
        "18s2_gmu": mlp + norms + 2 * 2560 * 5120,
        "19s2_cross_diff_attention": mlp + norms + cross}
    assert round(mamba / 1e6, 1) == 41.2 and round(attn / 1e6, 1) == 19.7
    assert round(cross / 1e6, 1) == 13.1
    total = count(shapes)
    assert "lm_head" not in shapes                    # tied
    assert round(total / 1e9, 2) == 3.85
    assert 7.70 <= 2 * total / 1e9 < 7.71             # bf16
    # the cache the serving cell holds: one paged layer of 10 x 128 pair
    # rows, eight window layers, nine states of [16, 5120] float32
    spec = model.cache_spec()
    assert [(a.kind, a.layers, a.shape) for a in spec] == [
        ("paged", 1, (1280,)), ("paged", 1, (1280,)),
        ("paged_window", 8, (1280,)), ("paged_window", 8, (1280,)),
        ("state", 9, (16, 5120)), ("state", 9, (3, 5120))]
    assert model.hybrid.shared_readers == 8
    state = 9 * (16 * 5120 * 4 + 3 * 5120 * 2)
    assert state == 9 * 358_400


# ---------------------------------------- spans, scopes and the programs

def test_device_scopes_are_in_the_compiled_steps(model_and_params):
    model, params = model_and_params
    eng = _engine(model, params, num_slots=2)
    eng.submit(_prompts([10])[0], 3)
    eng.run_until_drained(max_steps=50)
    want = {"ssm_mixer", "gmu", "swa_attention", "full_attention",
            "cross_attention", "embed"}
    assert want <= set(DEVICE_SCOPES)
    for program in (r"jit__decode_fn", r"jit__prefill_chunk_fn"):
        ops = " ".join(compiled_scopes(program).values())
        for scope in want:
            assert re.search(rf"[/(]{scope}[/)]", ops), (program, scope)
    eng.close()


@pytest.mark.parametrize("preset", ["tiny", "tiny-mla-moe"])
def test_paged_steps_of_one_kind_models_lower_as_before(preset):
    """The decode and chunk programs of a dense and of a latent-attention
    + routed-experts model, lowered, against the digests taken on the
    commit before the layer spec (tests/fixtures/paged_step_hlo.json):
    a model of one kind of layer is one run and lowers to the program it
    lowered to. A change that means to move these programs regenerates
    the fixture and says so; the failure names the operations whose
    counts differ."""
    want = json.loads((ROOT / "tests/fixtures/paged_step_hlo.json"
                       ).read_text())
    model = Transformer(get_model_config(preset))
    params = model.init(jax.random.key(0))
    gen = GenerationConfig(max_new_tokens=4, do_sample=False,
                           eos_token_id=-1, pad_token_id=0)
    eng = ServingEngine(model, params, gen, ServingConfig(
        page_size=4, num_pages=32, num_slots=2, max_model_len=32,
        prefill_chunk=8))
    args = {
        "decode": (eng._decode_fn, jnp.zeros(
            (2, eng._decode_layout.width), jnp.int32)),
        "prefill_chunk": (eng._prefill_chunk_fn, jnp.zeros(
            (eng._chunk_layout.width,), jnp.int32))}
    for name, (fn, packed) in args.items():
        text = jax.jit(fn, donate_argnums=1).lower(
            params, eng.cache.pools, packed).as_text()
        ops = {}
        for op in re.findall(r"= ([a-z_]+\.[a-z_.]+)", text):
            ops[op] = ops.get(op, 0) + 1
        was = want[f"{preset}/{name}"]
        differ = {op: (was["ops"].get(op, 0), ops.get(op, 0))
                  for op in set(ops) | set(was["ops"])
                  if ops.get(op, 0) != was["ops"].get(op, 0)}
        assert not differ, f"{preset}/{name}: (before, now) {differ}"
        assert hashlib.sha256(text.encode()).hexdigest() == was["sha256"], \
            f"{preset}/{name}: same operations, another program text"
    eng.close()
