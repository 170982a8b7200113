"""A decoder of Mamba-2 layers beside a grouped-query attention layer, with
routed experts and a shared expert in every layer and the four muP scalars
(``model_type: granitemoehybrid``, the ``tiny-granite-h`` preset: the
published period M x 5, A, M x 4 at toy widths), served by the paged
``ServingEngine`` and held, on logits, to the benchmark's plain float32
reference ``perfbench/reference/granite4h_block.py``.

Tolerances. Float32 against float32 differ by the order of the sums alone
(the chunked SSD form against the sequential recurrence, a softmax over
cached + fresh columns against one over the whole row, a grouped matmul
against one expert at a time): 1e-4 on a log-probability is a hundred
times what was read (1e-6) and a tenth of the smallest fault planted below
(a muP scalar at 1, the norm's gate left out: 1.7e-3 and more; the
attention scale is the smallest, one attention layer in ten). A bfloat16
engine keeps 8 bits and is held to 0.08; it fails the float32 limit.
"""
import dataclasses
import hashlib
import importlib.util
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dla_tpu.generation.engine import GenerationConfig
from dla_tpu.models.config import LayerSpec, get_model_config
from dla_tpu.models.hf_import import hf_config_to_model_config
from dla_tpu.models.hybrid import Run
from dla_tpu.models.transformer import Transformer
from dla_tpu.ops.selective_scan import ssd_chunk, ssd_step
from dla_tpu.serving import ServingConfig, ServingEngine
from dla_tpu.telemetry.xla_introspect import compiled_scopes
from dla_tpu.utils.profiling import DEVICE_SCOPES

ROOT = Path(__file__).resolve().parents[1]
CONFIG = ROOT / "perfbench/configs/granite4_h_small_serve_p10_ep2.json"
TOL_F32 = 1e-4
TOL_BF16 = 0.08
PAGE, CHUNK = 4, 8

#: the tiny model's Hugging Face keys, as the reference reads them
HF = dict(num_hidden_layers=10, hidden_size=64, num_attention_heads=4,
          num_key_value_heads=2, rms_norm_eps=1e-5,
          layer_types=["mamba"] * 5 + ["attention"] + ["mamba"] * 4,
          mamba_expand=2, mamba_n_heads=8, mamba_d_state=8,
          num_experts_per_tok=3, embedding_multiplier=12.0,
          residual_multiplier=0.22, attention_multiplier=1.0,
          logits_scaling=16.0)


def _load(path, name):
    spec = importlib.util.spec_from_file_location(name, ROOT / path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.fixture(scope="module")
def ref():
    return _load("perfbench/reference/granite4h_block.py", "granite_ref")


def _moved(params, key=1, scale=0.05):
    """Every leaf moved off its initial value, so norms and biases count;
    the embedding (which is the head) grown twentyfold so that the logits
    over 16 spread over a few nats and a fault shows."""
    leaves, tree = jax.tree_util.tree_flatten(params)
    keys = jax.random.split(jax.random.key(key), len(leaves))
    out = jax.tree_util.tree_unflatten(tree, [
        leaf + scale * jax.random.normal(k, leaf.shape, leaf.dtype)
        for leaf, k in zip(leaves, keys)])
    out["embed"] = {"embedding": out["embed"]["embedding"] * 20.0}
    return out


@pytest.fixture(scope="module")
def model_and_params():
    model = Transformer(get_model_config("tiny-granite-h"))
    return model, _moved(model.init(jax.random.key(0)))


def _reference_logprobs(ref, params, tokens, hf=HF, **kw):
    """[T, V] log-probabilities of the reference's forward over
    ``tokens``: row t is the distribution of token t + 1."""
    hidden = ref.hidden_states(
        np.asarray(tokens), params["embed"]["embedding"],
        lambda l: ref.take_layer(params["layers"], l),
        params["final_norm"], hf, **kw)
    logits = ref.logits(hidden, params["embed"]["embedding"],
                        hf["logits_scaling"])
    return np.asarray(jax.nn.log_softmax(logits, axis=-1))


def _apply_logprobs(model, params, tokens):
    with jax.default_matmul_precision("highest"):
        logits = jax.jit(model.apply)(params, jnp.asarray(tokens)[None])[0]
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


# ------------------------------------------------------- the model alone

def test_published_file_gives_the_cut_model():
    """The benchmark's configuration through the program's importer: one
    whole period of 10 layers (9 Mamba-2, attention at layer 5), 36 of 72
    experts held, the shared expert of 1,536 and the four muP scalars."""
    hf = json.loads(CONFIG.read_text())
    cfg = hf_config_to_model_config(hf)
    assert cfg.num_layers == 10 and len(cfg.layers) == 10
    assert [l for l, s in enumerate(cfg.layers) if s.mixer == "attention"] \
        == [5]
    assert sum(s == LayerSpec("ssm2", "state") for s in cfg.layers) == 9
    assert (cfg.num_experts, cfg.experts_held_, cfg.moe_first_expert,
            cfg.num_experts_per_token, cfg.expert_width_,
            cfg.num_shared_experts) == (72, 36, 0, 10, 768, 2)
    assert (cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state_size,
            cfg.ssm_groups, cfg.ssm_chunk, cfg.ssm_inner_) == (
        128, 64, 128, 1, 256, 8192)
    assert (cfg.embedding_multiplier, cfg.residual_multiplier,
            cfg.attention_multiplier, cfg.logits_scaling) == (
        12.0, 0.22, 0.0078125, 16.0)
    assert cfg.tie_embeddings and cfg.head_dim_ == 128
    model = Transformer(cfg)
    assert model.hybrid.runs == (Run(0, 1, 5), Run(5, 1, 1), Run(6, 1, 4))
    spec = model.cache_spec()
    assert [(a.kind, a.layers, a.shape) for a in spec] == [
        ("paged", 1, (1024,)), ("paged", 1, (1024,)),
        ("state", 9, (128, 64, 128)), ("state", 9, (3, 8448))]
    assert spec[2].dtype == jnp.float32


def test_published_parameter_count():
    """XLA-free: the shapes ``init`` would make, against the issue's
    arithmetic (4.963B parameters here, 9.24 GiB bf16)."""
    cfg = hf_config_to_model_config(json.loads(CONFIG.read_text()))
    shapes = jax.eval_shape(Transformer(cfg).init, jax.random.key(0))
    total = sum(int(np.prod(x.shape))
                for x in jax.tree_util.tree_leaves(shapes))
    assert total == 4_962_732_672


@pytest.mark.parametrize("block", [4, 5, 16])
def test_ssd_chunk_matches_the_recurrence(block):
    """The chunked SSD form against token after token of the recurrence:
    13 tokens in blocks of 4 (edges inside, a padded last block), 5 and 16
    (one block, padded); row 1's last four tokens are padding (dt 0) and
    move neither its state nor anything after them."""
    rs = np.random.RandomState(0)
    b, t, h, p, n = 2, 13, 3, 4, 5
    x = jnp.asarray(rs.randn(b, t, h, p), jnp.float32)
    dt = jnp.asarray(np.abs(rs.randn(b, t, h)) * 0.5, jnp.float32)
    dt = dt.at[1, 9:].set(0.0)
    a = -jnp.asarray(rs.uniform(1, 4, (h,)), jnp.float32)
    bm = jnp.asarray(rs.randn(b, t, n), jnp.float32)
    cm = jnp.asarray(rs.randn(b, t, n), jnp.float32)
    d = jnp.asarray(rs.randn(h), jnp.float32)
    s0 = jnp.asarray(rs.randn(b, h, p, n), jnp.float32)
    state, ys = s0, []
    for i in range(t):
        y, state = ssd_step(x[:, i], dt[:, i], a, bm[:, i], cm[:, i], d,
                            state)
        ys.append(y)
    got, last = ssd_chunk(x, dt, a, bm, cm, d, s0, block=block)
    assert np.abs(np.asarray(got) - np.stack(ys, 1)).max() < 1e-5
    assert np.abs(np.asarray(last - state)).max() < 1e-5
    # the padded tokens left row 1's state where token 8 left it
    _, at8 = ssd_chunk(x[:, :9], dt[:, :9], a, bm[:, :9], cm[:, :9], d, s0,
                       block=block)
    assert np.abs(np.asarray(last[1] - at8[1])).max() < 1e-5


def test_apply_matches_the_reference(model_and_params, ref):
    model, params = model_and_params
    tokens = _prompts([37])[0]
    got = _apply_logprobs(model, params, tokens)
    want = _reference_logprobs(ref, params, tokens)
    assert np.abs(got - want).max() < TOL_F32
    assert want.max(-1).mean() - want.min(-1).mean() > 3.0  # logits spread


# --------------------------------------------------- through the engine

@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_chunked_prefill_and_paged_decode_match_the_reference(
        model_and_params, ref, dtype):
    """Prompts of several chunks with a ragged last one (chunks of 8 run
    two SSD blocks of 4), five requests through three slots so that slots
    are freed and reused: a stale state or row would show. A bfloat16
    engine fails the float32 limit and meets its own."""
    model, params = model_and_params
    if dtype == "bfloat16":
        model = Transformer(dataclasses.replace(model.cfg, dtype="bfloat16"))
    eng = _engine(model, params)
    prompts = _prompts([19, 5, 23, 11, 17])
    new = [30, 28, 12, 33, 9]
    rids = [eng.submit(p, n) for p, n in zip(prompts, new)]
    while eng.has_work():
        eng.step()
        eng.scheduler.assert_consistent()
    assert eng.cache.allocator.used_count == 0
    worst = 0.0
    tol = TOL_F32 if dtype == "float32" else TOL_BF16
    for rid, prompt, n in zip(rids, prompts, new):
        res = eng.result(rid)
        assert len(res.generated) == n
        seq = prompt + list(res.generated)
        logp = _reference_logprobs(ref, params, seq[:-1])[len(prompt) - 1:]
        chosen = np.asarray(res.generated)
        picked = logp[np.arange(n), chosen]
        err = np.abs(picked - np.asarray(res.generated_logprobs)).max()
        worst = max(worst, float(err))
        assert err < tol and (logp.max(-1) - picked).max() < tol, (rid, err)
    if dtype == "bfloat16":
        assert worst > 10 * TOL_F32
    eng.close()


def _held_state(model, params):
    """(the driver module, tokens fed, the state) of a slot still decoding,
    as the benchmark's ``correct`` reads it."""
    driver = _load("perfbench/drivers/serve_closed_loop_ssm_moe.py",
                   "granite_driver")
    eng = _engine(model, params, num_slots=2)
    prompt = _prompts([21], seed=4)[0]
    rid = eng.submit(prompt, 20)
    while len(eng.result(rid).generated) < 6:
        eng.step()
    fed, got = driver.slot_state(eng, np.random.default_rng(0))
    assert fed == prompt + eng.result(rid).generated[:5]
    eng.close()
    return driver, fed, got


def test_a_decoding_slots_state_is_the_references(model_and_params, ref):
    """What the benchmark's ``correct`` reads of the state: a slot still
    decoding, read back from the pool (``slot_state`` of the driver), holds
    the state the reference reaches after the same tokens (the prompt and
    every answer token but the newest), in float32 precision; the
    reference in low precision (e4m3 operands, bfloat16 state) is off by
    far more."""
    model, params = model_and_params
    driver, fed, got = _held_state(model, params)
    assert driver.f32_share(got) > 0.99
    ids = np.zeros((32,), np.int32)
    ids[:len(fed)] = fed
    errors = {}
    for lowp in (False, True):
        _, want = ref.hidden_states(
            ids, params["embed"]["embedding"],
            lambda l: ref.take_layer(params["layers"], l),
            params["final_norm"], HF, lowp=lowp, n_valid=len(fed),
            states=True)
        want = np.stack([np.asarray(s) for s in want])
        assert want.shape == got.shape == (9, 8, 16, 8)
        errors[lowp] = np.abs(got - want).max() / np.abs(want).max()
    assert errors[False] < TOL_F32 and errors[True] > 100 * TOL_F32


def test_a_bfloat16_state_is_seen(model_and_params, monkeypatch):
    """The deliberately wrong engine of the benchmark's limits: both
    programs round the state to bfloat16 after their scan. It reaches the
    compiled steps, and no element of the state read back carries float32
    precision."""
    from dla_tpu.models import hybrid
    step, chunk = hybrid.ssd_step, hybrid.ssd_chunk

    def rounded(fn):
        def wrapped(*args, **kw):
            y, state = fn(*args, **kw)
            return y, jax.lax.reduce_precision(state, exponent_bits=8,
                                               mantissa_bits=7)
        return wrapped
    monkeypatch.setattr(hybrid, "ssd_step", rounded(step))
    monkeypatch.setattr(hybrid, "ssd_chunk", rounded(chunk))
    model, params = model_and_params
    driver, _, got = _held_state(model, params)
    assert driver.f32_share(got) == 0.0


# ------------------------------------------------------------ the experts

def test_expert_shares_sum_to_the_uncut_layer(model_and_params, ref):
    """Two chips' shares of the 8 experts (0..3 and 4..7): each computes
    its routed part and the shared expert; the routed parts of both, with
    the shared expert counted once, are the uncut reference layer. The
    program's own dropless layer, given each share, gives the same
    parts."""
    model, params = model_and_params
    layer = ref.take_layer(params["layers"], 2)
    h = jax.random.normal(jax.random.key(5), (23, 64), jnp.float32)
    whole_r, whole_s = ref.expert_layer(h, layer, HF)
    parts = []
    for first in (0, 4):
        share = {**layer, **{k: layer[k][first:first + 4]
                             for k in ref.EXPERT_LEAVES}}
        parts.append(ref.expert_layer(h, share, HF, experts=(first, 4)))
        cfg = dataclasses.replace(model.cfg, moe_first_expert=first,
                                  moe_experts_held=4)
        prog = Transformer(cfg)
        with jax.default_matmul_precision("highest"):
            out, _ = prog._mlp(share, h[None], lambda n, x: x @ share[n],
                               dropless=True)
        assert np.abs(np.asarray(out[0]) - np.asarray(
            parts[-1][0] + parts[-1][1])).max() < 1e-5
    summed = parts[0][0] + parts[1][0] + parts[0][1]
    assert np.abs(np.asarray(summed - whole_r - whole_s)).max() < 1e-5
    assert np.abs(np.asarray(parts[0][1] - parts[1][1])).max() == 0.0
    assert np.abs(np.asarray(parts[0][0])).max() > 1e-3   # both shares add
    assert np.abs(np.asarray(parts[1][0])).max() > 1e-3


# ------------------------------------------------------ faults are seen

@pytest.mark.parametrize("fault", [
    "residual_multiplier", "embedding_multiplier", "attention_multiplier",
    "logits_scaling", "ungated_norm"])
def test_a_wrong_engine_is_seen(model_and_params, ref, fault):
    """A muP scalar left at 1 (the attention scale at head_dim ** -0.5) in
    the program, or the reference's norm without its gate: each is off
    the other by ten times the float32 limit and more."""
    model, params = model_and_params
    tokens = _prompts([29], seed=3)[0]
    want = _reference_logprobs(ref, params, tokens)
    if fault == "ungated_norm":
        got = _reference_logprobs(ref, params, tokens, gate=False)
    else:
        wrong = {fault: 0.0 if fault == "attention_multiplier" else 1.0}
        got = _apply_logprobs(Transformer(dataclasses.replace(
            model.cfg, **wrong)), params, tokens)
    assert np.abs(got - want).max() > 10 * TOL_F32


# -------------------------------------------------------------- refusals

#: the published file with one key changed -> what the refusal names
IMPORT_REFUSALS = {
    "mamba_n_groups": (2, "mamba_n_groups"),
    "position_embedding_type": ("rope", "position_embedding_type"),
    "time_step_limit": ([0.0, 0.1], "time_step_limit"),
    "tie_word_embeddings": (False, "untied"),
    "mamba_proj_bias": (True, "mamba_proj_bias"),
}


@pytest.mark.parametrize("key", sorted(IMPORT_REFUSALS))
def test_importer_refuses_by_name(key):
    hf = json.loads(CONFIG.read_text())
    value, named = IMPORT_REFUSALS[key]
    with pytest.raises(ValueError, match=named):
        hf_config_to_model_config({**hf, key: value})


def test_config_refuses_mamba_1_and_2_in_one_spec():
    cfg = get_model_config("tiny-granite-h")
    layers = (LayerSpec("ssm", "state"),) + cfg.layers[1:]
    with pytest.raises(ValueError, match=r"Mamba-1 \(ssm\) and Mamba-2"):
        dataclasses.replace(cfg, layers=layers)
    with pytest.raises(ValueError, match="ssm_heads x ssm_head_dim"):
        dataclasses.replace(cfg, ssm_head_dim=8)


def test_a_granite_file_never_imports_as_mixtral():
    """``num_local_experts`` alone chose the Mixtral branch: a Granite
    4.0-H file under another model_type is refused by name, and under its
    own name it is the layer-spec model (no rotary, Mamba-2 layers)."""
    hf = json.loads(CONFIG.read_text())
    for other in ("granitemoehybrid_next", ""):
        with pytest.raises(ValueError, match="not the Mixtral block"):
            hf_config_to_model_config({**hf, "model_type": other})
    cfg = hf_config_to_model_config(hf)
    assert cfg.layers is not None and cfg.layers[0].mixer == "ssm2"
    mixtral = {k: hf[k] for k in (
        "vocab_size", "hidden_size", "intermediate_size", "num_hidden_layers",
        "num_attention_heads", "num_key_value_heads", "num_local_experts",
        "num_experts_per_tok")}
    plain = hf_config_to_model_config({**mixtral, "model_type": "mixtral"})
    assert plain.layers is None and plain.num_experts == 36


# ---------------------------------------- spans, scopes and the programs

def test_scopes_and_counters_are_in_the_compiled_steps(model_and_params):
    model, params = model_and_params
    eng = _engine(model, params, num_slots=2)
    eng.submit(_prompts([10])[0], 3)
    eng.run_until_drained(max_steps=50)
    want = {"ssm_mixer", "ssm_scan", "full_attention", "moe_router",
            "moe_experts", "moe_shared", "embed"}
    assert want <= set(DEVICE_SCOPES)
    for program in (r"jit__decode_fn", r"jit__prefill_chunk_fn"):
        ops = " ".join(compiled_scopes(program).values())
        for scope in want:
            assert f"/{scope}" in ops or f"({scope}" in ops, (program, scope)
        # the Mamba-2 mixer under the scopes a Mamba-1 one has (a spec
        # holds one kind or the other), so their readers read it
        assert "ssm_mixer/ssm_scan" in ops, program
    snap = eng.metrics.snapshot()
    assert snap["serving/kv_paged_layers"] == 1
    assert snap["serving/state_bytes_per_slot"] == 9 * (
        8 * 16 * 8 * 4 + 3 * 144 * 4)
    # 10 real tokens in two chunks through nine Mamba-2 layers
    assert snap["serving/prefill/chunks"] == 2
    assert snap["serving/prefill/scan_tokens"] == 10 * 9
    # two decode steps of one running slot: 3 choices in each of 10
    # layers land on held experts (all 8 are held)
    assert snap["serving/moe/expert_assignments"] == 2 * 10 * 3
    assert 10 <= snap["serving/moe/experts_hit"] <= 2 * 10 * 3
    eng.close()


def paged_steps(name):
    """A preset's two paged programs, lowered, and their logits over a
    live cache (the recipe of tests/test_jamba_serving.py
    ``sambay_paged_steps``), as sha256 digests."""
    model = Transformer(get_model_config(name))
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
    for program, (fn, packed) in lowered.items():
        text = jax.jit(fn, donate_argnums=1).lower(
            params, cache.pools, packed).as_text()
        out[f"{program}/hlo_sha256"] = hashlib.sha256(
            text.encode()).hexdigest()
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
    out["decode/logits_sha256"] = hashlib.sha256(
        np.asarray(logits[slot], np.float32).tobytes()).hexdigest()
    chunk = eng._chunk_layout.pack(
        block_tables=cache.block_tables[slot],
        window_tables=cache.window_tables[slot],
        ids=np.asarray(prompt[8:16], np.int32), start=np.int32(8),
        nvalid=np.int32(8), adapter=eng.adapter_idx[slot],
        slot=np.int32(slot))
    _, logits = jax.jit(eng._prefill_chunk_fn)(
        params, cache.pools, jnp.asarray(chunk))
    out["prefill_chunk/logits_sha256"] = hashlib.sha256(
        np.asarray(logits[0], np.float32).tobytes()).hexdigest()
    eng.close()
    return out


@pytest.mark.parametrize("name, fixture", [
    ("tiny-jamba", "jamba_paged_steps.json"),
    ("tiny-sambay", "sambay_paged_steps.json")])
def test_other_spec_models_paged_steps_are_what_they_were(name, fixture):
    """The Jamba and SambaY presets through both paged steps, against
    their fixtures (jamba's taken on this PR's parent): the same lowered
    programs and bit-identical logits. Mamba-2, the experts in a spec'd
    model and the muP scalars at their defaults move neither."""
    want = json.loads((ROOT / "tests/fixtures" / fixture).read_text())
    got = paged_steps(name)
    for key, value in got.items():
        assert value == want[key], (name, key)
