"""The chunk's selective-scan kernel (dla_tpu/ops/selective_scan_kernel.py),
its one call site (``HybridStack._ssm`` through the paged chunk program),
the choice (``Transformer.scan_chunk_kernel``) and the counter that says
it engaged (``serving/prefill/scan_kernel_chunks``).

The kernel runs interpreted here (the CPU); the call site chooses it on a
TPU backend only, so the engine tests steer ``_tpu_backend`` and the
lowering tests hand the kernel ``interpret=False``: steering is the tests'
business, the program has no option for it."""
import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dla_tpu.generation.engine import GenerationConfig
from dla_tpu.models import transformer as T
from dla_tpu.models.config import get_model_config
from dla_tpu.ops import selective_scan_kernel as K
from dla_tpu.ops.selective_scan import (
    selective_scan_chunk,
    selective_scan_step,
)
from dla_tpu.serving import ServingConfig, ServingEngine

CHUNK = 16


# ------------------------------------------------------------ the kernel

def _case(b, t, d, n, dtype, seed=0):
    """x, dt (after softplus), A < 0, B, C, D and a carried-in state that
    is not zero."""
    rng = np.random.default_rng(seed)
    return (
        jnp.asarray(rng.standard_normal((b, t, d)), dtype),
        jnp.asarray(np.log1p(np.exp(rng.standard_normal((b, t, d)) - 1)),
                    jnp.float32),
        -jnp.exp(jnp.asarray(rng.standard_normal((n, d)) * 0.5,
                             jnp.float32)),
        jnp.asarray(rng.standard_normal((b, t, n)), dtype),
        jnp.asarray(rng.standard_normal((b, t, n)), dtype),
        jnp.asarray(rng.standard_normal((d,)), jnp.float32),
        jnp.asarray(rng.standard_normal((b, n, d)), jnp.float32))


def _token_by_token(x, dt, a, b_in, c_out, d_skip, state):
    ys = []
    for i in range(x.shape[1]):
        y, state = selective_scan_step(
            x[:, i], dt[:, i], a, b_in[:, i], c_out[:, i], d_skip, state)
        ys.append(y)
    return jnp.stack(ys, axis=1), state


@pytest.mark.parametrize("t", [16, 64, 256])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
def test_kernel_matches_both_xla_forms(dtype, t):
    """Against the associative chunk form, and against the one-token
    form applied token by token, whose order of operations is the
    kernel's (the inputs are rounded to ``dtype`` before either sees
    them; everything after is float32 in all three)."""
    args = _case(2, t, 128, 16, dtype, seed=t)
    y, state = K.selective_scan_chunk_kernel(*args)
    assert y.dtype == state.dtype == jnp.float32
    assert y.shape == (2, t, 128) and state.shape == (2, 16, 128)
    y_chunk, s_chunk = selective_scan_chunk(*args)
    np.testing.assert_allclose(y, y_chunk, rtol=2e-5, atol=2e-4)
    np.testing.assert_allclose(state, s_chunk, rtol=2e-5, atol=2e-4)
    y_step, s_step = _token_by_token(*args)
    np.testing.assert_allclose(y, y_step, rtol=1e-5, atol=2e-5)
    np.testing.assert_allclose(state, s_step, rtol=1e-5, atol=2e-5)


@pytest.mark.parametrize("d,n,t", [
    (2048, 16, 32),     # two lane tiles of 1,024
    (1152, 4, 16),      # three of 384, a state under one sublane tile
    (128, 16, 256),     # two token blocks of 128: the state crosses them
], ids=["two-lane-tiles", "d1152-n4", "two-token-blocks"])
def test_tiles_and_token_blocks(d, n, t):
    args = _case(1, t, d, n, jnp.float32, seed=d)
    assert d // K._lane_tile(d) > 1 or t // K._token_block(t) > 1
    y, state = K.selective_scan_chunk_kernel(*args)
    y_ref, s_ref = selective_scan_chunk(*args)
    np.testing.assert_allclose(y, y_ref, rtol=2e-5, atol=2e-4)
    np.testing.assert_allclose(state, s_ref, rtol=2e-5, atol=2e-4)


def test_two_half_chunks_equal_one_whole():
    x, dt, a, b_in, c_out, d_skip, state = _case(2, 64, 256, 16,
                                                 jnp.bfloat16, seed=5)
    y, s_end = K.selective_scan_chunk_kernel(x, dt, a, b_in, c_out, d_skip,
                                             state)
    y1, s_mid = K.selective_scan_chunk_kernel(
        x[:, :32], dt[:, :32], a, b_in[:, :32], c_out[:, :32], d_skip,
        state)
    y2, s_two = K.selective_scan_chunk_kernel(
        x[:, 32:], dt[:, 32:], a, b_in[:, 32:], c_out[:, 32:], d_skip,
        s_mid)
    # the same operations in the same order: equal to the bit
    np.testing.assert_array_equal(jnp.concatenate([y1, y2], axis=1), y)
    np.testing.assert_array_equal(s_two, s_end)


def test_a_tail_of_zero_steps_leaves_the_state():
    """``dt = 0`` is how pad tokens and rows that are not running are
    masked: the state after a chunk whose tail is such tokens is the
    state after its real prefix, and the prefix's outputs are the
    prefix's."""
    x, dt, a, b_in, c_out, d_skip, state = _case(1, 32, 128, 16,
                                                 jnp.float32, seed=9)
    real = 21                                # not a multiple of anything
    dt = dt.at[:, real:].set(0.0)
    y, s_end = K.selective_scan_chunk_kernel(x, dt, a, b_in, c_out, d_skip,
                                             state)
    y_ref, s_ref = _token_by_token(
        x[:, :real], dt[:, :real], a, b_in[:, :real], c_out[:, :real],
        d_skip, state)
    np.testing.assert_allclose(y[:, :real], y_ref, rtol=1e-5, atol=2e-5)
    np.testing.assert_allclose(s_end, s_ref, rtol=1e-5, atol=2e-5)
    # and a chunk of nothing but such tokens moves nothing at all
    _, s_same = K.selective_scan_chunk_kernel(
        x, jnp.zeros_like(dt), a, b_in, c_out, d_skip, state)
    np.testing.assert_array_equal(s_same, state)


@pytest.mark.parametrize("t,d,n", [
    (1, 128, 4), (24, 128, 4), (17, 128, 4), (16, 192, 4), (16, 128, 32)])
def test_kernel_refuses_what_it_does_not_take(t, d, n):
    assert not K.takes(t, d, n)
    with pytest.raises(ValueError, match="selective_scan_chunk"):
        K.selective_scan_chunk_kernel(*_case(1, t, d, n, jnp.float32))


# ------------------------------------------- the choice and the call site

@pytest.fixture
def as_on_a_tpu(monkeypatch):
    """What a TPU backend makes the call site choose, on the CPU: the
    kernel itself still runs interpreted (its default off a TPU)."""
    monkeypatch.setattr(T, "_tpu_backend", lambda: True)


def _wide_ssm(preset, **kw):
    return dataclasses.replace(get_model_config(preset), **kw)


@pytest.mark.parametrize("preset,tokens,takes", [
    ("tiny-jamba", 16, True), ("tiny-sambay", 16, True),
    ("tiny-jamba", 256, True), ("tiny-jamba", 512, True),
    ("tiny-jamba", 1, False),               # the decode program
    ("tiny-jamba", 24, False), ("tiny-jamba", 17, False),
    ("tiny", 16, False), ("tiny-mla-moe", 16, False),
], ids=str)
def test_which_chunk_programs_run_the_kernel(preset, tokens, takes,
                                             as_on_a_tpu):
    model = T.Transformer(get_model_config(preset))
    assert (model.scan_chunk_kernel(tokens) is K) is takes
    assert (model.scan_chunk_kernel(tokens) is None) is not takes


def test_no_kernel_for_channels_off_the_lanes(as_on_a_tpu):
    # d_inner = 2 x 48 = 96: not a whole number of 128-lane vectors
    model = T.Transformer(_wide_ssm(
        "tiny-jamba", hidden_size=48, num_heads=3, head_dim=16))
    assert model.cfg.ssm_inner_ % 128
    assert not model._scan_kernel_layers
    assert model.scan_chunk_kernel(16) is None


def test_no_kernel_off_a_tpu_or_on_a_mesh(monkeypatch):
    model = T.Transformer(get_model_config("tiny-jamba"))
    assert model._scan_kernel_layers
    assert model.scan_chunk_kernel(16) is None            # the CPU
    monkeypatch.setattr(T, "_tpu_backend", lambda: True)
    assert model.scan_chunk_kernel(16) is K
    mesh = jax.make_mesh((2, 4), ("data", "model"))
    with jax.sharding.set_mesh(mesh):
        # a pallas_call has no SPMD rule
        assert model.scan_chunk_kernel(16) is None


def _count_kernel_calls(monkeypatch):
    calls = []
    real = K.selective_scan_chunk_kernel

    def counted(*a, **kw):
        calls.append(a[0].shape)
        return real(*a, **kw)
    monkeypatch.setattr(K, "selective_scan_chunk_kernel", counted)
    return calls


@pytest.mark.parametrize("preset", ["tiny-jamba", "tiny-sambay"])
def test_whole_sequence_forward_keeps_xlas_form(preset, as_on_a_tpu,
                                                monkeypatch):
    """``HybridStack.forward`` is the path a backward pass would take
    and the kernel has no VJP: on a TPU too it traces
    ``selective_scan_chunk``, and the gradient's trace goes through."""
    calls = _count_kernel_calls(monkeypatch)
    model = T.Transformer(get_model_config(preset))
    params = jax.eval_shape(model.init, jax.random.key(0))
    tokens = jnp.asarray(np.random.RandomState(0).randint(3, 500, (2, 32)))

    def loss(p):
        return jnp.mean(model.apply(p, tokens) ** 2)
    grads = jax.eval_shape(jax.grad(loss), params)
    assert not calls
    assert jax.tree.structure(grads) == jax.tree.structure(params)


def _engine(model, params, **kw):
    gen = GenerationConfig(max_new_tokens=16, do_sample=False,
                           eos_token_id=-1, pad_token_id=0)
    return ServingEngine(model, params, gen, ServingConfig(**{**dict(
        page_size=4, num_pages=96, num_slots=3, max_model_len=64,
        prefill_chunk=CHUNK), **kw}))


def _prompts(lengths, seed=0):
    rs = np.random.RandomState(seed)
    return [[int(t) for t in rs.randint(3, 500, (n,))] for n in lengths]


def _drain(eng, prompts, out_len=8):
    rids = [eng.submit(p, out_len) for p in prompts]
    eng.run_until_drained(max_steps=400)
    out = [(eng.result(r).generated, eng.result(r).generated_logprobs)
           for r in rids]
    snap = eng.metrics.snapshot()
    eng.close()
    return out, snap


@pytest.mark.parametrize("preset", ["tiny-jamba", "tiny-sambay"])
def test_engine_greedy_tokens_equal_on_both_paths_and_the_counter_ticks(
        preset, monkeypatch):
    """Five requests through three slots: prompts of one chunk, of
    several, and of a last chunk that is mostly padding; the kernel
    carries a slot's state from chunk to chunk and leaves it where the
    pad tokens found it."""
    prompts = _prompts((5, 16, 37, 21, 48))
    model = T.Transformer(get_model_config(preset))
    params = model.init(jax.random.key(0))
    xla, snap = _drain(_engine(model, params), prompts)
    assert snap["serving/prefill/chunks"] > len(prompts)
    assert snap["serving/prefill/scan_kernel_chunks"] == 0
    monkeypatch.setattr(T, "_tpu_backend", lambda: True)
    calls = _count_kernel_calls(monkeypatch)
    kernel, snap = _drain(_engine(model, params), prompts)
    # once a chunk on the kernel path: the chunk program holds it
    assert snap["serving/prefill/scan_kernel_chunks"] == \
        snap["serving/prefill/chunks"] > len(prompts)
    assert calls and all(shape[1] == CHUNK for shape in calls)
    for (x_tok, x_lp), (k_tok, k_lp) in zip(xla, kernel):
        assert x_tok == k_tok
        np.testing.assert_allclose(x_lp, k_lp, atol=2e-5)


def test_a_chunk_the_kernel_does_not_take_counts_nothing(as_on_a_tpu,
                                                         monkeypatch):
    calls = _count_kernel_calls(monkeypatch)
    model = T.Transformer(get_model_config("tiny-jamba"))
    _, snap = _drain(_engine(model, model.init(jax.random.key(0)),
                             prefill_chunk=8), _prompts((5, 19)))
    assert snap["serving/prefill/chunks"] > 2
    assert snap["serving/prefill/scan_kernel_chunks"] == 0 and not calls


# ------------------------------------------------- lowered for the chip

def _lower_chunk_for_tpu(model, monkeypatch):
    """The engine's chunk and decode programs lowered for a TPU from
    here, the kernel uninterpreted, with each operation's name stack."""
    real = K.selective_scan_chunk_kernel
    params = model.init(jax.random.key(0))
    eng = _engine(model, params, page_size=16, num_pages=32, num_slots=2)
    with monkeypatch.context() as patch:
        patch.setattr(
            K, "selective_scan_chunk_kernel",
            lambda *a, **kw: real(*a, **{**kw, "interpret": False}))
        chunk = jax.jit(eng._prefill_chunk_fn, donate_argnums=1).trace(
            params, eng.cache.pools,
            jnp.zeros((eng._chunk_layout.width,), jnp.int32)).lower(
            lowering_platforms=("tpu",)).as_text(debug_info=True)
        decode = jax.jit(eng._decode_fn, donate_argnums=1).trace(
            params, eng.cache.pools,
            jnp.zeros((2, eng._decode_layout.width), jnp.int32)).lower(
            lowering_platforms=("tpu",)).as_text()
    eng.close()
    return chunk, decode


@pytest.mark.parametrize("preset", ["tiny-jamba", "tiny-sambay"])
def test_chunk_program_holds_one_kernel_a_scan_under_the_scope(
        preset, monkeypatch):
    model = T.Transformer(get_model_config(preset))
    chunk, _ = _lower_chunk_for_tpu(model, monkeypatch)
    assert "tpu_custom_call" not in chunk               # off a TPU

    monkeypatch.setattr(T, "_tpu_backend", lambda: True)
    chunk, decode = _lower_chunk_for_tpu(model, monkeypatch)
    # traced and built once; called once a position of a run that is a
    # state-space layer (the layers of a run share the call inside the
    # run's scan)
    stack = model.hybrid
    scans = sum(stack.spec[run.start + j].mixer == "ssm"
                for run in stack.runs for j in range(run.period))
    assert chunk.count("stablehlo.custom_call @tpu_custom_call") == 1
    calls = [line for line in chunk.splitlines()
             if re.search(r"\bcall @_call\w*\(", line)]
    assert len(calls) == scans > 0
    # the benchmark joins an instruction's scope from its op_name: every
    # call sits under ``ssm_mixer/ssm_scan`` as XLA's form does
    locs = dict(re.findall(r"^(#loc\d+) = (.*)$", chunk, flags=re.M))
    for line in calls:
        ref = re.search(r"loc\((#loc\d+)\)\s*$", line).group(1)
        assert re.search(r'["/]ssm_mixer/ssm_scan/', locs[ref]), locs[ref]
    # the one-token step is not the kernel's
    assert "tpu_custom_call" not in decode


@pytest.mark.parametrize("layers,on_tpu,starts", [
    ("tiny-jamba", True, True), ("tiny-jamba", False, False),
    ("tiny", True, False)])
def test_constructor_starts_the_pallas_import_off_the_critical_path(
        layers, on_tpu, starts, monkeypatch):
    started = []

    class Recorder:
        def __init__(self, target=None, args=(), name=None, daemon=None):
            self.spec = (target, args, name, daemon)

        def start(self):
            started.append(self.spec)
    monkeypatch.setattr(T.threading, "Thread", Recorder)
    monkeypatch.setattr(T, "_tpu_backend", lambda: on_tpu)
    T.Transformer(get_model_config(layers))
    if not starts:
        assert not started
        return
    (target, args, name, daemon), = started
    assert target is K.pallas and args == ()
    assert name.startswith("dla-") and daemon is True


# ------------------------------------------- compiled for a described v5e
#
# Lowering stops at the ``tpu_custom_call``; what Mosaic makes of the
# kernel's body (a replicated row load, a one-row store) and which
# ``op_name`` the compiled instruction carries only the chip's compiler
# says. It is installed here and compiles for a chip that is described,
# not attached. The topology is described inside a fixture, in this file
# alone: only the worker that runs these tests loads the TPU's library.

@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                               # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    and cannot be read back without one: keep it off around these."""
    from jax.experimental.compilation_cache import compilation_cache
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("t", [512, 256])
def test_mosaic_compiles_the_kernel_at_the_cells_shapes(t, one_chip,
                                                        no_compile_cache):
    """jamba2_3b_serve (T 512) and phi4_mini_flash_serve (T 256): d_inner
    5,120, N 16, bfloat16 activations."""
    d, n = 5120, 16

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    compiled = jax.jit(
        lambda *a: K.selective_scan_chunk_kernel(*a, interpret=False)
    ).lower(
        sds((1, t, d), jnp.bfloat16), sds((1, t, d), jnp.float32),
        sds((n, d), jnp.float32), sds((1, t, n), jnp.bfloat16),
        sds((1, t, n), jnp.bfloat16), sds((d,), jnp.float32),
        sds((1, n, d), jnp.float32)).compile()
    assert "selective_scan_chunk" in compiled.as_text()


@pytest.mark.parametrize("layers,pages,slots,pps,h,kh,scale", [
    (2, 33792, 16, 2112, 20, 1, None),      # jamba2_3b_serve
    (1, 16384, 32, 512, 32, 8, 1 / 128),    # granite4_h_small_serve_p10_ep2
], ids=["jamba", "granite"])
def test_mosaic_compiles_the_flat_paged_decode_kernel_at_the_cells_shapes(
        layers, pages, slots, pps, h, kh, scale, one_chip, no_compile_cache):
    """The paged decode kernel's flat form (ops/paged_attention.py) over
    the plain-attention pools of the two cells, rows of kv heads x 128
    stored flat; kept here, beside this file's other compiles for a
    described chip: one worker loads the TPU's library."""
    from dla_tpu.ops.paged_attention import paged_decode_attention

    def sds(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    pool = sds((layers, pages, 16, kh * 128))
    compiled = jax.jit(
        lambda q, kp, vp, tables, lengths, kn, vn, layer:
        paged_decode_attention(q, kp, vp, tables, lengths, kn, vn,
                               layer=layer, softmax_scale=scale,
                               interpret=False)
    ).lower(sds((slots, h, 128)), pool, pool,
            sds((slots, pps), jnp.int32), sds((slots,), jnp.int32),
            sds((slots, kh, 128)), sds((slots, kh, 128)),
            sds((), jnp.int32)).compile()
    assert "paged_decode_attention" in compiled.as_text()


def test_compiled_chunk_program_names_the_kernel_under_the_scope(
        one_chip, no_compile_cache, monkeypatch):
    """What ``perfbench/lib/program_scopes.py`` joins: the instruction's
    name from the trace, its ``op_name`` from ``Compiled.as_text()``."""
    from dla_tpu.telemetry.xla_introspect import hlo_scopes
    monkeypatch.setattr(T, "_tpu_backend", lambda: True)
    real = K.selective_scan_chunk_kernel
    monkeypatch.setattr(
        K, "selective_scan_chunk_kernel",
        lambda *a, **kw: real(*a, **{**kw, "interpret": False}))
    model = T.Transformer(get_model_config("tiny-jamba"))
    params = model.init(jax.random.key(0))
    eng = _engine(model, params, page_size=16, num_pages=32, num_slots=2)
    args = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip),
        (params, eng.cache.pools,
         jnp.zeros((eng._chunk_layout.width,), jnp.int32)))
    compiled = jax.jit(eng._prefill_chunk_fn, donate_argnums=1).lower(
        *args).compile()
    eng.close()
    kernels = {name: op for name, op in hlo_scopes(
        compiled.as_text()).items() if name.startswith("selective_scan")}
    assert len(kernels) == 3                 # the three runs with M layers
    assert all(re.search(r"/ssm_mixer/ssm_scan/", op)
               for op in kernels.values()), kernels
