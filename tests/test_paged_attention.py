"""The paged decode attention kernel (dla_tpu/ops/paged_attention.py),
its one call site (``Transformer._paged_layers``, decode branch), the
counter that says it engaged (``ServingEngine._decode_span``) and the
set-up contract it ships under: no serving or training process imports
Pallas unless it runs the kernel, and the one that does traces and
lowers it once.

The kernel runs interpreted here (the CPU); the call site chooses it on
a TPU backend only, so the engine tests steer ``_tpu_backend`` and the
lowering tests hand the kernel ``interpret=False``: steering is the
tests' business, the program has no option for it."""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dla_tpu.generation.engine import GenerationConfig
from dla_tpu.models import transformer as T
from dla_tpu.models.config import ModelConfig, get_model_config
from dla_tpu.ops.attention import decode_attention
from dla_tpu.serving import ServingConfig, ServingEngine
from dla_tpu.serving import server as server_mod

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PAGE = 16


def _kernel():
    # the module imports Pallas: in a function, as the program does
    from dla_tpu.ops import paged_attention
    return paged_attention


# ------------------------------------------------------------ the kernel

def _case(lengths, *, h=8, k=2, d=128, pps=4, layers=2, dtype=jnp.bfloat16,
          seed=0):
    """Pools of ``layers`` layers with every slot's pages scattered over
    the pool, NaN in every dead column, every dead page and the trash
    page (page 0), and dead table entries pointing at the trash page."""
    rng = np.random.default_rng(seed)
    lengths = np.asarray(lengths, np.int32)
    b = len(lengths)
    pages = b * pps + 1
    kp = rng.standard_normal((layers, pages, PAGE, k, d)).astype(np.float32)
    vp = rng.standard_normal((layers, pages, PAGE, k, d)).astype(np.float32)
    tables = rng.permutation(np.arange(1, pages)).reshape(b, pps).astype(
        np.int32)
    for s in range(b):
        live = -(-int(lengths[s]) // PAGE)
        for j in range(pps):
            page = tables[s, j]
            if j >= live:
                kp[:, page] = vp[:, page] = np.nan
                tables[s, j] = 0
            elif j == live - 1 and lengths[s] % PAGE:
                kp[:, page, lengths[s] % PAGE:] = np.nan
                vp[:, page, lengths[s] % PAGE:] = np.nan
    kp[:, 0] = vp[:, 0] = np.nan

    def cast(x):
        return jnp.asarray(x, dtype)
    return dict(
        q=cast(rng.standard_normal((b, h, d))), k_pool=cast(kp),
        v_pool=cast(vp), block_tables=jnp.asarray(tables),
        lengths=jnp.asarray(lengths),
        k_new=cast(rng.standard_normal((b, k, d))),
        v_new=cast(rng.standard_normal((b, k, d))))


def _reference(c, layer, window=None, scale=None, softcap=0.0):
    """``decode_attention`` over the gathered window, as the gather path
    of ``_paged_layers`` runs it (dead columns zeroed first: the
    reference adds its mask, and NaN + mask is NaN)."""
    b, pps = c["block_tables"].shape
    s = pps * PAGE
    col = jnp.arange(s)[None]
    valid = col < c["lengths"][:, None]

    def window_of(pool):
        rows = pool[layer][c["block_tables"]].reshape(b, s, *pool.shape[3:])
        return jnp.where(valid[..., None, None], rows, 0)
    return decode_attention(
        c["q"][:, None], window_of(c["k_pool"]), window_of(c["v_pool"]),
        c["k_new"][:, None], c["v_new"][:, None], kv_valid=valid,
        q_positions=c["lengths"][:, None],
        kv_positions=jnp.broadcast_to(col, valid.shape), window=window,
        softmax_scale=scale, logit_softcap=softcap)[:, 0]


def _check(c, *, layer=1, window=None, scale=None, softcap=0.0,
           pages_per_block=2, tol=None):
    out = _kernel().paged_decode_attention(
        **c, layer=layer, window=window, softmax_scale=scale,
        logit_softcap=softcap, pages_per_block=pages_per_block,
        interpret=True)
    want = _reference(c, layer, window, scale, softcap)
    assert out.shape == want.shape and out.dtype == want.dtype
    out, want = np.asarray(out, np.float32), np.asarray(want, np.float32)
    assert np.isfinite(out).all(), "garbage (NaN) leaked out of a dead column"
    if tol is None:
        tol = 3e-2 if c["q"].dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(out, want, atol=tol, rtol=tol)


@pytest.mark.parametrize("lengths", [
    (5, 37, 64, 21),        # ragged
    (16, 32, 48, 64),       # len % 16 == 0: the last live page is full
    (1, 17, 33, 49),        # len % 16 == 1
    (15, 31, 47, 63),       # len % 16 == 15
    (0, 0, 40, 0),          # only the new token; freed slots at the trash
    (0, 0, 0, 0),           # an idle engine's step
    (64, 64, 64, 64),       # every page of every slot live
    (64, 1, 0, 33),         # a block with no live page after a full slot
], ids=lambda v: "-".join(map(str, v)))
def test_kernel_matches_decode_attention_over_the_gathered_window(lengths):
    _check(_case(lengths))


@pytest.mark.parametrize("group", [1, 4, 8])
@pytest.mark.parametrize("d", [128, 256])
def test_kernel_gqa_groups_and_head_widths(group, d):
    _check(_case((0, 19, 48), h=2 * group, k=2, d=d, pps=3))


@pytest.mark.parametrize("window", [1, 7, 16, 40, 4096])
def test_kernel_sliding_window_on_logical_positions(window):
    # lengths on both sides of the window; a window of 1 attends the new
    # token alone (delta 0), whatever the cache holds
    _check(_case((3, 16, 45, 64)), window=window)


def test_kernel_window_rides_as_a_traced_operand():
    """gemma-2's alternation: the window is a traced scalar inside the
    layer scan, one compile serves both kinds of layer."""
    c = _case((45, 64, 9, 30))
    paged = _kernel().paged_decode_attention

    @jax.jit
    def both(c, windows):
        return jax.lax.map(lambda w: paged(
            **c, layer=1, window=w, pages_per_block=2, interpret=True),
            windows)
    got = both(c, jnp.asarray([8, 2 ** 30], jnp.int32))
    for out, window in zip(got, (8, None)):
        np.testing.assert_allclose(
            np.asarray(out, np.float32),
            np.asarray(_reference(c, 1, window), np.float32),
            atol=3e-2, rtol=3e-2)


@pytest.mark.parametrize("softcap,scale", [(30.0, None), (50.0, 0.0625),
                                           (0.0, 0.0625)])
def test_kernel_softcap_and_stated_scale(softcap, scale):
    # gemma-2: heads of 256, the scale stated (query_pre_attn_scalar), the
    # cap on the scaled scores before the mask
    _check(_case((50, 3, 64), h=4, k=2, d=256, pps=4), softcap=softcap,
           scale=scale)


def test_kernel_float32_pages_agree_to_rounding():
    _check(_case((0, 17, 64, 31), dtype=jnp.float32))


@pytest.mark.parametrize("layer", [0, 2])
def test_kernel_reads_the_layer_it_is_given(layer):
    _check(_case((33, 64), layers=3), layer=layer)


@pytest.mark.parametrize("pages_per_block", [1, 3, 4, 16])
def test_kernel_block_size_does_not_change_the_result(pages_per_block):
    # 3 does not divide the 4 pages of a slot; 16 is clamped to them
    _check(_case((64, 17, 0, 50)), pages_per_block=pages_per_block)


def test_kernel_does_not_read_dead_pages():
    """Table entries past the live count may point anywhere: here at a
    page of NaN that belongs to nobody, and at another slot's page."""
    c = _case((20, 40))
    tables = np.array(c["block_tables"])
    tables[0, 2:] = tables[1, 0]
    tables[1, 3] = 0
    _check({**c, "block_tables": jnp.asarray(tables)})


def test_kernel_refuses_what_it_cannot_lay_out():
    c = _case((5,), h=18, k=2)
    with pytest.raises(ValueError, match="GQA group 9"):
        _kernel().paged_decode_attention(**c, layer=0, interpret=True)
    c = _case((5,), h=2, k=1)
    with pytest.raises(ValueError, match="32-bit words"):
        _kernel().paged_decode_attention(**c, layer=0, interpret=True)


# ------------------------------------------- the call site and the engine

def _dense(**kw):
    return ModelConfig(**{**dict(
        vocab_size=97, hidden_size=256, intermediate_size=256, num_layers=2,
        num_heads=4, num_kv_heads=2, head_dim=128, max_seq_length=64,
        dtype="float32", param_dtype="float32"), **kw})


@pytest.fixture
def as_on_a_tpu(monkeypatch):
    """What a TPU backend makes the call site choose, on the CPU: the
    kernel itself still runs interpreted (its default off a TPU)."""
    monkeypatch.setattr(T, "_tpu_backend", lambda: True)
    # the constructor's import thread is real here, and joined by the
    # in-line import: nothing to patch


@pytest.mark.parametrize("cfg,rows", [
    (_dense(), True),
    (_dense(head_dim=256, attn_logit_softcap=50.0, sliding_window=8,
            sliding_window_pattern=2, query_pre_attn_scalar=256), True),
    (_dense(num_heads=16, num_kv_heads=2), True),         # group 8
    (_dense(num_heads=32, num_kv_heads=2, hidden_size=512), False),  # 16
    (_dense(head_dim=64), False),
    (_dense(head_dim=80), False),                         # phi-2's
    (_dense(num_kv_heads=1, dtype="bfloat16"), False),    # half a word
    (_dense(kv_cache_dtype="int8"), False),
    ("tiny", False), ("tiny-gqa", False), ("tiny-mla-moe", False),
    ("tiny-sambay", False),
], ids=["dense128", "gemma2-like", "group8", "group16", "d64", "d80",
        "mqa-bf16", "int8-pages", "tiny", "tiny-gqa", "tiny-mla-moe",
        "tiny-sambay"])
def test_which_models_the_kernel_reads(cfg, rows, as_on_a_tpu):
    if isinstance(cfg, str):
        cfg = get_model_config(cfg)
    model = T.Transformer(cfg)
    assert model._paged_kernel_rows is rows
    assert (model.paged_decode_kernel() is not None) is rows


def test_no_kernel_off_a_tpu_or_on_a_mesh(monkeypatch):
    model = T.Transformer(_dense())
    assert model._paged_kernel_rows
    assert model.paged_decode_kernel() is None            # the CPU
    monkeypatch.setattr(T, "_tpu_backend", lambda: True)
    assert model.paged_decode_kernel() is not None
    mesh = jax.make_mesh((2, 4), ("data", "model"))
    with jax.sharding.set_mesh(mesh):
        # a pallas_call has no SPMD rule: GSPMD would replicate the pools
        assert model.paged_decode_kernel() is None


def _engine(model, params, **kw):
    gen = GenerationConfig(max_new_tokens=16, do_sample=False,
                           eos_token_id=-1, pad_token_id=0)
    return ServingEngine(model, params, gen, ServingConfig(**{**dict(
        page_size=4, num_pages=24, num_slots=3, max_model_len=48,
        prefill_chunk=8), **kw}))


def _drain(eng, prompts, out_len=10):
    rids = [eng.submit(p, out_len) for p in prompts]
    eng.run_until_drained(max_steps=400)
    out = [(eng.result(r).generated, eng.result(r).generated_logprobs)
           for r in rids]
    snap = eng.metrics.snapshot()
    eng.close()
    return out, snap


def _prompts(lengths, seed=0):
    rs = np.random.RandomState(seed)
    return [[int(t) for t in rs.randint(1, 97, size=n)] for n in lengths]


@pytest.mark.parametrize("cfg", [
    _dense(),
    _dense(head_dim=256, attn_logit_softcap=50.0, sliding_window=8,
           sliding_window_pattern=2, query_pre_attn_scalar=256,
           arch="gemma2"),
], ids=["dense128", "gemma2-like"])
def test_engine_greedy_tokens_equal_on_both_paths(cfg, monkeypatch):
    """Seven requests through three slots and a pool too small to hold
    them at once: admissions, finishes and page reuse; the kernel reads
    pages other requests wrote and freed."""
    prompts = _prompts((5, 9, 17, 3, 11, 8, 14))
    model = T.Transformer(cfg)
    params = model.init(jax.random.key(0))
    gather, snap = _drain(_engine(model, params), prompts)
    assert snap["serving/decode_steps_paged_kernel"] == 0
    monkeypatch.setattr(T, "_tpu_backend", lambda: True)
    kernel, snap = _drain(_engine(model, params), prompts)
    assert snap["serving/decode_steps_paged_kernel"] == \
        snap["serving/decode_steps"] > 0
    for (g_tok, g_lp), (k_tok, k_lp) in zip(gather, kernel):
        assert g_tok == k_tok
        np.testing.assert_allclose(g_lp, k_lp, atol=2e-5)


def _decode_spans(monkeypatch):
    """Record the arguments of every ``serve_decode`` span."""
    seen = []
    real = server_mod.annotate

    def recording(name, **kw):
        if name == "serve_decode":
            seen.append(kw)
        return real(name, **kw)
    monkeypatch.setattr(server_mod, "annotate", recording)
    return seen


@pytest.mark.parametrize("on_tpu", [False, True], ids=["gather", "kernel"])
def test_read_tokens_says_what_the_program_reads(on_tpu, monkeypatch):
    if on_tpu:
        monkeypatch.setattr(T, "_tpu_backend", lambda: True)
    seen = _decode_spans(monkeypatch)
    model = T.Transformer(_dense())
    eng = _engine(model, model.init(jax.random.key(0)))
    geom = eng.cache.geom
    lengths = []
    real_span = eng._decode_span

    def span(active, sampling):
        lengths.append(eng.cache.lengths[active].copy())
        return real_span(active, sampling)
    monkeypatch.setattr(eng, "_decode_span", span)
    _, snap = _drain(eng, _prompts((5, 9, 17, 3)), out_len=6)
    assert seen and len(seen) == len(lengths)
    for kw, lens in zip(seen, lengths):
        assert kw["live_tokens"] == int(lens.sum())
        if on_tpu:
            live_pages = sum(-(-int(n) // geom.page_size) for n in lens)
            assert kw["read_tokens"] == live_pages * geom.page_size
            assert kw["live_tokens"] <= kw["read_tokens"] \
                < kw["live_tokens"] + len(lens) * geom.page_size
        else:
            assert kw["read_tokens"] == geom.num_slots * geom.slot_window
    assert snap["serving/decode_steps_paged_kernel"] == (
        snap["serving/decode_steps"] if on_tpu else 0)


@pytest.mark.parametrize("preset", ["tiny-mla-moe", "tiny-sambay"])
def test_other_row_layouts_keep_the_gather_and_its_constant(
        preset, as_on_a_tpu, monkeypatch):
    seen = _decode_spans(monkeypatch)
    model = T.Transformer(get_model_config(preset))
    eng = _engine(model, model.init(jax.random.key(0)), num_slots=2,
                  max_model_len=32, num_pages=32)
    geom = eng.cache.geom
    _, snap = _drain(eng, _prompts((5, 9)), out_len=3)
    assert seen and all(
        kw["read_tokens"] == geom.num_slots * geom.slot_window
        for kw in seen)
    assert snap["serving/decode_steps_paged_kernel"] == 0


def test_speculative_round_counts_drafts_live_and_the_verify_whole(
        as_on_a_tpu, monkeypatch):
    seen = _decode_spans(monkeypatch)
    model = T.Transformer(_dense())
    eng = _engine(model, model.init(jax.random.key(0)),
                  speculative={"enabled": True, "k": 2, "draft": "self"})
    geom = eng.cache.geom
    plain, _ = _drain(eng, _prompts((5, 9)), out_len=6)
    whole = geom.num_slots * geom.slot_window
    assert seen and all(kw["read_tokens"] > whole
                        and (kw["read_tokens"] - whole)
                        % (2 * geom.page_size) == 0 for kw in seen)
    monkeypatch.setattr(T, "_tpu_backend", lambda: False)
    eng = _engine(model, model.init(jax.random.key(0)),
                  speculative={"enabled": True, "k": 2, "draft": "self"})
    gathered, _ = _drain(eng, _prompts((5, 9)), out_len=6)
    assert [t for t, _ in plain] == [t for t, _ in gathered]


# ------------------------------------------------------ the set-up contract

def test_serving_processes_that_do_not_run_the_kernel_never_import_pallas():
    """Importing the serving stack and building + stepping a latent and a
    layer-spec engine leaves Pallas out of the process: what PR 34 paid
    1.3 s of set-up for in every cell."""
    code = textwrap.dedent("""
        import sys
        import dla_tpu.serving, dla_tpu.models.transformer
        import dla_tpu.generation.engine
        import jax
        from dla_tpu.generation.engine import GenerationConfig
        from dla_tpu.models.config import get_model_config
        from dla_tpu.models.transformer import Transformer
        from dla_tpu.serving import ServingConfig, ServingEngine
        for preset in ("tiny-mla-moe", "tiny-sambay", "tiny"):
            model = Transformer(get_model_config(preset))
            eng = ServingEngine(
                model, model.init(jax.random.key(0)),
                GenerationConfig(max_new_tokens=4, do_sample=False,
                                 eos_token_id=-1, pad_token_id=0),
                ServingConfig(page_size=4, num_pages=32, num_slots=2,
                              max_model_len=32, prefill_chunk=8))
            eng.submit([3, 5, 7, 9, 11], 3)
            eng.run_until_drained(max_steps=20)
            assert eng.metrics.snapshot()["serving/decode_steps"] > 0
            eng.close()
        bad = sorted(m for m in sys.modules if "pallas" in m)
        assert not bad, bad
        print("NO-PALLAS")
    """)
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": ROOT}
    done = subprocess.run([sys.executable, "-c", code], env=env, cwd=ROOT,
                          capture_output=True, text=True, timeout=600)
    assert done.returncode == 0 and "NO-PALLAS" in done.stdout, \
        done.stderr[-2000:]


def test_only_the_kernel_module_and_its_lazy_importers_name_pallas():
    """No module imports Pallas at module level but the kernels' own
    (ops/), and nothing imports those at module level: every import of
    them sits inside the function that builds the branch."""
    import ast
    kernels = {"dla_tpu.ops.paged_attention", "dla_tpu.ops.decode_kernel",
               "dla_tpu.ops.flash_attention", "dla_tpu.ops.quant_matmul"}
    offenders = []
    for dirpath, _, files in os.walk(os.path.join(ROOT, "dla_tpu")):
        for name in files:
            if not name.endswith(".py"):
                continue
            path = os.path.join(dirpath, name)
            mod = os.path.relpath(path, ROOT)[:-3].replace(os.sep, ".")
            tree = ast.parse(open(path).read())
            for node in tree.body:           # module level only
                names = []
                if isinstance(node, ast.Import):
                    names = [a.name for a in node.names]
                elif isinstance(node, ast.ImportFrom) and node.module:
                    names = [node.module] + [
                        f"{node.module}.{a.name}" for a in node.names]
                for n in names:
                    if ("jax.experimental.pallas" in n
                            and mod not in kernels) or (
                            n in kernels and mod != n):
                        offenders.append((mod, n))
    assert not offenders, offenders


def _lower_decode_for_tpu(model, monkeypatch, counts):
    """The engine's decode program lowered for a TPU from here, the
    kernel uninterpreted; ``counts`` collects calls of the kernel's
    wrapper and of its body."""
    pa = _kernel()
    real_wrapper, real_body = pa.paged_decode_attention, pa._kernel

    def wrapper(*a, **kw):
        counts["wrapper"] = counts.get("wrapper", 0) + 1
        return real_wrapper(*a, **{**kw, "interpret": False})

    def body(*a, **kw):
        counts["body"] = counts.get("body", 0) + 1
        return real_body(*a, **kw)
    params = model.init(jax.random.key(0))
    eng = _engine(model, params, page_size=16, num_pages=16, num_slots=4,
                  max_model_len=64, prefill_chunk=16)
    packed = jnp.zeros((4, eng._decode_layout.width), jnp.int32)
    with monkeypatch.context() as patch:
        patch.setattr(pa, "paged_decode_attention", wrapper)
        patch.setattr(pa, "_kernel", body)
        text = jax.jit(eng._decode_fn, donate_argnums=1).trace(
            params, eng.cache.pools, packed).lower(
            lowering_platforms=("tpu",)).as_text()
        chunk = jax.jit(eng._prefill_chunk_fn, donate_argnums=1).trace(
            params, eng.cache.pools,
            jnp.zeros((eng._chunk_layout.width,), jnp.int32)).lower(
            lowering_platforms=("tpu",)).as_text()
    eng.close()
    return text, chunk


@pytest.mark.parametrize("cfg", [
    _dense(dtype="bfloat16"),
    _dense(dtype="bfloat16", head_dim=256, attn_logit_softcap=50.0,
           sliding_window=8, sliding_window_pattern=2,
           query_pre_attn_scalar=256, arch="gemma2"),
], ids=["dense128", "gemma2-like"])
def test_decode_program_holds_one_kernel_and_no_window_gather(
        cfg, monkeypatch):
    model = T.Transformer(cfg)
    # the window of the gather path: [slots, slot window, K, D]
    window = f"tensor<4x64x2x{cfg.head_dim_}x"
    counts = {}
    text, _ = _lower_decode_for_tpu(model, monkeypatch, counts)
    assert "tpu_custom_call" not in text and window in text and not counts

    monkeypatch.setattr(T, "_tpu_backend", lambda: True)
    text, chunk = _lower_decode_for_tpu(model, monkeypatch, counts)
    # one kernel in the layer scan, traced and built once; no gathered
    # window left in the program
    assert text.count("tpu_custom_call") == 1
    assert window not in text
    assert counts == {"wrapper": 1, "body": 1}
    # the chunk lane keeps the gather, on a TPU too
    assert "tpu_custom_call" not in chunk
    assert f"tensor<1x64x2x{cfg.head_dim_}x" in chunk


@pytest.mark.parametrize("rows,on_tpu,starts", [
    (True, True, True), (True, False, False), (False, True, False)])
def test_constructor_starts_the_import_off_the_critical_path(
        rows, on_tpu, starts, monkeypatch):
    """A model whose decode step will run the kernel starts importing
    its module on a daemon thread when it is built on a TPU backend;
    no other model, and no other backend, starts anything."""
    started = []

    class Recorder:
        def __init__(self, target=None, args=(), name=None, daemon=None):
            self.spec = (target, args, name, daemon)

        def start(self):
            started.append(self.spec)
    monkeypatch.setattr(T.threading, "Thread", Recorder)
    monkeypatch.setattr(T, "_tpu_backend", lambda: on_tpu)
    model = T.Transformer(_dense() if rows else get_model_config("tiny"))
    jax.eval_shape(model.init, jax.random.key(0))    # starts no second one
    if not starts:
        assert not started
        return
    (target, args, name, daemon), = started
    assert target is T._import_paged_kernel and args == ()
    assert name.startswith("dla-") and daemon is True


def _fresh_import_under_cache(tmp_path, cache_dir, enabled=True):
    """In a subprocess: import a throwaway module inside
    ``cached_bytecode()`` with the compile cache at ``cache_dir``;
    prints the interpreter's two switches inside and after."""
    source = tmp_path / "late_module_xyz.py"
    if not source.exists():           # a rewrite would stale the .pyc
        source.write_text("VALUE = 41 + 1\n")
    code = textwrap.dedent(f"""
        import sys
        sys.path.insert(0, {str(tmp_path)!r})
        import jax
        from dla_tpu.utils.compile_cache import cached_bytecode
        jax.config.update("jax_enable_compilation_cache", {enabled})
        jax.config.update("jax_compilation_cache_dir", {cache_dir!r})
        before = sys.pycache_prefix, sys.dont_write_bytecode
        with cached_bytecode():
            inside = sys.pycache_prefix, sys.dont_write_bytecode
            import late_module_xyz
        assert late_module_xyz.VALUE == 42
        assert (sys.pycache_prefix, sys.dont_write_bytecode) == before
        print("INSIDE", inside)
    """)
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": ROOT,
           "PYTHONDONTWRITEBYTECODE": "1"}
    done = subprocess.run([sys.executable, "-c", code], env=env, cwd=ROOT,
                          capture_output=True, text=True, timeout=300)
    assert done.returncode == 0, done.stderr[-2000:]
    return done.stdout


def test_late_imports_keep_their_bytecode_beside_the_compile_cache(
        tmp_path):
    """Where the interpreter keeps no bytecode, 0.83 s of the Pallas
    stack's 1.24 s import is ``compile()`` of its sources at every
    start: inside ``cached_bytecode()`` the ``.pyc`` land under the
    compile cache's directory, are found there by the next process, and
    the interpreter's switches are put back."""
    cache = tmp_path / "jc"
    out = _fresh_import_under_cache(tmp_path, str(cache))
    assert f"INSIDE ({str(cache / 'pycache')!r}, False)" in out
    pycs = [p for p in (cache / "pycache").rglob("*.pyc")
            if p.name.startswith("late_module_xyz.")]
    assert len(pycs) == 1
    stamp = pycs[0].stat().st_mtime_ns
    _fresh_import_under_cache(tmp_path, str(cache))
    assert pycs[0].stat().st_mtime_ns == stamp       # read, not rewritten


@pytest.mark.parametrize("cache_dir,enabled", [
    ("", True), ("gs://bucket/cache", True), ("LOCAL", False)],
    ids=["no-cache-dir", "remote-cache", "cache-off"])
def test_no_bytecode_cache_without_a_local_compile_cache(
        tmp_path, cache_dir, enabled):
    local = tmp_path / "jc"
    out = _fresh_import_under_cache(
        tmp_path, str(local) if cache_dir == "LOCAL" else cache_dir, enabled)
    assert "INSIDE (None, True)" in out
    assert not local.exists()
