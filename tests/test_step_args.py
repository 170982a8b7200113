"""One host-to-device put per serving dispatch (serving/step_args.py).

The step programs take their per-step host state as one packed int32
array and derive the window mask and the positions themselves. That rests
on a property of the host mirrors (``PagedKVCache``): a running slot's
``valid`` row is the prefix of length ``lengths``, a prefilling slot's the
prefix of length ``start``. Held here at every dispatch of a run that
crosses each writer of the mirrors, over dense and latent cache rows."""
import dataclasses
import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dla_tpu.generation.engine import GenerationConfig
from dla_tpu.models.config import get_model_config
from dla_tpu.models.transformer import Transformer
from dla_tpu.ops.sampling import SamplingParams
from dla_tpu.serving import ServingConfig, ServingEngine
from dla_tpu.serving import server as server_module
from dla_tpu.serving.step_args import PackedArgs

MAX_NEW = 8
#: 9 usable pages of 4 for three slots: the warm prompt's pages stay
#: cached, so the three requests that follow alias them, copy the shared
#: tail before writing into it, and cannot all grow to their last token
GEOMETRY = dict(page_size=4, num_pages=10, num_slots=3, max_model_len=24,
                prefill_chunk=4, prefix_cache=True)
SELF_DRAFT = {"enabled": True, "k": 2, "draft": "self"}
TENANCY = {"adapter_pool": {"max_adapters": 2, "max_rank": 2}}


@pytest.fixture(scope="module", params=["tiny", "tiny-mla-moe"],
                ids=["dense_rows", "latent_rows"])
def model_and_params(request):
    model = Transformer(get_model_config(request.param))
    return model, model.init(jax.random.key(7))


def _engine(model, params, **kw):
    gen = GenerationConfig(max_new_tokens=MAX_NEW, do_sample=False,
                           temperature=0.0, eos_token_id=-1)
    return ServingEngine(model, params, gen,
                         ServingConfig(**{**GEOMETRY, **kw}))


def _prompts():
    rs = np.random.RandomState(5)
    warm = [int(t) for t in rs.randint(3, 500, (10,))]
    partial = warm[:8] + [int(t) for t in rs.randint(3, 500, (5,))]
    return warm, partial


class Dispatches:
    """Wraps an engine's two ``pack`` calls: checks the mirrors at each,
    and keeps what was packed."""

    def __init__(self, eng, monkeypatch):
        self.eng = eng
        self.decode, self.chunk = [], []
        self.cows = 0
        self.window = np.arange(eng.cache.geom.slot_window)
        for layout, check in ((eng._decode_layout, self._at_decode),
                              (eng._chunk_layout, self._at_chunk)):
            monkeypatch.setattr(layout, "pack", self._wrap(layout.pack, check))
        cow = eng.cache.cow_page

        def counted(*args):
            self.cows += 1
            return cow(*args)
        monkeypatch.setattr(eng.cache, "cow_page", counted)

    @staticmethod
    def _wrap(pack, check):
        def wrapped(*lead, **values):
            packed = pack(*lead, **values)
            check(packed, values)
            return packed
        return wrapped

    def _at_decode(self, packed, values):
        eng, c = self.eng, self.eng.cache
        running = sorted(eng.scheduler.running)
        assert running and list(np.flatnonzero(values["active"])) == running
        # what the program will attend, computed the program's way
        f, _ = eng._unpack_decode(jnp.asarray(packed), None)
        valid, pos = np.asarray(f["valid"]), np.asarray(f["pos"])
        for slot in running:
            want = self.window < c.lengths[slot]
            assert np.array_equal(c.valid[slot], want), (slot, c.valid[slot])
            assert np.array_equal(valid[slot], want)
        assert np.array_equal(pos, np.broadcast_to(self.window, pos.shape))
        self.decode.append(packed)

    def _at_chunk(self, packed, values):
        eng, c = self.eng, self.eng.cache
        (slot, req), = eng.scheduler.prefilling.items()
        assert int(values["start"]) == req.prefill_pos
        assert np.array_equal(c.valid[slot], self.window < req.prefill_pos)
        assert np.array_equal(values["block_tables"], c.block_tables[slot])
        self.chunk.append(packed)


@pytest.mark.parametrize("speculative", [None, SELF_DRAFT],
                         ids=["plain", "self_draft"])
def test_valid_mirror_is_a_prefix_at_every_dispatch(
        model_and_params, monkeypatch, speculative):
    """(a) A warm prompt, then a prompt sharing two of its pages (partial
    cache hit, chunks for the rest) beside two copies of it (full hits,
    whose first decode write copies the shared tail page), in a pool too
    small for all three: the youngest is preempted and prefilled again.
    With the self-draft on, every decode phase is a speculative round."""
    model, params = model_and_params
    eng = _engine(model, params, speculative=speculative)
    seen = Dispatches(eng, monkeypatch)
    warm, partial = _prompts()
    eng.submit(warm, 6)
    eng.run_until_drained(max_steps=500)
    rids = [eng.submit(p, MAX_NEW) for p in (partial, warm, warm)]
    results = eng.run_until_drained(max_steps=2000)
    eng.scheduler.assert_consistent()
    assert all(len(results[r].generated) == MAX_NEW for r in rids)
    assert results[rids[1]].generated == results[rids[2]].generated

    snap = eng.metrics.snapshot()
    assert snap["serving/prefill/tokens_saved"] >= 8 + 2 * len(warm)
    assert snap["serving/prefill/chunks"] == len(seen.chunk) >= 5
    assert snap["serving/preemptions"] >= 1
    assert seen.cows >= 1
    assert len(seen.decode) == snap["serving/decode_steps"] > 0
    if speculative:
        assert snap["serving/spec/rounds"] > 0
        assert eng.spec_draft_compiles == eng.spec_verify_compiles == 1
    else:
        assert eng.decode_compiles == 1
    assert eng.prefill_chunk_compiles == 1
    assert not hasattr(eng.cache, "pos")    # the mirror nothing read is gone


def _fields(rs, layout, lead):
    """Random values for every field of ``layout``, awkward ones first."""
    out = {}
    for name, width, dtype in layout.fields:
        shape = lead + ((width,) if width > 1 else ())
        if dtype == np.float32:
            v = rs.standard_normal(shape).astype(np.float32)
            v.flat[:4] = [0.7, np.nextafter(np.float32(1), np.float32(0)),
                          1e-30, -0.0][:v.size]
        elif dtype == np.uint32:
            v = rs.randint(0, 2 ** 32, shape, dtype=np.uint64).astype(
                np.uint32)
            v.flat[:3] = [2 ** 32 - 1, 2 ** 31, 2 ** 31 + 12345][:v.size]
        elif dtype == np.bool_:
            v = rs.rand(*shape) < 0.5
        else:
            v = rs.randint(-2 ** 31, 2 ** 31, shape, dtype=np.int64).astype(
                np.int32)
        out[name] = v
    return out


@pytest.mark.parametrize("tenancy", [None, TENANCY],
                         ids=["base", "tenancy"])
def test_packed_array_round_trips_every_field(model_and_params, tenancy):
    """(b) Both of an engine's layouts: what the program unpacks is what
    the host packed, bit for bit, seeds above 2**31 and the floats' bit
    patterns included; and the pack shares no memory with what it was
    filled from (the dispatch may alias it, the mirrors are written
    next)."""
    model, params = model_and_params
    eng = _engine(model, params, tenancy=tenancy)
    geom = eng.cache.geom
    rs = np.random.RandomState(0)
    for layout, lead in ((eng._decode_layout, (geom.num_slots,)),
                         (eng._chunk_layout, ())):
        names = [n for n, _, _ in layout.fields]
        assert ("adapter" in names) is (tenancy is not None)
        assert "valid" not in names and "pos" not in names
        values = _fields(rs, layout, lead)
        packed = layout.pack(*lead, **values)
        assert packed.dtype == np.int32
        assert packed.shape == lead + (layout.width,)
        assert packed.nbytes == layout.nbytes(*lead)
        assert not any(np.shares_memory(packed, v) for v in values.values())
        assert not np.shares_memory(packed, layout.pack(*lead, **values))
        out = jax.jit(layout.unpack)(packed)
        assert sorted(out) == sorted(names)
        for name, _, dtype in layout.fields:
            got = np.asarray(out[name])
            assert got.dtype == dtype and got.shape == values[name].shape
            assert got.tobytes() == values[name].tobytes(), name


def test_pack_refuses_a_silent_cast():
    layout = PackedArgs(("seed", 1, np.uint32), ("temp", 1, np.float32))
    with pytest.raises(TypeError, match="seed"):
        layout.pack(2, seed=np.zeros(2, np.int64),
                    temp=np.zeros(2, np.float32))
    with pytest.raises(ValueError, match="wide"):
        PackedArgs(("wide", 1, np.float64))


class Clocked:
    """``jax.profiler.TraceAnnotation`` stand-in and a counting proxy for
    the engine module's ``jnp``, on one logical clock."""

    def __init__(self, monkeypatch):
        self.rows, self.puts = [], []
        self.tick = itertools.count()
        rec = self

        class Annotation:
            def __init__(self, name, **kwargs):
                self.row = [name, kwargs, None, None]

            def __enter__(self):
                self.row[2] = next(rec.tick)
                rec.rows.append(self.row)
                return self

            def __exit__(self, *exc):
                self.row[3] = next(rec.tick)
                return False

        class Jnp:
            def __getattr__(self, name):
                return getattr(jnp, name)

            @staticmethod
            def asarray(x, *args, **kwargs):
                if isinstance(x, np.ndarray):
                    rec.puts.append((next(rec.tick), x.nbytes))
                return jnp.asarray(x, *args, **kwargs)

        monkeypatch.setattr(jax.profiler, "TraceAnnotation", Annotation)
        monkeypatch.setattr(jax.profiler, "StepTraceAnnotation", Annotation)
        monkeypatch.setattr(server_module, "jnp", Jnp())

    def inside(self, row):
        return [nbytes for at, nbytes in self.puts if row[2] < at < row[3]]


def _one_put_a_dispatch(eng, monkeypatch, tenant=None):
    """Serves three sampled requests and checks: ``puts`` is 1 on every
    ``serve_decode_args`` and ``serve_prefill_chunk`` span, ``h2d_bytes``
    is the packed array's, one host array crosses inside each span,
    everything else a program is handed is on the device already, and the
    registry's two counters are the spans' sums."""
    clock = Clocked(monkeypatch)
    for name in ("_decode", "_prefill_chunk", "_spec_draft", "_spec_verify"):
        program = getattr(eng, name)
        if program is None:
            continue

        def on_device(*args, program=program):
            leaves = jax.tree_util.tree_leaves(args[2:])
            assert leaves and all(isinstance(x, jax.Array) for x in leaves)
            return program(*args)
        monkeypatch.setattr(eng, name, on_device)
    warm, partial = _prompts()
    for prompt in (warm, partial, warm):
        eng.submit(prompt, MAX_NEW, tenant=tenant, sampling=SamplingParams(
            temperature=0.8, top_p=0.9, top_k=5, seed=2 ** 31 + 7))
    eng.run_until_drained(max_steps=2000)

    geom = eng.cache.geom
    spans = {"serve_decode_args": eng._decode_layout.nbytes(geom.num_slots),
             "serve_prefill_chunk": eng._chunk_layout.nbytes()}
    rows = [r for r in clock.rows if r[0] in spans]
    assert {r[0] for r in rows} == set(spans)
    for row in rows:
        assert row[1]["puts"] == 1
        assert row[1]["h2d_bytes"] == spans[row[0]]
        assert clock.inside(row) == [spans[row[0]]]
    snap = eng.metrics.snapshot()
    assert snap["serving/step_arg_puts"] == len(rows) == sum(
        r[1]["puts"] for r in rows)
    assert snap["serving/step_arg_bytes"] == sum(
        r[1]["h2d_bytes"] for r in rows)
    assert snap["serving/step_arg_puts"] == (
        snap["serving/decode_steps"] + snap["serving/prefill/chunks"])


@pytest.mark.parametrize("speculative", [None, SELF_DRAFT],
                         ids=["plain", "self_draft"])
def test_one_put_a_dispatch_and_the_counters_add_up(
        model_and_params, monkeypatch, speculative):
    """(c) over both kinds of cache row; a speculative round's two
    programs share the one put."""
    model, params = model_and_params
    _one_put_a_dispatch(_engine(model, params, speculative=speculative),
                        monkeypatch)


def test_the_adapter_row_rides_the_same_put(monkeypatch):
    """(c) with tenancy on (dense rows: latent attention takes no LoRA):
    each slot's pool row is a column of the packed array, the stacked
    pools are device arrays already, and the count stays 1."""
    model = Transformer(dataclasses.replace(
        get_model_config("tiny"), lora_r=2, lora_alpha=4.0))
    eng = _engine(model, model.init(jax.random.key(7)), tenancy=TENANCY)
    eng.publish_adapter("t0", model.init_lora(jax.random.key(3)))
    _one_put_a_dispatch(eng, monkeypatch, tenant="t0")
    assert eng.decode_compiles == eng.prefill_chunk_compiles == 1


def test_mirrors_written_after_a_dispatch_do_not_reach_it(
        model_and_params, monkeypatch):
    """(d) The race the per-argument host copies guarded: a dispatch is
    asynchronous, may alias the host array it was handed, and the engine
    writes its mirrors right after it. Scribbling over every mirror
    between each program's dispatch and its completion changes no token
    and no log-probability, and no packed array shares memory with a
    mirror."""
    model, params = model_and_params
    warm, partial = _prompts()

    def serve(scribble):
        eng = _engine(model, params)
        c = eng.cache
        mirrors = (c.block_tables, c.valid, c.lengths, c.tokens,
                   eng.samp_temp, eng.samp_top_p, eng.samp_top_k,
                   eng.samp_seed, eng.gen_pos, eng.adapter_idx)
        put = eng._put_step_args

        def apart(packed):
            assert not any(np.shares_memory(packed, m) for m in mirrors)
            return put(packed)
        monkeypatch.setattr(eng, "_put_step_args", apart)
        for name in ("_decode", "_prefill_chunk") if scribble else ():
            program = getattr(eng, name)

            def scribbled(*args, program=program):
                out = program(*args)
                kept = [m.copy() for m in mirrors]
                for m in mirrors:
                    m[...] = 1
                jax.block_until_ready(out)
                for m, k in zip(mirrors, kept):
                    m[...] = k
                return out
            monkeypatch.setattr(eng, name, scribbled)
        rids = [eng.submit(p, MAX_NEW, sampling=SamplingParams(
            temperature=0.8, top_p=0.9, top_k=0, seed=2 ** 32 - 3 - i))
            for i, p in enumerate((warm, partial, warm))]
        results = eng.run_until_drained(max_steps=2000)
        return [(results[r].generated, results[r].generated_logprobs)
                for r in rids]

    assert serve(scribble=True) == serve(scribble=False)
