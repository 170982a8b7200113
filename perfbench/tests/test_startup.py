"""``perfbench/lib/startup.py`` on hand-made records: the cut between
set-up and the rest, self time by nesting on the main thread, the events
no span covers, and the seven readers against their manifest entries. No
chip; the program is read only where a test says so."""
import types

import pytest

from perfbench.lib import startup
from perfbench.lib.manifest import Manifest
from perfbench.tests.conftest import ROOT

NEW = ("setup_trace_lower_s", "setup_compile_s", "setup_cache_misses",
       "setup_kernel_import_wait_s", "setup_build_s", "setup_jit_other_s",
       "setup_attributed_pct")
DRIVERS = {"train_packed", "serve_closed_loop", "serve_closed_loop_hf",
           "serve_closed_loop_hybrid", "serve_closed_loop_ssm_attn"}
S = 1_000_000_000       # records and events are in nanoseconds


def rec(name, t0, t1, thread=startup.MAIN, **args):
    return {"name": name, "thread": thread, "start_ns": int(t0 * S),
            "end_ns": int(t1 * S), "args": args}


def ev(kind, end, seconds, fun="jit(f)", thread=startup.MAIN):
    event = {"trace": "/jax/core/compile/jaxpr_trace_duration",
             "lower": "/jax/core/compile/jaxpr_to_mlir_module_duration",
             "compile": "/jax/core/compile/backend_compile_duration",
             "miss": startup.CACHE_MISS,
             "hit": "/jax/compilation_cache/cache_hits"}[kind]
    return (event, int(end * S), float(seconds), fun, thread)


CUT = 20 * S


def a_start():
    """A serving process's set-up, to the second: the model at 1, the
    weights' ``init`` (the harness's, no span) lowered 2 to 3 and compiled
    3 to 5, the engine at 5 with its pools inside, the decode program
    lowered 8 to 11 with a main-thread wait for the kernel's module 9 to
    10 in it (the background import 1.5 to 9.5 overlaps), compiled 11 to
    11.5, the window open from 15, the traced steps from 20; the
    reference check after the window lowers and compiles again."""
    records = [
        rec("startup_model_build", 1.0, 1.5, layers=16,
            kernel_imports="dla-paged-kernel-import"),
        rec("startup_kernel_import", 1.5, 9.5,
            thread="dla-paged-kernel-import",
            module="dla_tpu.ops.paged_attention"),
        rec("startup_pool_alloc", 5.5, 6.5, arrays=2),
        rec("startup_engine_build", 5.0, 7.0, slots=16, pages=2048),
        rec("startup_kernel_import", 9.0, 10.0,
            module="dla_tpu.ops.paged_attention"),
        rec("xla_lower", 8.0, 11.0, fn="decode", n_compiles=1),
        rec("xla_compile", 11.0, 11.5, fn="decode", n_compiles=1,
            cache_hit=1),
        # after the cut: the reference check's own programs
        rec("xla_lower", 70.0, 72.0, fn="eval", n_compiles=1),
        rec("xla_compile", 72.0, 79.0, fn="eval", n_compiles=1,
            cache_hit=0),
    ]
    events = [
        ev("trace", 2.4, 0.4, "init"), ev("lower", 3.0, 0.6, "jit(init)"),
        ev("miss", 4.9, 0.0, ""), ev("compile", 5.0, 2.0, "jit(init)"),
        # an eager op inside the engine's constructor: the span's already
        ev("compile", 6.4, 0.5, "jit(broadcast_in_dim)"),
        # the decode program's own events: inside xla_lower / xla_compile
        ev("trace", 9.9, 1.9, "_decode_fn"),
        ev("lower", 11.0, 1.0, "jit(_decode_fn)"),
        ev("hit", 11.4, 0.0, ""), ev("compile", 11.5, 0.5,
                                     "jit(_decode_fn)"),
        # another thread's compile: not the critical path's
        ev("compile", 12.0, 3.0, "jit(other)", thread="sampler-0"),
        ev("miss", 11.9, 0.0, "", thread="sampler-0"),
        # after the cut
        ev("miss", 78.0, 0.0, ""), ev("compile", 79.0, 7.0, "jit(eval)"),
    ]
    return records, events


def test_the_cut_is_the_first_traced_steps_host_ns():
    host = [("serve_schedule", 0.0, 0.1, {}),
            ("serve", 3.0, 3.1, {"step_num": 7, "host_ns": 25 * S}),
            ("serve", 2.0, 2.1, {"step_num": 6, "host_ns": CUT}),
            ("train", 4.0, 4.1, {"step_num": 1}),        # no host_ns
            ("PjitFunction(f)", 1.0, 1.1, {"host_ns": 1})]
    assert startup.cut_ns(host) == CUT
    assert startup.cut_ns(host[:1] + host[3:]) is None


def test_self_time_by_nesting_on_the_main_thread():
    records, _ = a_start()
    own = startup.main_self_seconds(records, CUT)
    # the lowering's 3 s less the wait nested in it; the background
    # import overlaps it and is neither counted nor subtracted
    assert own["xla_lower"] == pytest.approx(2.0)
    assert own["startup_kernel_import"] == pytest.approx(1.0)
    assert own["xla_compile"] == pytest.approx(0.5)
    assert own["startup_engine_build"] == pytest.approx(1.0)
    assert own["startup_pool_alloc"] == pytest.approx(1.0)
    assert own["startup_model_build"] == pytest.approx(0.5)
    # nothing after the cut, nothing twice
    assert sum(own.values()) == pytest.approx(0.5 + 2.0 + 3.0 + 0.5)


def test_events_no_span_covers_are_counted_once():
    records, events = a_start()
    # init: trace 2.0-2.4, lowering 2.4-3.0, compile 3.0-5.0; the eager
    # op and the decode program's events lie under spans; the sampler
    # thread's compile is not the main thread's
    assert startup.jit_other_seconds(records, events, CUT) == \
        pytest.approx(3.0)
    # an event that straddles a span's start counts its bare part only
    straddle = [ev("compile", 5.25, 0.5, "jit(g)")]
    assert startup.jit_other_seconds(records, straddle, CUT) == \
        pytest.approx(0.25)
    # nested trace regions (a callee inside its caller) are one interval
    nested = [ev("trace", 3.9, 0.2, "inner"), ev("trace", 4.0, 1.0, "outer")]
    assert startup.jit_other_seconds([], nested, CUT) == pytest.approx(1.0)
    assert startup.cache_misses(events, CUT) == 2      # any thread
    assert startup.cache_misses(events, 100 * S) == 3


def test_the_seven_metrics_add_up_to_no_more_than_setup():
    records, events = a_start()
    setup_s = 14.0          # process start to the window, the harness's
    got = startup.reduce(records, events, CUT, setup_s)
    assert set(got) == set(NEW)
    assert got["setup_trace_lower_s"] == pytest.approx(2.0)
    assert got["setup_compile_s"] == pytest.approx(0.5)
    assert got["setup_kernel_import_wait_s"] == pytest.approx(1.0)
    assert got["setup_build_s"] == pytest.approx(2.5)
    assert got["setup_jit_other_s"] == pytest.approx(3.0)
    assert got["setup_cache_misses"] == 2.0
    assert got["setup_attributed_pct"] == pytest.approx(100 * 9.0 / 14.0)
    assert got["setup_attributed_pct"] <= 100.0
    # every main-thread second before the window is in one metric at most:
    # the spans and bare events cover 1-1.5, 2-7, 8-11.5 = 9.0 s
    assert sum(got[name] for name in startup.DURATIONS) == \
        pytest.approx(9.0)
    # a cold start only moves the compile and the misses
    cold = [dict(r) for r in records]
    cold[6] = rec("xla_compile", 11.0, 14.0, fn="decode", n_compiles=1,
                  cache_hit=0)
    slow = startup.reduce(cold, events + [ev("miss", 13.9, 0.0, "")], CUT,
                          16.5)
    assert slow["setup_compile_s"] == pytest.approx(3.0)
    assert slow["setup_cache_misses"] == 3.0
    assert slow["setup_trace_lower_s"] == got["setup_trace_lower_s"]


@pytest.fixture(scope="module")
def manifest():
    return Manifest(ROOT)


@pytest.mark.parametrize("name", NEW)
def test_reader_matches_its_entry_and_returns_none_without_a_trace(
        manifest, name):
    entry = {m["name"]: m for m in manifest.data["per_layer"]}[name]
    reader = manifest.layer_metric(name)
    assert (reader.LAYER, reader.UNIT, reader.BETTER, reader.MOVES,
            reader.SOURCE) == (entry["layer"], entry["unit"],
                               entry["better"], entry["moves"],
                               entry["source"])
    assert entry["layer"] == "start-up" and entry["moves"] == "setup_s"
    assert set(reader.DRIVERS) == DRIVERS
    cells = [w["name"] for w in manifest.data["workloads"]]
    assert entry["workloads"] == cells           # every cell reports it
    # appended: the entries the benchmark had come first, in their order
    names = [m["name"] for m in manifest.data["per_layer"]]
    assert names[-len(NEW):] == list(NEW)
    # a rehearsal, --trace 0: nothing to read, nothing raised
    ctx = types.SimpleNamespace(trace=None, cell={"name": "no_such_cell"},
                                end_to_end={"setup_s": 9.0})
    assert reader.read(ctx) is None


def test_readers_reduce_the_programs_own_records(monkeypatch):
    """The join with the program: its records and events, through
    ``collect``, cut at a traced step's ``host_ns``."""
    import time

    import jax
    import jax.numpy as jnp
    import numpy as np
    from dla_tpu.telemetry import xla_introspect
    from dla_tpu.utils import profiling
    profiling.reset_startup_spans()
    xla_introspect.install_compile_accounting()
    xla_introspect.reset_compile_accounting()
    t0 = time.perf_counter()
    fn = xla_introspect.IntrospectedFunction(
        "decode", jax.jit(lambda x: jnp.sum(x * 3.0)))
    fn(np.ones((4, 8), np.float32))
    jax.jit(lambda x: x + 1.0)(np.ones((3,), np.float32))     # unwrapped
    cut = time.perf_counter_ns()
    setup_s = time.perf_counter() - t0
    fn(np.ones((4, 16), np.float32))        # after the cut: not set-up
    host = [("serve", 1.0, 1.1, {"step_num": 0, "host_ns": cut})]
    monkeypatch.setattr(startup.spans, "for_context",
                        lambda ctx: types.SimpleNamespace(host=host))
    monkeypatch.setattr(startup, "_READ", {})
    ctx = types.SimpleNamespace(trace=object(), cell={"name": "a_cell"},
                                end_to_end={"setup_s": setup_s})
    got = {name: startup.metric(ctx, name) for name in NEW}
    records = profiling.startup_spans()
    first = [r for r in records if r["args"]["n_compiles"] == 1]
    assert len(records) == 4 and len(first) == 2
    assert got["setup_trace_lower_s"] == pytest.approx(
        (first[0]["end_ns"] - first[0]["start_ns"]) * 1e-9)
    assert got["setup_compile_s"] == pytest.approx(
        (first[1]["end_ns"] - first[1]["start_ns"]) * 1e-9)
    assert got["setup_kernel_import_wait_s"] == got["setup_build_s"] == 0.0
    assert got["setup_cache_misses"] == 0.0      # the cache is off here
    assert got["setup_jit_other_s"] > 0.0        # the unwrapped function
    assert 0.0 < got["setup_attributed_pct"] <= 100.0
    # a program that keeps no records: None, and nothing raised
    monkeypatch.setattr(startup, "_READ", {})
    monkeypatch.setattr(startup, "collect", lambda: None)
    assert startup.metric(ctx, "setup_build_s") is None
