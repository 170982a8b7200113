"""XLA introspection (docs/OBSERVABILITY.md "XLA introspection"):
retrace attribution via argument fingerprints, per-fn cost/memory
gauges from the AOT path, live-HBM accounting, and the analytic
roofline + 6N cross-check.

THE pins: (a) an induced recompile produces a ``compile`` flight-
recorder event naming the changed argument ``old aval -> new aval`` and
increments ``telemetry/xla/recompiles``; a steady run attributes ZERO
recompiles with the trainer's trace-time compile counter pinned at 1,
(b) XLA's analytic FLOPs agree with the 6N estimate within the
documented tolerance on a pure-matmul step and every introspected fn
gets a roofline verdict, (c) the wrapper adds ZERO extra compiles — its
``lower()`` IS the one trace.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dla_tpu.telemetry import (
    FlightRecorder,
    IntrospectedFunction,
    MetricRegistry,
    MFUCalculator,
    is_catalog_name,
    live_array_bytes,
    register_live_bytes_gauge,
)
from dla_tpu.telemetry.mfu import ESTIMATE_TOLERANCE
from dla_tpu.telemetry.xla_introspect import (
    diff_fingerprints,
    fingerprint_args,
)


# ---------------------------------------------------------------------------
# fingerprints: what re-keys, what doesn't, and how changes are named
# ---------------------------------------------------------------------------

def test_fingerprint_diff_names_the_changed_arg_old_to_new():
    a = fingerprint_args(({"ids": np.zeros((8, 16), np.int32)},
                          np.float32(0.0)))
    b = fingerprint_args(({"ids": np.zeros((8, 32), np.int32)},
                          np.float32(0.0)))
    changes = diff_fingerprints(a, b)
    assert len(changes) == 1
    assert "ids" in changes[0]["arg"]
    assert changes[0]["old"] == "int32[8,16]"
    assert changes[0]["new"] == "int32[8,32]"


def test_fingerprint_ignores_values_keys_on_aval():
    """Traced scalars change value every step (guard EMA, fault
    injectors) and must never re-key the cache — mirroring jit."""
    a = fingerprint_args((np.float32(1.0), 3))
    b = fingerprint_args((np.float32(2.0), 7))
    assert a == b
    # but a python-scalar TYPE change is a retrace, and says so
    c = fingerprint_args((np.float32(1.0), 7.5))
    assert diff_fingerprints(a, c)[0]["new"] == "weak_float[]"


def test_fingerprint_structure_change_is_one_row():
    a = fingerprint_args(({"x": np.zeros(2)},))
    b = fingerprint_args(({"x": np.zeros(2), "y": np.zeros(2)},))
    changes = diff_fingerprints(a, b)
    assert len(changes) == 1 and "structure" in changes[0]["new"]


# ---------------------------------------------------------------------------
# the wrapper: zero extra compiles, attributed recompiles, loud failures
# ---------------------------------------------------------------------------

def _wrapped(name="fn", **kw):
    """A jitted fn with a trace-time tick counter, wrapped."""
    ticks = []

    def f(x):
        ticks.append(1)              # ticks once per TRACE, not per call
        return jnp.sum(x * 2.0)

    return IntrospectedFunction(name, jax.jit(f), **kw), ticks


def test_wrapper_adds_zero_extra_compiles():
    fn, ticks = _wrapped()
    x = np.ones((4, 8), np.float32)
    outs = [float(fn(x)) for _ in range(5)]
    assert outs == [64.0] * 5        # results flow through untouched
    assert len(ticks) == 1           # the wrapper's lower() IS the trace
    assert fn.compiles == 1 and fn.recompiles == 0
    assert fn.last_event is None     # cache hit: nothing to attribute


def test_induced_recompile_emits_attributed_event_and_counters():
    reg = MetricRegistry()
    rec = FlightRecorder(capacity=32)
    seen = []
    fn, ticks = _wrapped("decode", registry=reg, recorder=rec,
                         on_compile=seen.append)
    fn.step = 3
    fn(np.ones((4, 8), np.float32))
    fn.step = 7
    fn(np.ones((4, 16), np.float32))          # induced: seq 8 -> 16
    assert len(ticks) == 2                    # same count plain jit pays
    assert fn.compiles == 2 and fn.recompiles == 1

    ev = fn.last_event
    assert ev is not None and ev["attributed"]
    assert ev["changed"][0]["old"] == "float32[4,8]"
    assert ev["changed"][0]["new"] == "float32[4,16]"

    # counters: the global rollup and the per-fn series
    snap = reg.snapshot()
    assert snap["telemetry/xla/recompiles"] == 1.0
    assert snap["telemetry/xla/decode/recompiles"] == 1.0
    assert is_catalog_name("telemetry/xla/recompiles")
    assert is_catalog_name("telemetry/xla/decode/recompiles")

    # the ring: first compile is marked first=True, the recompile names
    # the changed argument old -> new aval in human-readable text
    compiles = [e for e in rec.events if e["kind"] == "compile"]
    assert len(compiles) == 2
    assert compiles[0]["first"] and compiles[0]["step"] == 3
    assert "float32[4,8] -> float32[4,16]" in compiles[1]["changed"]
    assert compiles[1]["step"] == 7 and compiles[1]["attributed"]

    # on_compile forwarded the event (serving feeds anomaly from this);
    # first compiles never reach it
    assert len(seen) == 1 and seen[0]["step"] == 7


def test_cache_hit_after_recompile_leaves_last_event_none():
    fn, _ = _wrapped()
    a, b = np.ones((2, 4), np.float32), np.ones((2, 8), np.float32)
    fn(a)
    fn(b)
    assert fn.last_event is not None
    fn(a)                            # back to a cached specialization
    assert fn.last_event is None and fn.compiles == 2


def test_note_unattributed_compile_counts_and_records():
    reg = MetricRegistry()
    rec = FlightRecorder(capacity=8)
    fn, _ = _wrapped(registry=reg, recorder=rec)
    fn(np.ones((2, 2), np.float32))
    fn.note_unattributed_compile(step=11)
    ev = fn.last_event
    assert ev is not None and not ev["attributed"]
    assert reg.snapshot()["telemetry/xla/recompiles"] == 1.0
    ring = [e for e in rec.events if e["kind"] == "compile"
            and not e.get("first")]
    assert "unattributed" in ring[0]["changed"]
    assert ring[0]["step"] == 11


def test_disabled_wrapper_is_a_passthrough():
    fn, ticks = _wrapped(enabled=False)
    fn(np.ones((2, 2), np.float32))
    fn(np.ones((2, 4), np.float32))
    assert fn.compiles == 0 and fn.recompiles == 0
    assert len(ticks) == 2           # plain jit retraced, untouched


def test_compile_failure_propagates_without_a_second_compile():
    class BrokenJit:
        """Jitted-shaped callable whose lowering fails."""
        def __init__(self):
            self.calls = 0

        def lower(self, *args):
            raise RuntimeError("injected compile error")

        def __call__(self, x):
            self.calls += 1
            return x

    raw = BrokenJit()
    fn = IntrospectedFunction("broken", raw)
    with pytest.raises(RuntimeError, match="injected compile error"):
        fn(np.ones((2, 2), np.float32))
    assert raw.calls == 0            # never retried through the raw path
    assert fn.compiles == 0


def test_cache_eviction_respects_max_entries():
    fn, ticks = _wrapped(max_entries=2)
    shapes = [(2, 2), (2, 4), (2, 8)]
    for s in shapes:
        fn(np.ones(s, np.float32))
    assert len(fn._cache) == 2
    assert len(ticks) == 3
    fn(np.ones((2, 2), np.float32))  # evicted: compiles again
    assert fn.compiles == 4


# ---------------------------------------------------------------------------
# cost/memory gauges, 6N cross-check, roofline — the analytic layer
# ---------------------------------------------------------------------------

def test_six_n_crosscheck_and_roofline_with_zero_extra_compiles():
    """Pin (b)+(c): a pure-matmul train step's XLA FLOPs agree with the
    6N estimate within ESTIMATE_TOLERANCE; the roofline verdict gauges
    publish; the in-body trace counter stays at 1 across repeat calls."""
    D, O, B = 64, 64, 32
    rs = np.random.RandomState(0)
    w = rs.normal(size=(D, O)).astype(np.float32)
    x = rs.normal(size=(B, D)).astype(np.float32)
    y = rs.normal(size=(B, O)).astype(np.float32)
    ticks = []

    def loss(w, x, y):
        ticks.append(1)
        return jnp.mean((x @ w - y) ** 2)

    # the verdict is arithmetic on the compiled step's cost analysis and
    # a chip's published peaks; the CPU the test runs on has none
    mfu = MFUCalculator(D * O, device_kind="TPU v5 lite", platform="tpu",
                        training=True)
    reg = MetricRegistry()
    fn = IntrospectedFunction("train_step",
                              jax.jit(jax.value_and_grad(loss)),
                              registry=reg, mfu_calc=mfu)
    for _ in range(4):
        fn(w, x, y)
    assert len(ticks) == 1 and fn.compiles == 1

    # fwd + bwd of one [B,D]x[D,O] matmul is 3 matmuls = 6*B*D*O FLOPs
    # = 6N per token: XLA's count differs only by elementwise epsilon
    assert fn.stats["flops"] > 0
    chk = mfu.check_estimate(fn.stats["flops"], tokens=B)
    assert chk["within_tolerance"] == 1.0, chk
    assert abs(chk["ratio"] - 1.0) <= ESTIMATE_TOLERANCE

    snap = reg.snapshot()
    for key in ("flops", "bytes_accessed", "roofline_intensity",
                "roofline_ridge", "roofline_compute_bound"):
        name = f"telemetry/xla/train_step/{key}"
        assert name in snap, name
        assert is_catalog_name(name), name
    assert snap["telemetry/xla/train_step/roofline_ridge"] > 0.0
    assert snap["telemetry/xla/train_step/roofline_compute_bound"] \
        in (0.0, 1.0)


def test_live_bytes_gauge_tracks_allocation():
    reg = MetricRegistry()
    register_live_bytes_gauge(reg)
    register_live_bytes_gauge(reg)   # idempotent per registry
    before = live_array_bytes()
    keep = jnp.ones((256, 256), jnp.float32)   # 256 KiB live
    after = reg.snapshot()["telemetry/xla/live_bytes"]
    assert after >= before + keep.nbytes
    del keep


# ---------------------------------------------------------------------------
# trainer integration: steady run = 1 compile, gauges + 6N in payload
# ---------------------------------------------------------------------------

def test_trainer_steady_run_one_compile_with_xla_gauges(mesh8, tmp_path):
    """Pin (a) steady-state: introspection ON adds zero compiles
    (train_step_compiles == 1, zero recompiles attributed) while the
    telemetry/xla/train_step/* gauges, live bytes, and the 6N ratio all
    surface in the registry."""
    from tests.test_telemetry import BatchIter, _make_trainer
    with jax.sharding.set_mesh(mesh8):
        tr = _make_trainer(mesh8, tmp_path / "run", max_steps=6,
                           log_every=2)
        assert tr.xla_introspect_enabled      # default-on
        it = BatchIter()
        tr.fit(it, rng=jax.random.key(0), data_state=it.state_dict)
        assert tr.step == 6
        assert tr.train_step_compiles == 1    # THE zero-extra-compile pin
        step_fn = tr._jit_train_step
        assert isinstance(step_fn, IntrospectedFunction)
        assert step_fn.compiles == 1 and step_fn.recompiles == 0

        snap = tr.registry.snapshot()
        assert snap["telemetry/xla/train_step/flops"] > 0.0
        assert snap["telemetry/xla/train_step/bytes_accessed"] > 0.0
        # no roofline verdict for a device with no peaks on record
        assert "telemetry/xla/train_step/roofline_ridge" not in snap
        assert snap["telemetry/xla/live_bytes"] > 0.0
        # the 6N cross-check rode the log interval into the registry
        assert "telemetry/xla/train_step/flops_vs_6n_ratio" in snap
        assert "telemetry/xla/recompiles" not in snap \
            or snap["telemetry/xla/recompiles"] == 0.0


def test_trainer_introspection_off_switch(mesh8, tmp_path):
    from tests.test_telemetry import BatchIter, _make_trainer
    with jax.sharding.set_mesh(mesh8):
        tr = _make_trainer(mesh8, tmp_path / "run", max_steps=3,
                           telemetry={"xla_introspect":
                                      {"enabled": False}})
        it = BatchIter()
        tr.fit(it, rng=jax.random.key(0), data_state=it.state_dict)
        assert not tr.xla_introspect_enabled
        assert tr.train_step_compiles == 1
        assert not isinstance(tr._jit_train_step, IntrospectedFunction)
        assert "telemetry/xla/train_step/flops" not in \
            tr.registry.snapshot()


# ---------------------------------------------------------------------------
# what compiling cost: the lower / compile split, persistent-cache hits,
# and the process-wide accounting behind them
# ---------------------------------------------------------------------------

def test_lower_and_compile_seconds_gauges_and_recorder_event():
    """The wrapper times its one lowering and its one compile apart and
    says whether the persistent cache answered: on the per-fn gauges, on
    both kinds of ``compile`` event, and as start-up records. The tests
    run with the persistent cache off, so it was not asked: -1."""
    from dla_tpu.utils import profiling
    profiling.reset_startup_spans()
    reg = MetricRegistry()
    rec = FlightRecorder(capacity=8)
    fn, ticks = _wrapped("decode", registry=reg, recorder=rec)
    fn(np.ones((4, 8), np.float32))
    fn(np.ones((4, 8), np.float32))
    assert len(ticks) == 1 and fn.compiles == 1       # still one of each
    snap = reg.snapshot()
    for key in ("lower_s", "compile_s", "cache_hit"):
        assert is_catalog_name(f"telemetry/xla/decode/{key}")
    assert snap["telemetry/xla/decode/lower_s"] > 0.0
    assert snap["telemetry/xla/decode/compile_s"] > 0.0
    assert snap["telemetry/xla/decode/cache_hit"] == -1.0
    first = [e for e in rec.events if e["kind"] == "compile"][0]
    assert first["first"] and first["cache_hit"] == -1
    assert first["lower_s"] == pytest.approx(fn.stats["lower_s"])
    assert first["compile_s"] == pytest.approx(fn.stats["compile_s"])
    # the induced recompile's event carries its own three numbers
    fn(np.ones((4, 16), np.float32))
    again = [e for e in rec.events if e["kind"] == "compile"][1]
    assert again["attributed"] and again["n_compiles"] == 2
    assert again["lower_s"] > 0.0 and again["compile_s"] > 0.0
    assert again["cache_hit"] == -1 and fn.last_event["cache_hit"] == -1
    # two records a compile, one after the other on this thread
    records = [r for r in profiling.startup_spans()
               if r["args"].get("fn") == "decode"]
    assert [(r["name"], r["args"]["n_compiles"]) for r in records] == [
        ("xla_lower", 1), ("xla_compile", 1),
        ("xla_lower", 2), ("xla_compile", 2)]
    assert records[0]["end_ns"] <= records[1]["start_ns"]
    assert records[1]["args"]["cache_hit"] == -1
    assert (records[0]["end_ns"] - records[0]["start_ns"]) * 1e-9 == \
        pytest.approx(first["lower_s"])


_CACHE_PROBE = """
import json, sys
import jax, jax.numpy as jnp, numpy as np
jax.config.update("jax_compilation_cache_dir", sys.argv[1])
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
from dla_tpu.telemetry import xla_introspect as xi
ticks = []
def make():                 # two function objects, one program
    def f(x):
        ticks.append(1)
        return jnp.sum(x * 2.0)
    return f
x, out = np.ones((4, 8), np.float32), []
for _ in range(2):
    fn = xi.IntrospectedFunction("fn", jax.jit(make()))
    fn(x); fn(x)
    out.append([fn.stats["cache_hit"], fn.compiles])
print(json.dumps({"out": out, "ticks": len(ticks),
                  "acct": xi.compile_accounting()}))
"""


def test_cache_hit_reads_0_then_1_over_a_fresh_cache_directory(tmp_path):
    """A process of its own (the suite keeps the persistent cache off),
    thresholds at 0 so that a tiny program is written: the first wrapper
    misses and writes, the second, over the same program, is answered
    from the directory. One trace and one compile each."""
    import json
    import os
    import subprocess
    import sys
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.dirname(os.path.dirname(__file__)))
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    done = subprocess.run(
        [sys.executable, "-c", _CACHE_PROBE, str(tmp_path / "cache")],
        env=env, capture_output=True, text=True, timeout=300)
    assert done.returncode == 0, done.stderr[-2000:]
    got = json.loads(done.stdout.strip().splitlines()[-1])
    assert got["out"] == [[0, 1], [1, 1]]
    assert got["ticks"] == 2
    acct = got["acct"]
    assert (acct["cache_requests"], acct["cache_hits"],
            acct["cache_misses"]) == (2, 1, 1)
    assert acct["backend_compiles"] == 2 and acct["lower_s"] > 0.0


def test_accounting_listener_installs_once(monkeypatch):
    """However often ``enable_compile_cache()`` is called, and whoever
    else asks: one listener of each kind, so nothing is counted twice."""
    from jax._src import monitoring
    from dla_tpu.telemetry import xla_introspect as xi
    from dla_tpu.utils import compile_cache
    before_dir = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/nonexistent/unused")
    try:
        for _ in range(3):
            compile_cache.enable_compile_cache()
            xi.install_compile_accounting()
    finally:
        jax.config.update("jax_compilation_cache_dir", before_dir)
    acct = xi._ACCOUNTING
    assert monitoring.get_event_duration_listeners().count(
        acct.on_duration) == 1
    assert monitoring.get_event_listeners().count(acct.on_event) == 1
    assert monitoring.get_scalar_listeners().count(acct.on_open) == 1


def test_accounting_sees_every_jitted_function_and_counts_nesting_once():
    """An unwrapped ``jax.jit`` is on the books too; a callee traced
    inside its caller's trace adds no seconds of its own."""
    from dla_tpu.telemetry import xla_introspect as xi
    xi.install_compile_accounting()
    xi.reset_compile_accounting()

    @jax.jit
    def inner_fn(x):
        return jnp.tanh(x) * 3.0

    @jax.jit
    def outer_fn(x):
        return jnp.sum(inner_fn(x) + 1.0)

    import time
    mark = time.perf_counter_ns()
    outer_fn(np.ones((3, 5), np.float32))
    events = xi.compile_events(mark)
    names = [(e.event.rsplit("/", 1)[1], e.fun_name) for e in events]
    assert ("backend_compile_duration", "jit(outer_fn)") in names
    assert ("jaxpr_to_mlir_module_duration", "jit(outer_fn)") in names
    assert any(k == "jaxpr_trace_duration" and f == "outer_fn"
               for k, f in names)
    # the callee's trace lies inside the caller's: not an event of its own
    assert not any("inner_fn" in f for _, f in names)
    assert all(e.thread == "MainThread" and e.t_ns >= mark for e in events)
    acct = xi.compile_accounting()
    lowered = sum(e.seconds for e in events
                  if not e.event.endswith("backend_compile_duration"))
    assert acct["lower_s"] == pytest.approx(lowered)
    assert acct["backend_compiles"] >= 1
    assert acct["cache_hits"] == acct["cache_misses"] == 0   # cache off
    assert xi.compile_events(time.perf_counter_ns()) == []
    reg = MetricRegistry()
    xi.publish_compile_accounting(reg)
    snap = reg.snapshot()
    for key in ("cache_hits", "cache_misses", "backend_compile_s",
                "lower_s"):
        assert is_catalog_name(f"telemetry/xla/{key}")
        assert snap[f"telemetry/xla/{key}"] == acct[key]


def test_compile_event_list_honours_its_cap(monkeypatch):
    """The newest events stay (a long-lived process asks what its latest
    phase compiled: chip_smoke's trainer phase late in a pytest worker);
    the totals count on."""
    from dla_tpu.telemetry import xla_introspect as xi
    monkeypatch.setattr(xi, "COMPILE_EVENT_CAP", 3)
    xi.reset_compile_accounting()
    acct = xi._ACCOUNTING
    for i in range(5):
        acct.on_duration(xi.BACKEND_COMPILE_EVENT, 0.5, fun_name=f"jit(f{i})")
    assert [e.fun_name for e in xi.compile_events()] == [
        "jit(f2)", "jit(f3)", "jit(f4)"]
    got = xi.compile_accounting()
    assert got["events_dropped"] == 2 and got["backend_compiles"] == 5
    assert got["backend_compile_s"] == pytest.approx(2.5)  # totals go on
    monkeypatch.undo()          # the next list is made at the real cap
    xi.reset_compile_accounting()
    assert xi.compile_events() == []
    assert xi._ACCOUNTING.events.maxlen == xi.COMPILE_EVENT_CAP
