"""Host tracing, pod aggregation, and SLO watch tests
(docs/OBSERVABILITY.md: Host tracing / Pod-wide aggregation / SLO
watch).

THE pins: (a) a traced CPU train run writes Chrome-trace JSON whose
`step` slices sum to the StepClock wall clock (within 5%), contain an
async-checkpoint `ckpt_write` span on a DIFFERENT thread overlapping a
step, and prefetch slices on the prefetch thread — with
`train_step_compiles` still exactly 1; (b) a disabled tracer does ZERO
producer work (asserted by making the internal `_push` raise); (c) the
serving engine emits one complete async span tree per request whose
event timestamps agree exactly with the recorded TTFT/ITL; (d) the
straggler gauge lights up under injected skew (`simulate_skew` /
DLA_SIM_SKEW) and an SLO burn under a DLA_FAULT_PLAN checkpoint stall
writes `postmortem_slo_burn.json`.
"""
import json
import urllib.error
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from dla_tpu.resilience import ENV_VAR as FAULT_ENV
from dla_tpu.telemetry import (
    FlightRecorder,
    Histogram,
    MetricRegistry,
    MetricsHTTPServer,
    PodAggregator,
    ReadinessProbe,
    SkewSimulator,
    SLO,
    SLOWatch,
    StepClock,
    Tracer,
    get_tracer,
    install_tracer,
    is_catalog_name,
)
from dla_tpu.telemetry.trace import _NULL_SPAN
from dla_tpu.utils.logging import latency_summary


class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


def _strict_load(text: str) -> dict:
    """Perfetto's parser is strict JSON: bare NaN/Infinity must fail."""
    def _reject(tok):
        raise ValueError(f"bare {tok} is not strict JSON")
    return json.loads(text, parse_constant=_reject)


def _events(doc, ph=None, name=None, cat=None):
    out = []
    for e in doc["traceEvents"]:
        if ph is not None and e.get("ph") != ph:
            continue
        if name is not None and e.get("name") != name:
            continue
        if cat is not None and e.get("cat") != cat:
            continue
        out.append(e)
    return out


# ---------------------------------------------------------------------------
# tracer core: valid Chrome trace JSON, nesting, ring, off-switch
# ---------------------------------------------------------------------------

def test_tracer_exports_valid_nested_chrome_trace(tmp_path):
    fc = FakeClock()
    tr = Tracer(now=fc, path=str(tmp_path / "trace.json"))
    with tr.span("step", cat="step", step=1):
        fc.advance(0.001)
        with tr.span("compute", cat="step"):
            fc.advance(0.008)
        fc.advance(0.001)
    tr.counter("goodput", 0.8)
    tr.instant("fault", oops=float("nan"))        # sanitized, not bare NaN
    tr.async_begin("request", "request", 7, prompt_tokens=4)
    fc.advance(0.002)
    tr.async_instant("request", "first_token", 7, ttft_ms=2.0)
    tr.async_end("request", "request", 7, status="eos")

    path = tr.dump()
    assert path is not None and path.name == "trace.json"
    doc = _strict_load(path.read_text())
    assert doc["displayTimeUnit"] == "ms"
    assert doc["otherData"]["emitted"] == 7 and doc["otherData"]["dropped"] == 0

    # metadata names the process and the emitting thread
    meta = _events(doc, ph="M")
    assert any(m["name"] == "process_name" for m in meta)
    assert any(m["name"] == "thread_name" for m in meta)

    # positional nesting: the child X event sits inside the parent's span
    parent = _events(doc, ph="X", name="step")[0]
    child = _events(doc, ph="X", name="compute")[0]
    assert parent["ts"] <= child["ts"]
    assert child["ts"] + child["dur"] <= parent["ts"] + parent["dur"]
    assert parent["dur"] == pytest.approx(10_000.0)     # 10 ms in us
    assert child["dur"] == pytest.approx(8_000.0)
    assert parent["args"]["step"] == 1
    assert parent["tid"] == child["tid"]

    # counter / instant / async tree shapes
    assert _events(doc, ph="C", name="goodput")[0]["args"]["value"] == 0.8
    assert _events(doc, ph="i", name="fault")[0]["args"]["oops"] is None
    b = _events(doc, ph="b", cat="request")[0]
    n = _events(doc, ph="n", name="first_token")[0]
    e = _events(doc, ph="e", cat="request")[0]
    assert b["id"] == n["id"] == e["id"] == 7
    assert b["ts"] <= n["ts"] <= e["ts"]
    assert e["args"]["status"] == "eos"


def test_tracer_ring_evicts_and_counts_dropped():
    tr = Tracer(capacity=4)
    for i in range(10):
        tr.instant(f"e{i}")
    assert len(tr.events) == 4
    assert tr.emitted == 10
    assert tr.dropped == 6
    names = [e["name"] for e in tr.export()["traceEvents"]
             if e["ph"] == "i"]
    assert names == ["e6", "e7", "e8", "e9"]      # oldest evicted


def test_disabled_tracer_does_zero_work(monkeypatch):
    """THE off-switch pin: every emit path must return before doing ANY
    work when disabled — proven by making the internal _push raise."""
    tr = Tracer(enabled=False)

    def _boom(evt):
        raise AssertionError("disabled tracer did work")

    monkeypatch.setattr(tr, "_push", _boom)
    assert tr.span("x", cat="c", k=1) is _NULL_SPAN   # shared no-op
    with tr.span("x"):
        pass
    tr.complete("x", 0.0, 1.0)
    tr.instant("x")
    tr.counter("x", 1.0)
    tr.async_begin("c", "x", 1)
    tr.async_instant("c", "x", 1)
    tr.async_end("c", "x", 1)
    assert tr.emitted == 0 and tr.dropped == 0


def test_from_config_defaults_and_global_install(tmp_path):
    # no block / enabled:false -> disabled; path defaults under the dir
    assert not Tracer.from_config(None).enabled
    assert not Tracer.from_config({"enabled": False}).enabled
    tr = Tracer.from_config({"enabled": True, "capacity": 16},
                            default_dir=str(tmp_path))
    assert tr.enabled and tr.capacity == 16
    assert tr.path == str(tmp_path / "trace.json")
    # dump with nowhere to write is a safe no-op
    assert Tracer().dump() is None

    # install/get round-trip; None restores the disabled default
    assert not get_tracer().enabled
    try:
        assert install_tracer(tr) is tr
        assert get_tracer() is tr
    finally:
        install_tracer(None)
    assert not get_tracer().enabled


def test_stepclock_feeds_tracer_on_shared_clock():
    fc = FakeClock()
    tr = Tracer(now=fc)
    clock = StepClock(now=fc, tracer=tr)
    with clock.segment("data_wait"):
        fc.advance(0.010)
    with clock.segment("compute"):
        fc.advance(0.080)
    fc.advance(0.010)
    clock.end_step(ok=True, step=3)
    doc = tr.export()
    step = _events(doc, ph="X", name="step")[0]
    assert step["dur"] == pytest.approx(clock.wall_total * 1e6)
    assert step["args"] == {"ok": True, "step": 3}
    seg = _events(doc, ph="X", name="compute")[0]
    assert seg["dur"] == pytest.approx(80_000.0)
    # segment slices nest inside the step slice
    assert step["ts"] <= seg["ts"]
    assert seg["ts"] + seg["dur"] <= step["ts"] + step["dur"]
    good = _events(doc, ph="C", name="goodput")[0]
    assert good["args"]["value"] == pytest.approx(clock.goodput())


def test_profiling_annotations_mirror_into_installed_tracer():
    from dla_tpu.utils.profiling import annotate, step_annotation
    fc = FakeClock()
    tr = Tracer(now=fc)
    install_tracer(tr)
    try:
        with step_annotation(5, name="train"):
            fc.advance(0.004)
            with annotate("my_region"):
                fc.advance(0.002)
    finally:
        install_tracer(None)
    doc = tr.export()
    step = _events(doc, ph="X", name="train_step")[0]
    assert step["args"]["step"] == 5
    region = _events(doc, ph="X", name="my_region", cat="annotate")[0]
    assert region["ts"] >= step["ts"]
    assert region["ts"] + region["dur"] <= step["ts"] + step["dur"]


# ---------------------------------------------------------------------------
# pod aggregation: skew simulator, straggler attribution
# ---------------------------------------------------------------------------

def test_skew_simulator_spec_parsing():
    assert SkewSimulator.from_spec(None) is None
    assert SkewSimulator.from_spec("") is None
    sim = SkewSimulator.from_spec("hosts=8,slow=3,factor=2.5")
    assert (sim.hosts, sim.slow_host, sim.factor) == (8, 3, 2.5)
    sim2 = SkewSimulator.from_spec({"hosts": 4, "slow": 1})
    assert (sim2.hosts, sim2.slow_host, sim2.factor) == (4, 1, 2.0)
    with pytest.raises(ValueError, match="bad DLA_SIM_SKEW field"):
        SkewSimulator.from_spec("hosts=8,turbo=1")
    with pytest.raises(ValueError, match="outside"):
        SkewSimulator.from_spec("hosts=4,slow=4")


def test_pod_aggregator_straggler_and_skew_under_simulated_skew():
    agg = PodAggregator(
        simulate=SkewSimulator(hosts=4, slow_host=2, factor=3.0),
        host_index=0)
    out = agg.update(step_ms=100.0, goodput=0.9)
    for k in out:
        assert is_catalog_name(k), k
    assert out["telemetry/straggler_host"] == 2.0
    assert out["telemetry/pod_step_ms_max"] == pytest.approx(300.0)
    assert out["telemetry/pod_step_ms_min"] == pytest.approx(100.0)
    # skew = max / mean = 300 / 150 = 2.0
    assert out["telemetry/step_skew"] == pytest.approx(2.0)
    assert out["telemetry/pod_goodput_min"] == pytest.approx(0.3)

    # non-zero hosts contribute to the rendezvous but publish nothing
    agg1 = PodAggregator(
        simulate=SkewSimulator(hosts=4, slow_host=2, factor=3.0),
        host_index=1)
    assert agg1.update(100.0, 0.9) == {}
    assert agg1.last.straggler_host == 2     # ...but still computed

    assert PodAggregator(enabled=False, host_index=0).update(1.0, 1.0) == {}


def test_pod_aggregator_single_process_gather_degrades_gracefully():
    agg = PodAggregator(host_index=0)       # real gather path, 1 process
    out = agg.update(step_ms=50.0, goodput=0.7)
    assert out["telemetry/pod_step_ms_max"] == pytest.approx(50.0)
    assert out["telemetry/straggler_host"] == 0.0
    assert out["telemetry/step_skew"] == pytest.approx(1.0)


def test_pod_aggregator_from_config_reads_env(monkeypatch):
    from dla_tpu.telemetry.aggregate import ENV_VAR as SKEW_ENV
    monkeypatch.setenv(SKEW_ENV, "hosts=6,slow=5,factor=4.0")
    agg = PodAggregator.from_config({})
    assert agg.sim is not None and agg.sim.slow_host == 5
    monkeypatch.delenv(SKEW_ENV)
    assert PodAggregator.from_config(None).sim is None


# ---------------------------------------------------------------------------
# SLO watch: burn-rate edge triggering, gauges, postmortem
# ---------------------------------------------------------------------------

def test_slo_validation_and_violation():
    slo = SLO(name="ttft", metric="serving/ttft_ms_p95", objective=500.0)
    assert slo.violated(501.0) and not slo.violated(500.0)
    lo = SLO(name="goodput", metric="telemetry/goodput", objective=0.5,
             kind="min")
    assert lo.violated(0.4) and not lo.violated(0.6)
    with pytest.raises(ValueError, match="kind"):
        SLO(name="x", metric="m", objective=1.0, kind="between")
    with pytest.raises(ValueError, match="budget"):
        SLO(name="x", metric="m", objective=1.0, budget=0.0)


def test_slowatch_burn_edge_trigger_gauges_and_postmortem(tmp_path):
    fc = FakeClock()
    reg = MetricRegistry()
    rec = FlightRecorder(capacity=16, out_dir=str(tmp_path))
    watch = SLOWatch(
        [SLO(name="step_time", metric="telemetry/step_ms",
             objective=100.0, kind="max", window_s=60.0, budget=0.5)],
        registry=reg, recorder=rec, now=fc)

    # healthy: burn 0, ok, no alert
    out = watch.observe({"telemetry/step_ms": 50.0}, step=1)
    assert out["slo/step_time_ok"] == 1.0
    assert out["slo/step_time_burn_rate"] == 0.0
    assert out["slo/step_time_alerts"] == 0.0

    # 1 bad of 2 samples = 50% violating / 50% budget = burn 1.0 -> alert
    fc.advance(1.0)
    out = watch.observe({"telemetry/step_ms": 500.0}, step=2)
    assert out["slo/step_time_burn_rate"] == pytest.approx(1.0)
    assert out["slo/step_time_ok"] == 0.0
    assert out["slo/step_time_alerts"] == 1.0

    # still burning: edge-triggered, no second alert
    fc.advance(1.0)
    out = watch.observe({"telemetry/step_ms": 500.0}, step=3)
    assert out["slo/step_time_alerts"] == 1.0

    # postmortem written with the alert context
    pm = tmp_path / "postmortem_slo_burn.json"
    assert pm.exists()
    doc = _strict_load(pm.read_text())
    assert doc["reason"] == "slo_burn"
    burn_evt = [e for e in doc["events"] if e["kind"] == "slo_burn"][0]
    assert burn_evt["slo"] == "step_time"
    assert burn_evt["metric"] == "telemetry/step_ms"
    assert burn_evt["value"] == 500.0

    # recover: samples age out of the window, burn drops, re-armed
    fc.advance(120.0)
    for _ in range(3):
        fc.advance(1.0)
        out = watch.observe({"telemetry/step_ms": 50.0})
    assert out["slo/step_time_ok"] == 1.0
    # a fresh excursion fires a SECOND alert (re-armed below the line)
    for _ in range(4):
        fc.advance(1.0)
        watch.observe({"telemetry/step_ms": 500.0})
    assert watch._state["step_time"].alerts == 2

    # gauges mirrored into the registry under the slo/ dynamic prefix
    snap = reg.snapshot()
    assert snap["slo/step_time_alerts"] == 2.0
    for k in ("slo/step_time_ok", "slo/step_time_burn_rate"):
        assert k in snap and is_catalog_name(k)


def test_slowatch_from_config_and_absent_metric():
    watch = SLOWatch.from_config({
        "window_s": 30.0, "budget": 0.1,
        "objectives": [
            {"name": "TTFT p95!", "metric": "serving/ttft_ms_p95",
             "objective": 250.0},
            {"metric": "telemetry/goodput", "objective": 0.5,
             "kind": "min", "budget": 0.2},
        ]})
    assert [s.name for s in watch.slos] == ["ttft_p95", "telemetry_goodput"]
    assert watch.slos[0].window_s == 30.0 and watch.slos[0].budget == 0.1
    assert watch.slos[1].budget == 0.2
    # a snapshot missing the metric is simply not sampled that round
    out = watch.observe({"telemetry/goodput": 0.9})
    assert out["slo/ttft_p95_burn_rate"] == 0.0
    assert SLOWatch.from_config(None) is None
    assert SLOWatch.from_config({"objectives": []}) is None


# ---------------------------------------------------------------------------
# satellites: p99 everywhere, /healthz readiness, metrics_diff
# ---------------------------------------------------------------------------

def test_p99_in_latency_summary_histogram_and_prometheus():
    xs = list(range(1, 101))
    s = latency_summary(xs, prefix="ttft_ms_")
    assert s["ttft_ms_p99"] >= s["ttft_ms_p95"] >= s["ttft_ms_p50"]

    h = Histogram()
    for v in xs:
        h.record(float(v))
    hs = h.summary()
    assert hs["p99"] >= hs["p95"]
    assert hs["p99"] == pytest.approx(np.percentile(xs, 99), rel=0.05)

    reg = MetricRegistry()
    hh = reg.histogram("serving/ttft_ms")
    for v in xs:
        hh.record(float(v))
    snap = reg.snapshot()
    assert is_catalog_name("serving/ttft_ms_p99")
    assert snap["serving/ttft_ms_p99"] >= snap["serving/ttft_ms_p95"]
    text = reg.prometheus_text()
    assert 'dla_serving_ttft_ms{quantile="0.99"}' in text


def test_healthz_readiness_flips_to_503_on_staleness():
    fc = FakeClock()
    probe = ReadinessProbe(threshold_s=10.0, now=fc)
    assert probe.ready and probe.age_s == 0.0
    srv = MetricsHTTPServer(MetricRegistry(), port=0, readiness=probe)
    try:
        health = srv.url.replace("/metrics", "/healthz")
        fc.advance(3.0)
        with urllib.request.urlopen(health, timeout=5) as resp:
            assert resp.status == 200
            assert resp.read() == b"ok age_s=3.0\n"
        fc.advance(20.0)                 # stale: no beat for 23 s
        with pytest.raises(urllib.error.HTTPError) as exc_info:
            urllib.request.urlopen(health, timeout=5)
        assert exc_info.value.code == 503
        body = exc_info.value.read()
        assert body.startswith(b"stale age_s=23.0")
        assert b"threshold_s=10.0" in body
        probe.beat()                     # a completed step recovers it
        with urllib.request.urlopen(health, timeout=5) as resp:
            assert resp.status == 200
    finally:
        srv.stop()


def test_metrics_diff_detects_regressions_with_tolerance(tmp_path,
                                                         capsys):
    from tools.metrics_diff import main
    base = tmp_path / "base.json"
    cand = tmp_path / "cand.json"
    base.write_text(json.dumps({
        "telemetry": {"step_ms": 100.0, "goodput": 0.8},
        "tokens_per_sec_per_chip": 1000.0, "notes": "ignored"}))
    cand.write_text(json.dumps({
        "telemetry": {"step_ms": 130.0, "goodput": 0.82},
        "tokens_per_sec_per_chip": 1010.0}))

    # step_ms +30% against its good direction -> regression, exit 1
    assert main([str(base), str(cand)]) == 1
    out = capsys.readouterr().out
    assert "REGRESSION" in out and "telemetry/step_ms" in out

    # a per-metric tolerance waives exactly that regression
    assert main([str(base), str(cand),
                 "--tolerance-for", "telemetry/step_ms=0.5"]) == 0

    # Prometheus-text inputs: quantile-labeled series compare too
    bt = tmp_path / "base.txt"
    ct = tmp_path / "cand.txt"
    bt.write_text('dla_serving_ttft_ms{quantile="0.95"} 50.0\n')
    ct.write_text('dla_serving_ttft_ms{quantile="0.95"} 80.0\n')
    assert main([str(bt), str(ct)]) == 1
    assert main([str(bt), str(bt)]) == 0

    # disjoint snapshots: clean by default, a failure when required
    other = tmp_path / "other.json"
    other.write_text(json.dumps({"something_else": 1.0}))
    assert main([str(base), str(other)]) == 0
    assert main([str(base), str(other), "--require-common"]) == 1

    # unreadable input -> usage error, exit 2
    bad = tmp_path / "bad.json"
    bad.write_text("{not json")
    assert main([str(bad), str(cand)]) == 2


# ---------------------------------------------------------------------------
# trainer integration: THE acceptance trace on mesh8
# ---------------------------------------------------------------------------

DIM = 8


def _make_batch(i, bs=8):
    rs = np.random.RandomState(4000 + i)
    x = rs.normal(size=(bs, DIM)).astype(np.float32)
    w_true = np.arange(1, DIM + 1, dtype=np.float32)
    return {"x": x, "y": (x @ w_true).astype(np.float32)}


class BatchIter:
    def __init__(self):
        self.i = 0

    def __iter__(self):
        return self

    def __next__(self):
        b = _make_batch(self.i)
        self.i += 1
        return b

    def state_dict(self):
        return {"i": self.i}

    def load_state_dict(self, state):
        self.i = int(state["i"])


def _linreg_loss(params, frozen, batch, rng):
    del frozen, rng
    pred = batch["x"] @ params["w"]
    return jnp.mean((pred - batch["y"]) ** 2), {}


def _make_trainer(mesh, out_dir, *, max_steps=6, save_every=0,
                  log_every=10 ** 6, prefetch=0, telemetry=None,
                  resilience=None, slo=None):
    from dla_tpu.training.trainer import Trainer
    logging_cfg = {"output_dir": str(out_dir), "log_dir": None,
                   "save_every_steps": save_every,
                   "log_every_steps": log_every}
    if telemetry is not None:
        logging_cfg["telemetry"] = telemetry
    config = {
        "experiment_name": "trace_test",
        "data": {"prefetch": prefetch},
        "optimization": {"total_batch_size": 8, "micro_batch_size": 1,
                         "learning_rate": 1e-2, "max_train_steps": max_steps,
                         "lr_scheduler": "constant", "max_grad_norm": 1.0},
        "logging": logging_cfg,
        "hardware": {"gradient_accumulation_steps": 2},
    }
    if resilience is not None:
        config["resilience"] = resilience
    if slo is not None:
        config["slo"] = slo
    return Trainer(config=config, mesh=mesh, loss_fn=_linreg_loss,
                   params={"w": jnp.zeros((DIM,), jnp.float32)},
                   param_specs={"w": P()})


def test_traced_train_run_writes_consistent_chrome_trace(mesh8, tmp_path,
                                                         monkeypatch):
    """THE acceptance pin: a CPU run with tracing enabled writes a
    Chrome-trace JSON whose step slices sum to the StepClock wall clock
    (within 5%), shows the async-checkpoint writer span on a different
    thread overlapping a step slice, and carries prefetch slices — with
    the train step still compiling exactly once."""
    trace_path = tmp_path / "trace.json"
    with jax.sharding.set_mesh(mesh8):
        # an injected io_error makes the background write retry with
        # backoff, so the writer-thread span provably overlaps steps
        monkeypatch.setenv(FAULT_ENV, "step=2:io_error")
        tr = _make_trainer(
            mesh8, tmp_path / "run", max_steps=6, save_every=2,
            prefetch=2,
            telemetry={"trace": {"enabled": True,
                                 "path": str(trace_path)}},
            resilience={"async_checkpointing": True, "save_retries": 3,
                        "retry_backoff_s": 0.4})
        try:
            assert tr.tracer.enabled
            assert get_tracer() is tr.tracer      # installed process-wide
            it = BatchIter()
            tr.fit(it, rng=jax.random.key(0), data_state=it.state_dict)
            tr.checkpointer.wait()
        finally:
            install_tracer(None)
        assert tr.step == 6
        assert tr.train_step_compiles == 1        # tracing adds no compiles

        assert trace_path.exists()
        doc = _strict_load(trace_path.read_text())

        # step slices sum to the clock's wall total within 5%
        steps = _events(doc, ph="X", name="step")
        assert len(steps) == 6
        traced_s = sum(e["dur"] for e in steps) / 1e6
        assert traced_s == pytest.approx(tr.clock.wall_total, rel=0.05)
        step_tids = {e["tid"] for e in steps}
        assert len(step_tids) == 1                # all on the trainer thread

        # segment slices (data_wait/h2d/compute/...) nest under steps
        computes = _events(doc, ph="X", name="compute")
        assert len(computes) == 6
        assert all(e["tid"] in step_tids for e in computes)

        # the async-checkpoint writer span runs on a DIFFERENT thread
        # and overlaps at least one step slice — overlap made visible
        writes = _events(doc, ph="X", name="ckpt_write")
        assert writes, "no ckpt_write span from the writer thread"
        assert all(w["tid"] not in step_tids for w in writes)
        overlaps = any(
            w["ts"] < s["ts"] + s["dur"] and s["ts"] < w["ts"] + w["dur"]
            for w in writes for s in steps)
        assert overlaps, "checkpoint write never overlapped a step"

        # prefetch slices from the prefetch thread
        pf = _events(doc, ph="X", name="prefetch_next")
        assert pf and all(e["tid"] not in step_tids for e in pf)

        # goodput counter track sampled once per step
        assert len(_events(doc, ph="C", name="goodput")) == 6

        # tracer accounting rides the registry
        snap = tr.registry.snapshot()
        assert snap["telemetry/trace_events"] == float(tr.tracer.emitted)
        assert snap["telemetry/trace_dropped"] == 0.0


def test_untraced_train_run_emits_zero_events(mesh8, tmp_path):
    """Acceptance pin: tracing disabled (the default) means ZERO events
    pushed by any producer — not 'few', none."""
    with jax.sharding.set_mesh(mesh8):
        tr = _make_trainer(mesh8, tmp_path / "run", max_steps=4,
                           prefetch=2, save_every=2,
                           resilience={"async_checkpointing": True})
        it = BatchIter()
        tr.fit(it, rng=jax.random.key(0), data_state=it.state_dict)
        tr.checkpointer.wait()
        assert tr.step == 4
        assert not tr.tracer.enabled
        assert tr.tracer.emitted == 0
        assert not (tmp_path / "run" / "trace.json").exists()


def test_trainer_straggler_gauge_under_simulated_skew(mesh8, tmp_path):
    """The pod-aggregation path end to end on one CPU process: the
    configured skew simulation lights up the straggler gauge on the
    trainer's own /metrics registry."""
    with jax.sharding.set_mesh(mesh8):
        tr = _make_trainer(
            mesh8, tmp_path / "run", max_steps=4, log_every=2,
            telemetry={"aggregate": {
                "simulate_skew": "hosts=4,slow=2,factor=3.0"}})
        it = BatchIter()
        tr.fit(it, rng=jax.random.key(0), data_state=it.state_dict)
        snap = tr.registry.snapshot()
        assert snap["telemetry/straggler_host"] == 2.0
        assert snap["telemetry/step_skew"] == pytest.approx(2.0)
        assert snap["telemetry/pod_step_ms_max"] == pytest.approx(
            3.0 * snap["telemetry/pod_step_ms_min"], rel=1e-6)


def test_slo_burn_fires_under_injected_checkpoint_stall(mesh8, tmp_path,
                                                        monkeypatch):
    """Satellite pin: a DLA_FAULT_PLAN checkpoint stall drags goodput
    under a declared SLO; the burn alert lands in the flight recorder
    AND as a postmortem_slo_burn.json."""
    with jax.sharding.set_mesh(mesh8):
        out = tmp_path / "run"
        monkeypatch.setenv(FAULT_ENV, "step=2:io_error")
        tr = _make_trainer(
            mesh8, out, max_steps=6, save_every=2, log_every=2,
            resilience={"async_checkpointing": True, "save_retries": 3,
                        "retry_backoff_s": 0.4},
            slo={"objectives": [
                {"name": "goodput", "metric": "telemetry/goodput",
                 "objective": 0.999, "kind": "min", "budget": 0.01}]})
        it = BatchIter()
        tr.fit(it, rng=jax.random.key(0), data_state=it.state_dict)
        tr.checkpointer.wait()

        assert tr.slo is not None
        assert tr.slo._state["goodput"].alerts >= 1
        snap = tr.registry.snapshot()
        assert snap["slo/goodput_ok"] == 0.0
        assert snap["slo/goodput_alerts"] >= 1.0

        pm = out / "postmortem_slo_burn.json"
        assert pm.exists()
        doc = _strict_load(pm.read_text())
        assert doc["reason"] == "slo_burn"
        kinds = [e["kind"] for e in doc["events"]]
        assert "slo_burn" in kinds


# ---------------------------------------------------------------------------
# serving: one async span tree per request, consistent with TTFT/ITL
# ---------------------------------------------------------------------------

def test_serving_request_span_tree_matches_recorded_latencies(tmp_path):
    """Acceptance pin: the trace contains at least one COMPLETE request
    span tree (begin -> admitted -> first_token -> decode... -> end) and
    the span timestamps agree exactly with the engine's recorded
    request times — the tracer shares the engine's clock."""
    from dla_tpu.generation.engine import GenerationConfig
    from dla_tpu.models.config import get_model_config
    from dla_tpu.models.transformer import Transformer
    from dla_tpu.serving import ServingConfig, ServingEngine

    cfg = get_model_config("tiny")
    model = Transformer(cfg)
    params = model.init(jax.random.key(7))
    gen = GenerationConfig(max_new_tokens=5, do_sample=False,
                           eos_token_id=2, pad_token_id=0)
    trace_path = tmp_path / "serve_trace.json"
    eng = ServingEngine(model, params, gen, ServingConfig(
        page_size=4, num_pages=32, num_slots=2, max_model_len=32,
        trace={"enabled": True, "path": str(trace_path)}))
    try:
        assert eng.tracer.enabled and get_tracer() is eng.tracer
        rs = np.random.RandomState(5)
        rids = [eng.submit(list(rs.randint(3, 500, (4,))), 5)
                for _ in range(3)]
        eng.run_until_drained(max_steps=500)
        reqs = {rid: eng.result(rid) for rid in rids}
    finally:
        eng.close()
    # close() dumped the trace and restored the disabled global tracer
    assert not get_tracer().enabled
    assert trace_path.exists()
    doc = _strict_load(trace_path.read_text())

    complete_trees = 0
    for rid, req in reqs.items():
        begins = [e for e in _events(doc, ph="b", cat="request")
                  if e["id"] == rid]
        ends = [e for e in _events(doc, ph="e", cat="request")
                if e["id"] == rid]
        insts = [e for e in _events(doc, ph="n", cat="request")
                 if e["id"] == rid]
        if not (begins and ends):
            continue
        complete_trees += 1
        b, e = begins[0], ends[0]
        assert b["args"]["prompt_tokens"] == 4
        assert e["args"]["status"] in ("eos", "length")
        assert e["args"]["tokens"] == len(req.generated)
        assert b["ts"] <= e["ts"]

        admitted = [i for i in insts if i["name"] == "admitted"]
        first = [i for i in insts if i["name"] == "first_token"]
        decodes = [i for i in insts if i["name"] == "decode"]
        assert admitted and first
        # TTFT: the gap between the begin and first_token events IS the
        # recorded ttft_ms — same clock, no drift allowed
        ttft_from_trace = (first[0]["ts"] - b["ts"]) / 1000.0
        recorded = (req.first_token_time - req.arrival_time) * 1000.0
        assert ttft_from_trace == pytest.approx(recorded, abs=1e-6)
        assert first[0]["args"]["ttft_ms"] == pytest.approx(recorded)
        # decode instants are ordered and carry per-token ITL
        last_ts = first[0]["ts"]
        for d in sorted(decodes, key=lambda x: x["ts"]):
            assert d["ts"] >= last_ts
            assert d["args"]["itl_ms"] >= 0.0
            last_ts = d["ts"]
    assert complete_trees >= 1


def test_serving_timeout_and_drain_close_their_span_trees():
    """Requests that never finish normally still get their async end:
    timeout and drain-cancel both close the tree with a status."""
    from dla_tpu.generation.engine import GenerationConfig
    from dla_tpu.models.config import get_model_config
    from dla_tpu.models.transformer import Transformer
    from dla_tpu.serving import ServingConfig, ServingEngine

    cfg = get_model_config("tiny")
    model = Transformer(cfg)
    params = model.init(jax.random.key(7))
    gen = GenerationConfig(max_new_tokens=5, do_sample=False,
                           eos_token_id=2, pad_token_id=0)
    eng = ServingEngine(model, params, gen, ServingConfig(
        page_size=4, num_pages=32, num_slots=2, max_model_len=32,
        trace={"enabled": True}))
    try:
        rid = eng.submit([5, 6, 7], 5)
        eng.begin_drain()          # queued, no tokens -> cancelled
        ends = [e for e in eng.tracer.events
                if e.get("ph") == "e" and e.get("id") == rid]
        assert ends and ends[0]["args"]["status"] == "cancelled"
    finally:
        eng.close()
    assert not get_tracer().enabled
