"""Unified telemetry tests (docs/OBSERVABILITY.md): step-time/goodput
accounting, the in-graph scalar collector, MFU, the shared metric
registry + Prometheus exposition, the flight recorder's postmortems,
and the static metric-name check.

THE pins: (a) step segments sum to the step's wall clock and goodput
falls when a checkpoint stall is injected via DLA_FAULT_PLAN, (b) the
collector adds ZERO train-step compiles (trace-time counter stays 1),
(c) the Prometheus text a live engine serves round-trips through a
strict parser, (d) crash paths write a postmortem JSON naming the last
completed step.
"""
import json
import math
import threading
import time
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from dla_tpu.resilience import ENV_VAR, PreemptionExit, Watchdog
from dla_tpu.telemetry import (
    CATALOG,
    FlightRecorder,
    Gauge,
    Histogram,
    MFUCalculator,
    MetricRegistry,
    MetricsHTTPServer,
    StepClock,
    flops_per_token,
    hbm_bw_for,
    is_catalog_name,
    parse_prometheus_text,
    peak_flops_for,
    prometheus_name,
    stash_rms,
    stash_scalar,
)
from dla_tpu.utils.logging import MetricsLogger


# ---------------------------------------------------------------------------
# satellite regressions: Gauge.peak and strict-JSON logging
# ---------------------------------------------------------------------------

def test_gauge_peak_seeds_from_first_value_not_zero():
    """A gauge that only ever holds negative values must report that
    value as its peak — the old init-to-0.0 reported a phantom 0.0."""
    g = Gauge()
    g.set(-7.0)
    assert g.peak == -7.0
    g.set(-3.0)
    assert g.peak == -3.0
    g.set(-9.0)
    assert g.peak == -3.0          # peak still tracks the maximum
    fresh = Gauge()
    assert fresh.peak == 0.0       # never-set gauge mirrors its value


def test_metrics_logger_emits_strict_json_for_nonfinite(tmp_path):
    """A diverging loss (NaN/inf) must not corrupt metrics.jsonl: the
    row stays strict JSON with the non-finite scalars nulled."""
    logger = MetricsLogger(str(tmp_path), "t")
    logger.log({"train/loss": float("nan"),
                "train/grad_norm": float("inf"),
                "train/lr": 0.5}, step=3)
    line = (tmp_path / "metrics.jsonl").read_text().strip()

    def _reject(tok):
        raise ValueError(f"bare {tok} is not strict JSON")

    row = json.loads(line, parse_constant=_reject)   # must not raise
    assert row["train/loss"] is None
    assert row["train/grad_norm"] is None
    assert row["train/lr"] == 0.5 and row["step"] == 3


# ---------------------------------------------------------------------------
# step clock: attribution, goodput, interval metrics
# ---------------------------------------------------------------------------

class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


def test_stepclock_segments_sum_to_wall_clock():
    fc = FakeClock()
    clock = StepClock(now=fc)
    with clock.segment("data_wait"):
        fc.advance(0.010)
    with clock.segment("h2d"):
        fc.advance(0.005)
    with clock.segment("compute"):
        fc.advance(0.080)
    fc.advance(0.005)              # unattributed -> "other"
    clock.end_step(ok=True)
    assert clock.wall_total == pytest.approx(0.100)
    attributed = sum(clock.seg_total.values()) + clock.other_total
    assert attributed == pytest.approx(clock.wall_total, rel=1e-9)
    assert clock.other_total == pytest.approx(0.005)
    assert clock.goodput() == pytest.approx(0.80)


def test_stepclock_compile_fault_and_checkpoint_attribution():
    fc = FakeClock()
    clock = StepClock(now=fc)
    # step 1: compile — its compute is badput_compile, not goodput
    clock.mark_compile()
    with clock.segment("compute"):
        fc.advance(1.0)
    clock.end_step(ok=True)
    assert clock.goodput() == 0.0
    assert clock.badput()["compile"] == pytest.approx(1.0)
    # step 2: a failed attempt charges its WHOLE wall to fault
    with clock.segment("compute"):
        fc.advance(0.5)
    clock.end_step(ok=False)
    assert clock.lost["fault"] == pytest.approx(0.5)
    assert clock.steps_failed == 1
    # step 3: checkpoint stall is both a segment and badput_checkpoint
    with clock.segment("compute"):
        fc.advance(0.5)
    with clock.segment("checkpoint_stall"):
        fc.advance(2.0)
    clock.end_step(ok=True)
    assert clock.seg_total["checkpoint_stall"] == pytest.approx(2.0)
    assert clock.badput()["checkpoint"] == pytest.approx(2.0 / 4.0)
    assert clock.goodput() == pytest.approx(0.5 / 4.0)


def test_stepclock_interval_metrics_catalog_named_and_windowed():
    fc = FakeClock()
    clock = StepClock(now=fc)
    for _ in range(4):
        with clock.segment("compute"):
            fc.advance(0.020)
        clock.end_step(ok=True)
    out = clock.interval_metrics()
    for k in out:
        assert is_catalog_name(k), k
    assert out["telemetry/step_ms"] == pytest.approx(20.0)
    assert out["telemetry/compute_ms"] == pytest.approx(20.0)
    # the window reset: a second call with no new steps means empty means
    out2 = clock.interval_metrics()
    assert out2["telemetry/step_ms"] == 0.0
    # cumulative goodput survives the window reset
    assert out2["telemetry/goodput"] == out["telemetry/goodput"]


def test_stepclock_disabled_is_inert():
    clock = StepClock(enabled=False)
    with clock.segment("compute"):
        pass
    clock.mark_compile()
    clock.end_step(ok=True)
    assert clock.wall_total == 0.0
    assert clock.interval_metrics() == {}


def test_stepclock_rejects_unknown_segment():
    with pytest.raises(ValueError, match="unknown step segment"):
        with StepClock().segment("coffee"):
            pass


# ---------------------------------------------------------------------------
# MFU calculator + chip tables
# ---------------------------------------------------------------------------

def test_mfu_formula_and_peak_tables():
    assert flops_per_token(125_000_000, training=True) == 6 * 125_000_000
    assert flops_per_token(125_000_000, training=False) == 2 * 125_000_000
    assert peak_flops_for("TPU v5 lite", "tpu") == pytest.approx(197e12)
    assert peak_flops_for("TPU v5p", "tpu") == pytest.approx(459e12)
    assert hbm_bw_for("TPU v4", "tpu") == pytest.approx(1228e9)
    # a host CPU has no peak: no MFU, no roofline verdict
    assert peak_flops_for("cpu", "cpu") is None
    cpu = MFUCalculator(1_000_000, "cpu", "cpu")
    assert cpu.mfu(1e6) is None and cpu.roofline(1e9, 1e6) == {}
    calc = MFUCalculator(1_000_000, "TPU v5 lite", "tpu", training=True)
    # 1M params * 6 flops/token: mfu = rate * 6e6 / 197e12
    assert calc.mfu(1e6) == pytest.approx(6e12 / 197e12)
    assert calc.mfu(0.0) == 0.0
    assert calc.mfu(None) == 0.0


# ---------------------------------------------------------------------------
# registry: catalog validation, snapshot, Prometheus round-trip
# ---------------------------------------------------------------------------

def test_registry_rejects_undeclared_names():
    r = MetricRegistry()
    with pytest.raises(ValueError, match="CATALOG"):
        r.gauge("train/definitely_not_declared")
    # dynamic families are legal without a catalog row
    r.gauge("train/rms/layers/0/attn")
    r.gauge("train/aux/router_entropy")
    r.gauge("eval/my_benchmark")


def test_registry_snapshot_and_prometheus_round_trip():
    r = MetricRegistry()
    c = r.counter("serving/tokens_generated")
    g = r.gauge("serving/page_occupancy")
    h = r.histogram("serving/ttft_ms")
    r.func_gauge("resilience/guard_bad_steps", lambda: 5)
    c.inc(41)
    c.inc()
    g.set(0.75)
    g.set(float("nan"))            # scrapers must never see a NaN
    for v in (10.0, 20.0, 30.0):
        h.record(v)

    snap = r.snapshot()
    assert snap["serving/tokens_generated"] == 42.0
    assert snap["serving/page_occupancy_peak"] == 0.75
    assert snap["serving/ttft_ms_p50"] == 20.0
    assert snap["serving/ttft_ms_count"] == 3.0
    assert snap["resilience/guard_bad_steps"] == 5.0
    for k in snap:
        assert is_catalog_name(k), k

    text = r.prometheus_text()
    samples = parse_prometheus_text(text)   # strict: raises on bad lines
    assert samples[("dla_serving_tokens_generated_total", ())] == 42.0
    assert samples[("dla_serving_page_occupancy", ())] == 0.0  # NaN -> 0
    assert samples[("dla_serving_page_occupancy_peak", ())] == 0.75
    assert samples[("dla_serving_ttft_ms",
                    (("quantile", "0.5"),))] == 20.0
    assert samples[("dla_serving_ttft_ms_sum", ())] == 60.0
    assert samples[("dla_serving_ttft_ms_count", ())] == 3.0
    # counters follow the _total convention; TYPE comments are present
    assert "# TYPE dla_serving_tokens_generated_total counter" in text
    assert "# TYPE dla_serving_page_occupancy gauge" in text


def test_parse_prometheus_rejects_malformed_lines():
    with pytest.raises(ValueError, match="not a prometheus sample"):
        parse_prometheus_text("dla_x 1.0\nthis is { not a sample\n")
    with pytest.raises(ValueError, match="unquoted label"):
        parse_prometheus_text('dla_x{quantile=0.5} 1.0\n')


def test_prometheus_name_sanitizes():
    assert prometheus_name("serving/ttft_ms") == "dla_serving_ttft_ms"
    assert prometheus_name("train/rms/layers/0") == "dla_train_rms_layers_0"


def test_histogram_summary_is_windowed_but_totals_monotonic():
    h = Histogram(window=4)
    for v in (1.0, 2.0, 3.0, 4.0, 100.0, 100.0, 100.0, 100.0):
        h.record(v)
    s = h.summary()
    assert s["p50"] == 100.0       # window holds only the last 4
    assert h.total_count == 8      # but _count/_sum never forget
    assert h.total_sum == pytest.approx(410.0)


# ---------------------------------------------------------------------------
# flight recorder
# ---------------------------------------------------------------------------

def test_flight_recorder_ring_postmortem_and_sanitize(tmp_path):
    rec = FlightRecorder(capacity=4, out_dir=str(tmp_path))
    for s in range(1, 8):
        rec.record("step_end", step=s, loss=0.1 * s)
    rec.record("guard_bad_step", step=7, loss=float("nan"))
    assert len(rec.events) == 4    # bounded ring: oldest events dropped
    assert rec.last_completed_step() == 7

    path = rec.dump("watchdog_hang", extra={"stacks": "MainThread ..."})
    assert path is not None and path.name == "postmortem_watchdog_hang.json"

    def _reject(tok):
        raise ValueError(tok)

    doc = json.loads(path.read_text(), parse_constant=_reject)
    assert doc["reason"] == "watchdog_hang"
    assert doc["last_completed_step"] == 7
    assert doc["num_events"] == 4
    assert doc["stacks"] == "MainThread ..."
    nan_evt = [e for e in doc["events"]
               if e["kind"] == "guard_bad_step"][0]
    assert nan_evt["loss"] is None   # strict JSON even for a NaN loss
    # re-dump overwrites the same reason file (LAST occurrence survives)
    rec.record("step_end", step=9)
    rec.dump("watchdog_hang")
    assert json.loads(path.read_text())["last_completed_step"] == 9
    assert rec.dumps_written == 2


def test_flight_recorder_without_out_dir_needs_explicit_path(tmp_path):
    rec = FlightRecorder()
    rec.record("step_end", step=1)
    assert rec.dump("oops") is None
    p = rec.dump("oops", path=str(tmp_path / "pm.json"))
    assert p is not None and json.loads(p.read_text())["num_events"] == 1


def test_watchdog_fire_writes_postmortem(tmp_path):
    """Pin (d): a watchdog-style hang dumps the ring to a postmortem
    naming the last completed step — before on_hang/abort can kill the
    process."""
    rec = FlightRecorder(out_dir=str(tmp_path))
    for s in range(1, 6):
        rec.record("step_end", step=s)
    fired = threading.Event()
    wd = Watchdog(timeout_s=0.15, poll_s=0.03, abort=False,
                  on_hang=lambda dump: fired.set(), recorder=rec)
    wd.start()
    try:
        assert fired.wait(timeout=5.0)   # no beats -> it trips
    finally:
        wd.stop()
    pm = tmp_path / "postmortem_watchdog_hang.json"
    assert pm.exists()
    doc = json.loads(pm.read_text())
    assert doc["last_completed_step"] == 5
    assert "MainThread" in doc["stacks"]
    assert doc["events"][-1]["kind"] == "watchdog_hang"


# ---------------------------------------------------------------------------
# static metric-name check (tools/check_metric_names.py)
# ---------------------------------------------------------------------------

def test_check_metric_names_repo_is_clean_and_drift_detected(tmp_path,
                                                            capsys):
    from tools.check_metric_names import run
    from pathlib import Path
    assert run() == 0                      # the repo itself passes

    bad = tmp_path / "dla_tpu"
    bad.mkdir()
    (bad / "x.py").write_text('m = "train/not_in_the_catalog"\n')
    (tmp_path / "bench.py").write_text("")
    assert run(Path(tmp_path)) == 1
    err = capsys.readouterr().err
    assert "x.py:1" in err and "train/not_in_the_catalog" in err


def test_catalog_specs_are_well_formed():
    seen = set()
    for spec in CATALOG:
        assert spec.name not in seen, f"duplicate catalog row {spec.name}"
        seen.add(spec.name)
        assert spec.kind in ("counter", "gauge", "histogram"), spec


# ---------------------------------------------------------------------------
# trainer integration: zero-compile collector, goodput under stall,
# postmortem on preemption — tiny regression problem on mesh8
# ---------------------------------------------------------------------------

DIM = 8


def _make_batch(i, bs=8):
    rs = np.random.RandomState(2000 + i)
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


def _stashing_loss(params, frozen, batch, rng):
    """Loss that exercises the trace-time scalar stash from 'model
    code': per-layer RMS and an auxiliary scalar, both riding the
    existing step's metrics pytree."""
    del frozen, rng
    pred = batch["x"] @ params["w"]
    stash_rms("pred", pred)
    stash_scalar("pred_mean", jnp.mean(pred))
    return jnp.mean((pred - batch["y"]) ** 2), {}


def _make_trainer(mesh, out_dir, *, max_steps=8, save_every=0,
                  log_every=10 ** 6, telemetry=None, resilience=None,
                  loss_fn=_stashing_loss):
    from dla_tpu.training.trainer import Trainer
    logging_cfg = {"output_dir": str(out_dir), "log_dir": None,
                   "save_every_steps": save_every,
                   "log_every_steps": log_every}
    if telemetry is not None:
        logging_cfg["telemetry"] = telemetry
    config = {
        "experiment_name": "telemetry_test",
        "data": {"prefetch": 0},
        "optimization": {"total_batch_size": 8, "micro_batch_size": 1,
                         "learning_rate": 1e-2, "max_train_steps": max_steps,
                         "lr_scheduler": "constant", "max_grad_norm": 1.0},
        "logging": logging_cfg,
        "hardware": {"gradient_accumulation_steps": 2},
    }
    if resilience is not None:
        config["resilience"] = resilience
    return Trainer(config=config, mesh=mesh, loss_fn=loss_fn,
                   params={"w": jnp.zeros((DIM,), jnp.float32)},
                   param_specs={"w": P()})


def test_collector_adds_zero_compiles_and_surfaces_scalars(mesh8,
                                                           tmp_path):
    """Pin (b): the in-graph collector + stash ride the ONE jitted train
    step — the trace-time compile counter stays at exactly 1 — and the
    collected scalars surface under their catalog names."""
    with jax.sharding.set_mesh(mesh8):
        tr = _make_trainer(mesh8, tmp_path / "run", max_steps=8,
                           log_every=4,
                           telemetry={"collector": {"per_layer": True}})
        it = BatchIter()
        tr.fit(it, rng=jax.random.key(0), data_state=it.state_dict)
        assert tr.step == 8
        assert tr.train_step_compiles == 1     # THE zero-extra-compile pin

        snap = tr.registry.snapshot()
        # collector norms + per-layer grad RMS + the stash, catalog-named
        assert snap["train/param_norm"] > 0.0
        assert snap["train/update_norm"] > 0.0
        assert snap["train/rms/w"] > 0.0       # per-leaf grad RMS
        assert snap["train/rms/pred"] > 0.0    # stash_rms from loss code
        assert "train/aux/pred_mean" in snap   # stash_scalar
        assert snap["train/grad_norm"] > 0.0
        # step-time decomposition made it into the same snapshot; MFU
        # is a device metric and a CPU run must not publish one
        assert snap["telemetry/step_ms"] > 0.0
        assert 0.0 <= snap["telemetry/goodput"] <= 1.0
        assert "telemetry/mfu" not in snap
        assert snap["tokens_per_sec_per_chip"] > 0.0

        # segment attribution is exhaustive: segments + other == wall
        clk = tr.clock
        attributed = sum(clk.seg_total.values()) + clk.other_total
        assert attributed == pytest.approx(clk.wall_total, rel=1e-6)
        assert clk.seg_total["compute"] > 0.0
        assert clk.steps_ok == 8


def test_collector_off_switch_disables_cleanly(mesh8, tmp_path):
    with jax.sharding.set_mesh(mesh8):
        tr = _make_trainer(mesh8, tmp_path / "run", max_steps=4,
                           telemetry={"enabled": False})
        it = BatchIter()
        tr.fit(it, rng=jax.random.key(0), data_state=it.state_dict)
        assert tr.step == 4
        assert tr.train_step_compiles == 1
        assert tr.clock.wall_total == 0.0      # clock fully inert
        snap = tr.registry.snapshot()
        assert "train/param_norm" not in snap  # collector off too


class _StallClock:
    """``now`` for a trainer's StepClock under test: every reading
    advances one millisecond, so compute, data wait and logging cost the
    same however loaded the machine is, and the real seconds spent
    inside ``Trainer.save`` (the only wall time the test below is about)
    are added on top."""

    def __init__(self, trainer):
        self.t = 0.0
        trainer.clock.now = self
        save = trainer.save

        def timed_save(*args, **kwargs):
            t0 = time.perf_counter()
            try:
                return save(*args, **kwargs)
            finally:
                self.t += time.perf_counter() - t0
        trainer.save = timed_save

    def __call__(self):
        self.t += 0.001
        return self.t


def test_goodput_falls_under_injected_checkpoint_stall(mesh8, tmp_path,
                                                       monkeypatch):
    """Pin (a): an io_error injected via DLA_FAULT_PLAN makes the
    background checkpoint writer retry with backoff; the NEXT save's
    backpressure wait shows up as checkpoint_stall and drags goodput
    down vs the fault-free run. Asserted on an injected clock
    (_StallClock): under six test workers the steps' own wall time
    swamped a 0.4 s stall."""
    with jax.sharding.set_mesh(mesh8):
        monkeypatch.delenv(ENV_VAR, raising=False)
        clean = _make_trainer(mesh8, tmp_path / "clean", max_steps=6,
                              save_every=2,
                              resilience={"async_checkpointing": True})
        _StallClock(clean)
        it = BatchIter()
        clean.fit(it, rng=jax.random.key(0), data_state=it.state_dict)
        clean.checkpointer.wait()

        monkeypatch.setenv(ENV_VAR, "step=2:io_error")
        tr = _make_trainer(
            mesh8, tmp_path / "stalled", max_steps=6, save_every=2,
            resilience={"async_checkpointing": True, "save_retries": 3,
                        "retry_backoff_s": 0.4})
        _StallClock(tr)
        it2 = BatchIter()
        tr.fit(it2, rng=jax.random.key(0), data_state=it2.state_dict)
        tr.checkpointer.wait()

        assert tr.checkpointer.retries_total == 1
        # the retry backoff surfaced as step-loop checkpoint stall
        assert tr.clock.seg_total["checkpoint_stall"] >= 0.3
        assert tr.checkpointer.total_stall_ms >= 300.0
        assert tr.clock.badput()["checkpoint"] > 0.1
        assert tr.clock.goodput() < clean.clock.goodput()
        # the stall is attributed, not lost: accounting stays exhaustive
        attributed = sum(tr.clock.seg_total.values()) + tr.clock.other_total
        assert attributed == pytest.approx(tr.clock.wall_total, rel=1e-6)


def test_preemption_writes_postmortem_naming_last_step(mesh8, tmp_path):
    """Acceptance pin: killing a run mid-stream leaves a postmortem JSON
    whose last_completed_step says where to resume from."""
    with jax.sharding.set_mesh(mesh8):
        out = tmp_path / "run"
        tr = _make_trainer(
            mesh8, out, max_steps=8, save_every=4,
            resilience={"preemption": True, "fault_plan": "step=3:preempt"})
        it = BatchIter()
        with pytest.raises(PreemptionExit) as exc_info:
            tr.fit(it, rng=jax.random.key(0), data_state=it.state_dict)
        pm = out / "postmortem_preemption.json"
        assert pm.exists()
        doc = json.loads(pm.read_text())
        assert doc["reason"] == "preemption"
        assert doc["last_completed_step"] == exc_info.value.step == 3
        kinds = [e["kind"] for e in doc["events"]]
        assert "preempt_requested" in kinds
        assert "preemption_exit" in kinds


# ---------------------------------------------------------------------------
# serving: live /metrics endpoint round-trips through the strict parser
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def serve_setup():
    from dla_tpu.generation.engine import GenerationConfig
    from dla_tpu.models.config import get_model_config
    from dla_tpu.models.transformer import Transformer
    cfg = get_model_config("tiny")
    model = Transformer(cfg)
    params = model.init(jax.random.key(7))
    gen = GenerationConfig(max_new_tokens=5, do_sample=False,
                           eos_token_id=2, pad_token_id=0)
    return model, params, gen


def test_live_metrics_endpoint_round_trips(serve_setup):
    """Pin (c): GET /metrics on a live engine returns valid Prometheus
    text — every line parses strictly — including TTFT/ITL summaries
    and occupancy gauges with real values."""
    from dla_tpu.serving import ServingConfig, ServingEngine
    model, params, gen = serve_setup
    eng = ServingEngine(model, params, gen, ServingConfig(
        page_size=4, num_pages=32, num_slots=2, max_model_len=32))
    try:
        rs = np.random.RandomState(5)
        for _ in range(3):
            eng.submit(list(rs.randint(3, 500, (4,))), 5)
        eng.run_until_drained(max_steps=500)

        # the JSONL snapshot speaks catalog names, queue-wait included
        snap = eng.metrics.snapshot()
        for k in snap:
            assert is_catalog_name(k), k
        assert snap["serving/queue_wait_ms_count"] == 3.0
        assert not math.isnan(snap["serving/ttft_ms_p50"])

        srv = eng.start_metrics_server(port=0)
        assert eng.start_metrics_server() is srv   # idempotent
        with urllib.request.urlopen(srv.url, timeout=5) as resp:
            assert resp.status == 200
            assert resp.headers["Content-Type"].startswith(
                "text/plain; version=0.0.4")
            text = resp.read().decode()

        samples = parse_prometheus_text(text)      # strict round-trip
        assert samples[("dla_serving_requests_finished_total", ())] == 3.0
        assert samples[("dla_serving_tokens_generated_total", ())] > 0.0
        assert samples[("dla_serving_ttft_ms",
                        (("quantile", "0.5"),))] >= 0.0
        assert samples[("dla_serving_ttft_ms_count", ())] == 3.0
        assert samples[("dla_serving_itl_ms",
                        (("quantile", "0.95"),))] >= 0.0
        assert ("dla_serving_queue_wait_ms_count", ()) in samples
        assert samples[("dla_serving_page_occupancy_peak", ())] > 0.0
        assert samples[("dla_serving_active_requests", ())] == 0.0

        # readiness route (the engine's probe was beaten by its steps,
        # so it reports fresh) + 404 for anything else
        health = srv.url.replace("/metrics", "/healthz")
        with urllib.request.urlopen(health, timeout=5) as resp:
            assert resp.read().startswith(b"ok")
        with pytest.raises(urllib.error.HTTPError):
            urllib.request.urlopen(
                srv.url.replace("/metrics", "/nope"), timeout=5)
    finally:
        eng.close()
    assert eng.metrics_server is None              # close() tore it down
