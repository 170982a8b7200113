"""Fault-tolerance tests (docs/RESILIENCE.md): the deterministic fault
plan, the NaN guard's retry/rollback, async checkpointing with injected
I/O errors, preemption with emergency save + resume, corrupt-checkpoint
fallback, the watchdog, and the serving engine's deadline/drain paths.

THE acceptance pin: a run through an injected io_error + nan + preempt,
resumed after the preemption, reaches the same final step with
bit-identical parameters to a fault-free run — and the guard adds zero
extra train-step compiles (trace-time counter pinned at 1).
"""
import os
import signal
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from dla_tpu.checkpoint import Checkpointer
from dla_tpu.resilience import (
    ENV_VAR,
    RETRY,
    ROLLBACK,
    SKIP,
    AsyncCheckpointer,
    FaultPlan,
    GuardConfig,
    GuardState,
    PreemptionExit,
    PreemptionHandler,
    ResilienceConfig,
    Watchdog,
)


# ---------------------------------------------------------------------------
# fault plan
# ---------------------------------------------------------------------------

def test_fault_plan_parse_and_one_shot_take():
    plan = FaultPlan.parse("step=12:io_error; step=5:nan ;step=50:preempt")
    # entries sort by step; spec() round-trips
    assert plan.spec() == "step=5:nan;step=12:io_error;step=50:preempt"
    assert bool(plan)
    # not due yet
    assert plan.take("nan", 4) is None
    # fires at the first poll with step >= entry.step, exactly once
    hit = plan.take("nan", 7)
    assert hit is not None and hit.step == 5
    assert plan.take("nan", 7) is None
    # other kinds unaffected, and each is one-shot too
    assert plan.take("io_error", 100).kind == "io_error"
    assert plan.take("io_error", 100) is None
    assert [f.kind for f in plan.pending()] == ["preempt"]


def test_fault_plan_arg_and_empty():
    plan = FaultPlan.parse("step=3:hang:0.25")
    assert plan.take("hang", 3).arg == 0.25
    empty = FaultPlan.parse("")
    assert not empty and empty.take("nan", 10 ** 9) is None


def test_fault_plan_rejects_bad_specs():
    with pytest.raises(ValueError):
        FaultPlan.parse("step=1")            # missing kind
    with pytest.raises(ValueError):
        FaultPlan.parse("step=1:bogus")      # unknown kind
    with pytest.raises(ValueError):
        FaultPlan.parse("at=1:nan")          # wrong key


def test_resilience_config_env_and_block(monkeypatch):
    monkeypatch.setenv(ENV_VAR, "step=7:nan")
    rc = ResilienceConfig.from_config(None)
    # conservative code defaults: only the guard is on by default
    assert not rc.async_checkpointing and not rc.preemption
    assert not rc.watchdog_enabled
    assert rc.guard.enabled
    assert rc.fault_plan.spec() == "step=7:nan"      # env picked up
    # an explicit config block overrides the env plan
    rc2 = ResilienceConfig.from_config(
        {"fault_plan": "step=1:hang:0.5", "async_checkpointing": True,
         "guard": {"max_consecutive_bad": 5, "rollback": False}})
    assert rc2.async_checkpointing
    assert rc2.fault_plan.entries[0].arg == 0.5
    assert rc2.guard.max_consecutive_bad == 5 and not rc2.guard.rollback


# ---------------------------------------------------------------------------
# guard policy (host half)
# ---------------------------------------------------------------------------

def test_guard_retry_then_rollback_sequence():
    g = GuardState(GuardConfig(max_consecutive_bad=3))
    assert g.on_step(True, 2.0) is None
    assert g.ema == 2.0                       # cold EMA seeds on first good
    assert g.on_step(False, float("nan")) == RETRY
    assert g.on_step(False, float("nan")) == RETRY
    assert g.on_step(False, float("nan")) == ROLLBACK
    assert g.consecutive_bad == 0             # counter reset after verdict
    assert g.bad_steps_total == 3 and g.rollbacks == 1
    # a good step in between resets the consecutive counter
    assert g.on_step(False, float("nan")) == RETRY
    assert g.on_step(True, 1.0) is None
    assert g.on_step(False, float("nan")) == RETRY
    g.reset_ema()
    assert g.ema == 0.0


def test_guard_skip_when_rollback_disabled():
    g = GuardState(GuardConfig(max_consecutive_bad=1, rollback=False))
    assert g.on_step(False, float("inf")) == SKIP
    assert g.rollbacks == 0


# ---------------------------------------------------------------------------
# watchdog
# ---------------------------------------------------------------------------

def test_watchdog_beat_defers_then_fires():
    fired = threading.Event()
    dumps = []

    def on_hang(dump):
        dumps.append(dump)
        fired.set()

    wd = Watchdog(timeout_s=0.2, poll_s=0.03, on_hang=on_hang, abort=False)
    wd.start()
    try:
        for _ in range(10):                   # heartbeats keep it quiet
            wd.beat()
            time.sleep(0.04)
        assert not wd.fired
        assert fired.wait(timeout=5.0)        # stop beating -> it trips
        assert wd.fired
        # the dump attributes the hang: every thread's stack, named
        assert "stack dump" in dumps[0]
        assert "MainThread" in dumps[0]
    finally:
        wd.stop()


def test_watchdog_rejects_nonpositive_timeout():
    with pytest.raises(ValueError):
        Watchdog(timeout_s=0.0)


# ---------------------------------------------------------------------------
# preemption handler
# ---------------------------------------------------------------------------

def test_preemption_sigterm_sets_flag_and_agreement():
    h = PreemptionHandler(signals=(signal.SIGTERM,))
    h.install()
    try:
        assert not h.requested_local()
        assert not h.should_checkpoint(0)
        os.kill(os.getpid(), signal.SIGTERM)
        time.sleep(0.05)                      # let the handler run
        assert h.requested_local()
        assert h.should_checkpoint(1)         # single host: plain flag read
        assert h.should_checkpoint(2)         # sticky
    finally:
        h.uninstall()


def test_preemption_exit_is_clean_systemexit():
    exc = PreemptionExit(17)
    assert isinstance(exc, SystemExit)
    assert exc.code == 0 and exc.step == 17


# ---------------------------------------------------------------------------
# async checkpointer
# ---------------------------------------------------------------------------

def _ck_tree():
    return {"w": jnp.arange(16, dtype=jnp.float32).reshape(4, 4),
            "n": jnp.zeros((), jnp.int32)}


def _assert_tree_equal(a, b):
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_async_checkpointer_roundtrip(tmp_path):
    ck = AsyncCheckpointer(str(tmp_path / "ck"))
    tree = _ck_tree()
    ck.save(1, tree, aux={"step": 1})
    ck.wait()
    assert not ck.in_flight
    assert ck.saves_started == ck.saves_completed == 1
    assert ck.latest_tag() == "step_00000001"
    got, aux = ck.restore(tree)
    assert aux["step"] == 1
    _assert_tree_equal(tree, got)


def test_async_checkpointer_retries_injected_io_error(tmp_path):
    ck = AsyncCheckpointer(str(tmp_path / "ck"), max_retries=3,
                           backoff_s=0.01,
                           faults=FaultPlan.parse("step=0:io_error"))
    tree = _ck_tree()
    ck.save(2, tree, aux={"step": 2})
    ck.wait()                                 # retry recovered in background
    assert ck.retries_total == 1
    assert ck.saves_completed == 1
    got, _ = ck.restore(tree, tag="step_00000002")
    _assert_tree_equal(tree, got)


def test_async_checkpointer_surfaces_exhausted_retries(tmp_path):
    # two armed io_errors vs max_retries=1: both attempts fail and the
    # terminal error must re-raise on the TRAINING thread, not vanish
    ck = AsyncCheckpointer(
        str(tmp_path / "ck"), max_retries=1, backoff_s=0.001,
        faults=FaultPlan.parse("step=0:io_error;step=0:io_error"))
    tree = _ck_tree()
    ck.save(1, tree)
    with pytest.raises(OSError, match="injected io_error"):
        ck.wait()
    assert ck.retries_total == 1 and ck.saves_completed == 0
    # the checkpointer stays usable once the error has been surfaced
    ck.save(2, tree, aux={"step": 2})
    ck.wait()
    assert ck.latest_tag() == "step_00000002"


def test_async_checkpointer_exposes_last_error_age(tmp_path):
    """The flaky-FS gauges: ``last_error_age_s()`` is -1 until a write
    attempt fails, then tracks the age of the newest OSError — even when
    the retry recovered (a flaky FS shows up as a small, churning age
    next to a growing ``retries_total``)."""
    ck = AsyncCheckpointer(str(tmp_path / "ck"), max_retries=3,
                           backoff_s=0.01,
                           faults=FaultPlan.parse("step=0:io_error"))
    assert ck.last_error_age_s() == -1.0
    assert ck.last_error is None
    ck.save(1, _ck_tree(), aux={"step": 1})
    ck.wait()                                 # retry recovered
    assert ck.retries_total == 1 and ck.saves_completed == 1
    age = ck.last_error_age_s()
    assert 0.0 <= age < 60.0
    assert "injected io_error" in ck.last_error
    time.sleep(0.02)
    assert ck.last_error_age_s() > age        # it is an age, not a flag


def test_sweep_stale_tmp_and_atomic_latest(tmp_path):
    ck = Checkpointer(str(tmp_path / "ck"))
    ck.save(1, _ck_tree(), aux={"step": 1})
    # plant the debris a mid-write crash leaves behind
    (ck.dir / ".tmp_step_00000099").mkdir()
    (ck.dir / ".tmp_step_00000099" / "w.npy").write_bytes(b"junk")
    (ck.dir / ".latest.tmp").write_text("step_000000")  # truncated pointer
    removed = ck.sweep_stale_tmp()
    assert sorted(removed) == [".latest.tmp", ".tmp_step_00000099"]
    assert not (ck.dir / ".tmp_step_00000099").exists()
    # the real pointer was written atomically and survives the sweep
    assert (ck.dir / "latest").read_text().strip() == "step_00000001"
    assert ck.latest_tag() == "step_00000001"
    assert ck.sweep_stale_tmp() == []         # idempotent


# ---------------------------------------------------------------------------
# trainer integration: a tiny deterministic regression problem on mesh8
# ---------------------------------------------------------------------------

DIM = 8


def _make_batch(i, bs=8):
    rs = np.random.RandomState(1000 + i)
    x = rs.normal(size=(bs, DIM)).astype(np.float32)
    w_true = np.arange(1, DIM + 1, dtype=np.float32)
    return {"x": x, "y": (x @ w_true).astype(np.float32)}


class CountingIter:
    """Deterministic stream whose position is exact resume state
    (data.prefetch=0 keeps the trainer from wrapping it)."""

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


def _linear_loss(params, frozen, batch, rng):
    del frozen, rng
    pred = batch["x"] @ params["w"]
    return jnp.mean((pred - batch["y"]) ** 2), {}


def _make_trainer(mesh, out_dir, *, max_steps=12, save_every=4,
                  resilience=None):
    from dla_tpu.training.trainer import Trainer
    config = {
        "experiment_name": "resilience_test",
        "data": {"prefetch": 0},
        "optimization": {"total_batch_size": 8, "micro_batch_size": 1,
                         "learning_rate": 1e-2, "max_train_steps": max_steps,
                         "lr_scheduler": "constant", "max_grad_norm": 1.0},
        "logging": {"output_dir": str(out_dir), "log_dir": None,
                    "save_every_steps": save_every,
                    "log_every_steps": 10 ** 6},
        "hardware": {"gradient_accumulation_steps": 2},
    }
    if resilience is not None:
        config["resilience"] = resilience
    return Trainer(config=config, mesh=mesh, loss_fn=_linear_loss,
                   params={"w": jnp.zeros((DIM,), jnp.float32)},
                   param_specs={"w": P()})


def test_faulted_preempted_run_bit_identical_to_fault_free(mesh8, tmp_path):
    """THE acceptance pin: io_error (checkpoint write retried) + nan
    (guard retries the same batch with the same rng) + preempt (emergency
    save, clean exit, resume) must reproduce the fault-free run's final
    parameters bit-for-bit — and the guard/injector must add zero extra
    train-step compiles."""
    with jax.sharding.set_mesh(mesh8):
        ref = _make_trainer(mesh8, tmp_path / "ref",
                            resilience={"async_checkpointing": True})
        it_ref = CountingIter()
        p_ref = ref.fit(it_ref, rng=jax.random.key(42),
                        data_state=it_ref.state_dict)
        ref_bytes = np.asarray(p_ref["w"]).tobytes()
        assert ref.step == 12
        assert ref.train_step_compiles == 1

        faults = "step=3:io_error;step=5:nan;step=8:preempt"
        tr = _make_trainer(
            mesh8, tmp_path / "faulted",
            resilience={"async_checkpointing": True, "save_retries": 3,
                        "retry_backoff_s": 0.01, "preemption": True,
                        "fault_plan": faults})
        it = CountingIter()
        with pytest.raises(PreemptionExit) as exc_info:
            tr.fit(it, rng=jax.random.key(42), data_state=it.state_dict)
        assert exc_info.value.code == 0       # clean, resumable exit
        assert exc_info.value.step == 8       # emergency save boundary
        assert tr.guard.bad_steps_total == 1  # the injected NaN, retried
        assert tr.checkpointer.retries_total == 1     # the injected io_error
        assert tr.train_step_compiles == 1    # guard+injector: zero recompiles

        resumed = _make_trainer(mesh8, tmp_path / "faulted",
                                resilience={"async_checkpointing": True})
        it2 = CountingIter()
        p_res = resumed.fit(it2, rng=jax.random.key(42),
                            data_state=it2.state_dict, resume=True)
        assert it2.i == 12                    # data position resumed at 8
        assert resumed.step == 12
        assert resumed.train_step_compiles == 1
        assert np.asarray(p_res["w"]).tobytes() == ref_bytes


def test_persistent_nan_rolls_back_and_training_continues(mesh8, tmp_path):
    """A batch that NaNs deterministically exhausts the guard's retries;
    the trainer restores the last checkpoint, drops the poison batch,
    and still reaches max_steps with finite params."""
    with jax.sharding.set_mesh(mesh8):
        tr = _make_trainer(
            mesh8, tmp_path / "run", max_steps=8, save_every=4,
            resilience={"async_checkpointing": True,
                        "fault_plan": "step=5:nan;step=5:nan;step=5:nan",
                        "guard": {"max_consecutive_bad": 3}})
        it = CountingIter()
        p = tr.fit(it, rng=jax.random.key(0), data_state=it.state_dict)
        assert tr.step == 8
        assert tr.guard.bad_steps_total == 3
        assert tr.guard.rollbacks == 1        # rolled back to step_00000004
        assert tr.train_step_compiles == 1
        assert np.isfinite(np.asarray(p["w"])).all()


def test_resume_falls_back_past_corrupt_checkpoints(mesh8, tmp_path):
    """Satellite (d): a truncated index.json (ValueError) and a missing
    shard file (OSError) must each fall back to the previous good tag
    instead of crashing the resume."""
    with jax.sharding.set_mesh(mesh8):
        out = tmp_path / "run"
        tr = _make_trainer(mesh8, out, max_steps=8, save_every=4)
        it = CountingIter()
        tr.fit(it, rng=jax.random.key(0), data_state=it.state_dict)
        ckdir = tr.checkpointer.dir
        assert (ckdir / "latest").read_text().strip() == "final"

        # corrupt `final`: a write that died mid-index
        (ckdir / "final" / "index.json").write_text('{"leaves": [')
        t2 = _make_trainer(mesh8, out, max_steps=8, save_every=4)
        aux = t2.try_resume()
        assert t2.step == 8                   # fell back to step_00000008
        assert aux["step"] == 8

        # additionally lose a shard file from step_00000008
        victim = sorted((ckdir / "step_00000008").glob("*.npy"))[0]
        victim.unlink()
        t3 = _make_trainer(mesh8, out, max_steps=8, save_every=4)
        t3.try_resume()
        assert t3.step == 4                   # next fallback: step_00000004


def test_resume_with_every_tag_corrupt_raises_instead_of_looping(
        mesh8, tmp_path):
    """When `final` AND every step_* tag is corrupt there is nothing to
    fall back to: try_resume must surface the original corruption error
    promptly — not spin through fallbacks forever, and not leave the
    trainer half-restored."""
    with jax.sharding.set_mesh(mesh8):
        out = tmp_path / "run"
        tr = _make_trainer(mesh8, out, max_steps=8, save_every=4)
        it = CountingIter()
        tr.fit(it, rng=jax.random.key(0), data_state=it.state_dict)
        ckdir = tr.checkpointer.dir
        ntags = 0
        for tag_dir in ckdir.iterdir():
            if tag_dir.is_dir():
                (tag_dir / "index.json").write_text('{"leaves": [')
                ntags += 1
        assert ntags >= 3                     # final + two step tags

        t2 = _make_trainer(mesh8, out, max_steps=8, save_every=4)
        with pytest.raises(ValueError):       # the ORIGINAL error, loud
            t2.try_resume()
        assert t2.step == 0                   # no half-restored state


# ---------------------------------------------------------------------------
# serving: per-request deadlines + graceful drain
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


def _engine(serve_setup, clock=None, **cfg_kw):
    from dla_tpu.serving import ServingConfig, ServingEngine
    model, params, gen = serve_setup
    kw = dict(page_size=4, num_pages=32, num_slots=2, max_model_len=32)
    kw.update(cfg_kw)
    extra = {"now": clock} if clock is not None else {}
    return ServingEngine(model, params, gen, ServingConfig(**kw), **extra)


def _prompts(n, seed=5):
    rs = np.random.RandomState(seed)
    return [list(rs.randint(3, 500, (4,))) for _ in range(n)]


def test_serving_deadline_times_out_queued_and_running(serve_setup):
    from dla_tpu.serving import RequestState
    t = {"now": 0.0}
    eng = _engine(serve_setup, clock=lambda: t["now"], num_slots=1)
    p = _prompts(3)
    r_run = eng.submit(p[0], 5, deadline_s=1.0)     # admitted first
    r_queued = eng.submit(p[1], 5, deadline_s=0.5)  # one slot: waits
    r_free = eng.submit(p[2], 5)                    # no deadline
    eng.step()                                      # r_run prefills+decodes
    assert eng.result(r_run).generated              # sunk tokens exist
    t["now"] = 2.0
    eng.step()                                      # both deadlines passed
    assert eng.result(r_run).state is RequestState.TIMEOUT
    assert eng.result(r_run).finish_reason == "timeout"
    assert eng.result(r_run).generated              # kept on timeout
    assert eng.result(r_queued).state is RequestState.TIMEOUT
    assert not eng.result(r_queued).generated       # never started
    results = eng.run_until_drained(max_steps=500)
    assert results[r_free].state is RequestState.FINISHED
    assert eng.metrics.requests_timed_out.value == 2
    assert eng.cache.allocator.used_count == 0      # slot+pages reclaimed
    eng.scheduler.assert_consistent()


def test_serving_drain_closes_admission_and_sheds_unstarted(serve_setup):
    from dla_tpu.serving import RequestState
    eng = _engine(serve_setup, num_slots=1)
    p = _prompts(3, seed=9)
    r_run = eng.submit(p[0], 5)
    r_waiting = eng.submit(p[1], 5)
    eng.step()                                      # r_run takes the slot
    eng.begin_drain()
    eng.begin_drain()                               # idempotent
    assert eng.draining
    # never-started queued request was shed; admission is closed
    assert eng.result(r_waiting).finish_reason == "cancelled"
    assert eng.metrics.requests_cancelled.value == 1
    with pytest.raises(RuntimeError, match="draining"):
        eng.submit(p[2], 5)
    # the in-flight decode runs to completion — nothing dropped mid-token
    results = eng.run_until_drained(max_steps=500)
    assert results[r_run].state is RequestState.FINISHED
    assert len(results[r_run].generated) > 0
    assert eng.cache.allocator.used_count == 0
    eng.scheduler.assert_consistent()


def test_serving_sigterm_triggers_drain(serve_setup):
    eng = _engine(serve_setup)
    eng.install_drain_handler()
    assert eng._old_handlers is not None
    try:
        os.kill(os.getpid(), signal.SIGTERM)
        time.sleep(0.05)                            # deliver the signal
        assert eng.draining
    finally:
        for sig, old in eng._old_handlers.items():
            signal.signal(sig, old)


def test_serving_preemption_notice_mid_drain_is_idempotent(serve_setup):
    """Preemption notices landing MID-DRAIN — the cluster agent retries
    SIGTERM, plus a programmatic PreemptionHandler.request() — must not
    double-cancel the already-shed queue or disturb the in-flight
    decode: the drain keeps its nothing-dropped-mid-token guarantee."""
    from dla_tpu.resilience.preemption import PreemptionHandler
    from dla_tpu.serving import RequestState
    eng = _engine(serve_setup, num_slots=1)
    eng.install_drain_handler()
    handler = PreemptionHandler(recorder=eng.recorder)
    try:
        p = _prompts(2, seed=13)
        r_run = eng.submit(p[0], 5)
        r_wait = eng.submit(p[1], 5)
        eng.step()                          # r_run holds the slot
        eng.begin_drain()                   # drain begins: queue shed
        assert eng.result(r_wait).finish_reason == "cancelled"
        cancelled = eng.metrics.requests_cancelled.value
        eng.step()                          # mid-drain...
        os.kill(os.getpid(), signal.SIGTERM)    # ...the retry arrives
        time.sleep(0.05)
        handler.request()                   # and the agent RPC path
        assert eng.draining
        assert handler.requested_local()
        # idempotent: no double cancellation, no new terminal states
        assert eng.metrics.requests_cancelled.value == cancelled
        results = eng.run_until_drained(max_steps=500)
        assert results[r_run].state is RequestState.FINISHED
        assert len(results[r_run].generated) > 0
        # the RPC-path request landed on the engine's flight recorder
        assert any(e["kind"] == "preempt_requested"
                   for e in eng.recorder.events)
        assert eng.cache.allocator.used_count == 0
        eng.scheduler.assert_consistent()
    finally:
        for sig, old in eng._old_handlers.items():
            signal.signal(sig, old)
        eng.close()
