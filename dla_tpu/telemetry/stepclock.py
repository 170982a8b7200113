"""Step-time decomposition and goodput accounting.

A training step's wall clock hides several very different costs: waiting
on the input pipeline, reshaping/placing the batch on device, the jitted
device step itself, metric emission, and — the big silent one — blocking
on checkpoint I/O. ``StepClock`` attributes every wall-clock second of
the train loop to exactly one of those segments and rolls them up into
**goodput**: the fraction of total wall time spent doing useful device
compute (the definition Podracer / the TPUv4 scaling papers use for
fleet accounting).

Badput is broken out by cause so the fix is obvious from the metric:

- ``compile``    — device-compute time of steps flagged as compiling
  (first step, or any re-trace). Fix: static shapes, AOT warmup.
- ``fault``      — full wall time of failed attempts (NaN-guard retries,
  injected faults, held-batch replays). Fix: see resilience knobs.
- ``checkpoint`` — step-loop stall waiting on checkpoint writes. Fix:
  async checkpointing / larger writer backlog.
- ``elastic``    — wall time lost to a host-loss event: lease-expiry
  detection through the restart to the topology-shift resume (charged
  in one piece by the resumed trainer via ``charge_external``). Fix:
  tighter lease TTL, denser checkpoint cadence.

Usage (the trainer's fit loop)::

    clock = StepClock()
    with clock.segment("data_wait"):  batch = next(gen)
    with clock.segment("h2d"):        batch = place(batch)
    clock.mark_compile()              # first step only
    with clock.segment("compute"):    loss = step(batch)
    clock.end_step(ok=True)
    clock.count_tokens(n_tokens)      # feeds rates(): tokens_per_sec
    ...
    logger.log(clock.interval_metrics(), step)   # every log interval

The clock is host-side only (pure ``time.perf_counter``), costs tens of
nanoseconds per segment, and never touches jax.
"""
from __future__ import annotations

import time
from contextlib import contextmanager
from typing import Callable, ContextManager, Dict, List, Optional

from dla_tpu.telemetry.trace import Tracer, get_tracer

#: Segment names a step decomposes into. "other" is derived (wall minus
#: attributed), never passed to segment().
SEGMENTS = ("data_wait", "h2d", "compute", "metrics_fetch",
            "checkpoint_stall", "logging", "eval")
LOSS_KINDS = ("compile", "fault", "checkpoint", "elastic")


class _NullContext:
    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


class StepClock:
    """Per-step wall-clock attribution + cumulative goodput.

    ``enabled=False`` turns every method into a near-free no-op — the
    bench.py ``telemetry`` target uses this as the zero-overhead
    baseline, and it is the off-switch for ``logging.telemetry``.
    """

    def __init__(self, enabled: bool = True, now=time.perf_counter,
                 tracer: Optional[Tracer] = None,
                 span: Optional[Callable[[str], ContextManager]] = None):
        self.enabled = enabled
        self.now = now
        # a second sink for every segment: ``span(name)`` is entered
        # inside the timed region (the trainer hands the profiler's
        # ``train_<segment>`` annotation, so segments sit on the device
        # trace's clock too)
        self.span = span
        # trace feed: each segment becomes a slice on the trainer thread,
        # each step a parent slice + goodput counter sample. The tracer
        # must share this clock's time base (both default perf_counter);
        # the default global tracer is disabled, so this is free unless
        # a trace was configured.
        self.tracer = tracer if tracer is not None else get_tracer()
        # current-step accumulation
        self._step_start: Optional[float] = None
        self._seg_acc: Dict[str, float] = {}
        self._compile_pending = False
        # cumulative totals (seconds) since construction
        self.wall_total = 0.0
        self.good_compute = 0.0
        self.lost: Dict[str, float] = {k: 0.0 for k in LOSS_KINDS}
        self.seg_total: Dict[str, float] = {s: 0.0 for s in SEGMENTS}
        self.other_total = 0.0
        self.steps_ok = 0
        self.steps_failed = 0
        # last completed attempt's wall time (ms): the anomaly monitor's
        # per-step feed — no second timer around the same loop
        self.last_wall_ms = 0.0
        # interval window (reset by interval_metrics)
        self._win: List[Dict[str, float]] = []
        # throughput since the first counted step (see count_tokens)
        self._rate_t0: Optional[float] = None
        self._rate_tokens = 0
        self._rate_steps = 0

    # ------------------------------------------------------------- recording

    def _ensure_started(self) -> None:
        if self._step_start is None:
            self._step_start = self.now()
            self._seg_acc = {}

    @contextmanager
    def _timed(self, name: str):
        self._ensure_started()
        t0 = self.now()
        try:
            if self.span is None:
                yield
            else:
                with self.span(name):
                    yield
        finally:
            t1 = self.now()
            self._seg_acc[name] = (self._seg_acc.get(name, 0.0)
                                   + t1 - t0)
            self.tracer.complete(name, t0, t1, cat="step")

    def segment(self, name: str):
        """Context manager attributing the enclosed wall time to one
        segment of the current step. Re-entering the same name within a
        step accumulates."""
        if not self.enabled:
            return _NullContext()
        if name not in SEGMENTS:
            raise ValueError(f"unknown step segment {name!r}; "
                             f"one of {SEGMENTS}")
        return self._timed(name)

    def mark_compile(self) -> None:
        """Flag the current step's device compute as compile time (call
        before the first dispatch of a fresh jitted fn)."""
        if self.enabled:
            self._ensure_started()
            self._compile_pending = True

    def end_step(self, ok: bool = True, step: Optional[int] = None) -> None:
        """Close the current step attempt. ``ok=False`` (guard retry,
        injected fault) charges the attempt's entire wall time to
        ``lost["fault"]`` — a failed attempt produced no progress, so
        none of it is goodput. ``step`` (when the caller knows it) tags
        the trace slice."""
        if not self.enabled or self._step_start is None:
            return
        t_end = self.now()
        wall = t_end - self._step_start
        self.last_wall_ms = 1000.0 * wall
        seg = dict(self._seg_acc)
        other = max(0.0, wall - sum(seg.values()))
        compute = seg.get("compute", 0.0)

        self.wall_total += wall
        for s in SEGMENTS:
            self.seg_total[s] += seg.get(s, 0.0)
        self.other_total += other
        self.lost["checkpoint"] += seg.get("checkpoint_stall", 0.0)
        if not ok:
            self.steps_failed += 1
            self.lost["fault"] += wall
        else:
            self.steps_ok += 1
            if self._compile_pending:
                self.lost["compile"] += compute
            else:
                self.good_compute += compute
        self._win.append({"wall": wall, "other": other, **seg})

        if self.tracer.enabled:
            args: Dict[str, object] = {"ok": ok}
            if step is not None:
                args["step"] = int(step)
            if self._compile_pending:
                args["compile"] = True
            self.tracer.complete("step", self._step_start, t_end,
                                 cat="step", args=args)
            self.tracer.counter("goodput", self.goodput(), t=t_end)

        self._step_start = None
        self._seg_acc = {}
        self._compile_pending = False

    def count_tokens(self, n_tokens: int) -> None:
        """Count one completed step's tokens for :meth:`rates`. The first
        call only starts the rate clock, so the first (compiling) step is
        left out. Counts whether or not the clock is ``enabled``: the
        log payload's throughput keys do not depend on telemetry."""
        if self._rate_t0 is None:
            self._rate_t0 = self.now()
            return
        self._rate_tokens += n_tokens
        self._rate_steps += 1

    def rates(self, chips: int) -> Dict[str, float]:
        """Wall-clock throughput since the first counted step."""
        if self._rate_t0 is None or not self._rate_steps:
            return {"tokens_per_sec": 0.0, "ms_per_step": 0.0}
        dt = self.now() - self._rate_t0
        return {
            "tokens_per_sec": self._rate_tokens / dt,
            "tokens_per_sec_per_chip": self._rate_tokens / dt / chips,
            "ms_per_step": 1000.0 * dt / self._rate_steps,
        }

    def charge_external(self, kind: str, seconds: float) -> None:
        """Attribute wall time that happened OUTSIDE this step loop to
        one badput kind — the elastic detect → restart → resume gap
        spans a process exit, so the resumed trainer charges it here in
        one piece. Extends ``wall_total`` too, so goodput reflects the
        outage honestly."""
        if not self.enabled or seconds <= 0.0:
            return
        if kind not in LOSS_KINDS:
            raise ValueError(f"unknown badput kind {kind!r}; "
                             f"one of {LOSS_KINDS}")
        # dla: disable=host-sync-in-hot-loop -- caller passes a host wall-clock gap; once per resume, no device fetch
        self.lost[kind] += float(seconds)
        # dla: disable=host-sync-in-hot-loop -- caller passes a host wall-clock gap; once per resume, no device fetch
        self.wall_total += float(seconds)

    # --------------------------------------------------------------- exports

    def goodput(self) -> float:
        """Cumulative useful-device-compute fraction of wall clock."""
        if self.wall_total <= 0.0:
            return 0.0
        return self.good_compute / self.wall_total

    def badput(self) -> Dict[str, float]:
        if self.wall_total <= 0.0:
            return {k: 0.0 for k in LOSS_KINDS}
        return {k: v / self.wall_total for k, v in self.lost.items()}

    def interval_metrics(self, reset: bool = True) -> Dict[str, float]:
        """Catalog-named metric dict for one log interval: mean ms per
        segment over the window since the previous call, plus cumulative
        goodput/badput fractions."""
        if not self.enabled:
            return {}
        n = max(1, len(self._win))
        mean = lambda key: 1000.0 * sum(  # noqa: E731
            w.get(key, 0.0) for w in self._win) / n
        out = {
            "telemetry/step_ms": mean("wall"),
            "telemetry/data_wait_ms": mean("data_wait"),
            "telemetry/h2d_ms": mean("h2d"),
            "telemetry/compute_ms": mean("compute"),
            "telemetry/metrics_fetch_ms": mean("metrics_fetch"),
            "telemetry/checkpoint_stall_ms": mean("checkpoint_stall"),
            "telemetry/logging_ms": mean("logging"),
            "telemetry/eval_ms": mean("eval"),
            "telemetry/other_ms": mean("other"),
            "telemetry/goodput": self.goodput(),
        }
        for kind, frac in self.badput().items():
            out[f"telemetry/badput_{kind}"] = frac
        if reset:
            self._win = []
        return out
