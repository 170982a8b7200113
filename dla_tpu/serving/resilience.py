"""Serving resilience: admission control + load shedding, the graceful
degradation ladder, and the engine Supervisor with deterministic
request replay.

Production serving treats overload and crash recovery as first-class:
one bad request, one device error, or one burst must never wedge the
engine or silently drop work. This module composes two primitives the
stack already has — the deterministic-recompute contract of eviction
(``Request.prefix_tokens``: a greedy re-prefill of prompt + generated
reproduces the continuation bit-identically) and the ``DLA_FAULT_PLAN``
injection harness — into a self-healing layer:

**Admission control / shedding** (:class:`AdmissionController`): a
token-bucket + bounded-wait-queue gate consulted by
``ServingEngine.submit``, plus a per-step SLO-aware shed pass that
drops the lowest-priority queued requests (terminal ``SHED`` status)
when the :mod:`~dla_tpu.telemetry.slo` burn rate says queue wait would
only blow their deadlines. Only never-started requests are sheddable;
in-flight work (including evicted requests holding generated tokens)
is never dropped.

**Degradation ladder** (:class:`DegradationLadder`): under sustained
pressure the engine gives up throughput optimizations before it gives
up requests — rung 1 flushes prefix-cache pages, rung 2 stops
co-scheduling prefill chunks with decode, rung 3 halves the admission
batch, rung 4 sheds. Every rung change is a flight-recorder event and
moves the ``serving/degradation_level`` gauge.

**Supervision** (:class:`Supervisor`): wraps ``ServingEngine.step``
with a Watchdog (armed only *inside* the step — idle gaps between
open-loop arrivals are not hangs), catches device errors and NaN
logits, then tears the engine down, rebuilds it via the caller's
factory, and replays every in-flight request from its journaled prompt
+ streamed tokens. Replay reuses the eviction recompute path, so
already-streamed tokens are never re-emitted and greedy outputs stay
bit-identical to a fault-free run. Restarts are bounded by a
:class:`CircuitBreaker`; when it trips, ``/healthz`` flips to 503
(body ``draining``) and the engine drains.

Everything here is host-side Python — no jitted code, no device state
of its own — so the whole ladder is CPU-testable through the
``engine_step=`` fault-plan grammar (see resilience/faults.py).
"""
from __future__ import annotations

import dataclasses
import threading
import time
from collections import deque
from typing import Callable, Dict, List, Optional, Tuple

from dla_tpu.resilience.watchdog import Watchdog
from dla_tpu.serving.scheduler import (
    Request,
    RequestState,
    TERMINAL_STATES,
)


class DeviceStepError(RuntimeError):
    """A jitted serving step failed at the device/runtime layer (the
    CPU-testable stand-in for XlaRuntimeError and friends, raised by
    ``engine_step=N:device_error`` injection)."""


class NaNLogitsError(RuntimeError):
    """Non-finite logits came back from the model — the serving analog
    of the trainer's NaN-guard trip. Raised by real detection on the
    host-visible prefill logits and by ``engine_step=N:nan_logits``
    injection on the decode path."""


# ----------------------------------------------------------------- shedding


@dataclasses.dataclass(frozen=True)
class ShedConfig:
    """Admission-control + degradation policy (the serving ``shed:``
    config block; ``ShedSchema`` in training/config.py mirrors it)."""
    max_queue_depth: int = 64      # bounded wait queue (excess sheds)
    rate: float = 0.0              # token-bucket refill, requests/s; 0 = off
    burst: int = 0                 # bucket capacity; 0 -> max_queue_depth
    slo_burn_threshold: float = 1.0  # shed queued work at/above this burn
    # degradation ladder hysteresis: escalate after `patience` steps at
    # or above `high` pressure, de-escalate after `patience` below `low`
    degrade_high: float = 0.85
    degrade_low: float = 0.5
    degrade_patience: int = 3

    @classmethod
    def from_config(cls, cfg: Optional[Dict]) -> Optional["ShedConfig"]:
        """Build from a config dict; None (or ``enabled: false``)
        disables admission control entirely."""
        if not cfg:
            return None
        cfg = dict(cfg)
        if not cfg.pop("enabled", True):
            return None
        known = {f.name for f in dataclasses.fields(cls)}
        unknown = sorted(set(cfg) - known)
        if unknown:
            raise ValueError(f"unknown shed config keys: {unknown}")
        return cls(**cfg)


class TokenBucket:
    """Classic request-rate gate: ``rate`` tokens/s refill up to
    ``burst`` capacity; each admission takes one. Clock comes in as an
    argument so tests drive it deterministically."""

    def __init__(self, rate: float, burst: float):
        self.rate = rate
        self.burst = burst
        self.tokens = burst          # starts full: bursts up to capacity
        self._t: Optional[float] = None

    def try_take(self, now: float) -> bool:
        if self._t is not None:
            self.tokens = min(self.burst,
                              self.tokens + (now - self._t) * self.rate)
        self._t = now
        if self.tokens >= 1.0:
            self.tokens -= 1.0
            return True
        return False


class AdmissionController:
    """Token-bucket / bounded-queue admission gate with per-request
    priority and SLO-aware queue shedding. Pure decision logic: the
    engine owns the terminal-SHED bookkeeping (metrics, trace spans,
    flight-recorder events)."""

    def __init__(self, cfg: ShedConfig):
        self.cfg = cfg
        self.bucket: Optional[TokenBucket] = None
        if cfg.rate > 0:
            self.bucket = TokenBucket(
                cfg.rate, cfg.burst if cfg.burst > 0 else cfg.max_queue_depth)

    def on_submit(self, sched, req: Request,
                  now: float) -> Tuple[bool, List[Request]]:
        """Gate one JUST-QUEUED arrival. Returns ``(admitted, victims)``
        where victims are the requests to shed: the arrival itself
        (bucket empty, or it is the worst of a full queue), or the
        lowest-priority queued request it displaces."""
        if self.bucket is not None and not self.bucket.try_take(now):
            return False, [req]
        if sched.queue_depth > self.cfg.max_queue_depth:
            cands = sched.sheddable_queued()
            worst = cands[0] if cands else req
            return worst.rid != req.rid, [worst]
        return True, []

    def shed_pass(self, sched, burn: float, level: int) -> List[Request]:
        """Per-step shed decision: enforce the queue bound, and — when
        the SLO burn rate is at/over threshold or the ladder reached its
        shed rung — trim the queue down to what the decode slots can
        absorb promptly, lowest-priority first. Returns the victims
        (not yet cancelled)."""
        victims: List[Request] = []
        cands = sched.sheddable_queued()
        keep = sched.queue_depth
        while keep > self.cfg.max_queue_depth and cands:
            victims.append(cands.pop(0))
            keep -= 1
        if burn >= self.cfg.slo_burn_threshold or level >= SHED_LEVEL:
            target = sched.cache.geom.num_slots
            while keep > target and cands:
                victims.append(cands.pop(0))
                keep -= 1
        return victims


# -------------------------------------------------------- degradation ladder

#: Rung names, in escalation order. Each rung keeps every lower rung's
#: effect: at level 3 the cache is flushed AND co-scheduling is off AND
#: the batch is shrunk.
LADDER_RUNGS = ("none", "flush_prefix_cache", "no_coschedule",
                "shrink_batch", "shed")
SHED_LEVEL = len(LADDER_RUNGS) - 1


class DegradationLadder:
    """Hysteresis controller over a scalar pressure signal (max of page
    occupancy and queue-depth fraction). Sustained pressure climbs one
    rung per ``degrade_patience`` window; sustained calm climbs back
    down. The engine applies the rung effects; the ladder owns the
    level, the flight-recorder events, and nothing else."""

    def __init__(self, cfg: ShedConfig, recorder=None):
        self.cfg = cfg
        self.recorder = recorder
        self.level = 0
        self._over = 0
        self._under = 0

    @property
    def no_coschedule(self) -> bool:
        return self.level >= 2

    @property
    def shrink_batch(self) -> bool:
        return self.level >= 3

    def update(self, pressure: float, step: Optional[int] = None) -> int:
        cfg = self.cfg
        if pressure >= cfg.degrade_high:
            self._under = 0
            self._over += 1
            if self._over >= cfg.degrade_patience and \
                    self.level < SHED_LEVEL:
                self._over = 0
                self._move(self.level + 1, pressure, step)
        elif pressure < cfg.degrade_low:
            self._over = 0
            self._under += 1
            if self._under >= cfg.degrade_patience and self.level > 0:
                self._under = 0
                self._move(self.level - 1, pressure, step)
        else:
            self._over = 0
            self._under = 0
        return self.level

    def _move(self, level: int, pressure: float,
              step: Optional[int]) -> None:
        prev, self.level = self.level, level
        if self.recorder is not None:
            self.recorder.record(
                "degradation", step=step, level=level,
                rung=LADDER_RUNGS[level], prev_level=prev,
                pressure=round(pressure, 4))


# ------------------------------------------------------------- supervision


@dataclasses.dataclass(frozen=True)
class SupervisorConfig:
    """Supervisor policy (the serving ``supervisor:`` config block;
    ``SupervisorSchema`` in training/config.py mirrors it)."""
    watchdog_timeout_s: float = 60.0   # wedged-step threshold
    watchdog_poll_s: Optional[float] = None  # default: timeout/4
    max_restarts: int = 3              # breaker budget per window
    restart_window_s: float = 600.0

    @classmethod
    def from_config(cls, cfg: Optional[Dict]
                    ) -> Optional["SupervisorConfig"]:
        if not cfg:
            return None
        cfg = dict(cfg)
        if not cfg.pop("enabled", True):
            return None
        known = {f.name for f in dataclasses.fields(cls)}
        unknown = sorted(set(cfg) - known)
        if unknown:
            raise ValueError(f"unknown supervisor config keys: {unknown}")
        return cls(**cfg)


class CircuitBreaker:
    """Sliding-window restart budget: more than ``max_restarts``
    restarts inside ``window_s`` trips the breaker. A tripped breaker
    never closes again for the supervisor's lifetime — a restart loop
    is an operator page, not something to ride out."""

    def __init__(self, max_restarts: int, window_s: float,
                 now: Callable[[], float] = time.monotonic):
        self.max_restarts = int(max_restarts)
        self.window_s = window_s
        self.now = now
        self._events: deque = deque()

    def record(self, t: Optional[float] = None) -> None:
        t = self.now() if t is None else t
        self._events.append(t)
        self._prune(t)

    def _prune(self, t: float) -> None:
        while self._events and t - self._events[0] > self.window_s:
            self._events.popleft()

    @property
    def tripped(self) -> bool:
        self._prune(self.now())
        return len(self._events) > self.max_restarts


@dataclasses.dataclass
class JournalEntry:
    """Everything needed to replay one request deterministically on a
    rebuilt engine: the immutable submission plus the tokens the client
    has already seen. Sampling state is (seed, token index) — the
    engine's per-request PRNG keying is history-free like argmax — so
    prompt + streamed + sampling IS the state the replay resumes from,
    greedy and sampled alike. ``streamed_logps`` mirrors ``streamed``
    so the replayed request's logprob surface is also seamless."""
    prompt_tokens: List[int]
    max_new_tokens: int
    priority: int
    arrival_time: float
    deadline: Optional[float]
    streamed: List[int]
    done: bool
    request: Request      # live request object on the CURRENT engine
    sampling: Optional[object] = None          # SamplingParams override
    streamed_logps: List[float] = dataclasses.field(default_factory=list)
    # owning tenant (multi-tenant serving): replay re-binds the adapter
    # and KV namespace from this — the rebuilt engine's factory must
    # republish the tenant's adapter (engine.restore fails loudly if not)
    tenant: Optional[str] = None
    # migration provenance: the fleet moves a journal entry to the
    # TARGET member's supervisor atomically with the KV install (popped
    # from the source first), so replay after a mid-handoff crash lands
    # the request on exactly one engine. These fields record where it
    # came from and how many hops it has taken — postmortem breadcrumbs,
    # not replay inputs.
    migrated_from: Optional[int] = None        # source fleet slot
    migrations: int = 0                        # completed handoffs


class Supervisor:
    """Supervises a ServingEngine: journaled intake, failure detection
    around every step, bounded teardown/rebuild with deterministic
    replay of in-flight work.

    ``factory`` builds a fresh engine (same model/params/config); the
    supervisor owns the engine's lifecycle from then on. Drive it like
    the engine itself::

        sup = Supervisor(lambda: ServingEngine(...), SupervisorConfig())
        rid = sup.submit(prompt, max_new_tokens=32)
        results = sup.run()          # step() in a loop, self-healing
        sup.close()

    Failure kinds and their detection sites:

    - ``wedge``: the Watchdog (armed only while ``engine.step`` runs)
      fires; the step eventually returned, so journaled state is
      consistent — rebuild to shed whatever latency debt built up.
    - ``device_error``: any non-NaN exception out of ``engine.step``.
    - ``nan_logits``: :class:`NaNLogitsError` out of the step.

    Every restart rebuilds the engine (compile counters restart at
    zero and pin at one per build — the static-shape invariant is per
    engine) and replays all non-terminal journal entries via
    ``engine.restore``; tokens emitted by a failed step were never
    committed to the journal, so the replay recomputes them — greedy
    outputs stay bit-identical. When the breaker trips, the rebuilt
    engine comes up draining (``/healthz`` 503 ``draining``); a
    further failure past that point resolves all remaining in-flight
    requests as SHED rather than restarting forever.
    """

    def __init__(self, factory: Callable[[], object],
                 cfg: Optional[SupervisorConfig] = None,
                 now: Callable[[], float] = time.monotonic,
                 on_burst: Optional[Callable[[int], None]] = None):
        self.factory = factory
        self.cfg = cfg or SupervisorConfig()
        self.now = now
        # burst-fault hook: called with K when an engine_step=N:burst=K
        # entry fires; None submits K synthetic low-priority requests
        self.on_burst = on_burst
        self.journal: Dict[int, JournalEntry] = {}
        self.restarts = 0
        self.replayed = 0
        # cumulative speculative-round totals captured from each dying
        # engine and re-seeded into its replacement, so serving/spec/*
        # stay monotonic across rebuilds like the supervisor counters
        self._spec_totals = {"rounds": 0, "proposed": 0, "accepted": 0,
                             "rollbacks": 0}
        # same carry for the KV-migration counters: serving/migration/*
        # totals survive rebuilds of the engine that earned them
        self._mig_totals = {"migrations": 0, "migrated_pages": 0,
                            "host_bounce_bytes": 0,
                            "failed_migrations": 0}
        # and for the adapter-pool counters: serving/adapter_pool/*
        # (a rebuilt engine's AdapterStore restarts at zero; republishes
        # by the factory then count on top of the carried totals)
        self._adapter_totals = {"publishes": 0, "loads": 0, "spills": 0}
        self.failures: List[str] = []     # restart kinds, in order
        self.tripped = False
        self.breaker = CircuitBreaker(
            self.cfg.max_restarts, self.cfg.restart_window_s, now=now)
        self._hang = threading.Event()
        self._watchdog: Optional[Watchdog] = None
        # one fault plan for the supervised run, carried across engine
        # generations: a rebuilt engine re-parses its config plan with
        # fresh consumed-state and a reset step counter, so without
        # this the same injected fault re-fires after every rebuild
        # and no plan ever drains
        self._fault_plan = None
        self.engine = None
        self._build_engine()

    # ----------------------------------------------------------- lifecycle

    def _build_engine(self) -> None:
        self.engine = self.factory()
        if self._fault_plan is None:
            self._fault_plan = getattr(self.engine, "faults", None)
        else:
            self.engine.faults = self._fault_plan
        m = self.engine.metrics
        # supervisor totals outlive engine rebuilds: re-seed the fresh
        # registry so /metrics stays monotonic across restarts
        m.supervisor_restarts.inc(self.restarts)
        m.replayed_requests.inc(self.replayed)
        m.breaker_open.set(1.0 if self.tripped else 0.0)
        t = self._spec_totals
        if any(t.values()):
            m.spec_rounds.inc(t["rounds"])
            m.spec_proposed.inc(t["proposed"])
            m.spec_accepted.inc(t["accepted"])
            m.spec_rollbacks.inc(t["rollbacks"])
            if t["proposed"]:
                m.spec_acceptance_rate.set(t["accepted"] / t["proposed"])
        mt = self._mig_totals
        if any(mt.values()):
            m.migrations.inc(mt["migrations"])
            m.migrated_pages.inc(mt["migrated_pages"])
            m.host_bounce_bytes.inc(mt["host_bounce_bytes"])
            m.failed_migrations.inc(mt["failed_migrations"])
        at = self._adapter_totals
        if any(at.values()):
            m.adapter_publishes.inc(at["publishes"])
            m.adapter_loads.inc(at["loads"])
            m.adapter_spills.inc(at["spills"])
        self._arm_watchdog()
        if self.tripped:
            self.engine.begin_drain()

    def _arm_watchdog(self) -> None:
        if self._watchdog is not None:
            self._watchdog.stop()
        self._hang.clear()
        wd = Watchdog(timeout_s=self.cfg.watchdog_timeout_s,
                      poll_s=self.cfg.watchdog_poll_s,
                      on_hang=lambda dump: self._hang.set(),
                      abort=False,
                      recorder=getattr(self.engine, "recorder", None))
        wd.pause()                 # armed only inside engine.step
        wd.start()
        self._watchdog = wd

    def close(self) -> None:
        if self._watchdog is not None:
            self._watchdog.stop()
            self._watchdog = None
        if self.engine is not None:
            self.engine.close()

    # -------------------------------------------------------------- intake

    def submit(self, prompt_tokens: List[int], max_new_tokens: int,
               arrival_time: Optional[float] = None,
               deadline_s: Optional[float] = None,
               priority: int = 0, sampling=None,
               tenant: Optional[str] = None) -> int:
        rid = self.engine.submit(
            prompt_tokens, max_new_tokens, arrival_time=arrival_time,
            deadline_s=deadline_s, priority=priority, sampling=sampling,
            tenant=tenant)
        req = self.engine.result(rid)
        self.journal[rid] = JournalEntry(
            prompt_tokens=list(prompt_tokens),
            max_new_tokens=int(max_new_tokens),
            priority=priority,
            arrival_time=req.arrival_time,
            deadline=req.deadline,
            streamed=[],
            done=req.state in TERMINAL_STATES,   # shed at the gate
            request=req,
            sampling=sampling,
            tenant=tenant)
        return rid

    def result(self, rid: int) -> Request:
        return self.journal[rid].request

    def cancel(self, rid: int, reason: str = "cancelled") -> Request:
        """Client-initiated cancellation through the journal: the entry
        is marked done so a later engine rebuild does NOT replay the
        request the client already walked away from."""
        req = self.engine.cancel(rid, reason)
        entry = self.journal.get(rid)
        if entry is not None:
            entry.done = True
        return req

    def results(self) -> Dict[int, Request]:
        return {rid: e.request for rid, e in self.journal.items()}

    def has_work(self) -> bool:
        return self.engine.has_work()

    @property
    def draining(self) -> bool:
        return self.engine.draining

    # --------------------------------------------------------- supervision

    def step(self) -> List[Tuple[int, int]]:
        """One supervised engine step: poll the burst fault, run the
        step under the watchdog, commit emitted tokens to the journal,
        restart on failure. Returns the step's (rid, token) stream —
        empty on a failed step (its tokens were never streamed and the
        replay recomputes them)."""
        self._poll_burst()
        eng = self.engine
        compile_mark = (eng.decode_compiles, eng.prefill_chunk_compiles,
                        eng.spec_draft_compiles, eng.spec_verify_compiles,
                        eng.export_compiles, eng.import_compiles)
        wd = self._watchdog
        wd.resume()
        try:
            emitted = eng.step()
        except Exception as exc:  # noqa: BLE001 — every step failure
            wd.pause()            # routes through the restart path
            kind = ("nan_logits" if isinstance(exc, NaNLogitsError)
                    else "device_error")
            self._restart(kind, repr(exc))
            return []
        wd.pause()
        self._commit(emitted)
        if self._hang.is_set():
            if (eng.decode_compiles, eng.prefill_chunk_compiles,
                    eng.spec_draft_compiles, eng.spec_verify_compiles,
                    eng.export_compiles,
                    eng.import_compiles) != compile_mark:
                # an XLA compile landed in this step: tracing/lowering
                # legitimately blows any serving latency budget (and
                # recurs on every rebuilt engine), so it is a known
                # outlier, not a wedge. The fired watchdog is spent —
                # arm a fresh one and move on.
                self._arm_watchdog()
            else:
                # the step DID return (an injected wedge sleeps; a
                # truly never-returning step is the process watchdog's
                # job) but blew the budget: state is consistent and
                # committed, so the emitted tokens are real — journal
                # first, then rebuild
                self._restart("wedge", None)
        return emitted

    # dla: hot-loop-root
    def run(self, max_steps: int = 100000) -> Dict[int, Request]:
        """Drive the supervised engine until drained; the self-healing
        analog of ``ServingEngine.run_until_drained``."""
        for _ in range(max_steps):
            if not self.has_work():
                return self.results()
            self.step()
        raise RuntimeError(
            f"supervised serving loop did not drain in {max_steps} steps")

    # ----------------------------------------------------------- internals

    def _commit(self, emitted: List[Tuple[int, int]]) -> None:
        for rid, tok in emitted:
            e = self.journal.get(rid)
            if e is not None and not e.done:
                e.streamed.append(tok)
                # the request's logprob list advances in lockstep with
                # its generated tokens (failed steps never commit), so
                # the committed token's logp is at the same index
                lps = e.request.generated_logprobs
                e.streamed_logps.append(
                    float(lps[len(e.streamed) - 1])
                    if len(lps) >= len(e.streamed) else 0.0)
        for e in self.journal.values():
            if not e.done and e.request.state in TERMINAL_STATES:
                e.done = True

    def _poll_burst(self) -> None:
        plan = getattr(self.engine, "faults", None)
        if not plan or self.engine.draining:
            return
        f = plan.take("burst", self.engine.engine_steps,
                      site="engine_step")
        if f is None:
            return
        k = 8 if f.arg is None else int(f.arg)
        rec = getattr(self.engine, "recorder", None)
        if rec is not None:
            rec.record("fault_injected", step=self.engine.engine_steps,
                       fault="burst", count=k)
        if self.on_burst is not None:
            self.on_burst(k)
            return
        ps = self.engine.cfg.page_size
        for i in range(k):
            self.submit([2 + (i % 7)] * ps, 4, priority=-1)

    def _restart(self, kind: str, detail: Optional[str]) -> None:
        eng = self.engine
        rec = getattr(eng, "recorder", None)
        if rec is not None:
            rec.record("engine_restart", step=eng.engine_steps,
                       failure=kind, detail=detail)
            rec.dump(f"engine_restart_{kind}")
        self.restarts += 1
        self.failures.append(kind)
        stats = getattr(eng, "_spec_stats", None)
        if stats:
            # fold the dying engine's speculative totals into the carry
            # before teardown; _build_engine re-seeds them
            for key in self._spec_totals:
                self._spec_totals[key] += int(stats.get(key, 0))
        mig = getattr(eng, "_mig_stats", None)
        if mig:
            for key in self._mig_totals:
                self._mig_totals[key] += int(mig.get(key, 0))
        store = getattr(eng, "adapter_store", None)
        if store is not None:
            for key in self._adapter_totals:
                self._adapter_totals[key] += int(getattr(store, key, 0))
        self.breaker.record(self.now())
        out_of_budget = self.tripped   # tripped BEFORE this failure
        self.tripped = self.tripped or self.breaker.tripped
        try:
            eng.close()
        except Exception:  # noqa: BLE001 — teardown of a failed engine
            pass
        if out_of_budget:
            # the post-trip drain engine failed too: stop restarting.
            # Everything still in flight resolves terminally as SHED —
            # the client sees a final status, never a hang.
            for e in self.journal.values():
                if not e.done:
                    e.request.finish_reason = "shed"
                    e.request.state = RequestState.SHED
                    e.done = True
        self._build_engine()
        rec = getattr(self.engine, "recorder", None)
        if self.tripped and not out_of_budget and rec is not None:
            rec.record("breaker_open", restarts=self.restarts)
            rec.dump("breaker_open")
        if not out_of_budget:
            self._replay()

    def _replay(self) -> None:
        pending = [e for e in self.journal.values() if not e.done]
        pending.sort(key=lambda e: e.request.rid)
        m = self.engine.metrics
        for e in pending:
            req = self.engine.restore(
                e.prompt_tokens, e.max_new_tokens,
                generated=list(e.streamed),
                arrival_time=e.arrival_time,
                deadline=e.deadline, priority=e.priority,
                rid=e.request.rid, sampling=e.sampling,
                generated_logprobs=list(e.streamed_logps),
                tenant=e.tenant)
            e.request = req
            self.replayed += 1
            m.replayed_requests.inc()
