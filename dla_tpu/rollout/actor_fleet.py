"""Elastic sampler fleet: N independent rollout engines behind one
``generate()`` — lose a sampler, not the run.

The Podracer-shaped layer for disaggregated RLHF (docs/RLHF.md
"Disaggregated sampler fleet"): a learner pod feeds weight refits to N
sampler members, each a supervised :class:`RolloutEngine` pinned to its
own single-thread executor (the serving-fleet member idiom), and the
members stream completed *trajectory groups* — one unique prompt with
its G seeded samples — back through a bounded multi-producer queue.
Three robustness mechanisms make the fleet many-and-lossy:

**Refit fanout.** ``publish_params`` walks the broadcast-tree wave
schedule (:func:`~dla_tpu.serving.fleet.broadcast_waves`): each wave's
publishes run concurrently on the target members' executors, so refit
wall time is bounded by the tree depth (``O(log N)`` waves), not by N
serial publishes (``bench.py rollout-fleet`` pins the ratio). Every
member publish gets a per-member timeout and bounded retry; a member
that exhausts its retries keeps sampling with its OLD weights (its
groups carry an older version tag — the per-trajectory staleness the
pipeline corrects for), and a member that fails
``retire_after_failures`` consecutive fanouts is retired instead of
ever stalling the learner's step loop.

**Trajectory sharding.** Completed groups land on the bounded queue
tagged with the emitting member's slot, param version (the learner
update count stamped at its last successful refit), membership epoch,
and the rollout index they were generated for — the collector accepts
a group only from its current owner for the current rollout (and the
queue is drained at each rollout start), so a slow retired member can
never leak rows across a rollout boundary. The consumer side
reassembles strictly in group order —
completion order can never change the arrays — and
:func:`shard_trajectory_groups` deterministically slices groups across
learner data-parallel ranks. Because members refit at different times
(a fanout-failed member lags), staleness is a per-trajectory vector
(``row_versions``), not a batch scalar.

**Elastic gang semantics.** Every member beats an in-process lease
(the ``resilience/elastic.py`` lease+epoch idiom, wall-clock TTL) from
its drive loop. A dead/wedged/silent member stops beating; the
collector detects the stale lease within one TTL, retires the member
(membership epoch bump), and reassigns its unfinished prompt indices
to survivors. Reassigned groups regenerate **bit-identically** from
the journaled (prompt, seed) pairs: token streams are pure functions
of (seed, token index) — never of placement — so any partition of
groups over any surviving member set yields the same arrays (given
equal member versions). ``sampler=I:rollout_step=N:lost|slow`` fault
plans (resilience.faults) drive all of this deterministically; the
fleet can re-grow to target size through the same engine factory
(``regrow: true``).
"""
from __future__ import annotations

import contextlib
import dataclasses
import queue
import threading
import time
from concurrent.futures import Future, ThreadPoolExecutor
from concurrent.futures import TimeoutError as FutureTimeout
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from dla_tpu.generation.engine import GenerationConfig
from dla_tpu.ops.sampling import SamplingParams
from dla_tpu.resilience.faults import Fault, FaultPlan
from dla_tpu.rollout.engine import (RolloutEngine, RolloutMetrics,
                                    RolloutStopped, assemble_rows)
from dla_tpu.serving.fleet import broadcast_waves
from dla_tpu.serving.scheduler import TERMINAL_STATES
from dla_tpu.serving.server import ServingConfig
from dla_tpu.telemetry.registry import MetricRegistry
from dla_tpu.telemetry.trace import get_tracer, register_trace_gauges
from dla_tpu.telemetry.trace_context import TraceContext


class SamplerFleetMetrics:
    """The ``rollout/fleet/*`` CATALOG panel. Lives on the FLEET's
    registry (shared with the fleet-level :class:`RolloutMetrics`), not
    any member's — member engines retire and respawn, the fleet object
    does not, so these totals are monotone across both by construction
    (the delta-mirror rule every fleet-scoped panel follows)."""

    def __init__(self, registry: Optional[MetricRegistry] = None):
        r = self.registry = registry or MetricRegistry()
        self.samplers_active = r.gauge("rollout/fleet/samplers_active")
        self.refit_fanout_ms = r.gauge("rollout/fleet/refit_fanout_ms")
        self.retired_samplers = r.counter("rollout/fleet/retired_samplers")
        self.reassigned_rollouts = r.counter(
            "rollout/fleet/reassigned_rollouts")
        self.trajectory_queue_depth = r.gauge(
            "rollout/fleet/trajectory_queue_depth")
        # span-drop accounting for the fleet process's tracer ring
        # (members share it), the trainer tracer's contract
        register_trace_gauges(r)

    def snapshot(self) -> Dict[str, float]:
        return {
            "rollout/fleet/samplers_active": self.samplers_active.value,
            "rollout/fleet/refit_fanout_ms": self.refit_fanout_ms.value,
            "rollout/fleet/retired_samplers": self.retired_samplers.value,
            "rollout/fleet/reassigned_rollouts":
                self.reassigned_rollouts.value,
            "rollout/fleet/trajectory_queue_depth":
                self.trajectory_queue_depth.value,
        }


@dataclasses.dataclass(frozen=True)
class SamplerFleetConfig:
    """``ppo.rollout.fleet``: sampler-fleet shape and failure policy.

    ``refit_delay_s`` is a bench/chaos knob — a per-member sleep inside
    each publish, making the serial-vs-broadcast fanout A/B
    deterministic on CPU (``bench.py rollout-fleet``)."""
    samplers: int = 2
    fanout_branch: int = 2          # broadcast-tree children per holder
    refit_timeout_s: float = 30.0   # per-member publish deadline
    refit_retries: int = 1          # extra attempts after the first
    retire_after_failures: int = 2  # consecutive failed fanouts -> retire
    lease_ttl_s: float = 5.0        # heartbeat staleness -> member lost
    step_wedge_s: float = 60.0      # in-step grace (first step compiles)
    collect_poll_s: float = 0.05    # queue poll + lease check cadence
    traj_queue_cap: int = 8         # bounded group queue (backpressure)
    regrow: bool = False            # respawn to target size next rollout
    min_samplers: int = 1           # fewer survivors than this -> raise
    refit_delay_s: float = 0.0      # bench knob: sleep per member publish

    def __post_init__(self):
        if self.samplers < 1:
            raise ValueError(
                f"fleet.samplers must be >= 1, got {self.samplers}")
        if self.min_samplers < 1 or self.min_samplers > self.samplers:
            raise ValueError(
                f"fleet.min_samplers must be in [1, samplers], got "
                f"{self.min_samplers}")

    @classmethod
    def from_config(cls, cfg: Optional[Dict]) -> "SamplerFleetConfig":
        cfg = dict(cfg or {})
        known = {f.name for f in dataclasses.fields(cls)}
        unknown = set(cfg) - known
        if unknown:
            raise ValueError(
                f"unknown ppo.rollout.fleet keys {sorted(unknown)}; "
                f"known: {sorted(known)}")
        return cls(**cfg)


@dataclasses.dataclass
class TrajectoryGroup:
    """One completed trajectory group: prompt ``group``'s G seeded
    samples as host arrays (the per-group slice of the
    ``build_generate_fn`` output contract), staleness-tagged with the
    emitting member's param ``version`` (learner update count at its
    last successful refit) and the fleet membership ``epoch``.
    ``rollout`` is the fleet rollout index the group was generated FOR:
    the collector discards any group whose tag does not match the
    rollout it is assembling, so a slow retired-but-alive member can
    never leak rollout N's rows into rollout N+1."""
    group: int
    member: int
    version: int
    epoch: int
    rows: Dict[str, np.ndarray]
    rollout: int = 0
    error: Optional[BaseException] = None   # drive-crash sentinel
    # {"trace", "span"} hex ids of the dispatch that produced this group
    # (None with tracing disabled): the merged fleet timeline can tie a
    # consumed group back to the member-side drive that generated it
    trace: Optional[Dict[str, str]] = None


def shard_trajectory_groups(groups: Sequence[TrajectoryGroup],
                            dp_ranks: int) -> List[List[TrajectoryGroup]]:
    """Deterministically shard completed groups across learner
    data-parallel ranks: sort by group index (completion order never
    leaks into placement) and deal contiguous, size-balanced slices —
    the first ``len % dp`` ranks take one extra group, matching how a
    global batch splits over a data axis."""
    if dp_ranks < 1:
        raise ValueError(f"dp_ranks must be >= 1, got {dp_ranks}")
    ordered = sorted(groups, key=lambda g: g.group)
    base, rem = divmod(len(ordered), dp_ranks)
    shards: List[List[TrajectoryGroup]] = []
    at = 0
    for r in range(dp_ranks):
        take = base + (1 if r < rem else 0)
        shards.append(ordered[at:at + take])
        at += take
    return shards


# On the virtual CPU mesh, every sharded program needs all 8 device
# participants to rendezvous inside XLA's intra-op thread pool; N member
# threads plus the learner dispatching concurrently can starve the pool
# and deadlock the rendezvous (observed live on a 1-core box: two
# drive-loop run_ids plus a train step interleaved, all stuck; also
# reproduced with just ONE member program against the learner's train
# step). The gate serializes the fleet's dispatches against each other
# AND — via :func:`learner_dispatch_gate` — against the learner's
# sharded programs, so exactly one multi-participant program runs at a
# time. Process-wide on purpose: two fleets in one process
# (chaos-vs-planned A/Bs) share the one CPU runtime. None on TPU,
# where the runtime queues per-device and members own their own
# slices.
_CPU_DISPATCH_GATE = threading.Lock()


def ensure_cpu_sync_dispatch() -> None:
    """Disable async dispatch for the CPU backend. MUST run before the
    process's first jax computation: the flag is read ONCE when the CPU
    client is created, and updating it afterwards is a no-op — so the
    :class:`SamplerFleet` constructor's own update only protects
    processes that build the fleet before touching jax (the test
    suite's conftest sets it at import for the same reason; a training
    CLI builds the learner first and needs this called up front).
    Harmless when the backend is TPU — the flag only shapes the cpu
    client."""
    jax.config.update("jax_cpu_enable_async_dispatch", False)


def learner_dispatch_gate():
    """Context manager serializing the CALLER's XLA dispatch with fleet
    members' (see ``_CPU_DISPATCH_GATE``). The learner's rollout loop
    wraps its score/update section in this so its sharded programs
    never interleave with a member's — members queue at the gate
    (lease-safe: a queued ``_drive`` refreshes ``step_started``) and
    resume the moment the learner's section ends. Null away from the
    cpu backend, where overlap is the point, not a hazard."""
    if jax.default_backend() == "cpu":
        return _CPU_DISPATCH_GATE
    return contextlib.nullcontext()


class _Sampler:
    """One fleet member: a supervised RolloutEngine pinned to its own
    single-thread executor (serializes that member's JAX dispatch —
    drive loops and refit publishes share the one thread). Cross-thread
    fields (killed/slow flags, retirement) are guarded by the fleet's
    ``_state_lock``."""

    def __init__(self, slot: int, engine: RolloutEngine, version: int):
        self.slot = slot
        self.engine = engine
        self.pool = ThreadPoolExecutor(
            max_workers=1, thread_name_prefix=f"dla-sampler-{slot}")
        self.version = version        # learner updates at last refit;
        #                               written on the member's own
        #                               executor thread (_publish_one)
        self.refit_failures = 0       # consecutive; fanout caller only
        self.retired = False
        self.killed = False           # sampler=lost fired: go silent
        self.kill_budget = 0          # groups still allowed once killed
        self.slow_s = 0.0             # sampler=slow: sleep per step
        # wall-clock mark while the drive is INSIDE driver.step(): a
        # member can't beat mid-step, and a first step that's busy
        # compiling can outlive any honest lease TTL — the collector
        # grants in-step members step_wedge_s before declaring death
        self.step_started: Optional[float] = None

    @property
    def driver(self):
        """The submit/step/result surface: the supervisor when
        supervised (rebuild + replay on engine failure), else the bare
        engine."""
        return self.engine.supervisor or self.engine.engine


class SamplerFleet:
    """N rollout engines behind the single-engine rollout surface
    (``generate`` / ``publish_params`` / ``request_stop`` / ``close`` /
    ``metrics``), so :class:`~dla_tpu.rollout.pipeline.RolloutPipeline`
    and :class:`~dla_tpu.rollout.refit.WeightRefitter` run unchanged on
    a fleet. See the module docstring for the robustness contract."""

    is_fleet = True       # pipeline marker: per-trajectory staleness

    def __init__(self, model, params, gen: GenerationConfig,
                 cfg: ServingConfig, fleet_cfg: SamplerFleetConfig, *,
                 samples_per_prompt: int = 1,
                 supervisor=True,
                 metrics: Optional[RolloutMetrics] = None,
                 now=time.monotonic):
        self.model = model
        self.gen = gen
        self.cfg = cfg
        self.fleet_cfg = fleet_cfg
        self.G = int(samples_per_prompt)
        # members are always supervised: reassignment and re-grow both
        # lean on the factory/replay machinery
        self._supervisor = supervisor if supervisor else True
        self._params = params
        self._now = now
        self.metrics = metrics or RolloutMetrics()
        self.fleet_metrics = SamplerFleetMetrics(self.metrics.registry)
        # sampler=/rollout_step= entries are fleet-scoped: ONE plan with
        # one one-shot state, polled here — member engines get an empty
        # plan (cfg.fault_plan="" parses empty; None would re-read the
        # env var per member and multiply every entry by N)
        self.faults = (FaultPlan.parse(cfg.fault_plan)
                       if cfg.fault_plan is not None
                       else FaultPlan.from_env())
        self._member_cfg = dataclasses.replace(cfg, fault_plan="")
        self.rollouts_started = 0
        self.epoch = 0                # membership epoch: retire/grow
        self.version = 0              # last successfully fanned version
        self._stop_requested = threading.Event()
        # _state_lock guards the cross-thread state: leases, member
        # flags, epoch. Held for field flips only; the trajectory queue
        # is its own synchronization
        self._state_lock = threading.Lock()
        self._leases: Dict[int, float] = {}
        self._traj_q: "queue.Queue[TrajectoryGroup]" = queue.Queue(
            maxsize=int(fleet_cfg.traj_queue_cap))
        self._samplers: List[_Sampler] = []   # retired stay (accounting)
        self._next_slot = 0
        # group -> (prompt tokens, G seeds, G max_new): the
        # bit-identical regeneration source for reassignment
        self._journal: Dict[int, Tuple] = {}
        # group -> TraceContext of its CURRENT dispatch (empty with
        # tracing disabled): _reassign parents the replacement dispatch
        # span under the original one, so a chaos run's merged timeline
        # shows reassignment as a child of the dispatch it replaced
        self._dispatch_ctx: Dict[int, TraceContext] = {}
        # N member threads stepping sharded programs on the SAME virtual
        # CPU mesh interleave collective participants across rendezvous
        # and deadlock the inline CPU runtime; synchronous dispatch is
        # the documented escape (tests/conftest.py applies it suite-wide
        # for the same reason). The update below only bites if the CPU
        # client does not exist yet — the flag is baked in at client
        # creation, which is why fleet-building CLIs call
        # ensure_cpu_sync_dispatch() before their first jax use, and
        # why _dispatch_gate exists as the in-process second layer. The
        # flag is process-global, so the prior value is saved and
        # restored on close() — the override must not outlive the
        # fleet. No-op on TPU, where the runtime queues per-device and
        # samplers own their own slices.
        self._prev_async_dispatch: Optional[bool] = None
        self._dispatch_gate: Optional[threading.Lock] = None
        if jax.default_backend() == "cpu":
            self._prev_async_dispatch = bool(jax.config.read(
                "jax_cpu_enable_async_dispatch"))
            jax.config.update("jax_cpu_enable_async_dispatch", False)
            self._dispatch_gate = _CPU_DISPATCH_GATE
        for _ in range(int(fleet_cfg.samplers)):
            self._spawn()

    @property
    def engine(self):
        """The first active member's LIVE serving engine — the
        fleet-level answer to ``RolloutEngine.engine`` for callers
        that want a recorder/step counter (WeightRefitter's refit
        event)."""
        members = self.active() or self._samplers
        if not members:
            raise RuntimeError("sampler fleet has no members")
        return members[0].engine.engine

    # ---------------------------------------------------------- membership

    def _spawn(self) -> _Sampler:
        slot = self._next_slot
        self._next_slot += 1
        eng = RolloutEngine(self.model, self._params, self.gen,
                            self._member_cfg,
                            samples_per_prompt=self.G,
                            supervisor=self._supervisor,
                            metrics=RolloutMetrics())
        m = _Sampler(slot, eng, self.version)
        with self._state_lock:
            self._samplers.append(m)
            self._leases[slot] = self._now()
        self.fleet_metrics.samplers_active.set(len(self.active()))
        return m

    def active(self) -> List[_Sampler]:
        """Members the fleet still schedules onto. A ``killed``
        (fault-injected) member stays here until its lease expires —
        the fleet must not "know" a member is about to die; detection
        is the lease's job."""
        with self._state_lock:
            return [m for m in self._samplers if not m.retired]

    def _retire(self, m: _Sampler, reason: str) -> None:
        with self._state_lock:
            if m.retired:
                return
            m.retired = True
            self.epoch += 1
        self.fleet_metrics.retired_samplers.inc()
        self.fleet_metrics.samplers_active.set(len(self.active()))
        self._record("sampler_retired", slot=m.slot, reason=reason,
                     epoch=self.epoch)

    def _record(self, event: str, **fields) -> None:
        """Fleet events land on the first live member's flight recorder
        (the fleet has no engine of its own); best-effort — a fleet
        down to zero members still has its exception to tell the
        story."""
        for m in self.active() or self._samplers:
            try:
                m.engine.engine.recorder.record(event, **fields)
                return
            except Exception:
                continue

    # --------------------------------------------------------------- refit

    def publish_params(self, params, donate: bool = False,
                       version: Optional[int] = None) -> None:
        """Broadcast-tree refit fanout. Wave k's publishes are
        submitted to their members' executors together and harvested
        with ``refit_timeout_s`` per member + ``refit_retries``
        resubmits; wall time is bounded by the wave count
        (``broadcast_waves``), not N. A member that exhausts retries
        keeps its old version (per-trajectory staleness covers it);
        ``retire_after_failures`` consecutive failed fanouts retire it.
        The learner never waits on a wedged member longer than
        ``(1 + retries) * timeout``."""
        t0 = self._now()
        fc = self.fleet_cfg
        members = self.active()
        for wave in broadcast_waves(len(members), fc.fanout_branch):
            pubs: List[Tuple[_Sampler, Future]] = [
                (members[i], members[i].pool.submit(
                    self._publish_one, members[i], params, donate,
                    version))
                for i in wave]
            for m, fut in pubs:
                ok = False
                for attempt in range(1 + int(fc.refit_retries)):
                    try:
                        fut.result(timeout=fc.refit_timeout_s)
                        ok = True
                        break
                    except FutureTimeout:
                        # the member's single executor thread is wedged
                        # (or merely slow): a resubmit would queue
                        # BEHIND the stuck attempt on that same thread
                        # and can never run sooner, so retries only
                        # burn learner time — give up now. If the
                        # original later completes it applies params on
                        # the member's own drive thread, so m.version
                        # and the rows it tags stay consistent; this
                        # fanout still records the failure because the
                        # learner could not confirm it in time.
                        break
                    except Exception:
                        pass   # publish raised (validation/transient):
                        #        the thread is alive, a retry can help
                    if attempt < int(fc.refit_retries):
                        fut = m.pool.submit(self._publish_one, m,
                                            params, donate, version)
                if ok:
                    m.refit_failures = 0
                else:
                    m.refit_failures += 1
                    self._record("sampler_refit_failed", slot=m.slot,
                                 failures=m.refit_failures)
                    if m.refit_failures >= int(fc.retire_after_failures):
                        self._retire(m, "refit_timeout")
        self._params = params            # grow/respawn source tree
        if version is not None:
            self.version = int(version)
        self.fleet_metrics.refit_fanout_ms.set((self._now() - t0) * 1e3)

    def publish_params_serial(self, params, donate: bool = False,
                              version: Optional[int] = None) -> None:
        """N sequential member publishes — the pre-fanout baseline the
        ``bench.py rollout-fleet`` A/B measures against. No timeout or
        retirement: this is the stall-the-learner behavior the
        broadcast fanout exists to replace."""
        for m in self.active():
            m.pool.submit(self._publish_one, m, params, False,
                          version).result()
        self._params = params
        if version is not None:
            self.version = int(version)

    def _publish_one(self, m: _Sampler, params, donate: bool,
                     version: Optional[int]) -> None:
        """Runs ON the member's executor thread: the same thread that
        drives the engine, so the pointer swap never races a decode
        dispatch, and ``m.version`` is only ever written here."""
        if self.fleet_cfg.refit_delay_s > 0:
            time.sleep(self.fleet_cfg.refit_delay_s)
        with self._dispatch_gate or contextlib.nullcontext():
            m.engine.publish_params(params, donate=donate,
                                    version=version)
        if version is not None:
            m.version = int(version)

    # ------------------------------------------------------------ rollouts

    def generate(self, ids: np.ndarray, mask: np.ndarray,
                 seeds: Sequence[int],
                 max_new: Optional[Sequence[int]] = None
                 ) -> Dict[str, jnp.ndarray]:
        """One fleet rollout: journal every (prompt, seeds) group,
        partition groups round-robin over the active members, drive
        them concurrently, collect staleness-tagged groups off the
        bounded queue (reassigning any lost member's groups to
        survivors), and reassemble in group order. Output contract =
        ``RolloutEngine.generate`` + ``row_versions`` (int32 ``[B*G]``,
        the per-trajectory behavior-param version tags)."""
        ids = np.asarray(ids)
        mask = np.asarray(mask)
        b_unique, p_width = ids.shape
        rows = b_unique * self.G
        seeds = list(seeds)
        if len(seeds) != rows:
            raise ValueError(
                f"need {rows} seeds ({b_unique} prompts x G={self.G}), "
                f"got {len(seeds)}")
        if max_new is not None and len(max_new) != rows:
            raise ValueError(
                f"max_new must have {rows} entries, got {len(max_new)}")
        idx = self.rollouts_started
        self.rollouts_started += 1
        fc = self.fleet_cfg
        if fc.regrow:
            # bounded attempts: a factory that keeps producing wedged
            # members must not turn the rollout into a spawn loop
            attempts = int(fc.samplers)
            while len(self.active()) < int(fc.samplers) and attempts > 0:
                attempts -= 1
                grown = self._spawn()
                # a fresh member starts from the CURRENT tree+version;
                # same deadline as the fanout — regrow must never stall
                # the learner on a wedged fresh member either
                fut = grown.pool.submit(self._publish_one, grown,
                                        self._params, False, self.version)
                try:
                    fut.result(timeout=fc.refit_timeout_s)
                except Exception:   # FutureTimeout or a raised publish
                    self._retire(grown, "regrow_refit_failed")
                    continue
                with self._state_lock:
                    self.epoch += 1
                self._record("sampler_grown", slot=grown.slot,
                             epoch=self.epoch)
        self._poll_sampler_faults(idx)
        self._poll_rollout_faults(idx)
        members = self.active()
        if len(members) < int(fc.min_samplers):
            raise RuntimeError(
                f"sampler fleet below min_samplers: {len(members)} < "
                f"{fc.min_samplers}")
        tracer = get_tracer()
        # rollout root context, minted at the dispatch origin (the
        # trace-context contract: mint at origin, child() per hop);
        # skipped entirely when tracing is off — no ids, no span work
        root = TraceContext.mint() if tracer.enabled else None
        tr_t0 = tracer.now()
        with self._state_lock:
            self._journal.clear()
            self._dispatch_ctx.clear()
            for i in range(b_unique):
                toks = [int(t) for t, m in zip(ids[i], mask[i]) if m]
                g_seeds = [int(s)
                           for s in seeds[i * self.G:(i + 1) * self.G]]
                g_new = (None if max_new is None
                         else [int(x) for x in
                               max_new[i * self.G:(i + 1) * self.G]])
                self._journal[i] = (toks, g_seeds, g_new)
        n_pad = (int(self.gen.max_new_tokens) if max_new is None
                 else max(int(x) for x in max_new))
        shape = (p_width, n_pad)
        owner: Dict[int, int] = {}
        assignment: Dict[int, List[int]] = {m.slot: [] for m in members}
        for g in range(b_unique):
            m = members[g % len(members)]
            assignment[m.slot].append(g)
            owner[g] = m.slot
        t0 = self._now()
        steps0 = {m.slot: m.engine._decode_steps_total()
                  for m in self._samplers}
        self._record("fleet_rollout_begin", rollout=idx,
                     groups=b_unique, samplers=len(members))
        # drain stale leftovers before dispatching: a member retired
        # mid-collect (lease expiry) may have emitted its group after
        # the reassigned copy won, and nothing consumes the queue
        # between rollouts
        try:
            while True:
                self._traj_q.get_nowait()
        except queue.Empty:
            pass
        for m in members:
            if assignment[m.slot]:
                self._dispatch_drive(m, assignment[m.slot], shape, idx,
                                     parent=root)
        done = self._collect(idx, b_unique, owner, shape)
        out = self._assemble(done, b_unique)
        t1 = self._now()
        tokens = int(np.sum(np.asarray(out["response_mask"])))
        steps = sum(m.engine._decode_steps_total()
                    - steps0.get(m.slot, 0) for m in self._samplers)
        fm = self.metrics
        fm.rollouts.inc()
        if t1 > t0:
            fm.gen_tokens_per_s.set(tokens / (t1 - t0))
        if tokens:
            fm.slot_steps_per_token.set(
                steps * self.cfg.num_slots / tokens)
        self.fleet_metrics.trajectory_queue_depth.set(
            self._traj_q.qsize())
        # a killed member that drained its budget merely looks idle;
        # make the shrink explicit at the rollout boundary
        for m in list(self._samplers):
            if m.killed and not m.retired:
                self._retire(m, "sampler_lost")
        if root is not None:
            tracer.complete("fleet_rollout", tr_t0, tracer.now(),
                            cat="rollout",
                            args=dict(rollout=idx, groups=b_unique,
                                      samplers=len(members),
                                      **root.tags()))
        return out

    def _dispatch_drive(self, m: _Sampler, groups: List[int],
                        shape: Tuple[int, int], idx: int,
                        parent: Optional[TraceContext] = None,
                        name: str = "sampler_dispatch") -> None:
        """Reset the member's lease (it may have idled since its last
        drive — an instant re-expiry is not a death) and queue the
        drive on its executor. With tracing on, ``parent`` is the
        rollout root (initial dispatch) or the ORIGINAL dispatch's
        context (reassignment) — the dispatch span parents under it,
        and the drive span under the dispatch."""
        dtags = None
        if parent is not None:
            tracer = get_tracer()
            ctx = parent.child()
            with self._state_lock:
                for g in groups:
                    self._dispatch_ctx[g] = ctx
            t = tracer.now()
            tracer.complete(name, t, t, cat="rollout",
                            args=dict(slot=m.slot, rollout=idx,
                                      groups=len(groups),
                                      **ctx.tags(parent)))
            dtags = ctx.child().tags(ctx)
        with self._state_lock:
            self._leases[m.slot] = self._now()
        m.pool.submit(self._drive, m, groups, shape, idx, dtags)

    def _drive(self, m: _Sampler, groups: List[int],
               shape: Tuple[int, int], idx: int,
               dtags: Optional[Dict[str, str]] = None) -> None:
        """Runs ON the member's executor: submit the assigned groups'
        G seeded requests, step the supervised engine, beat the lease
        each step, and emit each group onto the bounded queue as its
        last request reaches a terminal state. A ``killed`` member
        honors its remaining ``kill_budget`` then goes silent (no
        beats, no emissions) — the collector's lease check finds the
        corpse. A member retired mid-drive (lease expired while merely
        slow) notices at the next loop check and exits: its groups were
        reassigned, so anything it would still produce is garbage."""
        p_width, n_pad = shape
        tracer = get_tracer()
        drive_t0 = tracer.now()
        try:
            driver = m.driver
            pending: Dict[int, List[int]] = {}
            for g in groups:
                with self._state_lock:
                    toks, g_seeds, g_new = self._journal[g]
                rids = []
                for k, seed in enumerate(g_seeds):
                    sp = SamplingParams(
                        temperature=float(self.gen.temperature),
                        top_p=float(self.gen.top_p),
                        top_k=int(self.gen.top_k),
                        seed=seed & 0xFFFFFFFF,
                        do_sample=bool(self.gen.do_sample))
                    n_new = (int(self.gen.max_new_tokens)
                             if g_new is None else int(g_new[k]))
                    rids.append(driver.submit(toks, n_new, sampling=sp))
                pending[g] = rids
            while pending:
                if self._stop_requested.is_set():
                    return
                with self._state_lock:
                    dead = m.killed and m.kill_budget <= 0
                    retired = m.retired
                    slow_s = m.slow_s
                if dead:
                    return               # silent: no beat, no emission
                if retired:
                    return               # reassigned: stop producing
                if slow_s > 0:
                    time.sleep(slow_s)
                now = self._now()
                with self._state_lock:
                    self._leases[m.slot] = now
                    m.step_started = now
                try:
                    if driver.has_work():
                        # gate waits look mid-step to the collector:
                        # step_started is already set, so step_wedge_s
                        # (not the lease TTL) covers a queued member.
                        # A wait can outlive even that grace (the
                        # learner holds the gate across its first-step
                        # compiles), so refresh step_started while
                        # queued: waiting at the gate is queued, not
                        # wedged
                        gate = self._dispatch_gate
                        if gate is None:
                            driver.step()
                        else:
                            while not gate.acquire(timeout=5.0):
                                if self._stop_requested.is_set():
                                    return
                                with self._state_lock:
                                    if m.retired:
                                        return
                                    m.step_started = self._now()
                            try:
                                driver.step()
                            finally:
                                gate.release()
                finally:
                    with self._state_lock:
                        m.step_started = None
                        self._leases[m.slot] = self._now()
                for g in list(pending):
                    reqs = [driver.result(rid) for rid in pending[g]]
                    if not all(r.state in TERMINAL_STATES for r in reqs):
                        continue
                    # assemble_rows raises on any non-FINISHED terminal
                    rows = assemble_rows(driver.result, pending.pop(g),
                                         p_width, n_pad,
                                         int(self.gen.pad_token_id))
                    self._emit(m, g, rows, idx)
                    with self._state_lock:
                        if m.killed:
                            m.kill_budget -= 1
                            if m.kill_budget <= 0:
                                return   # budget spent: die mid-drive
        except RolloutStopped:
            return
        except BaseException as exc:
            # drive crash (supervisor breaker open, ...): tell the
            # collector immediately instead of waiting out a lease TTL
            with self._state_lock:
                ep = self.epoch
            try:
                self._traj_q.put(
                    TrajectoryGroup(group=-1, member=m.slot,
                                    version=m.version, epoch=ep,
                                    rows={}, rollout=idx, error=exc),
                    timeout=1.0)
            except queue.Full:
                pass
        finally:
            if dtags is not None:
                tracer.complete("sampler_drive", drive_t0, tracer.now(),
                                cat="rollout",
                                args=dict(slot=m.slot, rollout=idx,
                                          groups=len(groups), **dtags))

    def _emit(self, m: _Sampler, g: int,
              rows: Dict[str, np.ndarray], idx: int) -> None:
        with self._state_lock:
            ep = self.epoch
            ctx = self._dispatch_ctx.get(g)
        tg = TrajectoryGroup(group=g, member=m.slot, version=m.version,
                             epoch=ep, rows=rows, rollout=idx,
                             trace=ctx.tags() if ctx is not None
                             else None)
        while not self._stop_requested.is_set():
            with self._state_lock:
                retired = m.retired
            if retired:
                # retired mid-backpressure: the group was reassigned
                # and nothing will ever consume this emission — drop it
                # rather than spin on a bounded queue forever
                return
            try:
                self._traj_q.put(tg, timeout=0.1)
                return
            except queue.Full:
                # backpressure: keep beating so a slow CONSUMER never
                # reads as a dead producer
                with self._state_lock:
                    self._leases[m.slot] = self._now()

    def _collect(self, idx: int, b_unique: int, owner: Dict[int, int],
                 shape: Tuple[int, int]) -> Dict[int, TrajectoryGroup]:
        """Consumer side: drain the queue until every group arrived,
        checking leases on every poll timeout. A stale lease retires
        the member and reassigns its unfinished groups to survivors
        (journaled prompts + seeds -> bit-identical regeneration). Only
        groups tagged with THIS rollout index and emitted by the
        group's CURRENT owner are accepted: a stale emission from a
        prior rollout, or from a member retired after its groups were
        reassigned, is discarded — the owner regenerates bit-identically
        from the journal, so a discard is never a hole."""
        done: Dict[int, TrajectoryGroup] = {}
        while len(done) < b_unique:
            if self._stop_requested.is_set():
                raise RolloutStopped("fleet rollout aborted: closing")
            try:
                tg = self._traj_q.get(
                    timeout=self.fleet_cfg.collect_poll_s)
            except queue.Empty:
                self._check_leases(idx, b_unique, owner, done, shape)
                continue
            self.fleet_metrics.trajectory_queue_depth.set(
                self._traj_q.qsize())
            if tg.rollout != idx:
                # stale leak from a prior rollout (slow retired member
                # still flushing): its rows belong to other prompts
                self._record("stale_group_discarded", rollout=idx,
                             stale_rollout=tg.rollout, group=tg.group,
                             slot=tg.member)
                continue
            if tg.error is not None:
                by_slot = {m.slot: m for m in self._samplers}
                m = by_slot.get(tg.member)
                if m is not None and not m.retired:
                    self._retire(
                        m, f"drive_error:{type(tg.error).__name__}")
                    self._reassign(idx, b_unique, owner, done, shape,
                                   m.slot)
                continue
            if owner.get(tg.group) != tg.member:
                # emitter lost ownership (retired + reassigned) before
                # this arrival was consumed; the new owner's copy is
                # the canonical one
                continue
            done.setdefault(tg.group, tg)
        return done

    def _check_leases(self, idx: int, b_unique: int,
                      owner: Dict[int, int],
                      done: Dict[int, TrajectoryGroup],
                      shape: Tuple[int, int]) -> None:
        now = self._now()
        ttl = float(self.fleet_cfg.lease_ttl_s)
        wedge = float(self.fleet_cfg.step_wedge_s)
        for m in list(self.active()):
            remaining = [g for g in range(b_unique)
                         if g not in done and owner.get(g) == m.slot]
            if not remaining:
                continue
            with self._state_lock:
                last = self._leases.get(m.slot, 0.0)
                step_started = m.step_started
            if now - last <= ttl:
                continue
            if step_started is not None and now - step_started <= wedge:
                # mid-step, not silent: the step is merely long (first
                # steps compile). Only a step outliving step_wedge_s is
                # treated as a wedged member.
                continue
            self._record("sampler_lost", slot=m.slot, rollout=idx,
                         lease_age_s=round(now - last, 3))
            self._retire(m, "lease_expired")
            self._reassign(idx, b_unique, owner, done, shape, m.slot)

    def _reassign(self, idx: int, b_unique: int, owner: Dict[int, int],
                  done: Dict[int, TrajectoryGroup],
                  shape: Tuple[int, int], dead_slot: int) -> None:
        orphans = [g for g in range(b_unique)
                   if g not in done and owner.get(g) == dead_slot]
        if not orphans:
            return
        survivors = self.active()
        if not survivors:
            raise RuntimeError(
                f"sampler fleet lost its last member with "
                f"{len(orphans)} trajectory groups in flight")
        per: Dict[int, List[int]] = {s.slot: [] for s in survivors}
        for j, g in enumerate(orphans):
            s = survivors[j % len(survivors)]
            owner[g] = s.slot
            per[s.slot].append(g)
        by_slot = {s.slot: s for s in survivors}
        # parent each replacement dispatch under the orphans' ORIGINAL
        # dispatch span: the merged timeline then shows the reassignment
        # as a child of the dispatch it replaced, not a fresh root
        with self._state_lock:
            orig = self._dispatch_ctx.get(orphans[0])
        for slot, groups in per.items():
            if groups:
                self._dispatch_drive(by_slot[slot], groups, shape, idx,
                                     parent=orig,
                                     name="sampler_reassign_dispatch")
        self.fleet_metrics.reassigned_rollouts.inc(len(orphans))
        self._record("sampler_reassigned", rollout=idx,
                     from_slot=dead_slot, groups=len(orphans),
                     epoch=self.epoch)

    def _assemble(self, done: Dict[int, TrajectoryGroup],
                  b_unique: int) -> Dict[str, jnp.ndarray]:
        groups = [done[g] for g in range(b_unique)]   # group order
        out: Dict[str, jnp.ndarray] = {}
        for key in ("sequences", "sequence_mask", "response_tokens",
                    "response_mask", "response_logps", "lengths",
                    "prompt_lens"):
            out[key] = jnp.asarray(np.concatenate(
                [tg.rows[key] for tg in groups], axis=0))
        out["row_versions"] = jnp.asarray(np.concatenate(
            [np.full((int(tg.rows["lengths"].shape[0]),), tg.version,
                     np.int32) for tg in groups]))
        return out

    # -------------------------------------------------------------- faults

    def _poll_sampler_faults(self, idx: int) -> None:
        """Fire due ``sampler=I:rollout_step=N:lost|slow`` entries.
        ``lost``: member I completes at most one more group this
        rollout, then goes silent (lease expiry does the detecting).
        ``slow``: member I sleeps ``arg`` seconds (default 0.05) before
        each engine step this rollout — an early-warning event fires,
        but nothing retires unless the lag outlives the lease TTL."""
        if not self.faults:
            return
        by_slot = {m.slot: m for m in self._samplers}
        while True:
            f = self.faults.take("lost", idx, site="sampler")
            if f is None:
                break
            m = by_slot.get(int(f.host or 0))
            if m is None or m.retired:
                continue
            with self._state_lock:
                m.killed = True
                m.kill_budget = 1
            self._record("sampler_fault", slot=m.slot, rollout=idx,
                         fault="lost")
        while True:
            f = self.faults.take("slow", idx, site="sampler")
            if f is None:
                break
            m = by_slot.get(int(f.host or 0))
            if m is None or m.retired:
                continue
            with self._state_lock:
                m.slow_s = 0.05 if f.arg is None else float(f.arg)
            self._record("sampler_slow", slot=m.slot, rollout=idx,
                         lag_s=m.slow_s)

    def _poll_rollout_faults(self, idx: int) -> None:
        """Fleet translation of ``rollout_step=`` entries: same
        re-arming the single-engine RolloutEngine does, landed on the
        FIRST active member's live engine (one one-shot plan at fleet
        level — member engines carry empty plans)."""
        if not self.faults:
            return
        members = self.active()
        if not members:
            return
        eng = members[0].engine.engine
        for kind in ("device_error", "nan_logits", "wedge"):
            f = self.faults.take(kind, idx, site="rollout_step")
            if f is None:
                continue
            if kind == "wedge":
                at, arg = eng.engine_steps + 1, f.arg
            else:
                at = eng.engine_steps + (2 if f.arg is None
                                         else max(1, int(f.arg)))
                arg = None
            self._record("rollout_fault", rollout=idx, fault=kind,
                         engine_step=at, slot=members[0].slot)
            eng.faults.add(Fault(step=at, kind=kind, arg=arg,
                                 site="engine_step"))

    # ----------------------------------------------------------- lifecycle

    def request_stop(self) -> None:
        """Abort in-flight drives promptly (pipeline close path)."""
        self._stop_requested.set()
        for m in self._samplers:
            m.engine.request_stop()

    def close(self) -> None:
        self.request_stop()
        for m in self._samplers:
            # wait=False: a wedged member's executor must not block
            # teardown — its drive loop exits at the next stop check
            m.pool.shutdown(wait=False)
        for m in self._samplers:
            try:
                m.engine.close()
            except Exception:
                pass
        if self._prev_async_dispatch is not None:
            jax.config.update("jax_cpu_enable_async_dispatch",
                              self._prev_async_dispatch)
            self._prev_async_dispatch = None
