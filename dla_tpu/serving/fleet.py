"""Serving fleet: a multi-engine router with cache-aware placement,
SLO-driven autoscaling, and fleet-wide draining.

One ``ServingEngine`` is a hard throughput ceiling; the ``FleetRouter``
fronts N of them — each member on its own worker thread with its own
scheduler, page pool, prefix cache, metrics registry, and per-member
``Supervisor`` (a wedged member rebuilds and replays while the router
keeps steering new arrivals elsewhere). The router exposes the same
``submit / step / has_work / result / drain`` surface as a single
engine, so ``eval_latency``'s open-loop driver, ``RolloutEngine``, and
the Supervisor factory pattern work unchanged on top of a fleet.

Placement scores every live member by:

- **prefix affinity** — the longest-prefix-cache match length via the
  read-only ``PrefixCache.peek()`` (no increfs, no LRU touch: the N-1
  losing candidates must be left exactly as found), plus a sticky
  family map that keeps a request family on the member that owns its
  pages even before the first member's prefix registers;
- **load** — page-pool occupancy plus normalized queue depth (and the
  admission controller's configured bound when shedding is on);
- **draining state** — members answering ``/healthz`` 503 ``draining``
  (supervisor breaker trip, scale-down, or fleet drain) take no new
  placements.

The ``Autoscaler`` consumes the SLO burn-rate signal ``telemetry/slo``
already computes plus fleet pressure, spawns members through the same
engine factory the supervisors rebuild with, and retires members
through the existing draining contract: queued requests are
redistributed to peers FIRST (rid, sampling params, and streamed
tokens preserved through ``engine.restore`` — the supervisor-replay
idiom), in-flight decodes run to completion, and the member is
reclaimed only after its last request resolves. Zero lost requests,
ever.

``FleetConfig.roles`` disaggregates the fleet into prefill and decode
members: prefill-role members run chunked prefill only, and after every
router step the handoff pass exports each freshly-prefilled request's
committed KV pages as a :class:`~dla_tpu.serving.migration
.MigrationTicket` and installs it on the least-pressured decode-capable
member (``KVMigrator`` device-to-device transfer, one jitted gather on
the source and one jitted scatter on the target). The journal entry
moves between supervisors atomically with the install — popped from the
source before, re-inserted on failure — so a request lands exactly once
even when the source dies mid-handoff. Scale-down migrates committed KV
the same way instead of re-prefilling on a peer.

Outputs are placement-independent by construction: generated token k
of a request is sampled with ``fold_in(PRNGKey(seed), k)`` where the
seed depends only on (engine config seed, rid) or on explicit
``SamplingParams`` — never on slot, batch, or member — so a routed
fleet reproduces a single engine's tokens bit-for-bit on the same
trace. Fleet metrics live in the ROUTER's registry, not a member's,
so ``serving/fleet/*`` totals are monotone across member rebuilds by
construction.
"""
from __future__ import annotations

import functools
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass, fields
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

from dla_tpu.serving.migration import (TRANSPORTS, KVMigrator,
                                       MigrationConfig, MigrationError)
from dla_tpu.serving.scheduler import TERMINAL_STATES, Request, RequestState
from dla_tpu.serving.resilience import Supervisor, SupervisorConfig
from dla_tpu.telemetry.registry import MetricRegistry

PLACEMENTS = ("cache_aware", "random", "round_robin")
ROLES = ("prefill", "decode", "mixed")


def broadcast_waves(n: int, branch: int) -> List[List[int]]:
    """Partition member indices ``0..n-1`` into broadcast-tree waves:
    the root (the caller — learner or router) sends to ``branch``
    members in wave 0, then every member that already holds the payload
    forwards to ``branch`` more per wave, so coverage multiplies by
    ``1 + branch`` each wave and the wave count — the wall-clock bound
    when each wave runs concurrently on the target members' executors —
    is ``ceil(log_{1+branch}(n/branch + 1))``, not ``n``. Shared by the
    sampler-fleet refit fanout (rollout.actor_fleet) and
    :meth:`FleetRouter.publish_params`."""
    if branch < 1:
        raise ValueError(f"broadcast branch must be >= 1, got {branch}")
    waves: List[List[int]] = []
    holders = 1                     # the root already has the payload
    nxt = 0
    while nxt < n:
        wave = list(range(nxt, min(n, nxt + holders * branch)))
        waves.append(wave)
        nxt += len(wave)
        holders += len(wave)
    return waves


@dataclass(frozen=True)
class FleetConfig:
    """Router + autoscaler knobs (``latency.serving.fleet`` in config).

    ``placement`` picks the routing policy: ``cache_aware`` (peek +
    load + affinity, the default), ``random`` (seeded — the A/B
    baseline that destroys cross-request prefix locality), or
    ``round_robin``. Autoscaling is off unless ``autoscale`` is set;
    scale decisions need ``patience`` consecutive over/under-threshold
    checks, one check every ``check_every`` router steps.

    ``roles`` disaggregates the fleet: one role per startup member
    (``prefill`` members run chunked prefill only and hand finished
    prefixes to the least-pressured ``decode``/``mixed`` member as KV
    migration tickets after every router step; ``decode`` members take
    no router admissions). None keeps every member ``mixed`` — the
    co-scheduled default. Explicit roles pin the topology, so they are
    mutually exclusive with ``autoscale``. ``migration_transport`` is
    the :class:`~dla_tpu.serving.migration.MigrationConfig` transport
    the handoff path uses. ``max_handoff_retries`` bounds how many
    times one request's decode handoff may be refused (page exhaustion,
    geometry mismatch) before the router gives up on migrating it:
    the request then finishes decoding on its prefill member, or is
    shed if that member is draining — never an unbounded
    refuse/re-insert cycle."""

    engines: int = 2                   # members at startup
    min_engines: int = 1
    max_engines: int = 4
    placement: str = "cache_aware"
    prefix_weight: float = 2.0         # score weight of peek hit frac
    load_weight: float = 1.0           # score weight of member pressure
    sticky_bonus: float = 0.5          # hit-frac stand-in for a sticky
                                       # family whose pages are not yet
                                       # registered (in-flight prefill)
    adapter_weight: float = 1.0        # score weight of the tenant's
                                       # adapter residency (device-hot
                                       # 1.0, published-but-spilled 0.5)
    autoscale: bool = False
    scale_up_burn: float = 1.0         # max member SLO burn rate >= this
    scale_up_pressure: float = 0.85    # mean member pressure >= this
    scale_down_pressure: float = 0.25  # mean member pressure <= this
    patience: int = 3                  # consecutive checks before acting
    check_every: int = 10              # router steps between checks
    seed: int = 0                      # random-placement stream
    roles: Optional[Tuple[str, ...]] = None  # per-slot disaggregation
    migration_transport: str = "auto"  # handoff KV transport
    max_handoff_retries: int = 8       # refusals before decoding at home

    def __post_init__(self):
        if self.placement not in PLACEMENTS:
            raise ValueError(
                f"fleet placement must be one of {PLACEMENTS}, "
                f"got {self.placement!r}")
        if self.engines < 1:
            raise ValueError("fleet needs engines >= 1")
        if not (1 <= self.min_engines <= self.max_engines):
            raise ValueError("fleet wants 1 <= min_engines <= max_engines")
        if not (self.min_engines <= self.engines <= self.max_engines):
            raise ValueError(
                "fleet wants min_engines <= engines <= max_engines")
        if self.max_handoff_retries < 1:
            raise ValueError("fleet needs max_handoff_retries >= 1")
        if self.migration_transport not in TRANSPORTS:
            raise ValueError(
                f"fleet migration_transport must be one of {TRANSPORTS}, "
                f"got {self.migration_transport!r}")
        if self.roles is not None:
            if len(self.roles) != self.engines:
                raise ValueError(
                    f"fleet roles must name every startup member: got "
                    f"{len(self.roles)} roles for {self.engines} engines")
            bad = sorted(set(self.roles) - set(ROLES))
            if bad:
                raise ValueError(
                    f"fleet roles must be drawn from {ROLES}, got {bad}")
            if all(r == "prefill" for r in self.roles):
                raise ValueError(
                    "fleet roles need at least one decode-capable "
                    "(decode/mixed) member to land handoffs on")
            if self.autoscale:
                raise ValueError(
                    "explicit fleet roles pin the topology and cannot "
                    "be combined with autoscale")

    def role_for(self, slot: int) -> str:
        """Slot -> role, defaulting to ``mixed`` past the pinned list
        (slots recycled by a future scale cycle stay co-scheduled)."""
        if self.roles is not None and 0 <= slot < len(self.roles):
            return self.roles[slot]
        return "mixed"

    @classmethod
    def from_config(cls, cfg: Optional[Dict]) -> Optional["FleetConfig"]:
        """None/falsy or ``enabled: false`` -> None (no fleet); unknown
        keys raise — config drift surfaces at startup, not at 3am."""
        if not cfg:
            return None
        cfg = dict(cfg)
        if not cfg.pop("enabled", True):
            return None
        known = {f.name for f in fields(cls)}
        unknown = set(cfg) - known
        if unknown:
            raise ValueError(f"unknown fleet config keys: {sorted(unknown)}")
        if isinstance(cfg.get("roles"), list):
            cfg["roles"] = tuple(cfg["roles"])
        return cls(**cfg)


class FleetMetrics:
    """The ``serving/fleet/*`` panel. Instruments are owned by the
    router's registry, which outlives every member engine (and its
    per-rebuild registries) — monotonicity across rebuilds needs no
    re-seeding here, unlike the supervisor counters."""

    def __init__(self, registry: Optional[MetricRegistry] = None):
        self.registry = registry or MetricRegistry()
        r = self.registry
        self.engines_active = r.gauge("serving/fleet/engines_active")
        self.routed_by_prefix = r.counter("serving/fleet/routed_by_prefix")
        self.routed_by_load = r.counter("serving/fleet/routed_by_load")
        self.scale_ups = r.counter("serving/fleet/scale_ups")
        self.scale_downs = r.counter("serving/fleet/scale_downs")
        self.rebalanced_requests = r.counter(
            "serving/fleet/rebalanced_requests")
        self.failed_handoffs = r.counter(
            "serving/migration/failed_handoffs")
        self._slot_gauges: set = set()

    def ensure_slot_gauge(self, slot: int,
                          fn: Callable[[], float]) -> None:
        """Per-member occupancy FuncGauge, registered once per slot
        (slots are reused across scale cycles; the read-through closure
        resolves the CURRENT occupant, 0.0 when the slot is empty)."""
        if slot in self._slot_gauges:
            return
        self._slot_gauges.add(slot)
        self.registry.func_gauge(
            f"serving/fleet/engine/{slot}/page_occupancy", fn)

    def snapshot(self) -> Dict[str, float]:
        return self.registry.snapshot()


class _Member:
    """One fleet slot: a supervised engine pinned to its own worker
    thread (a single-thread executor keeps the thread persistent and
    the member's JAX dispatch serialized)."""

    def __init__(self, slot: int, sup: Supervisor, role: str = "mixed"):
        self.slot = slot
        self.sup = sup
        self.role = role               # prefill | decode | mixed
        self.pool = ThreadPoolExecutor(
            max_workers=1, thread_name_prefix=f"dla-fleet-engine-{slot}")
        self.retiring = False          # scale-down in progress

    @property
    def engine(self):
        return self.sup.engine

    def accepting(self) -> bool:
        return not self.retiring and not self.sup.draining

    def close(self) -> None:
        self.sup.close()
        self.pool.shutdown(wait=True)


class Autoscaler:
    """SLO-burn + pressure driven member count. Pure decision logic —
    the router owns spawn/retire mechanics; this just watches the
    signals ``_resilience_pass`` already trusts (max member burn rate,
    mean of max(occupancy, queue fraction)) and debounces with
    ``patience`` so one hot check never flaps the fleet."""

    def __init__(self, router: "FleetRouter", cfg: FleetConfig):
        self.router = router
        self.cfg = cfg
        self._up_streak = 0
        self._down_streak = 0

    def evaluate(self) -> None:
        r, cfg = self.router, self.cfg
        active = [m for m in r.members() if not m.retiring]
        if not active:
            return
        pressure = float(np.mean([r.member_pressure(m) for m in active]))
        burn = max(r.member_burn(m) for m in active)
        want_up = (pressure >= cfg.scale_up_pressure
                   or burn >= cfg.scale_up_burn)
        want_down = (pressure <= cfg.scale_down_pressure
                     and burn < cfg.scale_up_burn)
        self._up_streak = self._up_streak + 1 if want_up else 0
        self._down_streak = self._down_streak + 1 if want_down else 0
        if self._up_streak >= cfg.patience and len(active) < cfg.max_engines:
            self._up_streak = 0
            r.scale_up()
        elif (self._down_streak >= cfg.patience
              and len(active) > cfg.min_engines):
            self._down_streak = 0
            r.scale_down()


class FleetRouter:
    """N supervised ``ServingEngine`` members behind one engine-shaped
    front end (see the module docstring for the architecture).

    ``factory(slot)`` builds a fresh engine for fleet slot ``slot`` —
    the same callable serves initial spawn, supervisor rebuild after a
    fault, and autoscaler scale-up, so every generation of a slot's
    engine shares its config (including ``cfg.seed``, which is what
    keeps default-seeded sampling placement-independent)."""

    def __init__(self, factory: Callable[[int], object],
                 cfg: Optional[FleetConfig] = None,
                 supervisor: Optional[SupervisorConfig] = None,
                 registry: Optional[MetricRegistry] = None):
        self.factory = factory
        self.cfg = cfg or FleetConfig()
        self.sup_cfg = supervisor
        self.metrics = FleetMetrics(registry)
        self._slots: Dict[int, _Member] = {}
        self._placement: Dict[int, _Member] = {}       # rid -> member
        self._affinity: Dict[Tuple[int, ...], int] = {}  # family -> slot
        self._archive: Dict[int, Request] = {}  # results of retired slots
        self._handoff_fails: Dict[int, int] = {}  # rid -> refusal count
        self._handoff_pinned: set = set()  # rids decoding at home for good
        self._rs = np.random.RandomState(self.cfg.seed)
        self._rr = 0                   # round-robin cursor
        self._steps = 0
        self._draining = False
        self.autoscaler = Autoscaler(self, self.cfg)
        self.migrator = KVMigrator(MigrationConfig(
            transport=self.cfg.migration_transport))
        for _ in range(self.cfg.engines):
            self._spawn()

    # ------------------------------------------------------------ members

    def members(self) -> List[_Member]:
        return [self._slots[s] for s in sorted(self._slots)]

    @property
    def num_engines(self) -> int:
        return len([m for m in self._slots.values() if not m.retiring])

    def member_pressure(self, member: _Member) -> float:
        """The scalar ``_resilience_pass`` steers by: max of page-pool
        occupancy and queue depth over its bound."""
        eng = member.engine
        occ = eng.cache.allocator.occupancy
        qcap = (eng.admission.cfg.max_queue_depth
                if eng.admission is not None
                else max(8, 2 * eng.cfg.num_slots))
        return max(occ, eng.scheduler.queue_depth / max(1, qcap))

    def member_burn(self, member: _Member) -> float:
        eng = member.engine
        slo = eng.slo
        burn = (max(slo.burn_rate(obj) for obj in slo.slos)
                if slo is not None and slo.slos else 0.0)
        # per-tenant SLOs feed the same autoscale signal: one tenant
        # burning its budget scales the fleet even when the aggregate
        # latency surface looks healthy
        tenants = getattr(eng, "tenants", None)
        if tenants is not None:
            burn = max(burn, tenants.max_burn())
        return burn

    def _spawn(self) -> _Member:
        slot = next(i for i in range(len(self._slots) + 1)
                    if i not in self._slots)
        sup = Supervisor(functools.partial(self.factory, slot),
                         self.sup_cfg)
        role = self.cfg.role_for(slot)
        if role == "mixed":
            # a factory may disaggregate on its own (per-slot engine
            # configs) — honor the engine's declared role in that case
            role = getattr(sup.engine.cfg, "role", "mixed")
        member = _Member(slot, sup, role)
        self._slots[slot] = member
        self.metrics.ensure_slot_gauge(slot, functools.partial(
            self._slot_occupancy, slot))
        self.metrics.engines_active.set(self.num_engines)
        return member

    def _slot_occupancy(self, slot: int) -> float:
        member = self._slots.get(slot)
        if member is None:
            return 0.0
        return float(member.engine.cache.allocator.occupancy)

    # ------------------------------------------------------------- intake

    def submit(self, prompt_tokens: List[int], max_new_tokens: int,
               arrival_time: Optional[float] = None,
               deadline_s: Optional[float] = None,
               priority: int = 0, sampling=None,
               tenant: Optional[str] = None) -> int:
        candidates = [m for m in self.members()
                      if m.accepting() and m.role != "decode"]
        if self._draining or not candidates:
            raise RuntimeError(
                "fleet is draining: no member accepts admissions")
        member, by_prefix = self._choose(prompt_tokens, candidates,
                                         tenant=tenant)
        rid = member.sup.submit(
            prompt_tokens, max_new_tokens, arrival_time=arrival_time,
            deadline_s=deadline_s, priority=priority, sampling=sampling,
            tenant=tenant)
        self._placement[rid] = member
        self._affinity[self._family(prompt_tokens)] = member.slot
        if by_prefix:
            self.metrics.routed_by_prefix.inc()
        else:
            self.metrics.routed_by_load.inc()
        return rid

    def _family(self, prompt_tokens: List[int]) -> Tuple[int, ...]:
        ps = self.members()[0].engine.cfg.page_size if self._slots else 16
        return tuple(prompt_tokens[:ps])

    def _peek(self, member: _Member, prompt_tokens: List[int],
              tenant: Optional[str] = None) -> int:
        eng = member.engine
        if eng.prefix_cache is None:
            return 0
        return eng.prefix_cache.peek(prompt_tokens, eng.cfg.prefill_chunk,
                                     namespace=tenant)

    def _adapter_heat(self, member: _Member,
                      tenant: Optional[str]) -> float:
        """Adapter residency scored like prefix-cache heat: a member
        whose pool already holds the tenant's adapter on device serves
        its first token without a host->device load (1.0); a member
        holding only the spilled host copy avoids a publish but pays
        the load (0.5); anywhere else the adapter is absent (0.0)."""
        if tenant is None:
            return 0.0
        store = getattr(member.engine, "adapter_store", None)
        if store is None or not store.has(tenant):
            return 0.0
        return 1.0 if store.resident(tenant) else 0.5

    def _choose(self, prompt_tokens: List[int],
                candidates: List[_Member],
                tenant: Optional[str] = None) -> Tuple[_Member, bool]:
        """-> (member, routed_by_prefix). Deterministic: score ties
        break toward the sticky-affinity slot, then the lowest slot."""
        if self.cfg.placement == "random":
            return candidates[self._rs.randint(len(candidates))], False
        if self.cfg.placement == "round_robin":
            member = candidates[self._rr % len(candidates)]
            self._rr += 1
            return member, False
        n = max(1, len(prompt_tokens))
        sticky = self._affinity.get(self._family(prompt_tokens))
        best, best_key, best_hit = None, None, 0.0
        for m in candidates:
            # affinity covers the registration gap: the family owner's
            # first prefill may still be in flight, so peek reads 0
            # there — score it as if the expected shared prefix were
            # already cached, or placement scatters a family submitted
            # in one burst across the whole fleet
            hit = self._peek(m, prompt_tokens, tenant) / n
            if m.slot == sticky:
                hit = max(hit, self.cfg.sticky_bonus)
            score = (self.cfg.prefix_weight * hit
                     + self.cfg.adapter_weight * self._adapter_heat(
                         m, tenant)
                     - self.cfg.load_weight * self.member_pressure(m))
            key = (score, -m.slot)
            if best is None or key > best_key:
                best, best_key, best_hit = m, key, hit
        return best, best_hit > 0

    # ----------------------------------------------------------- stepping

    def step(self) -> List[Tuple[int, int]]:
        """One fleet step: every member advances one supervised engine
        step on its own thread; emitted (rid, token) streams merge in
        slot order (deterministic — member states are independent, so
        thread completion order cannot change any token)."""
        members = self.members()
        futures = [(m, m.pool.submit(m.sup.step)) for m in members
                   if m.sup.has_work() or not m.retiring]
        emitted: List[Tuple[int, int]] = []
        for _, fut in futures:
            emitted.extend(fut.result())
        self._steps += 1
        self._handoff_pass()
        self._finalize_retired()
        if self.cfg.autoscale and not self._draining \
                and self._steps % self.cfg.check_every == 0:
            self.autoscaler.evaluate()
        return emitted

    # ``poll`` is the streaming-consumer name for the same operation
    poll = step

    def publish_params(self, params, donate: bool = False,
                       branch: int = 2) -> None:
        """Fleet-wide weight refit: publish ``params`` into every live
        member's engine via the broadcast-tree wave schedule
        (:func:`broadcast_waves`) — each wave's publishes run
        concurrently on the target members' own executors, so wall time
        is bounded by the tree depth, not the member count. The swap is
        the usual zero-recompile pointer update per member. Note:
        publishes reach the LIVE engines only; a later supervisor
        rebuild re-reads the caller's factory tree, so callers that
        refit must also update whatever their factory closes over (the
        RolloutEngine-per-member sampler fleet does; see
        rollout.actor_fleet)."""
        members = self.members()
        for wave in broadcast_waves(len(members), branch):
            futures = [members[i].pool.submit(
                members[i].engine.publish_params, params, donate=donate)
                for i in wave]
            for fut in futures:
                fut.result()

    def publish_adapter(self, tenant: str, tree, *, alpha=None,
                        rank=None, branch: int = 2) -> None:
        """Fleet-wide adapter refit: publish ``tenant``'s LoRA tree into
        every live member's AdapterStore on the same broadcast-tree wave
        schedule as :meth:`publish_params` — every member can then land
        the tenant's requests (placement still prefers members where the
        adapter is device-resident, see ``adapter_weight``). Same
        caveat: a supervisor rebuild re-runs the factory, which must
        republish adapters it wants the rebuilt engine to serve."""
        members = self.members()
        for wave in broadcast_waves(len(members), branch):
            futures = [members[i].pool.submit(
                members[i].engine.publish_adapter, tenant, tree,
                alpha=alpha, rank=rank)
                for i in wave]
            for fut in futures:
                fut.result()

    def has_work(self) -> bool:
        return any(m.sup.has_work() for m in self.members())

    def result(self, rid: int) -> Request:
        member = self._placement.get(rid)
        if member is not None and rid in member.sup.journal:
            return member.sup.result(rid)
        for m in self.members():       # burst-synthetic intake
            if rid in m.sup.journal:
                return m.sup.result(rid)
        return self._archive[rid]

    def cancel(self, rid: int, reason: str = "cancelled") -> Request:
        """Client-initiated cancellation, routed to whichever member
        currently owns the request (handoffs move ownership)."""
        member = self._placement.get(rid)
        if member is None or rid not in member.sup.journal:
            member = next((m for m in self.members()
                           if rid in m.sup.journal), None)
        if member is None:
            return self._archive[rid]
        return member.sup.cancel(rid, reason)

    def export_request(self, rid: int):
        """Export ``rid``'s resumable state for a CROSS-FLEET handoff
        (the gateway's ``/v1/migrate_out``): the owning member's journal
        entry is popped and its engine copy released — from here the
        serialized ticket IS the request, and the shipper owns replay
        if the remote install fails (FederatedRouter journals prompts
        for exactly that). Raises :class:`MigrationError` when the
        request is not resumable in place."""
        member = self._placement.get(rid)
        if member is None or rid not in member.sup.journal:
            member = next((m for m in self.members()
                           if rid in m.sup.journal), None)
        if member is None:
            raise MigrationError(f"request {rid} is not on this fleet")
        ticket = self.migrator.export_ticket(
            member.engine, rid, src_slot=member.slot)
        entry = member.sup.journal.pop(rid, None)
        member.engine.release_migrated(rid)
        if entry is not None:
            self._archive[rid] = entry.request
        self._placement.pop(rid, None)
        return ticket

    def import_request(self, ticket) -> Request:
        """Install a cross-fleet ticket onto the least-pressured
        decode-capable member, journaled for replay like any local
        submission (the exactly-once discipline of
        ``_migrate_request``, with the source on another host)."""
        from dla_tpu.serving.resilience import JournalEntry
        candidates = [m for m in self.members()
                      if m.accepting() and m.role != "prefill"]
        if self._draining or not candidates:
            raise MigrationError(
                "fleet is draining: no member accepts an import")
        dst = min(candidates,
                  key=lambda m: (self.member_pressure(m), m.slot))
        req = self.migrator.install(dst.engine, ticket)
        dst.sup.journal[req.rid] = JournalEntry(
            prompt_tokens=list(req.prompt_tokens),
            max_new_tokens=int(req.max_new_tokens),
            priority=req.priority, arrival_time=req.arrival_time,
            deadline=req.deadline, streamed=list(req.generated),
            done=req.state in TERMINAL_STATES, request=req,
            sampling=req.sampling,
            streamed_logps=list(req.generated_logprobs),
            tenant=req.tenant,
            migrated_from=ticket.src_slot, migrations=1)
        self._placement[req.rid] = dst
        self._affinity[self._family(list(req.prompt_tokens))] = dst.slot
        return req

    def peek_score(self, prompt_tokens: List[int],
                   tenant: Optional[str] = None) -> Tuple[float, float]:
        """-> (best peeked hit-frac, mean member pressure) over the
        accepting members — the gateway's ``/v1/peek`` surface, so a
        FederatedRouter scores this fleet with the same inputs
        ``_choose`` uses locally."""
        candidates = [m for m in self.members()
                      if m.accepting() and m.role != "decode"]
        if self._draining or not candidates:
            return 0.0, 1.0
        n = max(1, len(prompt_tokens))
        hit = max(self._peek(m, prompt_tokens, tenant) / n
                  for m in candidates)
        pressure = float(np.mean(
            [self.member_pressure(m) for m in candidates]))
        return hit, pressure

    def results(self) -> Dict[int, Request]:
        out = dict(self._archive)
        for m in self.members():
            out.update(m.sup.results())
        return out

    def run_until_drained(self, max_steps: int = 100000,
                          on_cap: str = "raise") -> Dict[int, Request]:
        for _ in range(max_steps):
            if not self.has_work():
                return self.results()
            self.step()
        if on_cap == "shed":
            for m in self.members():
                if m.sup.has_work():
                    m.engine._shed_stragglers()
            return self.results()
        raise RuntimeError(
            f"fleet did not drain in {max_steps} steps")

    # ----------------------------------------------------------- handoffs

    def _handoff_pass(self) -> None:
        """Ship every freshly-prefilled request off prefill-role members
        to the least-pressured decode-capable member. Runs synchronously
        between fleet steps — member faults only surface inside
        ``engine.step()``, so nothing can interrupt a handoff halfway.

        Refusals (page exhaustion, geometry mismatch) are retried on
        later passes at most ``max_handoff_retries`` times per request;
        past the bound the request is pinned to finish decoding on its
        prefill member (the engine is decode-capable, the role is router
        policy) — or shed if that member is draining — and
        ``serving/migration/failed_handoffs`` ticks once."""
        sources = [m for m in self.members() if m.role == "prefill"]
        if not sources:
            return
        if self._handoff_pinned or self._handoff_fails:
            # retire bookkeeping only for requests the source scheduler
            # no longer tracks (terminal): an evicted-but-live request
            # keeps its refusal count and its pin across re-admission
            live = {req.rid for m in sources
                    for req in (*m.engine.scheduler.queue,
                                *m.engine.scheduler.prefilling.values(),
                                *m.engine.scheduler.running.values())}
            self._handoff_pinned &= live
            self._handoff_fails = {r: c for r, c in
                                   self._handoff_fails.items() if r in live}
        for src in sources:
            for req in list(src.engine.scheduler.running.values()):
                if not req.generated:
                    continue           # prefill not finished this step
                if req.rid in self._handoff_pinned:
                    continue           # gave up: decoding at home
                sinks = [m for m in self.members()
                         if m is not src and m.accepting()
                         and m.role != "prefill"]
                dedicated = [m for m in sinks if m.role == "decode"]
                if dedicated:
                    sinks = dedicated
                if not sinks:
                    return             # decode locally; retry next step
                dst = min(sinks, key=lambda m: (
                    self.member_pressure(m), m.slot))
                if self._migrate_request(src, req, dst):
                    self._handoff_fails.pop(req.rid, None)
                else:
                    self._note_handoff_failure(src, req)

    def _note_handoff_failure(self, src: _Member, req: Request) -> None:
        """One refused handoff attempt; enforce the retry bound."""
        fails = self._handoff_fails.get(req.rid, 0) + 1
        if fails < self.cfg.max_handoff_retries:
            self._handoff_fails[req.rid] = fails
            return
        self._handoff_fails.pop(req.rid, None)
        self.metrics.failed_handoffs.inc()
        if src.accepting():
            self._handoff_pinned.add(req.rid)
            return
        # a draining/retiring source cannot keep the decode: terminal shed
        # (tokens-so-far preserved on the request, journal entry closed)
        src.engine.scheduler.cancel(req, "handoff_failed",
                                    RequestState.SHED)
        entry = src.sup.journal.get(req.rid)
        if entry is not None:
            entry.request = req
            entry.done = True

    def _migrate_request(self, src: _Member, req: Request,
                         dst: _Member) -> bool:
        """Move one mid-decode request ``src`` -> ``dst`` by KV page
        migration, exactly once: the journal entry is popped from the
        source supervisor BEFORE the install (a source crash after a
        successful install must not replay the request there) and
        re-inserted on failure (the request keeps decoding at home, a
        later pass retries). Refusals are already counted on the
        refusing engine's ``serving/migration/failed_migrations``."""
        try:
            ticket = self.migrator.export_ticket(
                src.engine, req.rid, src_slot=src.slot)
        except MigrationError:
            return False
        entry = src.sup.journal.pop(req.rid, None)
        try:
            moved = self.migrator.install(dst.engine, ticket)
        except MigrationError:
            if entry is not None:
                src.sup.journal[req.rid] = entry
            return False
        src.engine.release_migrated(req.rid)
        if entry is not None:
            entry.request = moved
            entry.done = moved.state in TERMINAL_STATES
            entry.migrated_from = src.slot
            entry.migrations += 1
            dst.sup.journal[req.rid] = entry
        self._placement[req.rid] = dst
        self._affinity[self._family(list(req.prompt_tokens))] = dst.slot
        return True

    def _migrate_running(self, member: _Member) -> int:
        """Scale-down path: migrate the member's mid-decode requests to
        the least-pressured decode-capable peer instead of letting them
        run out on the retiring member (frees the slot sooner) or
        re-prefilling elsewhere (wastes the committed KV)."""
        peers = [m for m in self.members()
                 if m is not member and m.accepting()
                 and m.role != "prefill"]
        if not peers:
            return 0
        moved = 0
        for req in list(member.engine.scheduler.running.values()):
            if not req.generated:
                continue
            dst = min(peers, key=lambda m: (
                self.member_pressure(m), m.slot))
            if self._migrate_request(member, req, dst):
                moved += 1
        return moved

    # ------------------------------------------------------------ scaling

    def scale_up(self) -> _Member:
        member = self._spawn()
        self.metrics.scale_ups.inc()
        return member

    def scale_down(self, member: Optional[_Member] = None) -> None:
        """Retire one member through the draining contract: queued work
        moves to peers first (rid/sampling/streamed preserved), the
        member stops admitting, in-flight decodes run to completion
        under ``step()``, and the slot is reclaimed by
        ``_finalize_retired`` after the last request resolves."""
        active = [m for m in self.members() if not m.retiring]
        if len(active) <= 1:
            raise RuntimeError("cannot scale down the last fleet member")
        if member is None:
            # least sunk work: emptiest queue, fewest active slots
            member = min(active, key=lambda m: (
                m.engine.scheduler.queue_depth,
                m.engine.scheduler.active_count, m.slot))
        if member.role != "prefill" and not any(
                m.role != "prefill" for m in active if m is not member):
            raise RuntimeError(
                "cannot retire the last decode-capable fleet member")
        moved = self._rebalance_queued(member)
        moved += self._migrate_running(member)
        member.retiring = True
        member.engine.begin_drain()
        self.metrics.scale_downs.inc()
        self.metrics.rebalanced_requests.inc(moved)
        self.metrics.engines_active.set(self.num_engines)

    def _rebalance_queued(self, member: _Member) -> int:
        """Move every queued request off ``member`` onto a scoring peer
        via ``engine.restore`` — the supervisor-replay idiom, so rid,
        sampling params, streamed tokens, and journal entry all carry
        over and a later peer rebuild still replays the moved work."""
        peers = [m for m in self.members()
                 if m is not member and m.accepting()]
        # restore re-runs prefill on the peer, so prefer prefill-capable
        # members; a decode-only fleet remnant still beats losing work
        non_decode = [m for m in peers if m.role != "decode"]
        if non_decode:
            peers = non_decode
        if not peers:
            return 0
        src = member.sup
        moved = 0
        for req in list(member.engine.scheduler.queue):
            entry = src.journal.get(req.rid)
            member.engine.scheduler.cancel(req, "rebalanced")
            if entry is None or entry.done:
                continue
            dst, _ = self._choose(entry.prompt_tokens, peers,
                                  tenant=entry.tenant)
            restored = dst.engine.restore(
                entry.prompt_tokens, entry.max_new_tokens,
                generated=list(entry.streamed),
                arrival_time=entry.arrival_time,
                deadline=entry.deadline, priority=entry.priority,
                rid=req.rid, sampling=entry.sampling,
                generated_logprobs=list(entry.streamed_logps),
                tenant=entry.tenant)
            entry.request = restored
            entry.done = restored.state in TERMINAL_STATES
            del src.journal[req.rid]
            dst.sup.journal[req.rid] = entry
            self._placement[req.rid] = dst
            self._affinity[self._family(entry.prompt_tokens)] = dst.slot
            moved += 1
        return moved

    def _finalize_retired(self) -> None:
        """Reclaim retired members whose last in-flight request has
        resolved: archive their terminal results, drop their affinity
        entries, close the supervised engine, release the thread."""
        for member in [m for m in self.members()
                       if m.retiring and not m.sup.has_work()]:
            for rid, req in member.sup.results().items():
                self._archive[rid] = req
                self._placement.pop(rid, None)
            for fam in [k for k, s in self._affinity.items()
                        if s == member.slot]:
                del self._affinity[fam]
            del self._slots[member.slot]
            member.close()
        self.metrics.engines_active.set(self.num_engines)

    # ------------------------------------------------------------- drain

    def begin_drain(self) -> None:
        """Fleet-wide drain: every member enters the single-engine
        draining contract (healthz 503, queued-never-started cancelled,
        in-flight runs out); admission closes at the router."""
        self._draining = True
        for m in self.members():
            m.engine.begin_drain()

    @property
    def draining(self) -> bool:
        return self._draining

    def drain(self, logger=None, max_steps: int = 100000,
              on_cap: str = "raise") -> Dict[int, Request]:
        self.begin_drain()
        return self.run_until_drained(max_steps, on_cap=on_cap)

    def close(self) -> None:
        for m in self.members():
            m.close()
        self._slots.clear()

    # ------------------------------------------------------ observability

    def fleet_snapshot(self) -> Dict[str, float]:
        return self.metrics.snapshot()

    def engine_snapshots(self) -> List[Dict[str, float]]:
        return [m.engine.metrics.snapshot() for m in self.members()]
