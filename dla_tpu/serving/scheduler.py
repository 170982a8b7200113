"""Continuous-batching request scheduler: the lifecycle state machine
that decides, each engine step, which waiting requests prefill into
freed decode slots and which in-flight requests must yield pages.

States:  WAITING -> PREFILL -> DECODE -> FINISHED
                        ^         |
                        +-- EVICTED (preempted on page-pool OOM; the
                            request keeps its generated tokens, re-enters
                            the queue head, and RECOMPUTES its prefix —
                            prompt + generated-so-far — on re-admission;
                            with the prefix cache on, the recompute
                            restarts from the longest still-cached
                            chunk-aligned prefix, not from token 0)

Admission policy: strict FCFS, one request prefilling at a time. The
head takes a slot plus every page its prompt needs up front — aliasing
already-cached prefix pages via the
:class:`~dla_tpu.serving.kv_blocks.PrefixCache` (incref, no copy) and
allocating only the rest — then the engine advances it one fixed-shape
chunk per engine step, co-scheduled with the running decode batch under
``prefill_token_budget``.

Backpressure: admission requires the FULL prompt page count plus one
decode page up front (no admission that would immediately preempt
someone). Mid-decode page exhaustion preempts the YOUNGEST running
request (LIFO eviction — it has the least sunk compute and its
recompute is the cheapest), freeing pages for requests ahead of it.
Eviction is refcount-aware: a victim's shared pages just drop one
reference, so pages another request (or the cache) still needs are
never actually freed.
"""
from __future__ import annotations

import dataclasses
import enum
import itertools
import numpy as np
from collections import deque
from typing import Deque, Dict, List, Optional

from dla_tpu.serving.kv_blocks import PagedKVCache, PrefixCache


class RequestState(enum.Enum):
    WAITING = "waiting"
    PREFILL = "prefill"
    DECODE = "decode"
    FINISHED = "finished"
    EVICTED = "evicted"
    TIMEOUT = "timeout"      # deadline passed before completion
    SHED = "shed"            # dropped by admission control / load shed


#: states a request never leaves — the "every request terminates"
#: contract the resilience layer (and its chaos tests) assert on
TERMINAL_STATES = (RequestState.FINISHED, RequestState.TIMEOUT,
                   RequestState.SHED)


_rid_counter = itertools.count()


@dataclasses.dataclass
class Request:
    """One generation request moving through the serving engine."""
    prompt_tokens: List[int]
    max_new_tokens: int
    rid: int = dataclasses.field(default_factory=lambda: next(_rid_counter))
    arrival_time: float = 0.0
    state: RequestState = RequestState.WAITING
    generated: List[int] = dataclasses.field(default_factory=list)
    slot: Optional[int] = None
    pages: List[int] = dataclasses.field(default_factory=list)
    evictions: int = 0
    finish_reason: Optional[str] = None   # "eos" | "length" | "timeout"
                                          # | "cancelled" | "shed"
    deadline: Optional[float] = None      # absolute engine-clock cutoff
    # load-shed ranking: HIGHER outranks lower; shedding drops the
    # lowest-priority queued request first (FIFO-tail among equals)
    priority: int = 0
    # chunked prefill progress: prefix tokens already in the cache pool
    # (shared hit pages + chunks computed so far)
    prefill_pos: int = 0
    # exact-full-prompt cache hit: the stored last-token prefill logits
    # (numpy [V]); decoding starts from these with no prefill at all
    cached_logits: Optional[object] = None
    # per-request sampling override (ops.sampling.SamplingParams); None
    # means the engine-global GenerationConfig with a seed derived from
    # (engine seed, rid)
    sampling: Optional[object] = None
    # multi-tenant serving: the tenant whose LoRA adapter (and quota /
    # SLO accounting) this request runs under; None = base model
    tenant: Optional[str] = None
    # chosen-token logprobs under the raw model distribution, parallel
    # to `generated` — the per-request logprob surface (rollout behavior
    # logps, eval/debugging)
    generated_logprobs: List[float] = dataclasses.field(default_factory=list)
    # wall-clock marks for TTFT / queue-wait / inter-token latency metrics
    admitted_time: Optional[float] = None  # first prefill admission
    first_token_time: Optional[float] = None
    last_token_time: Optional[float] = None

    @property
    def prefix_tokens(self) -> List[int]:
        """What a (re-)prefill must run: prompt plus everything already
        generated — the recompute contract of eviction."""
        return self.prompt_tokens + self.generated

    @property
    def remaining_new_tokens(self) -> int:
        return self.max_new_tokens - len(self.generated)


@dataclasses.dataclass(frozen=True)
class SchedulerConfig:
    prefill_chunk: int             # chunk width in tokens (cache-hit grain)
    decode_reserve_pages: int = 1  # pages beyond the prompt required to admit
    prefill_token_budget: int = 0  # per-engine-step token cap; 0 = none


class Scheduler:
    """Pure host-side state machine over a PagedKVCache's allocator and
    slots. The engine loop calls, per step:

      1. ``release(req)``      for finished requests (slots/pages back)
      2. ``ensure_decode_pages()``  grow running requests' block tables,
                                    copy-on-write shared write targets,
                                    preempting on OOM
      3. ``admit_chunk_prefill()``  admission into a free slot
    """

    def __init__(self, cache: PagedKVCache, cfg: SchedulerConfig,
                 prefix_cache: Optional[PrefixCache] = None):
        self.cache = cache
        self.cfg = cfg
        self.prefix_cache = prefix_cache
        self.queue: Deque[Request] = deque()
        self.running: Dict[int, Request] = {}    # slot -> request
        self.prefilling: Dict[int, Request] = {} # slot -> mid-chunk req
        self.free_slots: List[int] = list(
            range(cache.geom.num_slots - 1, -1, -1))
        self.preemptions = 0
        # degradation-ladder batch shrink: admission stops once this many
        # requests hold slots (None = every slot usable). Purely an
        # admission cap — shapes stay static, running requests finish.
        self.max_active: Optional[int] = None
        # called with the request on every slot release (finish, evict,
        # cancel) — the engine pairs it with its per-slot-bind adapter
        # acquire so AdapterStore refcounts track slot residency exactly
        self.release_hook = None

    def _admission_headroom(self) -> Optional[int]:
        """Slots admission may still fill under ``max_active``; None
        means unlimited."""
        if self.max_active is None:
            return None
        held = len(self.running) + len(self.prefilling)
        return max(0, self.max_active - held)

    # ------------------------------------------------------------- intake

    def submit(self, req: Request) -> None:
        geom = self.cache.geom
        need = len(req.prompt_tokens) + req.max_new_tokens
        if need > geom.slot_window:
            raise ValueError(
                f"request {req.rid}: prompt+max_new ({need}) exceeds the "
                f"slot window ({geom.slot_window} = {geom.pages_per_slot} "
                f"pages x {geom.page_size})")
        if not req.prompt_tokens:
            raise ValueError(f"request {req.rid}: empty prompt")
        req.state = RequestState.WAITING
        self.queue.append(req)

    def admission_pages(self, prefix_len: int) -> int:
        """Pages admission takes for a prefix of ``prefix_len`` tokens:
        its own plus the decode reserve, capped at the block table's
        width (a max-width prompt whose reserve would overflow the table
        just starts reserve-less)."""
        geom = self.cache.geom
        return min(geom.pages_for(prefix_len)
                   + self.cfg.decode_reserve_pages, geom.pages_per_slot)

    # ----------------------------------------------------------- admission

    def admit_chunk_prefill(self) -> Optional[Request]:
        """Strict-FCFS admission: at most one request is
        mid-prefill at a time (its chunks run one per engine step). The
        head gets a slot plus its FULL page demand up front — cached
        prefix pages alias (incref, no copy, no recompute), only the
        uncovered suffix and the decode reserve allocate fresh.

        Returns the admitted request, or None (queue empty, no slot, a
        request already prefilling, or the pool can't cover the fresh
        pages — hit pages are released again on that backpressure path).
        A returned request with ``prefill_pos == len(prefix_tokens)``
        was an exact-full-prompt hit: ``cached_logits`` is set, no
        prefill runs, and the engine activates it directly."""
        if not self.queue or not self.free_slots or self.prefilling:
            return None
        if self._admission_headroom() == 0:
            return None
        req = self.queue[0]
        prefix = req.prefix_tokens
        n = len(prefix)
        hit_pages: List[int] = []
        hit = 0
        logits = None
        if self.prefix_cache is not None:
            # namespaced by tenant: one tenant's cached KV never serves
            # another's lookups (adapters change the KV contents)
            hit_pages, hit, logits = self.prefix_cache.lookup(
                prefix, self.cfg.prefill_chunk, namespace=req.tenant)
        fresh = self.cache.allocator.alloc(
            self.admission_pages(n) - len(hit_pages))
        if fresh is None:
            # backpressure: give the hit references back and wait
            for p in hit_pages:
                self.cache.allocator.decref(p)
            return None
        self.queue.popleft()
        req.pages = hit_pages + fresh        # block-table order
        req.slot = self.free_slots.pop()
        req.state = RequestState.PREFILL
        req.prefill_pos = hit
        req.cached_logits = logits
        self.cache.open_slot_prefill(req.slot, req.pages, hit)
        if hit < n:
            self.prefilling[req.slot] = req
        return req

    def activate(self, req: Request) -> None:
        """PREFILL -> DECODE once the engine has run every chunk of the
        prefill forward and the slot has begun decoding."""
        req.state = RequestState.DECODE
        self.prefilling.pop(req.slot, None)
        self.running[req.slot] = req

    def adopt(self, req: Request, pages: List[int]) -> int:
        """Bind a request whose committed KV already sits in the pool
        straight into a DECODE slot — the KV-import and restore-from-
        cache entry point (no queue, no prefill). The caller owns one
        reference per page in ``pages`` (freshly allocated, or increfed
        cache aliases) and sets up the slot's cache metadata itself;
        from here the request is indistinguishable from one that
        prefilled locally. Raises when the request cannot fit the slot
        window or no slot is free — the caller unwinds its references."""
        geom = self.cache.geom
        need = len(req.prompt_tokens) + req.max_new_tokens
        if need > geom.slot_window:
            raise ValueError(
                f"request {req.rid}: prompt+max_new ({need}) exceeds the "
                f"slot window ({geom.slot_window})")
        if not self.free_slots:
            raise RuntimeError(
                f"request {req.rid}: no free slot to adopt into")
        req.pages = list(pages)
        req.slot = self.free_slots.pop()
        req.state = RequestState.DECODE
        self.running[req.slot] = req
        return req.slot

    # --------------------------------------------------- page-pool safety

    def ensure_decode_pages(self, span: int = 1) -> List[Request]:
        """Before a decode step: every running request whose next write
        column crosses into an unallocated page gets one, and a next
        write landing on a SHARED or cache-indexed page is copy-on-
        written to a private one first (the shared original stays
        pristine for its other readers). On exhaustion, preempt the
        youngest running request (drop its slot AND its page references)
        and retry; the preempted requests are returned (already
        re-queued at the head, FIFO among themselves).

        ``span`` is the number of columns the coming step may COMMIT per
        slot (K+1 for a speculative round, 1 otherwise): headroom and
        COW cover the whole write range ``[lengths, lengths+need)`` where
        ``need = min(span, remaining_new_tokens)`` — a request near its
        token budget never reserves pages it cannot fill. Speculative
        scatters beyond the allocated range hit the trash page by the
        block-table-zero convention and are rolled back for free (their
        columns are never marked valid)."""
        evicted: List[Request] = []
        for slot in sorted(self.running):
            req = self.running.get(slot)
            if req is None:
                continue   # evicted while growing an earlier slot
            while True:
                if self._needs_page(req, span):
                    page = self.cache.allocator.alloc(1)
                    if page is not None:
                        # table entry i holds req.pages[i]; the new page
                        # lands at the next free entry
                        req.pages.extend(page)
                        self.cache.block_tables[
                            slot, len(req.pages) - 1] = page[0]
                        continue
                elif self._ensure_writable(req, span):
                    if self.cache.window_allocator is not None:
                        # the window pool's page under the same write
                        # range (it owns a ring a slot: never short)
                        self.cache.ensure_window(
                            slot, int(self.cache.lengths[slot])
                            + self._write_need(req, span) - 1)
                    break
                victim = self._youngest_running(exclude_rid=None)
                if victim is None or victim.rid == req.rid:
                    # nothing left to evict but this request itself:
                    # evict it (its own pages may unblock older ones)
                    victim = req
                self.evict(victim)
                evicted.append(victim)
                if victim.rid == req.rid:
                    break  # this request is gone; stop growing it
        return evicted

    def _write_need(self, req: Request, span: int) -> int:
        """Columns the next step may commit for this request: the span,
        clamped to its remaining token budget (always >= 1 — a running
        request has at least one token left to emit)."""
        return max(1, min(int(span), req.remaining_new_tokens))

    def _needs_page(self, req: Request, span: int = 1) -> bool:
        geom = self.cache.geom
        next_col = int(self.cache.lengths[req.slot])
        last_col = next_col + self._write_need(req, span) - 1
        return last_col // geom.page_size >= len(req.pages)

    def _ensure_writable(self, req: Request, span: int = 1) -> bool:
        """Copy-on-write guard: every page under this request's write
        range (``span`` columns from the next decode write) must be
        exclusively owned and unindexed, or the writes would corrupt
        pages other readers / the prefix cache still rely on. Returns
        False only when a COW copy can't get a destination page (caller
        preempts and retries)."""
        if self.prefix_cache is None:
            return True
        geom = self.cache.geom
        next_col = int(self.cache.lengths[req.slot])
        last_col = next_col + self._write_need(req, span) - 1
        alloc = self.cache.allocator
        for idx in range(next_col // geom.page_size,
                         last_col // geom.page_size + 1):
            if idx >= len(req.pages):
                break      # beyond allocation: trash-page writes only
            page = int(self.cache.block_tables[req.slot, idx])
            if page == 0:
                continue
            if alloc.refcount(page) <= 1 and \
                    not self.prefix_cache.is_indexed(page):
                continue
            fresh = alloc.alloc(1)
            if fresh is None:
                return False
            self.cache.cow_page(req.slot, idx, fresh[0])
            req.pages[idx] = fresh[0]
            alloc.decref(page)
        return True

    def _youngest_running(self, exclude_rid=None) -> Optional[Request]:
        cands = [r for r in self.running.values()
                 if r.rid != exclude_rid]
        if not cands:
            return None
        return max(cands, key=lambda r: r.rid)

    def evict(self, req: Request) -> None:
        """Preempt: free slot, DROP this request's page references
        (shared pages survive for their other holders — refcounting is
        what makes eviction safe under prefix sharing), keep generated
        tokens, requeue at the FRONT (it was admitted before everything
        still waiting)."""
        self.preemptions += 1
        req.evictions += 1
        self._release_resources(req)
        req.state = RequestState.EVICTED
        self.queue.appendleft(req)
        req.state = RequestState.WAITING

    def finish(self, req: Request, reason: str) -> None:
        req.finish_reason = reason
        self._release_resources(req)
        req.state = RequestState.FINISHED

    def cancel(self, req: Request, reason: str,
               state: RequestState = RequestState.FINISHED) -> None:
        """Terminal removal from wherever the request currently lives —
        the queue (waiting/evicted), a decode slot, or mid-chunked-
        prefill. Generated-so-far tokens stay on the request; resources
        go back to the pool. Used for deadline expiry (state=TIMEOUT)
        and drain cancellation."""
        self.queue = deque(r for r in self.queue if r.rid != req.rid)
        self._release_resources(req)
        req.finish_reason = reason
        req.state = state

    def expired(self, now: float) -> List[Request]:
        """Every queued, prefilling, or running request whose deadline
        has passed."""
        out = [r for r in self.queue
               if r.deadline is not None and now >= r.deadline]
        out += [r for r in self.running.values()
                if r.deadline is not None and now >= r.deadline]
        out += [r for r in self.prefilling.values()
                if r.deadline is not None and now >= r.deadline]
        return out

    def sheddable_queued(self) -> List[Request]:
        """Queued requests load shedding may drop, worst-first: lowest
        priority, then latest arrival (least sunk wait) among equals.
        Evicted in-flight requests are exempt — they hold generated
        tokens and sunk compute, and shedding them would break the
        streaming contract mid-request."""
        cands = [r for r in self.queue if not r.generated]
        cands.sort(key=lambda r: (r.priority, -r.arrival_time, -r.rid))
        return cands

    def _release_resources(self, req: Request) -> None:
        if req.slot is not None:
            if self.release_hook is not None:
                self.release_hook(req)
            self.running.pop(req.slot, None)
            self.prefilling.pop(req.slot, None)
            self.cache.close_slot(req.slot)
            self.free_slots.append(req.slot)
            req.slot = None
        if req.pages:
            # one decref per held reference: uniquely-owned pages free
            # (or park on the cache's LRU), shared pages merely lose
            # this holder
            self.cache.allocator.free(req.pages)
            req.pages = []
        req.prefill_pos = 0
        req.cached_logits = None

    # ------------------------------------------------------------- status

    @property
    def queue_depth(self) -> int:
        return len(self.queue)

    @property
    def active_count(self) -> int:
        return len(self.running)

    def assert_consistent(self) -> None:
        """Slot/page accounting invariants (tests call this every step):
        no slot leaks, no page leaks, no slot double-booked, and — under
        prefix sharing — reference counts exactly equal to the number of
        block tables holding each page."""
        from collections import Counter
        geom = self.cache.geom
        holders = list(self.running.values()) + \
            list(self.prefilling.values())
        assert len(self.free_slots) + len(holders) == geom.num_slots, (
            f"slot leak: {len(self.free_slots)} free + "
            f"{len(holders)} held != {geom.num_slots}")
        assert len(set(self.free_slots)) == len(self.free_slots)
        booked = set(self.running) | set(self.prefilling)
        assert not (set(self.free_slots) & booked)
        assert not (set(self.running) & set(self.prefilling))
        held = Counter(p for r in holders for p in r.pages)
        refs = self.cache.allocator.refcounts
        assert held == Counter(refs), (
            f"page refcount drift: requests hold {dict(held)}, "
            f"allocator says {refs}")
        alloc = self.cache.allocator
        assert alloc.used_count + alloc.free_count + \
            alloc.cached_count == alloc.capacity, (
            f"page state leak: {alloc.used_count} used + "
            f"{alloc.free_count} free + {alloc.cached_count} cached "
            f"!= {alloc.capacity}")
        assert 0 not in refs and 0 not in alloc.cached_pages, (
            "trash page entered the allocator")
        walloc = self.cache.window_allocator
        if walloc is not None:
            held = int((self.cache.window_next
                        - self.cache.window_first).sum())
            assert held == walloc.used_count == int(
                np.count_nonzero(self.cache.window_tables)), (
                f"window page drift: slots hold {held}, allocator says "
                f"{walloc.used_count}")
            assert held <= geom.window_ring * len(holders)
