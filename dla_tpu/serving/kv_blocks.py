"""Block-paged KV cache: a fixed page pool shared by every in-flight
sequence, so sequences of wildly different lengths never reserve
worst-case contiguous cache.

Layout: preallocated ``pools``, one [L, num_pages, page_size, heads,
width] array per kind of row the model caches (``Transformer.cache_spec``:
keys and values of [KH, D]; or one latent row of [1, r + rope], lane-
padded). A model whose layers are of several kinds states three kinds of
array and the cache obeys (``PagedKVCache``): ``paged`` as above,
``paged_window`` over a second pool whose pages go back to its allocator
once they lie behind the window, and per-slot ``state``. Each decode SLOT (a row of the static-shape decode batch) owns a
block table row — ``pages_per_slot`` physical page ids — and the model's
paged steps (``Transformer._paged_layers``) gather, a layer at a time,

    view = slab[block_table]           # [B, P/slot, ps, heads, width]
           .reshape(B, S, heads, width)        # S = pages_per_slot * ps

out of that layer's slab of each pool, and write the step's fresh rows
back at the (page, offset) the engine computed. The gather is the whole
trick: attention math stays layout-agnostic, the pool stays fixed-size,
and page ownership is pure host-side bookkeeping (PageAllocator) that
never touches the graph.

Physical page 0 is RESERVED as the trash page: free slots' block tables
point at it, so the static-shape decode step can let inactive rows
write/read garbage there without branching. The allocator never hands
page 0 out and the prefix cache never indexes it.

THE POOL DOUBLES AS A PREFIX CACHE. Pages are refcounted: several block
tables may alias one physical page when their requests share a token
prefix (KV content is position-dependent but prefix-determined, so equal
prefixes mean bit-equal pages). When the last reference drops, a page
that the :class:`PrefixCache` still indexes is RETAINED on an LRU list
instead of freed — zero extra memory, the cache simply delays reuse.
Allocation under pressure reclaims retained pages LRU-first, unindexing
them as it goes, so a busy pool degrades gracefully to the uncached
behavior. Every page is always in exactly one of three states: free,
used (refcount >= 1), or cached (refcount 0, content retained).
"""
from __future__ import annotations

import dataclasses
import functools
from collections import OrderedDict
from typing import (Callable, Dict, List, Optional, Sequence, Tuple)

import jax
import jax.numpy as jnp
import numpy as np

from dla_tpu.utils.profiling import startup_span


class PageAllocator:
    """Host-side refcounted free-list allocator over the fixed page pool.

    Pages are fixed-size, so there is no external fragmentation — any
    interleaving of alloc/free keeps every free page usable. Allocation
    is all-or-nothing: a request that cannot get ALL ``n`` pages gets
    none (no partial reservations to unwind on admission failure).

    ``alloc`` hands out pages at refcount 1; ``incref`` lets another
    block table alias a page (prefix sharing); ``decref``/``free`` drop
    references. A page reaching refcount 0 normally returns to the free
    list, but when ``retain_hook`` claims it (the prefix cache still
    indexes its content) it parks on an LRU cached list instead —
    revivable by ``incref`` (a cache hit) and reclaimable by ``alloc``
    under pressure (``evict_hook`` fires so the index forgets it).
    """

    def __init__(self, num_pages: int):
        if num_pages < 2:
            raise ValueError("need >= 2 pages (page 0 is the trash page)")
        self.num_pages = num_pages
        # page 0 reserved: free slots alias it for garbage traffic
        self._free: List[int] = list(range(num_pages - 1, 0, -1))
        self._used: set = set()
        self._ref: Dict[int, int] = {}
        # refcount-0 pages whose content the prefix cache still indexes,
        # insertion-ordered: front = least recently released = evicted
        # first when alloc outruns the free list
        self._cached: "OrderedDict[int, None]" = OrderedDict()
        # policy hooks the PrefixCache installs; absent hooks give the
        # plain uncached allocator (decref-0 always frees)
        self.retain_hook: Optional[Callable[[int], bool]] = None
        self.evict_hook: Optional[Callable[[int], None]] = None
        self.cache_evictions = 0

    @property
    def free_count(self) -> int:
        return len(self._free)

    @property
    def used_count(self) -> int:
        return len(self._used)

    @property
    def cached_count(self) -> int:
        return len(self._cached)

    @property
    def capacity(self) -> int:
        """Allocatable pages (excludes the reserved trash page)."""
        return self.num_pages - 1

    @property
    def occupancy(self) -> float:
        """Fraction of allocatable pages currently owned (cached pages
        are reclaimable, so they count as free here)."""
        return self.used_count / max(1, self.capacity)

    @property
    def refcounts(self) -> Dict[int, int]:
        """Copy of the live page -> refcount map (invariant checks)."""
        return dict(self._ref)

    @property
    def cached_pages(self) -> List[int]:
        """LRU-ordered refcount-0 retained pages (eviction order)."""
        return list(self._cached)

    def can_alloc(self, n: int) -> bool:
        return n <= len(self._free) + len(self._cached)

    def alloc(self, n: int) -> Optional[List[int]]:
        """``n`` pages at refcount 1, or None if free + reclaimable
        cached pages cannot supply all of them. Reclaims cached pages
        LRU-first, unindexing each via ``evict_hook``."""
        if n < 0:
            raise ValueError(f"alloc({n})")
        if n > len(self._free) + len(self._cached):
            return None
        pages: List[int] = []
        for _ in range(n):
            if self._free:
                p = self._free.pop()
            else:
                p, _ = self._cached.popitem(last=False)
                self.cache_evictions += 1
                if self.evict_hook is not None:
                    self.evict_hook(p)
            self._used.add(p)
            self._ref[p] = 1
            pages.append(p)
        return pages

    def incref(self, page: int) -> None:
        """Add a reference: another block table now aliases ``page``.
        Reviving a cached page (a prefix-cache hit) moves it back to the
        used state."""
        if page in self._ref:
            self._ref[page] += 1
        elif page in self._cached:
            del self._cached[page]
            self._used.add(page)
            self._ref[page] = 1
        else:
            raise ValueError(f"incref of free/foreign page {page}")

    def decref(self, page: int) -> None:
        """Drop a reference. At refcount 0 the page frees — unless the
        retain hook claims it for the prefix cache, in which case it
        parks on the cached LRU list (most-recently-released last)."""
        if page not in self._ref:
            raise ValueError(f"double free / foreign page {page}")
        self._ref[page] -= 1
        if self._ref[page] == 0:
            del self._ref[page]
            self._used.discard(page)
            if self.retain_hook is not None and self.retain_hook(page):
                self._cached[page] = None
            else:
                self._free.append(page)

    def refcount(self, page: int) -> int:
        return self._ref.get(page, 0)

    def free(self, pages: List[int]) -> None:
        """Drop one reference per page (the historical bulk-release
        surface; exact old behavior when nothing is shared)."""
        for p in pages:
            self.decref(p)

    def uncache(self, page: int) -> None:
        """Drop a retained refcount-0 page straight to the free list
        (its index entry is gone, so there is nothing to hit)."""
        if page in self._cached:
            del self._cached[page]
            self._free.append(page)

    def reclaim_cached(self) -> int:
        """Evict EVERY retained refcount-0 page back to the free list,
        unindexing each via ``evict_hook`` — the degradation ladder's
        first rung under sustained pressure (alloc would reclaim them
        one-by-one anyway; this trades the whole cache for headroom at
        once). Pages still referenced by live block tables are untouched.
        Returns the number of pages reclaimed."""
        n = 0
        while self._cached:
            p, _ = self._cached.popitem(last=False)
            self.cache_evictions += 1
            if self.evict_hook is not None:
                self.evict_hook(p)
            self._free.append(p)
            n += 1
        return n


@dataclasses.dataclass(frozen=True)
class PageGeometry:
    """Static shape parameters of a paged pool — everything the jitted
    serving steps specialize on."""
    page_size: int
    num_pages: int
    num_slots: int
    pages_per_slot: int
    # the window pool of a model with ``paged_window`` layers (0 / 0 / 0:
    # none): the attention window in tokens, the width of a slot's ring
    # of window pages, and the pool's pages (page 0 is its trash page).
    # ``for_model`` derives them; no configuration sets them
    window: int = 0
    window_ring: int = 0
    num_window_pages: int = 0

    @classmethod
    def for_model(cls, model, *, page_size: int, num_pages: int,
                  num_slots: int, pages_per_slot: int,
                  prefill_chunk: int) -> "PageGeometry":
        """The geometry a model's ``cache_spec()`` asks for. A slot holds
        the window's pages and the chunk being written, and a page more
        where the two do not line up: ceil((window + chunk) / page) + 1
        entries, logical page j at entry j % ring; the pool owns a ring
        for every slot, so a window page is never waited for."""
        window = max((a.window or 0 for a in model.cache_spec()
                      if a.kind == "paged_window"), default=0)
        ring = (-(-(window + prefill_chunk) // page_size) + 1
                if window else 0)
        return cls(page_size=page_size, num_pages=num_pages,
                   num_slots=num_slots, pages_per_slot=pages_per_slot,
                   window=window, window_ring=ring,
                   num_window_pages=num_slots * ring + 1 if ring else 0)

    @property
    def slot_window(self) -> int:
        """S: the per-slot logical window the gather materializes."""
        return self.pages_per_slot * self.page_size

    @property
    def window_gather_pages(self) -> int:
        """Pages of the window pool a step reads for one slot: those
        holding (pos - window, pos), a page more where the window's
        start lies inside one."""
        return -(-self.window // self.page_size) + 1 if self.window else 0

    def pages_for(self, n_tokens: int) -> int:
        """Pages needed to hold ``n_tokens`` (ceil)."""
        return -(-n_tokens // self.page_size)


@dataclasses.dataclass
class _FullEntry:
    """Full-exact-prompt cache entry: the partial tail page (None when
    the prompt is page-aligned) plus the last-token prefill logits, so a
    repeat of the exact prompt skips prefill entirely."""
    tail_page: Optional[int]
    logits: np.ndarray


class PrefixCache:
    """Content-addressed index over the pool's pages.

    Two granularities:

    * **Full pages** — ``_index`` maps the exact token tuple of a
      page-aligned prefix to the physical page holding its KV. Keys are
      the tokens themselves (no hashing), so a hit is a guarantee, never
      a collision. A lookup walks prefixes page by page and stops at the
      first miss, so an interior eviction simply shortens later hits
      (orphaned longer entries age out via the allocator's LRU).
    * **Exact full prompts** — ``_full`` additionally remembers the
      partial tail page and the last-token prefill LOGITS for recently
      completed prompts (LRU-capped), so an identical prompt skips
      prefill completely: all pages alias (including the partial tail,
      which copy-on-write protects once decode writes into it) and the
      first token samples from the stored logits.

    The cache holds NO references itself: retention of refcount-0 pages
    happens through the allocator hooks installed here, and the pool
    reclaims retained pages LRU-first under allocation pressure.
    """

    def __init__(self, allocator: PageAllocator, page_size: int,
                 logits_capacity: int = 128):
        self.allocator = allocator
        self.page_size = page_size
        self.logits_capacity = max(1, int(logits_capacity))
        self._index: Dict[Tuple[int, ...], int] = {}
        # page -> ("page" | "tail", key): which entry retains this page
        self._page_key: Dict[int, Tuple[str, Tuple[int, ...]]] = {}
        self._full: "OrderedDict[Tuple[int, ...], _FullEntry]" = \
            OrderedDict()
        self.lookups = 0
        self.hit_tokens = 0
        self.evictions = 0
        self.peeks = 0
        allocator.retain_hook = self._retain
        allocator.evict_hook = self._on_evict

    # ------------------------------------------------------------- hooks

    def _retain(self, page: int) -> bool:
        return page in self._page_key

    def _on_evict(self, page: int) -> None:
        """The allocator reclaimed a retained page: forget its entry.
        Children of an evicted interior page stay indexed — harmlessly,
        since lookups walk from the start and stop at the hole."""
        self.evictions += 1
        kind, key = self._page_key.pop(page)
        if kind == "page":
            if self._index.get(key) == page:
                del self._index[key]
        else:
            self._full.pop(key, None)

    # ----------------------------------------------------------- queries

    def is_indexed(self, page: int) -> bool:
        """True when the cache indexes ``page``'s content — writing to
        it would corrupt future hits, so writers must copy first."""
        return page in self._page_key

    @staticmethod
    def _nskey(namespace: Optional[str], sub: Tuple[int, ...]):
        """Namespace a token-tuple key. Multi-tenant serving keys cached
        KV by ``(tenant, tokens)`` — adapters change KV contents, so one
        tenant's pages must never answer another's lookup. Applied at
        the dict-key layer only: prefix slicing stays on the raw token
        tuple, so page alignment is untouched."""
        return sub if namespace is None else (namespace,) + sub

    def lookup(self, tokens: Sequence[int], chunk: int,
               namespace: Optional[str] = None,
               ) -> Tuple[List[int], int, Optional[np.ndarray]]:
        """Longest usable cached prefix of ``tokens``.

        Returns ``(pages, hit_len, logits)`` with every returned page
        ALREADY increfed (the caller decrefs on admission failure). An
        exact-full-prompt hit returns every page plus the stored logits
        (``hit_len == len(tokens)``: no prefill at all). Otherwise the
        hit is truncated to a multiple of ``chunk`` and strictly below
        ``len(tokens)`` — chunked prefill restarts at a fixed absolute
        chunk boundary, which is what keeps cache-on decoding
        bit-identical to cache-off."""
        self.lookups += 1
        key = tuple(tokens)
        pages, hit, entry = self._walk(key, chunk, namespace)
        if entry is not None:
            self._full.move_to_end(self._nskey(namespace, key))
            for p in pages:
                self.allocator.incref(p)
            self.hit_tokens += hit
            return pages, hit, entry.logits
        for p in pages:
            self.allocator.incref(p)
        self.hit_tokens += hit
        return pages, hit, None

    def peek(self, tokens: Sequence[int], chunk: int,
             namespace: Optional[str] = None) -> int:
        """Read-only hit-length estimate: the ``hit_len`` a ``lookup``
        of ``tokens`` would return right now, WITHOUT taking page
        references, touching the full-prompt LRU order, or advancing the
        lookup/hit-token counters. The fleet router calls this on every
        candidate engine per placement decision, so a peek must be
        side-effect-free — a peek that increfed would leak references on
        the N-1 engines that lose the placement."""
        self.peeks += 1
        _, hit, _ = self._walk(tuple(tokens), chunk, namespace)
        return hit

    def _walk(self, key: Tuple[int, ...], chunk: int,
              namespace: Optional[str] = None,
              ) -> Tuple[List[int], int, Optional["_FullEntry"]]:
        """Shared read-only index walk behind ``lookup`` and ``peek``:
        ``(pages, hit_len, full_entry)`` with NO side effects — the
        caller applies increfs, LRU touches and counters (or, for peek,
        nothing at all). ``full_entry`` is non-None only on an
        exact-full-prompt hit (``hit_len == len(key)``)."""
        n = len(key)
        ps = self.page_size
        entry = self._full.get(self._nskey(namespace, key))
        if entry is not None:
            pages = self._assemble_full(key, entry, namespace)
            if pages is not None:
                return pages, n, entry
        # chunk-granular: the last token's logits must be recomputed, so
        # the hit stays < n; chunk alignment keeps the restart boundary
        # on the fixed absolute schedule
        max_hit = ((n - 1) // chunk) * chunk if chunk > 0 else 0
        pages: List[int] = []
        k = 1
        while k * ps <= max_hit:
            p = self._index.get(self._nskey(namespace, key[:k * ps]))
            if p is None:
                break
            pages.append(p)
            k += 1
        hit = (len(pages) * ps // chunk) * chunk if chunk > 0 else 0
        return pages[:hit // ps], hit, None

    def _assemble_full(self, key: Tuple[int, ...], entry: _FullEntry,
                       namespace: Optional[str] = None,
                       ) -> Optional[List[int]]:
        """All physical pages of an exact-prompt entry, or None when an
        interior page was evicted (fall back to the chunked walk)."""
        n, ps = len(key), self.page_size
        pages: List[int] = []
        for k in range(1, n // ps + 1):
            p = self._index.get(self._nskey(namespace, key[:k * ps]))
            if p is None:
                return None
            pages.append(p)
        if n % ps:
            if entry.tail_page is None:
                return None
            pages.append(entry.tail_page)
        return pages

    def acquire_pages(self, tokens: Sequence[int],
                      namespace: Optional[str] = None,
                      ) -> Optional[List[int]]:
        """Every full page of a PAGE-ALIGNED prefix, each ALREADY
        increfed — or None, with no references taken, when the prefix is
        not aligned or any page is missing (an interior eviction hole).

        This is the adopt-without-prefill surface behind
        ``ServingEngine.restore``'s cache fast path and KV import: unlike
        ``lookup`` there is no chunk truncation (the caller resumes
        DECODE, not prefill, so it needs the committed columns exactly)
        and no full-prompt logits (the next decode input is the last
        generated token, so no logits are consumed at all)."""
        key = tuple(tokens)
        n, ps = len(key), self.page_size
        self.lookups += 1
        if n == 0 or n % ps:
            return None
        pages: List[int] = []
        for k in range(1, n // ps + 1):
            p = self._index.get(self._nskey(namespace, key[:k * ps]))
            if p is None:
                for q in pages:
                    self.allocator.decref(q)
                return None
            self.allocator.incref(p)
            pages.append(p)
        self.hit_tokens += n
        return pages

    # ------------------------------------------------------- registration

    def register(self, tokens: Sequence[int], pages: Sequence[int],
                 logits: Optional[np.ndarray] = None,
                 namespace: Optional[str] = None) -> None:
        """Index a freshly prefilled prefix: one entry per FULL page
        (first writer wins — an existing entry for the same tokens keeps
        its page), plus, when ``logits`` is given, an exact-full-prompt
        entry retaining the partial tail page and the last-token logits.
        The trash page is never indexed."""
        key = tuple(tokens)
        n, ps = len(key), self.page_size
        for k in range(1, n // ps + 1):
            sub = self._nskey(namespace, key[:k * ps])
            page = pages[k - 1]
            if sub in self._index or page == 0:
                continue
            self._index[sub] = page
            self._page_key[page] = ("page", sub)
        nkey = self._nskey(namespace, key)
        if logits is None or nkey in self._full:
            return
        tail: Optional[int] = None
        if n % ps:
            tail = pages[n // ps]
            if tail == 0:
                return
            self._page_key[tail] = ("tail", nkey)
        self._full[nkey] = _FullEntry(tail, np.asarray(logits))
        while len(self._full) > self.logits_capacity:
            old_key, old = self._full.popitem(last=False)
            if old.tail_page is not None and \
                    self._page_key.get(old.tail_page) == ("tail", old_key):
                del self._page_key[old.tail_page]
                self.allocator.uncache(old.tail_page)


@functools.partial(jax.jit, donate_argnums=0)
def copy_page(pools: Tuple[jnp.ndarray, ...], src, dst
              ) -> Tuple[jnp.ndarray, ...]:
    """Device-side physical page copy — the copy-on-write primitive —
    in every pool. ``src``/``dst`` are traced scalars, so this compiles
    once per pool shape no matter which pages get copied. Consumes
    ``pools`` (donated): rebind to what it returns."""
    return tuple(p.at[:, dst].set(p[:, src]) for p in pools)


class PagedKVCache:
    """Device pool + host metadata mirror for the serving decode batch.

    Device state (jitted steps read/write):
      pools  a tuple of arrays, one per entry of ``model.cache_spec()``,
             in its order. ``paged`` entries are [L, num_pages,
             page_size, heads, width]: keys and values of [KH, D] for
             dense attention, one pool of [1, r + rope] latent rows for
             latent attention. Every consumer (the engine's gather /
             scatter, copy-on-write, migration) maps over the tuple, so
             the row's shape is the model's business. A model with a
             per-layer spec adds ``paged_window`` entries ([layers,
             num_window_pages, page_size, heads, width], addressed
             through ``window_tables``) and ``state`` entries ([layers,
             num_slots, *shape], indexed by slot; a request's first
             prefill chunk starts from zeros whatever the slot held, so
             nothing is zeroed on the host's side), all in the one
             tuple, donated and rebound together.
             This object alone owns the buffers: every jitted program
             that returns the pools (decode, prefill chunk, the
             speculative pair, KV import, ``copy_page``) is given them
             DONATED and updates them in place, so whoever gets pools
             back has consumed the ones it gave and rebinds ``pools`` in
             the same statement; the arrays that went in are deleted.
             Nothing else may keep a pool array across a step (the
             migration export is the one reader, and it returns new
             arrays). A program that fails after its inputs were
             consumed leaves ``pools`` dead (``pools_dead``): there is
             no content left to save, the engine reports
             ``DeviceStepError`` and its supervisor rebuilds a fresh
             cache and replays.

    Host mirror (authoritative, numpy — the scheduler mutates it and the
    engine packs it into each dispatch's one argument array,
    serving/step_args.py; decode-step updates are deterministic (+1
    length, one valid column) so the host applies them itself rather than
    fetching arrays back):
      block_tables  [num_slots, pages_per_slot] int32 physical page ids
      valid         [num_slots, S] attendable columns: host bookkeeping,
                    never sent. Every writer below keeps a slot's row a
                    PREFIX (of length ``lengths`` while it decodes, of
                    the computed columns while it prefills) and a
                    column's logical position is its index, so the step
                    programs compute the mask and the positions from
                    ``lengths`` / the chunk's start
                    (tests/test_step_args.py holds the writers to it)
      lengths       [num_slots]    true tokens so far
      tokens        [num_slots]    last sampled token (next step's input)
      window_tables [num_slots, window_ring] page ids of the window pool:
                    a slot holds logical pages [window_first,
                    window_next), page j at entry j % window_ring; the
                    pass that advances a slot (``mark_computed``,
                    ``advance_slot``) returns every page that lies
                    wholly behind the next query's window to
                    ``window_allocator``
    """

    def __init__(self, model, geom: PageGeometry):
        self.geom = geom
        self.dtype = model.adtype
        self.spec = tuple(model.cache_spec())
        with startup_span("startup_pool_alloc", arrays=len(self.spec)):
            self.pools: Tuple[jnp.ndarray, ...] = tuple(
                jnp.zeros(self.array_shape(a, geom), a.dtype)
                for a in self.spec)
        self.window_tables = np.zeros(
            (geom.num_slots, geom.window_ring), np.int32)
        self.window_first = np.zeros((geom.num_slots,), np.int64)
        self.window_next = np.zeros((geom.num_slots,), np.int64)
        self.window_allocator = (PageAllocator(geom.num_window_pages)
                                 if geom.window_ring else None)
        self.window_pages_released = 0
        s = geom.slot_window
        self.block_tables = np.zeros(
            (geom.num_slots, geom.pages_per_slot), np.int32)
        self.valid = np.zeros((geom.num_slots, s), bool)
        self.lengths = np.zeros((geom.num_slots,), np.int32)
        self.tokens = np.zeros((geom.num_slots,), np.int32)
        self.allocator = PageAllocator(geom.num_pages)

    @staticmethod
    def array_shape(a, geom: PageGeometry) -> Tuple[int, ...]:
        """The device array of one ``cache_spec()`` entry."""
        if a.kind == "state":
            return (a.layers, geom.num_slots) + tuple(a.shape)
        pages = (geom.num_pages if a.kind == "paged"
                 else geom.num_window_pages)
        return (a.layers, pages, geom.page_size) + tuple(a.shape)

    # ---------------------------------------------------- slot lifecycle

    def open_slot_prefill(self, slot: int, pages: List[int],
                          cached_len: int) -> None:
        """Bind ``pages`` for a prefill: columns [0, cached_len)
        are shared cache pages, already valid and attendable; later
        columns become valid as chunks scatter into them
        (``mark_computed``). ``lengths`` stays 0 — the slot joins the
        decode batch only at ``begin_decode``."""
        self.block_tables[slot] = 0
        self.block_tables[slot, :len(pages)] = pages
        self.valid[slot] = False
        self.valid[slot, :cached_len] = True
        self.lengths[slot] = 0
        self.tokens[slot] = 0

    def mark_computed(self, slot: int, start: int, count: int) -> None:
        """A prefill chunk scattered columns [start, start+count)."""
        self.valid[slot, start:start + count] = True
        if self.window_allocator is not None:
            self._release_window(slot, start + count)

    # ------------------------------------------------------ window pages

    def ensure_window(self, slot: int, last_col: int) -> None:
        """Window pages for every column up to ``last_col``, which the
        coming dispatch writes. The pool owns a ring a slot, so this
        cannot run dry while ``_release_window`` keeps up."""
        ps, ring = self.geom.page_size, self.geom.window_ring
        while self.window_next[slot] * ps <= last_col:
            page = self.window_allocator.alloc(1)
            j = int(self.window_next[slot])
            if page is None or j - int(self.window_first[slot]) >= ring:
                raise RuntimeError(
                    f"slot {slot}: window ring of {ring} pages overrun at "
                    f"logical page {j} (held from "
                    f"{int(self.window_first[slot])})")
            self.window_tables[slot, j % ring] = page[0]
            self.window_next[slot] = j + 1

    def _release_window(self, slot: int, next_query: int) -> None:
        """Give back every window page no later query can see: the next
        query stands at ``next_query`` and sees keys in (next_query -
        window, next_query], so page j goes once its last column, (j +
        1) * page - 1, is at or below next_query - window."""
        ps, ring = self.geom.page_size, self.geom.window_ring
        behind = (next_query - self.geom.window + 1) // ps   # pages < this
        while self.window_first[slot] < min(behind, self.window_next[slot]):
            entry = int(self.window_first[slot]) % ring
            self.window_allocator.decref(
                int(self.window_tables[slot, entry]))
            self.window_tables[slot, entry] = 0
            self.window_first[slot] += 1
            self.window_pages_released += 1

    def _close_window(self, slot: int) -> None:
        ring = self.geom.window_ring
        for j in range(int(self.window_first[slot]),
                       int(self.window_next[slot])):
            self.window_allocator.decref(
                int(self.window_tables[slot, j % ring]))
        self.window_tables[slot] = 0
        self.window_first[slot] = self.window_next[slot] = 0

    def begin_decode(self, slot: int, prompt_len: int,
                     first_token: int) -> None:
        """Prefill complete (computed or fully cached): the slot enters
        the decode batch at position ``prompt_len`` with ``first_token``
        as its next input."""
        self.valid[slot, :prompt_len] = True
        self.lengths[slot] = prompt_len
        self.tokens[slot] = first_token

    def close_slot(self, slot: int) -> None:
        """Reset a slot to trash-page aliasing (pages are freed by the
        scheduler, which owns the request -> pages mapping)."""
        self.block_tables[slot] = 0
        self.valid[slot] = False
        self.lengths[slot] = 0
        self.tokens[slot] = 0
        if self.window_allocator is not None:
            self._close_window(slot)

    def advance_slot(self, slot: int, token: int) -> None:
        """Apply one decode step's deterministic metadata update: the
        step wrote this slot's KV at column ``lengths`` with logical
        position ``lengths``; ``token`` was sampled and becomes the next
        step's input.

        Speculative rounds commit per accepted token through this same
        method — the host mirrors only ever advance by the ACCEPTED
        prefix, so a rejected draft tail needs no rollback: its columns
        were written on device but never marked valid here, and the next
        round's scatter overwrites them (the write-cursor "rewind" is
        that the cursor simply never moved)."""
        col = int(self.lengths[slot])
        self.valid[slot, col] = True
        self.lengths[slot] = col + 1
        self.tokens[slot] = token
        if self.window_allocator is not None:
            self._release_window(slot, col + 1)

    @property
    def pools_dead(self) -> bool:
        """True once a failed dispatch consumed the pools and returned
        nothing to rebind (see ``pools`` above)."""
        return any(p.is_deleted() for p in self.pools)

    def _bytes(self, kind: str) -> int:
        """Bytes of one unit (a token of a paged kind, a slot of
        ``state``) over every layer of every array of ``kind``."""
        return sum(int(np.prod(a.shape)) * a.layers
                   * jnp.dtype(a.dtype).itemsize
                   for a in self.spec if a.kind == kind)

    @property
    def bytes_per_token(self) -> int:
        """Bytes one cached token takes over every layer whose pages
        live as long as the request (the ``paged`` arrays)."""
        return self._bytes("paged")

    def layers_of(self, kind: str) -> int:
        """Layers that keep arrays of ``kind`` (0: none)."""
        return max((a.layers for a in self.spec if a.kind == kind),
                   default=0)

    @property
    def window_bytes_per_token(self) -> int:
        """Bytes a token takes in the window pool while inside the
        window, over its layers."""
        return self._bytes("paged_window")

    @property
    def state_bytes_per_slot(self) -> int:
        """Bytes of recurrent state a slot holds, whatever its length."""
        return self._bytes("state")

    @property
    def occupancy(self) -> float:
        """Pages owned over pages allocatable, both pools together."""
        allocs = [a for a in (self.allocator, self.window_allocator) if a]
        return (sum(a.used_count for a in allocs)
                / max(1, sum(a.capacity for a in allocs)))

    def slot_page_index(self, slot: int) -> int:
        """Block-table index the NEXT decode write for ``slot`` needs
        (its write column / page_size)."""
        return int(self.lengths[slot]) // self.geom.page_size

    def cow_page(self, slot: int, page_index: int, new_page: int) -> None:
        """Copy-on-write: duplicate the physical page behind
        ``block_tables[slot, page_index]`` into ``new_page`` on device
        and repoint the table — the shared original stays pristine for
        its other readers and the index."""
        src = int(self.block_tables[slot, page_index])
        self.pools = copy_page(
            self.pools,
            jnp.asarray(src, jnp.int32), jnp.asarray(new_page, jnp.int32))
        self.block_tables[slot, page_index] = new_page
