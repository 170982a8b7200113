"""Prefix-cache + chunked-prefill tests: allocator refcount/tri-state
invariants (shared pages are never freed while referenced, the trash
page is never cached), PrefixCache register/lookup/eviction semantics,
token-budget chunk admission, and the load-bearing e2e guarantees — on
a shared-prefix trace the cache saves >= 50% of prefill token compute,
greedy decode is TOKEN-IDENTICAL cache on vs off, and neither chunked
prefill nor the cache ever recompiles a step function."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from dla_tpu.generation.engine import GenerationConfig, build_generate_fn
from dla_tpu.models.config import get_model_config
from dla_tpu.models.transformer import Transformer
from dla_tpu.serving import (
    PageAllocator,
    PrefixCache,
    ServingConfig,
    ServingEngine,
)


# ---------------------------------------------------------------------------
# allocator refcounting + cached tri-state (pure host)
# ---------------------------------------------------------------------------

def test_allocator_incref_keeps_shared_page_allocated():
    a = PageAllocator(8)
    pages = a.alloc(2)
    a.incref(pages[0])               # second holder
    a.decref(pages[0])               # first holder drops
    assert a.refcount(pages[0]) == 1  # still allocated: not freed
    assert a.used_count == 2
    a.free(pages)                     # last references drop
    assert a.used_count == 0
    assert a.free_count == 7


def test_allocator_refzero_is_cached_not_free_with_retain_hook():
    """With a retain hook, a page dropping to refcount 0 parks on the
    cached LRU (revivable via incref) instead of returning to the free
    list; alloc under pressure reclaims cached pages oldest-first and
    fires the evict hook."""
    evicted = []
    a = PageAllocator(4)
    a.retain_hook = lambda p: True
    a.evict_hook = evicted.append
    pages = a.alloc(3)               # whole capacity
    a.decref(pages[0])
    a.decref(pages[1])
    assert a.cached_count == 2 and a.free_count == 0
    a.incref(pages[1])               # revive from cached
    assert a.refcount(pages[1]) == 1 and a.cached_count == 1
    got = a.alloc(1)                 # no free page: reclaims cached
    assert got == [pages[0]]
    assert evicted == [pages[0]]
    assert a.cache_evictions == 1
    a.free(got + [pages[1], pages[2]])


def test_allocator_trash_page_never_cached_and_errors_surface():
    a = PageAllocator(4)
    a.retain_hook = lambda p: True
    with pytest.raises(ValueError):
        a.incref(0)                  # trash page has no refcount
    with pytest.raises(ValueError):
        a.decref(0)
    pages = a.alloc(a.capacity)
    a.free(pages)                    # all parked on the cached LRU
    assert 0 not in a.cached_pages
    with pytest.raises(ValueError):
        a.decref(pages[0])           # page is cached, not referenced


def test_allocator_accounting_partitions_pool():
    a = PageAllocator(10)
    a.retain_hook = lambda p: p % 2 == 1
    held = a.alloc(6)
    for p in held[:4]:
        a.decref(p)                  # odd pages cache, even pages free
    assert a.used_count + a.free_count + a.cached_count == a.capacity
    assert a.used_count == 2
    assert a.cached_count == len([p for p in held[:4] if p % 2 == 1])


# ---------------------------------------------------------------------------
# PrefixCache register / lookup / eviction (host + tiny device pool)
# ---------------------------------------------------------------------------

PS = 4      # page size for the cache-level tests
CHUNK = 4


def _cache(num_pages=16):
    a = PageAllocator(num_pages)
    return PrefixCache(a, PS), a


def test_prefix_lookup_hits_full_pages_truncated_to_chunks():
    pc, a = _cache()
    toks = list(range(100, 112))            # 12 tokens = 3 full pages
    pages = a.alloc(3)
    pc.register(toks, pages)
    # identical 12-token prompt: hit must stay STRICTLY below n so the
    # final chunk always runs (it produces the first decode logits)...
    hit_pages, hit, logits = pc.lookup(toks, CHUNK)
    assert hit == 8 and hit_pages == pages[:2] and logits is None
    assert [a.refcount(p) for p in hit_pages] == [2, 2]  # pre-increfed
    for p in hit_pages:
        a.decref(p)
    # ...and a hit is truncated to a CHUNK multiple: 6 shared tokens
    # cover 1 full page but only chunk-aligned reuse keeps the absolute
    # chunk schedule (and the compiled chunk shape) intact
    hit_pages, hit, _ = pc.lookup(toks[:6] + [7, 8], CHUNK)
    assert hit == 4 and hit_pages == pages[:1]
    a.decref(pages[0])


def test_prefix_lookup_stops_at_first_hole():
    pc, a = _cache()
    toks = list(range(100, 112))
    pages = a.alloc(3)
    pc.register(toks, pages)
    pc.uncache_page = None  # not part of the API: just documenting
    # evict the MIDDLE page: the chain must truncate there, even though
    # the third page is still indexed
    a.free([pages[1]])  # refcount 0 -> cached
    # force reclaim of exactly that page
    while pages[1] in a.cached_pages:
        a.alloc(1)
    hit_pages, hit, _ = pc.lookup(toks, CHUNK)
    assert hit == 4 and hit_pages == pages[:1]
    a.decref(pages[0])


def test_prefix_register_first_writer_wins():
    pc, a = _cache()
    toks = list(range(100, 108))
    first = a.alloc(2)
    second = a.alloc(2)
    pc.register(toks, first)
    pc.register(toks, second)               # duplicate content: ignored
    hit_pages, hit, _ = pc.lookup(toks + [1, 2, 3, 4], CHUNK)
    assert hit_pages == first
    for p in first:
        a.decref(p)


def test_prefix_full_prompt_hit_returns_logits():
    pc, a = _cache()
    toks = list(range(100, 110))            # 10 tokens: 2 full + tail
    pages = a.alloc(3)
    stored = np.arange(8, dtype=np.float32)
    pc.register(toks, pages, stored)
    hit_pages, hit, logits = pc.lookup(toks, CHUNK)
    assert hit == len(toks)                 # exact-prompt: zero prefill
    assert hit_pages == pages               # tail page aliased too
    np.testing.assert_array_equal(logits, stored)
    # a DIFFERENT prompt sharing the full pages gets only those
    for p in hit_pages:
        a.decref(p)
    hit_pages, hit, logits = pc.lookup(toks[:9] + [7, 8, 9], CHUNK)
    assert hit == 8 and logits is None and hit_pages == pages[:2]
    for p in hit_pages:
        a.decref(p)


def test_prefix_peek_matches_lookup_without_side_effects():
    """peek() is the fleet router's placement probe: it must predict
    exactly what lookup() would hit while leaving refcounts, the cached
    LRU, and the lookup/hit counters untouched — probing N engines per
    admission must not distort cache behavior on any of them."""
    pc, a = _cache()
    toks = list(range(100, 112))            # 12 tokens = 3 full pages
    pages = a.alloc(3)
    pc.register(toks, pages)
    for p in pages:
        a.decref(p)                         # park all three on the LRU
    lru_before = list(a.cached_pages)
    lookups_before, hit_tokens_before = pc.lookups, pc.hit_tokens

    assert pc.peek(toks, CHUNK) == 8        # strict-below-n truncation
    assert pc.peek(toks[:6] + [7, 8], CHUNK) == 4
    assert pc.peek([1, 2, 3], CHUNK) == 0   # cold prompt

    # no refcounts taken, no LRU touch, no stats drift, peeks counted
    assert [a.refcount(p) for p in pages] == [0, 0, 0]
    assert list(a.cached_pages) == lru_before
    assert pc.lookups == lookups_before
    assert pc.hit_tokens == hit_tokens_before
    assert pc.peeks == 3

    # the probe's promise: the subsequent lookup hits exactly peek's
    # estimate (and only the lookup increfs)
    hit_pages, hit, _ = pc.lookup(toks, CHUNK)
    assert hit == 8 and [a.refcount(p) for p in hit_pages] == [1, 1]
    for p in hit_pages:
        a.decref(p)


def test_prefix_peek_full_prompt_and_eviction_order_unchanged():
    pc, a = _cache(num_pages=5)             # 4 usable: pool exactly full
    toks_a = list(range(100, 108))          # 2 full pages each
    toks_b = list(range(200, 208))
    pages_a, pages_b = a.alloc(2), a.alloc(2)
    pc.register(toks_a, pages_a, np.arange(4, dtype=np.float32))
    pc.register(toks_b, pages_b, np.arange(4, dtype=np.float32))
    for p in pages_a + pages_b:
        a.decref(p)
    assert pc.peek(toks_a, CHUNK) == len(toks_a)   # exact-prompt hit
    # peek must NOT refresh a's LRU position: under pressure a's pages
    # (the oldest) are still reclaimed first, exactly as if never peeked
    got = a.alloc(2)
    assert set(got) == set(pages_a)
    assert pc.peek(toks_a, CHUNK) < len(toks_a)    # full entry pruned
    a.free(got)


# ---------------------------------------------------------------------------
# e2e on the tiny model
# ---------------------------------------------------------------------------

MAX_NEW = 3
FAMILIES = 8
PER_FAMILY = 16
PREFIX_LEN = 9
SUFFIX_LEN = 3


@pytest.fixture(scope="module")
def model_and_params():
    cfg = get_model_config("tiny")
    model = Transformer(cfg)
    return model, model.init(jax.random.key(7))


@pytest.fixture(scope="module", params=["tiny", "tiny-mla"])
def pool_model_and_params(request):
    """``model_and_params`` over both kinds of cache row (dense keys and
    values; one latent row): register / lookup, copy-on-write and
    eviction follow ``Transformer.cache_rows()``."""
    model = Transformer(get_model_config(request.param))
    return model, model.init(jax.random.key(7))


@pytest.fixture(scope="module")
def shared_prefix_prompts():
    rs = np.random.RandomState(11)
    prompts = []
    for _ in range(FAMILIES):
        head = [int(t) for t in rs.randint(3, 500, (PREFIX_LEN,))]
        for _ in range(PER_FAMILY):
            prompts.append(head + [int(t)
                                   for t in rs.randint(3, 500, (SUFFIX_LEN,))])
    return prompts


def _engine(model, params, **kw):
    gen = GenerationConfig(max_new_tokens=MAX_NEW, do_sample=False,
                           temperature=0.0, eos_token_id=-1)
    scfg = ServingConfig(page_size=4, num_pages=kw.pop("num_pages", 64),
                         num_slots=4, max_model_len=16,
                         prefill_chunk=kw.pop("prefill_chunk", 4), **kw)
    return ServingEngine(model, params, gen, scfg)


def _serve(eng, prompts):
    # rids are process-global and results accumulate across drains:
    # return THIS call's outputs, in submission order
    rids = [eng.submit(p, MAX_NEW) for p in prompts]
    results = eng.run_until_drained(max_steps=5000)
    eng.scheduler.assert_consistent()
    return [results[r].generated for r in rids]


def test_prefix_cache_saves_half_of_prefill_bit_identically(
        pool_model_and_params, shared_prefix_prompts):
    """The acceptance gate: 8 families x 16 requests, prefill token
    compute drops >= 50%, greedy outputs are bit-identical cache on vs
    off, and both engines pin their compile counts (one decode, one
    chunk fn)."""
    model, params = pool_model_and_params
    prompts = shared_prefix_prompts
    total = sum(len(p) for p in prompts)

    on = _engine(model, params, prefix_cache=True)
    out_on = _serve(on, prompts)
    off = _engine(model, params)
    out_off = _serve(off, prompts)

    assert out_on == out_off                    # greedy decode unchanged
    snap = on.metrics.snapshot()
    saved = snap["serving/prefill/tokens_saved"]
    assert saved >= 0.5 * total
    assert snap["serving/prefix_cache/hit_tokens"] == saved
    assert snap["serving/prefix_cache/lookups"] == len(prompts)
    # computed + saved covers every prompt token (chunks are shape-
    # padded, so count VALID tokens: total - saved must equal the sum
    # of per-chunk nvalid, bounded by chunks * chunk_size)
    chunks_on = snap["serving/prefill/chunks"]
    assert (total - saved) <= chunks_on * 4
    for eng in (on, off):
        assert eng.decode_compiles == 1
        assert eng.prefill_chunk_compiles == 1


def test_full_prompt_hit_skips_prefill_and_cow_protects_pages(
        pool_model_and_params):
    """Identical prompts: the second is an exact-full-prompt hit (zero
    chunks run — stored logits + aliased tail page), and the THIRD still
    matches, proving the second request's first decode write went to a
    copy, not the cached tail page."""
    model, params = pool_model_and_params
    rs = np.random.RandomState(5)
    prompt = [int(t) for t in rs.randint(3, 500, (10,))]

    eng = _engine(model, params, prefix_cache=True)
    base = _serve(eng, [prompt])
    chunks_before = eng.metrics.snapshot()["serving/prefill/chunks"]
    second = _serve(eng, [prompt])
    snap = eng.metrics.snapshot()
    assert snap["serving/prefill/chunks"] == chunks_before  # no chunks ran
    assert snap["serving/prefix_cache/hit_tokens"] >= len(prompt)
    third = _serve(eng, [prompt])
    assert base == second == third


def test_eviction_under_cache_pressure_recomputes_identically(
        pool_model_and_params, shared_prefix_prompts):
    """A pool too small to retain every family's chain forces cached-
    page eviction; outputs must still match the cache-off run (evicted
    prefixes recompute, stale chains never resurface)."""
    model, params = pool_model_and_params
    prompts = shared_prefix_prompts
    # 24 pages: 4 slots x 4 pages in flight leaves ~7 cacheable pages —
    # far fewer than 8 families x 3 pages of prefix
    on = _engine(model, params, prefix_cache=True, num_pages=24)
    out_on = _serve(on, prompts)
    off = _engine(model, params, num_pages=24)
    out_off = _serve(off, prompts)
    assert out_on == out_off
    snap = on.metrics.snapshot()
    assert snap["serving/prefix_cache/evictions"] > 0
    assert on.cache.allocator.used_count == 0   # nothing leaked


def test_token_budget_defers_chunk_while_decodes_fill_it(
        model_and_params):
    """prefill_token_budget co-schedules: while running decodes fill the
    per-step budget the pending chunk waits, and with NO running decodes
    the chunk always runs (no livelock)."""
    model, params = model_and_params
    rs = np.random.RandomState(9)
    # budget 4 == one chunk exactly: any running decode defers the chunk
    eng = _engine(model, params, prefill_token_budget=4)
    a = eng.submit([int(t) for t in rs.randint(3, 500, (4,))], MAX_NEW)
    eng.step()                       # empty engine: chunk ALWAYS runs
    assert a in {r.rid for r in eng.scheduler.running.values()}
    chunks_a = eng.metrics.snapshot()["serving/prefill/chunks"]
    assert chunks_a == 1
    b = eng.submit([int(t) for t in rs.randint(3, 500, (8,))], MAX_NEW)
    eng.step()
    # B is admitted (slot + pages bound) but its chunk waits: 1 running
    # decode + chunk of 4 > budget 4
    breq = next(r for r in eng.scheduler.prefilling.values()
                if r.rid == b)
    assert breq.prefill_pos == 0
    assert eng.metrics.snapshot()["serving/prefill/chunks"] == chunks_a
    results = eng.run_until_drained(max_steps=5000)
    # once A drains the budget frees up and B's chunks run to completion
    assert sorted(results) == [a, b]
    assert all(len(r.generated) == MAX_NEW for r in results.values())
    eng.scheduler.assert_consistent()


def test_chunked_prefill_matches_teacher_forced_argmax(
        pool_model_and_params):
    """The chunk lane's independent reference: prompts of one, three and
    four chunks (no cache) emit, token for token, the arg-max of one
    whole-sequence forward over prompt + generated — the chunk schedule
    changes which program computes a column, never its value."""
    model, params = pool_model_and_params
    rs = np.random.RandomState(13)
    prompts = [[int(t) for t in rs.randint(3, 500, (n,))]
               for n in (5, 9, 12, 7)]
    outs = _serve(_engine(model, params), prompts)
    for prompt, out in zip(prompts, outs):
        assert len(out) == MAX_NEW
        seq = prompt + out
        logits = np.asarray(model.apply(
            params, jnp.asarray([seq], jnp.int32),
            jnp.ones((1, len(seq)), jnp.int32))[0])
        want = logits[len(prompt) - 1:len(seq) - 1].argmax(-1)
        assert out == [int(t) for t in want]


@pytest.mark.parametrize("chunk, why", [
    (0, "must be positive"), (-4, "must be positive"),
    (6, "multiple of page_size"), (20, "exceeds max_model_len")],
    ids=["zero", "negative", "not_a_page_multiple", "above_max_model_len"])
def test_bad_prefill_chunk_is_refused(model_and_params, chunk, why):
    """Input from a config file is still checked; 0 names the removed
    lane. With the features that used to need a chunk of their own on,
    so that none of them is what refuses."""
    model, params = model_and_params
    gen = GenerationConfig(max_new_tokens=2, do_sample=False,
                           eos_token_id=-1)
    with pytest.raises(ValueError, match=why):
        ServingEngine(model, params, gen, ServingConfig(
            page_size=4, num_pages=32, num_slots=2, max_model_len=16,
            prefill_chunk=chunk, prefix_cache=True, role="prefill",
            tenancy={"adapter_pool": {"max_adapters": 2, "max_rank": 4}}))
    with pytest.raises(ValueError):
        ServingEngine(model, params, gen,
                      ServingConfig(page_size=4, num_pages=32, num_slots=2,
                                    max_model_len=16, prefill_chunk=6,
                                    prefix_cache=True))  # not page-aligned


# ---------------------------------------------------------------------------
# grouped generation (RLHF rollout reuse)
# ---------------------------------------------------------------------------

def test_grouped_generation_matches_repeated_prompts(model_and_params):
    """build_generate_fn(group_size=G) on B unique prompts must emit the
    SAME tokens as group_size=1 on the G-fold repeated batch — prompt KV
    is computed once per unique prompt and expanded in-graph, and greedy
    decode is row-independent."""
    model, params = model_and_params
    rs = np.random.RandomState(17)
    uniq = np.asarray(rs.randint(3, 500, (2, 6)), np.int32)
    mask = np.ones_like(uniq)
    G = 3
    gen = GenerationConfig(max_new_tokens=4, do_sample=False,
                           eos_token_id=-1)
    grouped = jax.jit(build_generate_fn(model, gen, group_size=G))
    flat = jax.jit(build_generate_fn(model, gen))
    out_g = grouped(params, jnp.asarray(uniq), jnp.asarray(mask),
                    jax.random.key(0))
    rep_ids = jnp.asarray(np.repeat(uniq, G, axis=0))
    rep_mask = jnp.asarray(np.repeat(mask, G, axis=0))
    out_f = flat(params, rep_ids, rep_mask, jax.random.key(0))
    np.testing.assert_array_equal(np.asarray(out_g["response_tokens"]),
                                  np.asarray(out_f["response_tokens"]))
    np.testing.assert_array_equal(np.asarray(out_g["sequences"]),
                                  np.asarray(out_f["sequences"]))
