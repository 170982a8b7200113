"""Serving subsystem tests: page allocator invariants, scheduler state
machine (admission, eviction-recompute, no leaks), and the
load-bearing e2e guarantees — paged decode is TOKEN-IDENTICAL to the
contiguous GenerationEngine path, and mid-decode arrivals never
recompile the decode step."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from dla_tpu.generation.engine import GenerationConfig, build_generate_fn
from dla_tpu.models.config import CacheArray, get_model_config
from dla_tpu.models.transformer import Transformer
from dla_tpu.serving import (
    PageAllocator,
    PagedKVCache,
    PageGeometry,
    Request,
    RequestState,
    Scheduler,
    SchedulerConfig,
    ServingConfig,
    ServingEngine,
)


# ---------------------------------------------------------------------------
# page allocator (pure host, no model)
# ---------------------------------------------------------------------------

def test_allocator_basic_alloc_free():
    a = PageAllocator(8)
    assert a.capacity == 7          # page 0 reserved
    pages = a.alloc(3)
    assert len(pages) == 3 and 0 not in pages
    assert a.used_count == 3 and a.free_count == 4
    a.free(pages)
    assert a.used_count == 0 and a.free_count == 7


def test_allocator_all_or_nothing_exhaustion():
    a = PageAllocator(5)            # capacity 4
    first = a.alloc(3)
    assert first is not None
    assert a.alloc(2) is None       # only 1 free: nothing handed out
    assert a.free_count == 1        # failed alloc left the pool untouched
    assert a.alloc(1) is not None
    assert a.alloc(1) is None
    assert not a.can_alloc(1)


def test_allocator_no_fragmentation_across_interleaving():
    """Fixed-size pages: any alloc/free interleaving keeps every free
    page usable (no external fragmentation)."""
    a = PageAllocator(9)            # capacity 8
    held = [a.alloc(2) for _ in range(4)]
    a.free(held[1])
    a.free(held[3])
    big = a.alloc(4)                # freed pages coalesce trivially
    assert big is not None and len(big) == 4
    assert a.free_count == 0


def test_allocator_double_free_and_trash_page():
    a = PageAllocator(4)
    pages = a.alloc(2)
    a.free(pages)
    with pytest.raises(ValueError):
        a.free(pages)               # double free
    with pytest.raises(ValueError):
        a.free([0])                 # trash page is never allocatable
    seen = set()
    while a.can_alloc(1):
        seen.update(a.alloc(1))
    assert 0 not in seen


# ---------------------------------------------------------------------------
# scheduler state machine (host-only: a model-free cache stand-in)
# ---------------------------------------------------------------------------

class _Cfg:
    num_layers = 1
    num_kv_heads = 1
    head_dim_ = 2


class _ModelStub:
    cfg = _Cfg()
    adtype = jnp.float32

    def cache_spec(self):       # keys and values of [KH, D] per token
        return (CacheArray("paged", 1, (1, 2), jnp.float32),) * 2


def _sched(page_size=4, num_pages=16, num_slots=2, pages_per_slot=4,
           **cfg_kw):
    geom = PageGeometry(page_size=page_size, num_pages=num_pages,
                        num_slots=num_slots, pages_per_slot=pages_per_slot)
    cache = PagedKVCache(_ModelStub(), geom)
    cfg = SchedulerConfig(prefill_chunk=page_size, **cfg_kw)
    return Scheduler(cache, cfg), cache


def test_scheduler_admission_binds_slot_and_pages():
    sched, cache = _sched()
    req = Request(prompt_tokens=[1, 2, 3], max_new_tokens=4)
    sched.submit(req)
    assert sched.admit_chunk_prefill() is req
    assert req.state is RequestState.PREFILL
    assert req.slot is not None
    # 3 tokens -> 1 prompt page + 1 decode reserve
    assert len(req.pages) == 2
    sched.activate(req)
    assert req.state is RequestState.DECODE
    sched.assert_consistent()
    sched.finish(req, "length")
    assert req.state is RequestState.FINISHED
    assert cache.allocator.used_count == 0
    assert len(sched.free_slots) == cache.geom.num_slots
    sched.assert_consistent()


def test_scheduler_rejects_oversized_and_empty():
    sched, _ = _sched()   # slot window = 16
    with pytest.raises(ValueError):
        sched.submit(Request(prompt_tokens=list(range(10)),
                             max_new_tokens=10))
    with pytest.raises(ValueError):
        sched.submit(Request(prompt_tokens=[], max_new_tokens=4))


def test_scheduler_eviction_on_oom_requeues_and_frees():
    """Page exhaustion mid-decode evicts the YOUNGEST running request:
    its pages return to the pool, it re-enters the queue head with its
    generated tokens intact (the recompute contract)."""
    # capacity 5: two requests at 2 pages each fit, growth doesn't
    sched, cache = _sched(num_pages=6, num_slots=2)
    old = Request(prompt_tokens=[1, 2, 3], max_new_tokens=8)
    young = Request(prompt_tokens=[4, 5, 6], max_new_tokens=8)
    sched.submit(old)
    sched.submit(young)
    for req in (old, young):
        assert sched.admit_chunk_prefill() is req
        cache.begin_decode(req.slot, 3, 7)
        sched.activate(req)
    sched.assert_consistent()
    old_slot = old.slot
    # drive the old request's length to column 12: page index 3, two
    # pages past its allocation — the pool has only 1 spare, so the
    # second growth must evict `young`
    for _ in range(9):
        cache.advance_slot(old_slot, 9)
    evicted = sched.ensure_decode_pages()
    assert evicted == [young]
    assert young.state is RequestState.WAITING
    assert young.evictions == 1
    assert young.slot is None and young.pages == []
    assert sched.queue[0] is young           # requeued at the FRONT
    assert young.prefix_tokens == [4, 5, 6]  # prompt kept for recompute
    assert old.state is RequestState.DECODE  # survivor kept running
    sched.assert_consistent()
    sched.finish(old, "length")
    assert cache.allocator.used_count == 0   # no page leaked through OOM
    sched.assert_consistent()


# ---------------------------------------------------------------------------
# e2e on the tiny model
# ---------------------------------------------------------------------------

MAX_NEW = 5


@pytest.fixture(scope="module")
def model_and_params():
    cfg = get_model_config("tiny")
    model = Transformer(cfg)
    return model, model.init(jax.random.key(7))


@pytest.fixture(scope="module")
def reference_tokens(model_and_params):
    """Greedy reference per prompt from the contiguous fixed-batch
    engine — the serving path must reproduce these exactly."""
    model, params = model_and_params
    rs = np.random.RandomState(3)
    prompts = [list(rs.randint(3, 500, (n,))) for n in (6, 4, 9, 5)]
    width = max(len(p) for p in prompts)
    ids = np.zeros((len(prompts), width), np.int32)
    mask = np.zeros_like(ids)
    for i, p in enumerate(prompts):
        ids[i, :len(p)] = p
        mask[i, :len(p)] = 1
    gen = GenerationConfig(max_new_tokens=MAX_NEW, do_sample=False,
                           eos_token_id=2, pad_token_id=0)
    fn = jax.jit(build_generate_fn(model, gen))
    out = fn(params, jnp.asarray(ids), jnp.asarray(mask), jax.random.key(0))
    resp = np.asarray(out["response_tokens"])
    rmask = np.asarray(out["response_mask"])
    ref = [[int(t) for t, m in zip(resp[i], rmask[i]) if m]
           for i in range(len(prompts))]
    return prompts, ref, gen


def _drain(eng):
    results = eng.run_until_drained(max_steps=500)
    eng.scheduler.assert_consistent()
    return results


def test_serving_matches_contiguous_engine(model_and_params,
                                           reference_tokens):
    """THE parity pin: block-paged decode through gather/scatter and the
    static-shape slot batch produces byte-for-byte the tokens of the
    contiguous GenerationEngine decode on the same model."""
    model, params = model_and_params
    prompts, ref, gen = reference_tokens
    eng = ServingEngine(model, params, gen,
                        ServingConfig(page_size=4, num_pages=32,
                                      num_slots=3, max_model_len=32))
    rids = [eng.submit(p, MAX_NEW) for p in prompts]
    results = _drain(eng)
    for i, rid in enumerate(rids):
        assert results[rid].generated == ref[i], f"prompt {i} diverged"
        assert results[rid].state is RequestState.FINISHED
    assert eng.cache.allocator.used_count == 0, "pages leaked after drain"


def test_serving_no_recompile_and_no_leaks_across_arrivals(
        model_and_params, reference_tokens):
    """Mid-decode arrivals land in freed slots without retracing the
    decode step (static shapes), and the page pool drains to empty."""
    model, params = model_and_params
    prompts, ref, gen = reference_tokens
    eng = ServingEngine(model, params, gen,
                        ServingConfig(page_size=4, num_pages=32,
                                      num_slots=2, max_model_len=32))
    # wave 1: two requests saturate both slots
    rids = {eng.submit(p, MAX_NEW): i for i, p in enumerate(prompts[:2])}
    for _ in range(2):
        eng.step()
    assert eng.scheduler.active_count == 2
    # wave 2 arrives mid-decode; admitted only as slots free up
    for i, p in enumerate(prompts[2:], start=2):
        rids[eng.submit(p, MAX_NEW)] = i
        eng.step()
        eng.scheduler.assert_consistent()
    results = _drain(eng)
    for rid, i in rids.items():
        assert results[rid].generated == ref[i], f"prompt {i} diverged"
    assert eng.decode_compiles == 1, (
        f"decode step retraced {eng.decode_compiles}x — static-shape "
        "guarantee broken")
    assert eng.cache.allocator.used_count == 0
    assert len(eng.scheduler.free_slots) == 2
    # one chunk shape: prefill compiles once, whatever the prompt lengths
    assert len({len(p) for p in prompts}) > 1
    assert eng.prefill_chunk_compiles == 1


@pytest.mark.parametrize("preset", ["tiny", "tiny-mla"])
def test_serving_eviction_recomputes_identically(preset, model_and_params):
    """A pool sized to force mid-decode preemption: the evicted request
    re-prefills prompt+generated and still lands on the reference
    tokens (greedy recompute is deterministic). Over both kinds of cache
    row: keys and values (``tiny``) and one latent row (``tiny-mla``)."""
    if preset == "tiny":
        model, params = model_and_params
    else:
        model = Transformer(get_model_config(preset))
        params = model.init(jax.random.key(7))
    rs = np.random.RandomState(11)
    use = [list(rs.randint(3, 500, (4,))) for _ in range(2)]
    gen = GenerationConfig(max_new_tokens=MAX_NEW, do_sample=False,
                           eos_token_id=2, pad_token_id=0)

    def serve(num_pages):
        eng = ServingEngine(model, params, gen,
                            ServingConfig(page_size=2, num_pages=num_pages,
                                          num_slots=2, max_model_len=12))
        rids = [eng.submit(p, MAX_NEW) for p in use]
        results = _drain(eng)
        return eng, [results[rid] for rid in rids]

    # the reference: the same engine with room for both requests, and for
    # the dense model also the contiguous fixed-batch engine (latent
    # attention decodes against the paged pool only)
    roomy, unpressured = serve(num_pages=32)
    assert roomy.metrics.preemptions.value == 0
    want = [list(req.generated) for req in unpressured]
    if preset == "tiny":
        fn = jax.jit(build_generate_fn(model, gen))
        ids = np.asarray(use, np.int32)
        out = fn(params, jnp.asarray(ids), jnp.ones_like(jnp.asarray(ids)),
                 jax.random.key(0))
        resp = np.asarray(out["response_tokens"])
        rmask = np.asarray(out["response_mask"])
        assert want == [[int(t) for t, m in zip(resp[i], rmask[i]) if m]
                        for i in range(len(use))]
    # capacity 7 pages: both 4-token prompts admit at 3 pages (2 prompt
    # + reserve) but cannot BOTH grow to 9 tokens (5 pages each) ->
    # someone gets preempted mid-decode
    eng, results = serve(num_pages=8)
    assert eng.metrics.preemptions.value >= 1, (
        "config was meant to force at least one preemption")
    for req, expect in zip(results, want):
        assert req.generated == expect, (
            f"eviction recompute diverged (evictions={req.evictions})")
    assert eng.cache.allocator.used_count == 0
    eng.scheduler.assert_consistent()


def test_serving_metrics_surface(model_and_params, reference_tokens):
    model, params = model_and_params
    prompts, _, gen = reference_tokens
    eng = ServingEngine(model, params, gen,
                        ServingConfig(page_size=4, num_pages=32,
                                      num_slots=2, max_model_len=32))
    for p in prompts[:2]:
        eng.submit(p, MAX_NEW)
    _drain(eng)
    snap = eng.metrics.snapshot()
    assert snap["serving/requests_submitted"] == 2.0
    assert snap["serving/requests_finished"] == 2.0
    assert snap["serving/tokens_generated"] == 2.0 * MAX_NEW
    assert snap["serving/ttft_ms_count"] == 2.0
    assert snap["serving/itl_ms_count"] > 0
    assert snap["serving/ttft_ms_p50"] >= 0.0
    assert snap["serving/page_occupancy_peak"] > 0.0
    assert snap["serving/page_occupancy"] == 0.0   # drained
    # a dense model's chunk program gathers: no block walk to count
    assert snap["serving/prefill/chunks"] >= 2.0
    assert snap["serving/prefill/attn_read_tokens"] == 0.0
    assert snap["serving/prefill/attn_window_tokens"] == 0.0


@pytest.mark.parametrize("page_size, max_model_len, want", [
    (4, 32, 32),        # the whole window: a test prompt is one chunk
    (2, 64, 32)])       # sixteen pages
def test_unset_prefill_chunk_is_worked_out_from_the_geometry(
        model_and_params, page_size, max_model_len, want):
    model, params = model_and_params
    gen = GenerationConfig(max_new_tokens=2, do_sample=False,
                           eos_token_id=2, pad_token_id=0)
    cfg = ServingConfig(page_size=page_size, num_pages=40, num_slots=2,
                        max_model_len=max_model_len)
    assert cfg.prefill_chunk is None
    eng = ServingEngine(model, params, gen, cfg)
    assert eng.cfg.prefill_chunk == want == min(max_model_len,
                                                16 * page_size)
    assert eng.scheduler.cfg.prefill_chunk == want
    rid = eng.submit(list(range(3, 12)), 2)
    _drain(eng)
    assert len(eng.result(rid).generated) == 2
    assert eng.metrics.prefill_chunks.value == 1    # 9 tokens, one chunk
    assert eng.prefill_chunk_compiles == 1


def test_burst_beyond_the_slots_drains_in_arrival_order(model_and_params,
                                                        reference_tokens):
    """Four prompts of four lengths at once on a default-config engine
    with two slots: admission is strict FCFS (first tokens come out in
    the order of arrival, whatever the lengths) and every stream is the
    contiguous engine's."""
    model, params = model_and_params
    prompts, ref, gen = reference_tokens
    eng = ServingEngine(model, params, gen,
                        ServingConfig(page_size=4, num_pages=32,
                                      num_slots=2, max_model_len=32))
    rids = [eng.submit(p, MAX_NEW) for p in prompts]
    first_seen = []
    for _ in range(500):
        if not eng.has_work():
            break
        for rid, _tok in eng.step():
            if rid not in first_seen:
                first_seen.append(rid)
        eng.scheduler.assert_consistent()
    assert first_seen == rids
    admitted = [eng.result(r).admitted_time for r in rids]
    assert admitted == sorted(admitted)
    assert [eng.result(r).generated for r in rids] == ref
    assert eng.prefill_chunk_compiles == eng.decode_compiles == 1


def test_submit_counts_the_pages_admission_takes(model_and_params):
    """A 20-token worst case takes 5 pages and the reserve, which a pool
    of 7 holds (the power-of-two bucket of the removed lane, 32 tokens,
    would have asked for 9 and refused it); 28 tokens take 8 and are
    still refused. ``submit`` and admission share one count."""
    model, params = model_and_params
    gen = GenerationConfig(max_new_tokens=3, do_sample=False,
                           eos_token_id=-1, pad_token_id=0)
    eng = ServingEngine(model, params, gen,
                        ServingConfig(page_size=4, num_pages=8,
                                      num_slots=1, max_model_len=64))
    assert eng.cache.allocator.capacity == 7
    assert eng.scheduler.admission_pages(20) == 6
    rid = eng.submit(list(range(3, 20)), 3)         # 17 + 3 = 20 tokens
    eng.step()
    assert len(eng.result(rid).pages) == eng.scheduler.admission_pages(17)
    _drain(eng)
    assert len(eng.result(rid).generated) == 3
    assert eng.scheduler.admission_pages(28) == 8
    with pytest.raises(ValueError, match="can never be served"):
        eng.submit(list(range(3, 28)), 3)           # 25 + 3 = 28 tokens
    # the block table's width caps the count: a window-filling request
    # starts without its reserve
    assert eng.scheduler.admission_pages(64) == 16


def test_serving_rejects_request_that_can_never_fit(model_and_params):
    model, params = model_and_params
    gen = GenerationConfig(max_new_tokens=4, do_sample=False,
                           eos_token_id=2, pad_token_id=0)
    # pool capacity (3 pages) below one slot's worst-case demand
    eng = ServingEngine(model, params, gen,
                        ServingConfig(page_size=4, num_pages=4,
                                      num_slots=1, max_model_len=32))
    with pytest.raises(ValueError):
        eng.submit(list(range(1, 20)), 8)


# ---------------------------------------------------------------------------
# per-request sampling + streamed logprobs
# ---------------------------------------------------------------------------

def test_serving_greedy_logprobs_match_teacher_forced_rescore(
        model_and_params, reference_tokens):
    """The chosen-token logprobs streamed during decode are log-softmax
    of the RAW logits (pre-temperature/filter): for greedy they must
    equal a teacher-forced re-score of the final sequence through the
    full forward pass."""
    model, params = model_and_params
    prompts, _, gen = reference_tokens
    eng = ServingEngine(model, params, gen,
                        ServingConfig(page_size=4, num_pages=32,
                                      num_slots=3, max_model_len=32))
    rids = [eng.submit(p, MAX_NEW) for p in prompts]
    results = _drain(eng)
    for i, rid in enumerate(rids):
        req = results[rid]
        assert len(req.generated_logprobs) == len(req.generated)
        seq = list(prompts[i]) + list(req.generated)
        logits = np.asarray(model.apply(
            params, jnp.asarray([seq], jnp.int32),
            jnp.ones((1, len(seq)), jnp.int32))[0], np.float64)
        lse = np.log(np.sum(np.exp(
            logits - logits.max(-1, keepdims=True)), -1)) \
            + logits.max(-1)
        for k, (tok, lp) in enumerate(zip(req.generated,
                                          req.generated_logprobs)):
            pos = len(prompts[i]) - 1 + k   # column scoring token k
            want = logits[pos, tok] - lse[pos]
            assert abs(lp - want) < 1e-4, (i, k, lp, want)


def test_serving_per_request_seed_determinism(model_and_params):
    """A request's sampled stream is a pure function of (seed, token
    index): identical across engines, across co-resident requests, and
    distinct for distinct seeds."""
    from dla_tpu.serving import SamplingParams
    model, params = model_and_params
    gen = GenerationConfig(max_new_tokens=MAX_NEW, do_sample=True,
                           temperature=0.9, top_p=0.9, top_k=8,
                           eos_token_id=2, pad_token_id=0)
    prompt = list(range(5, 13))
    sp = SamplingParams(temperature=0.9, top_p=0.9, top_k=8,
                        seed=77, do_sample=True)
    sp2 = SamplingParams(temperature=0.9, top_p=0.9, top_k=8,
                         seed=78, do_sample=True)
    streams = []
    for extra in (sp2, sp):     # engine 2 flips submission order
        eng = ServingEngine(model, params, gen,
                            ServingConfig(page_size=4, num_pages=32,
                                          num_slots=2, max_model_len=32))
        rid = eng.submit(prompt, MAX_NEW, sampling=sp)
        rid_x = eng.submit(prompt, MAX_NEW, sampling=extra)
        results = _drain(eng)
        streams.append((results[rid].generated,
                        results[rid].generated_logprobs,
                        results[rid_x].generated))
    (tok_a, lp_a, x_a), (tok_b, lp_b, x_b) = streams
    assert tok_a == tok_b                  # same seed, different engine
    np.testing.assert_allclose(lp_a, lp_b, atol=1e-5, rtol=0)
    assert x_b == tok_a                    # seed 77 again, other slot
    assert x_a != tok_a                    # seed 78 diverges
