"""KV page migration tests: a mid-decode request's committed pages
export as a ``MigrationTicket`` (one jitted gather), install on another
engine (one jitted scatter — compile counters pinned at 1 across every
further migration), and the request resumes bit-identically — greedy
AND explicitly-seeded sampled, COW-shared and cache-indexed pages
included, with correct refcounts and zero page leaks on both sides.
Exports refuse eviction holes (not-mid-decode, block-table drift) and
count them; a disaggregated 1-prefill + 2-decode fleet reproduces the
single engine's tokens exactly, including while the prefill member is
under chaos (handoffs are exactly-once: the journal entry moves between
supervisors atomically with the install)."""
import jax
import numpy as np
import pytest

from dla_tpu.serving import (
    TERMINAL_STATES,
    FleetConfig,
    FleetRouter,
    KVMigrator,
    MigrationConfig,
    MigrationError,
    SamplingParams,
    ServingConfig,
    ServingEngine,
    SupervisorConfig,
)

MAX_NEW = 6
PAGE = 4


def _setup(preset):
    from dla_tpu.generation.engine import GenerationConfig
    from dla_tpu.models.config import get_model_config
    from dla_tpu.models.transformer import Transformer
    model = Transformer(get_model_config(preset))
    params = model.init(jax.random.key(7))
    gen = GenerationConfig(max_new_tokens=MAX_NEW, do_sample=False,
                           eos_token_id=-1, pad_token_id=0)
    return model, params, gen


@pytest.fixture(scope="module")
def serve_setup():
    return _setup("tiny")


@pytest.fixture(scope="module", params=["tiny", "tiny-mla"])
def pool_setup(request):
    """``serve_setup`` over both kinds of cache row: keys and values of
    [KH, D] a token (dense attention) and one latent row of [1, r + rope]
    (latent attention). The pool, copy-on-write, export and import follow
    the model's ``cache_rows()``; these tests hold both to one behaviour."""
    return _setup(request.param)


def _engine(serve_setup, **cfg_kw):
    """One engine with the migration-test geometry; fault_plan="" (not
    None) pins it fault-free even when $DLA_FAULT_PLAN is set."""
    model, params, gen = serve_setup
    kw = dict(page_size=PAGE, num_pages=64, num_slots=2,
              max_model_len=32, prefill_chunk=PAGE,
              prefix_cache=True, fault_plan="")
    kw.update(cfg_kw)
    return ServingEngine(model, params, gen, ServingConfig(**kw))


def _run_to(eng, rid, n_generated):
    """Step until the request has streamed >= n_generated tokens —
    parked mid-decode, the only state a migration can export."""
    for _ in range(500):
        if len(eng.result(rid).generated) >= n_generated:
            return
        eng.step()
    raise AssertionError(f"request {rid} never reached "
                         f"{n_generated} generated tokens")


def _drain(eng):
    while eng.has_work():
        eng.step()


# ---------------------------------------------------------------------------
# config plumbing
# ---------------------------------------------------------------------------

def test_migration_config_validation():
    assert MigrationConfig.from_config(None).transport == "auto"
    assert MigrationConfig.from_config(
        {"enabled": True, "transport": "host"}).transport == "host"
    with pytest.raises(ValueError, match="transport"):
        MigrationConfig(transport="pigeon")
    with pytest.raises(ValueError, match="unknown migration"):
        MigrationConfig.from_config({"transports": "auto"})


def test_fleet_role_config_validation():
    cfg = FleetConfig(engines=3, roles=("prefill", "decode", "mixed"))
    assert cfg.role_for(0) == "prefill" and cfg.role_for(7) == "mixed"
    with pytest.raises(ValueError, match="every startup member"):
        FleetConfig(engines=3, roles=("prefill", "decode"))
    with pytest.raises(ValueError, match="drawn from"):
        FleetConfig(engines=2, roles=("prefill", "verifier"))
    with pytest.raises(ValueError, match="decode-capable"):
        FleetConfig(engines=2, roles=("prefill", "prefill"))
    with pytest.raises(ValueError, match="autoscale"):
        FleetConfig(engines=2, roles=("prefill", "decode"),
                    autoscale=True, max_engines=4)
    with pytest.raises(ValueError, match="migration_transport"):
        FleetConfig(migration_transport="carrier")
    with pytest.raises(ValueError, match="max_handoff_retries"):
        FleetConfig(max_handoff_retries=0)
    # list from YAML coerces to tuple
    cfg = FleetConfig.from_config(
        {"engines": 2, "roles": ["prefill", "decode"]})
    assert cfg.roles == ("prefill", "decode")


def test_decode_role_gates_submit(serve_setup):
    eng = _engine(serve_setup, role="decode")
    with pytest.raises(RuntimeError, match="handoff-only"):
        eng.submit([3, 5, 7, 2], MAX_NEW)
    eng.close()
    with pytest.raises(ValueError, match="role"):
        _engine(serve_setup, role="verifier")


# ---------------------------------------------------------------------------
# ticket round-trip: bit-identity, refcounts, compile pinning
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("sampling", [
    None,
    SamplingParams(temperature=0.8, top_k=20, seed=1234),
], ids=["greedy", "seeded-sampled"])
def test_migrate_mid_decode_resumes_bit_identical(pool_setup, sampling):
    """Export after 2 streamed tokens, install on a fresh decode-role
    engine, finish there: the merged stream equals the single-engine
    run exactly — the scatter restored the exact committed KV columns
    and the ``fold_in(seed, k)`` sampling stream is engine-independent."""
    prompt = [3, 5, 7, 2, 9, 4, 6, 8, 11, 13]
    ref = _engine(pool_setup)
    rid = ref.submit(prompt, MAX_NEW, sampling=sampling)
    _drain(ref)
    want = list(ref.result(rid).generated)
    assert len(want) == MAX_NEW
    ref.close()

    src = _engine(pool_setup)
    dst = _engine(pool_setup, role="decode")
    rid = src.submit(prompt, MAX_NEW, sampling=sampling)
    _run_to(src, rid, 2)
    streamed = list(src.result(rid).generated)

    mig = KVMigrator(MigrationConfig())
    moved = mig.migrate(src, rid, dst)
    # exactly-once: the source forgot the request, the target owns it
    assert rid not in src._results
    assert dst.result(rid) is moved
    assert list(moved.generated) == streamed     # nothing re-emitted
    _drain(dst)
    got = list(dst.result(rid).generated)
    assert got == want
    assert src._mig_stats["migrations"] == 0      # source only exports
    assert dst._mig_stats["migrations"] == 1
    assert dst._mig_stats["migrated_pages"] > 0
    # nothing leaked on either side
    _drain(src)
    src.scheduler.assert_consistent()
    dst.scheduler.assert_consistent()
    assert src.cache.allocator.used_count == 0
    assert dst.cache.allocator.used_count == 0
    src.close()
    dst.close()


def test_migrate_cow_shared_pages_keeps_refcounts(pool_setup):
    """Two same-prompt requests share prefix pages on the source (COW
    via the prefix cache). Migrating one must not disturb the stayer:
    export is read-only, release decrefs only the mover's references,
    and the target registers its fresh copies into its own cache at
    refcount 1 + indexed."""
    prompt = [3, 5, 7, 2, 9, 4, 6, 8]           # 2 full pages
    src = _engine(pool_setup)
    dst = _engine(pool_setup, role="decode")
    warm = src.submit(prompt, MAX_NEW)           # registers the prefix
    _drain(src)
    del warm
    rid_a = src.submit(prompt, MAX_NEW)          # both alias the cached
    rid_b = src.submit(prompt, MAX_NEW)          # prompt pages
    _run_to(src, rid_a, 2)
    req_a, req_b = src.result(rid_a), src.result(rid_b)
    shared = set(req_a.pages) & set(req_b.pages)
    assert shared, "prefix cache should COW-share the prompt pages"
    before = {p: src.cache.allocator.refcount(p) for p in shared}

    moved = KVMigrator(MigrationConfig()).migrate(src, rid_a, dst)
    # stayer's shared pages lost exactly the mover's reference
    for p in shared:
        assert src.cache.allocator.refcount(p) == before[p] - 1
    src.scheduler.assert_consistent()
    # target owns fresh pages, refcount 1, committed ones cache-indexed
    committed = len(moved.prefix_tokens) - 1
    n_full = committed // PAGE
    for i, p in enumerate(moved.pages[:n_full]):
        assert dst.cache.allocator.refcount(p) == 1
        assert dst.prefix_cache.is_indexed(p)
    dst.scheduler.assert_consistent()

    _drain(src)
    _drain(dst)
    assert list(dst.result(rid_a).generated) \
        == list(src.result(rid_b).generated)    # same prompt, same tokens
    assert src.cache.allocator.used_count == 0
    assert dst.cache.allocator.used_count == 0
    src.close()
    dst.close()


def test_export_refuses_eviction_holes_and_counts(serve_setup):
    """A request that is not mid-decode (finished, queued, or evicted
    back to WAITING) has no committed-KV contract to export — the
    refusal is an error to the caller and a counter on the engine."""
    src = _engine(serve_setup)
    dst = _engine(serve_setup, role="decode")
    mig = KVMigrator(MigrationConfig())
    rid = src.submit([3, 5, 7, 2, 9], MAX_NEW)
    _drain(src)                                  # FINISHED: a hole
    with pytest.raises(MigrationError, match="mid-decode"):
        mig.migrate(src, rid, dst)
    with pytest.raises(MigrationError, match="unknown"):
        mig.export_ticket(src, 10 ** 9)
    assert src._mig_stats["failed_migrations"] == 2
    src.step()                                   # idle step mirrors
    snap = src.metrics.snapshot()
    assert snap["serving/migration/failed_migrations"] == 2
    assert snap["serving/migration/migrations"] == 0
    src.close()
    dst.close()


def test_import_and_export_compile_exactly_once(pool_setup):
    """The gather/scatter pair is fixed-shape (pad page ids route to
    the trash page): migrating requests of different lengths must not
    recompile either side."""
    src = _engine(pool_setup)
    dst = _engine(pool_setup, role="decode")
    mig = KVMigrator(MigrationConfig())
    for i, plen in enumerate((5, 9, 13)):        # 2, 3, 4 pages committed
        prompt = [3 + i] * plen
        rid = src.submit(prompt, MAX_NEW)
        _run_to(src, rid, 2)
        mig.migrate(src, rid, dst)
        assert src.export_compiles == 1
        assert dst.import_compiles == 1
        _drain(dst)                              # free the decode slot
    _drain(src)
    assert dst._mig_stats["migrations"] == 3
    assert src.cache.allocator.used_count == 0
    assert dst.cache.allocator.used_count == 0
    src.close()
    dst.close()


def test_host_transport_bounces_and_counts_bytes(pool_setup):
    src = _engine(pool_setup)
    dst = _engine(pool_setup, role="decode")
    rid = src.submit([1, 2, 3, 4, 5, 6, 7, 8], MAX_NEW)
    _run_to(src, rid, 2)
    KVMigrator(MigrationConfig("host")).migrate(src, rid, dst)
    _drain(dst)
    assert dst._mig_stats["host_bounce_bytes"] > 0
    snap = dst.metrics.snapshot()
    assert snap["serving/migration/host_bounce_bytes"] > 0
    src.close()
    dst.close()


# ---------------------------------------------------------------------------
# restore fast path: alias cached pages instead of re-prefilling
# ---------------------------------------------------------------------------

def test_restore_aliases_cached_pages_without_prefill(pool_setup):
    """When the prefix cache holds EVERY committed page, restore adopts
    straight into decode — zero prefill chunks — and still reproduces
    the original continuation bit-for-bit."""
    eng = _engine(pool_setup)
    prompt = [3, 5, 7, 2, 9, 4, 6, 8]            # page-aligned prompt
    rid = eng.submit(prompt, MAX_NEW)
    _drain(eng)
    full = list(eng.result(rid).generated)

    chunks_before = eng.metrics.prefill_chunks.value
    saved_before = eng.metrics.prefill_tokens_saved.value
    # committed = len(prompt) + 1 - 1 = 8: both pages sit in the cache
    restored = eng.restore(prompt, MAX_NEW, generated=full[:1],
                           arrival_time=0.0, rid=rid)
    assert restored.state.value == "decode"      # adopted, never queued
    _drain(eng)
    assert eng.metrics.prefill_chunks.value == chunks_before
    assert eng.metrics.prefill_tokens_saved.value \
        == saved_before + len(prompt)
    assert list(restored.generated) == full
    eng.scheduler.assert_consistent()
    assert eng.cache.allocator.used_count == 0
    eng.close()


# ---------------------------------------------------------------------------
# disaggregated fleet: bit-identity, exactly-once under chaos
# ---------------------------------------------------------------------------

ROLES = ("prefill", "decode", "decode")


def _prompts(n=12, seed=11):
    rs = np.random.RandomState(seed)
    return [[int(t) for t in rs.randint(3, 500, (10,))] for _ in range(n)]


def _serve(eng, prompts, sampling=None):
    params = sampling or [None] * len(prompts)
    rids = [eng.submit(p, MAX_NEW, sampling=s)
            for p, s in zip(prompts, params)]
    results = eng.run_until_drained(max_steps=5000)
    assert all(results[r].state in TERMINAL_STATES for r in rids)
    return [list(results[r].generated) for r in rids]


def _role_factory(serve_setup, **cfg_kw):
    def factory(slot):
        role = ROLES[slot] if slot < len(ROLES) else "mixed"
        return _engine(serve_setup, role=role, **cfg_kw)
    return factory


@pytest.mark.parametrize("sampled", [False, True],
                         ids=["greedy", "seeded-sampled"])
def test_disagg_fleet_bit_identical_to_single_engine(serve_setup,
                                                     sampled):
    """1 prefill + 2 decode members reproduce the single engine's
    tokens exactly; every request is handed off (the prefill member
    never decodes past its first token) and no member leaks a page."""
    prompts = _prompts()
    sampling = ([SamplingParams(temperature=0.8, top_k=20, seed=100 + i)
                 for i in range(len(prompts))] if sampled else None)
    single = _engine(serve_setup)
    want = _serve(single, prompts, sampling)
    single.close()

    router = FleetRouter(_role_factory(serve_setup),
                         FleetConfig(engines=3, roles=ROLES))
    got = _serve(router, prompts, sampling)
    migrations = sum(
        m.engine.metrics.snapshot()["serving/migration/migrations"]
        for m in router.members())
    for m in router.members():
        m.engine.scheduler.assert_consistent()
        assert m.engine.cache.allocator.used_count == 0
    router.close()
    assert got == want
    assert migrations == len(prompts)            # every request moved


def test_disagg_chaos_on_source_lands_requests_exactly_once(serve_setup):
    """The prefill member wedges and then dies mid-trace: supervised
    rebuild + replay re-runs only the requests whose journal entries
    still live on the source — already-handed-off requests moved with
    their entries, so every rid lands on exactly one member, nothing is
    lost, and the merged output still equals the fault-free fleet."""
    prompts = _prompts()
    sup_cfg = SupervisorConfig(watchdog_timeout_s=0.05,
                               watchdog_poll_s=0.01, max_restarts=3)
    clean_factory = _role_factory(serve_setup)

    clean = FleetRouter(clean_factory, FleetConfig(engines=3, roles=ROLES),
                        supervisor=sup_cfg)
    want = _serve(clean, prompts)
    clean.close()

    chaos_engine = _role_factory(
        serve_setup,
        fault_plan="engine_step=2:wedge:0.3;engine_step=4:device_error")

    def chaos_factory(slot):
        return chaos_engine(slot) if slot == 0 else clean_factory(slot)

    router = FleetRouter(chaos_factory, FleetConfig(engines=3, roles=ROLES),
                         supervisor=sup_cfg)
    rids = [router.submit(p, MAX_NEW) for p in prompts]
    results = router.run_until_drained(max_steps=5000)
    restarts = [m.sup.restarts for m in router.members()]
    # exactly-once: each rid's journal entry lives on exactly one member
    for rid in rids:
        holders = [m.slot for m in router.members()
                   if rid in m.sup.journal]
        assert len(holders) == 1, (rid, holders)
    got = [list(results[r].generated) for r in rids]
    lost = [r for r in rids if results[r].state not in TERMINAL_STATES]
    for m in router.members():
        assert m.engine.cache.allocator.used_count == 0
    router.close()
    assert lost == []
    assert restarts[0] >= 1 and restarts[1:] == [0, 0]
    assert got == want


def test_handoff_retry_bound_pins_requests_locally(serve_setup,
                                                   monkeypatch):
    """Every install refused: after ``max_handoff_retries`` passes the
    router stops re-offering each request (no unbounded refuse/re-insert
    cycle), ticks ``serving/migration/failed_handoffs`` once per
    request, and the requests finish decoding on their prefill member —
    the engine is decode-capable, the role is router policy — with
    tokens still equal to the single-engine run."""
    prompts = _prompts(n=4, seed=17)
    single = _engine(serve_setup)
    want = _serve(single, prompts)
    single.close()

    router = FleetRouter(_role_factory(serve_setup),
                         FleetConfig(engines=3, roles=ROLES,
                                     max_handoff_retries=2))

    def refuse(dst_engine, ticket):
        raise MigrationError("injected: sink refuses every install")

    monkeypatch.setattr(router.migrator, "install", refuse)
    got = _serve(router, prompts)
    assert got == want                   # placement-independent tokens
    assert router.metrics.failed_handoffs.value == len(prompts)
    migrations = sum(
        m.engine.metrics.snapshot()["serving/migration/migrations"]
        for m in router.members())
    assert migrations == 0               # nothing ever moved
    # bookkeeping retired once the pinned requests finished
    assert not router._handoff_pinned and not router._handoff_fails
    for m in router.members():
        m.engine.scheduler.assert_consistent()
        assert m.engine.cache.allocator.used_count == 0
    router.close()


def test_scale_down_migrates_running_work_zero_loss(serve_setup):
    """Retiring a mixed member mid-burst ships its in-flight decodes to
    the surviving member as KV tickets (no re-prefill) and nothing is
    lost."""
    model_prompts = _prompts(n=6)

    def factory(slot):
        return _engine(serve_setup)
    single = factory(0)
    want = _serve(single, model_prompts)
    single.close()

    router = FleetRouter(factory, FleetConfig(engines=2))
    rids = [router.submit(p, MAX_NEW) for p in model_prompts]
    for _ in range(3):                           # some requests mid-decode
        router.step()
    victim = next(m for m in router.members()
                  if m.engine.scheduler.running)
    router.scale_down(victim)
    results = router.run_until_drained(max_steps=5000)
    survivor = router.members()[0]
    migrated = survivor.engine.metrics.snapshot()[
        "serving/migration/migrations"]
    router.close()
    assert all(results[r].state in TERMINAL_STATES for r in rids)
    assert [list(results[r].generated) for r in rids] == want
    assert migrated > 0                          # running work moved as KV
