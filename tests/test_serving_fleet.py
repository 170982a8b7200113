"""Fleet-router tests: cache-aware placement (peek + sticky-prefix
affinity) keeps a routed N-engine fleet BIT-IDENTICAL to a single
engine on the same trace — greedy and explicitly-seeded sampled, and
with one member under chaos — while scale-down drains through the
existing draining contract with zero lost requests (queued work
rebalanced to peers with rid/sampling state intact), the autoscaler
grows and shrinks the fleet on the pressure signal, capped drains shed
stragglers to a terminal state, and ``serving/fleet/*`` counters live
in the router's registry so member rebuilds never reset them."""
import jax
import numpy as np
import pytest

from dla_tpu.serving import (
    TERMINAL_STATES,
    FleetConfig,
    FleetRouter,
    RequestState,
    SamplingParams,
    ServingConfig,
    ServingEngine,
    SupervisorConfig,
)

MAX_NEW = 4
FAMILIES = 4
PER_FAMILY = 6
PAGE = 4


@pytest.fixture(scope="module")
def serve_setup():
    from dla_tpu.generation.engine import GenerationConfig
    from dla_tpu.models.config import get_model_config
    from dla_tpu.models.transformer import Transformer
    cfg = get_model_config("tiny")
    model = Transformer(cfg)
    params = model.init(jax.random.key(7))
    gen = GenerationConfig(max_new_tokens=MAX_NEW, do_sample=False,
                           eos_token_id=-1, pad_token_id=0)
    return model, params, gen


def _factory(serve_setup, **cfg_kw):
    """-> factory(slot) for FleetRouter; also builds the single-engine
    baseline via factory(0). fault_plan="" (not None) pins members
    fault-free even when $DLA_FAULT_PLAN is set in the environment."""
    model, params, gen = serve_setup
    kw = dict(page_size=PAGE, num_pages=64, num_slots=2,
              max_model_len=32, prefill_chunk=PAGE,
              prefix_cache=True, fault_plan="")
    kw.update(cfg_kw)

    def factory(slot):
        return ServingEngine(model, params, gen, ServingConfig(**kw))
    return factory


def _shared_prefix_prompts(families=FAMILIES, per_family=PER_FAMILY,
                           seed=11):
    # uniform length (one full page head + 2-token suffix): a single
    # prefill bucket, so chaos-arm rebuild compiles never land inside
    # a watchdog window
    rs = np.random.RandomState(seed)
    prompts = []
    for _ in range(families):
        head = [int(t) for t in rs.randint(3, 500, (PAGE,))]
        for _ in range(per_family):
            prompts.append(head + [int(t)
                                   for t in rs.randint(3, 500, (2,))])
    return prompts


def _serve(eng, prompts, sampling=None):
    """Outputs of THIS call in submission order; engine-shaped: works
    identically on a bare ServingEngine and a FleetRouter."""
    params = sampling or [None] * len(prompts)
    rids = [eng.submit(p, MAX_NEW, sampling=s)
            for p, s in zip(prompts, params)]
    results = eng.run_until_drained(max_steps=5000)
    assert all(results[r].state in TERMINAL_STATES for r in rids)
    return [list(results[r].generated) for r in rids]


# ---------------------------------------------------------------------------
# config plumbing
# ---------------------------------------------------------------------------

def test_fleet_config_from_config_and_validation():
    assert FleetConfig.from_config(None) is None
    assert FleetConfig.from_config({"enabled": False}) is None
    cfg = FleetConfig.from_config({"engines": 3, "placement": "random"})
    assert cfg.engines == 3 and cfg.placement == "random"
    with pytest.raises(ValueError, match="unknown fleet config"):
        FleetConfig.from_config({"engine_count": 3})
    with pytest.raises(ValueError, match="placement"):
        FleetConfig(placement="sticky")
    with pytest.raises(ValueError):
        FleetConfig(engines=5, max_engines=4)


# ---------------------------------------------------------------------------
# placement-independence: the core bit-identity guarantee
# ---------------------------------------------------------------------------

def test_fleet_greedy_bit_identical_to_single_engine(serve_setup):
    """A routed N=4 fleet emits exactly the single engine's tokens on
    the same shared-prefix trace, and placement actually engages: most
    requests route by prefix (peek hit or sticky affinity), spread
    over more than one member."""
    factory = _factory(serve_setup)
    prompts = _shared_prefix_prompts()

    single = factory(0)
    want = _serve(single, prompts)
    single.close()

    router = FleetRouter(factory, FleetConfig(engines=4))
    got = _serve(router, prompts)
    snap = router.fleet_snapshot()
    placed_slots = {m.slot for m in router._placement.values()}
    router.close()

    assert got == want
    assert snap["serving/fleet/engines_active"] == 4
    assert (snap["serving/fleet/routed_by_prefix"]
            + snap["serving/fleet/routed_by_load"]) == len(prompts)
    # sticky affinity must dominate a burst-submitted shared-prefix mix
    assert snap["serving/fleet/routed_by_prefix"] > len(prompts) / 2
    assert len(placed_slots) > 1          # it is actually a fleet


def test_fleet_seeded_sampling_bit_identical(serve_setup):
    """Sampled outputs are placement-independent too: token k is a pure
    function of (seed, k), so explicit per-request seeds give the same
    streams no matter which member decodes them."""
    factory = _factory(serve_setup)
    prompts = _shared_prefix_prompts(families=2, per_family=4)
    sampling = [SamplingParams(seed=1000 + i, temperature=0.8)
                for i in range(len(prompts))]

    single = factory(0)
    want = _serve(single, prompts, sampling)
    single.close()

    router = FleetRouter(factory, FleetConfig(engines=4))
    got = _serve(router, prompts, sampling)
    router.close()

    assert got == want


def test_fleet_random_placement_same_outputs(serve_setup):
    """The control arm: random placement scatters families (worse hit
    rate) but the emitted tokens are still identical — proof the router
    never lets placement leak into results."""
    factory = _factory(serve_setup)
    prompts = _shared_prefix_prompts(families=2, per_family=4)

    single = factory(0)
    want = _serve(single, prompts)
    single.close()

    router = FleetRouter(factory, FleetConfig(engines=3,
                                              placement="random"))
    got = _serve(router, prompts)
    router.close()
    assert got == want


# ---------------------------------------------------------------------------
# chaos: one member faulting must not change fleet output
# ---------------------------------------------------------------------------

def test_fleet_single_member_chaos_bit_identical_zero_loss(serve_setup):
    """Member 0 wedges (watchdog restart) and then raises a device
    error (supervised rebuild + replay); the router keeps the rest of
    the fleet serving. Every request reaches a terminal state and the
    outputs equal the fault-free fleet run — and the fleet counters,
    living in the router's registry, survive the member rebuilds."""
    clean_factory = _factory(serve_setup)
    chaos_engine = _factory(
        serve_setup,
        fault_plan="engine_step=2:wedge:0.3;engine_step=4:device_error")

    def chaos_factory(slot):
        return chaos_engine(slot) if slot == 0 else clean_factory(slot)

    sup_cfg = SupervisorConfig(watchdog_timeout_s=0.05,
                               watchdog_poll_s=0.01, max_restarts=3)
    prompts = _shared_prefix_prompts()
    fleet_cfg = FleetConfig(engines=3)

    clean = FleetRouter(clean_factory, fleet_cfg, supervisor=sup_cfg)
    want = _serve(clean, prompts)
    clean.close()

    router = FleetRouter(chaos_factory, fleet_cfg, supervisor=sup_cfg)
    got = _serve(router, prompts)
    snap = router.fleet_snapshot()
    restarts = [m.sup.restarts for m in router.members()]
    router.close()

    assert got == want
    assert restarts[0] >= 1 and restarts[1:] == [0, 0]
    # monotone across rebuilds: routing counters were incremented before
    # the faults fired and must still account for every admission
    assert (snap["serving/fleet/routed_by_prefix"]
            + snap["serving/fleet/routed_by_load"]) == len(prompts)
    assert snap["serving/fleet/engines_active"] == 3


# ---------------------------------------------------------------------------
# scaling: zero-loss drain, rebalance, autoscaler
# ---------------------------------------------------------------------------

def test_fleet_scale_down_rebalances_queued_zero_loss(serve_setup):
    """Retiring a member mid-burst moves its queued requests to peers
    (rid and streamed state preserved) and runs its in-flight work to
    completion: nothing is lost, outputs still match a single engine."""
    factory = _factory(serve_setup)
    prompts = _shared_prefix_prompts(families=2, per_family=6)

    single = factory(0)
    want = _serve(single, prompts)
    single.close()

    router = FleetRouter(factory, FleetConfig(engines=2))
    rids = [router.submit(p, MAX_NEW) for p in prompts]
    victim = router.members()[0]
    router.scale_down(victim)
    with pytest.raises(RuntimeError, match="last fleet member"):
        router.scale_down(router.members()[1])
    results = router.run_until_drained(max_steps=5000)
    snap = router.fleet_snapshot()
    got = [list(results[r].generated) for r in rids]
    remaining = router.members()
    router.close()

    assert all(results[r].state == RequestState.FINISHED for r in rids)
    assert got == want
    assert snap["serving/fleet/scale_downs"] == 1
    assert snap["serving/fleet/rebalanced_requests"] > 0
    assert snap["serving/fleet/engines_active"] == 1
    assert [m.slot for m in remaining] == [1]   # victim reclaimed


def test_fleet_autoscaler_grows_under_pressure_shrinks_idle(serve_setup):
    """Queue pressure above the threshold for ``patience`` checks adds
    members up to max_engines; a drained, idle fleet falls back to
    min_engines through the zero-loss retire path."""
    factory = _factory(serve_setup)
    cfg = FleetConfig(engines=1, min_engines=1, max_engines=3,
                      autoscale=True, scale_up_pressure=0.3,
                      scale_down_pressure=0.05, patience=2,
                      check_every=1)
    router = FleetRouter(factory, cfg)
    prompts = _shared_prefix_prompts(families=3, per_family=6)
    rids = [router.submit(p, MAX_NEW) for p in prompts]
    results = router.run_until_drained(max_steps=5000)
    snap_up = router.fleet_snapshot()
    assert all(results[r].state in TERMINAL_STATES for r in rids)
    # the fleet grew under the burst (it may already have begun
    # shrinking during the low-pressure tail of the drain — that is
    # the autoscaler working, not a miss)
    assert snap_up["serving/fleet/scale_ups"] >= 1

    for _ in range(60):                   # idle ticks: pressure ~ 0
        router.step()
        if router.num_engines == 1:
            break
    snap_down = router.fleet_snapshot()
    router.close()
    assert snap_down["serving/fleet/engines_active"] == 1
    assert snap_down["serving/fleet/scale_downs"] >= 1


def test_fleet_draining_rejects_admissions_then_drains(serve_setup):
    factory = _factory(serve_setup)
    router = FleetRouter(factory, FleetConfig(engines=2))
    prompts = _shared_prefix_prompts(families=1, per_family=3)
    rids = [router.submit(p, MAX_NEW) for p in prompts]
    router.begin_drain()
    with pytest.raises(RuntimeError, match="draining"):
        router.submit(prompts[0], MAX_NEW)
    results = router.drain(max_steps=5000)
    router.close()
    assert all(results[r].state == RequestState.FINISHED for r in rids)


# ---------------------------------------------------------------------------
# capped drain: stragglers shed, never stranded
# ---------------------------------------------------------------------------

def test_drain_on_cap_shed_resolves_stragglers(serve_setup):
    """run_until_drained(on_cap="shed") converts the old raise into a
    recorded disposition: every straggler reaches SHED, pages are
    released, and the flight recorder keeps the evidence."""
    eng = _factory(serve_setup)(0)
    prompts = _shared_prefix_prompts(families=1, per_family=4)
    rids = [eng.submit(p, MAX_NEW) for p in prompts]
    with pytest.raises(RuntimeError, match="did not drain"):
        eng.run_until_drained(max_steps=1)
    results = eng.run_until_drained(max_steps=1, on_cap="shed")
    assert all(results[r].state in TERMINAL_STATES for r in rids)
    assert any(results[r].state == RequestState.SHED for r in rids)
    assert eng.metrics.requests_shed.value > 0
    kinds = [e["kind"] for e in eng.recorder.events]
    assert "drain_cap" in kinds and "request_shed" in kinds
    eng.scheduler.assert_consistent()
    assert eng.cache.allocator.used_count == 0   # pages all released
    eng.close()


def test_fleet_drain_on_cap_shed(serve_setup):
    factory = _factory(serve_setup)
    router = FleetRouter(factory, FleetConfig(engines=2))
    prompts = _shared_prefix_prompts(families=2, per_family=3)
    rids = [router.submit(p, MAX_NEW) for p in prompts]
    results = router.run_until_drained(max_steps=1, on_cap="shed")
    router.close()
    assert all(results[r].state in TERMINAL_STATES for r in rids)
