"""Serving-resilience tests: the engine_step= fault grammar, admission
control + load shedding (token bucket, bounded queue, priority
displacement, SLO-aware shed pass), the degradation ladder, queued-
deadline expiry, the draining /healthz, and the Supervisor's
rebuild-and-replay guarantees — every submitted request reaches a
terminal state, greedy outputs are bit-identical to a fault-free run,
restarts are bounded by the circuit breaker, and compile counters stay
pinned at one per engine build."""
import urllib.error
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dla_tpu.models.config import CacheArray
from dla_tpu.resilience.faults import FaultPlan
from dla_tpu.serving import (
    TERMINAL_STATES,
    AdmissionController,
    DegradationLadder,
    PageAllocator,
    PagedKVCache,
    PageGeometry,
    Request,
    RequestState,
    Scheduler,
    SchedulerConfig,
    ServingConfig,
    ServingEngine,
    ShedConfig,
    Supervisor,
    SupervisorConfig,
)


# ---------------------------------------------------------------------------
# fault-plan grammar: the engine_step= site
# ---------------------------------------------------------------------------

def test_fault_plan_engine_step_grammar_and_sites():
    plan = FaultPlan.parse(
        "step=3:nan;engine_step=2:wedge:0.5;engine_step=5:burst=4;"
        "engine_step=7:device_error;engine_step=9:nan_logits")
    # sites are disjoint: a training-step query never consumes a
    # serving entry and vice versa
    assert plan.take("nan", 3, site="engine_step") is None
    assert plan.take("wedge", 2) is None          # default site="step"
    f = plan.take("wedge", 2, site="engine_step")
    assert f is not None and f.arg == 0.5
    f = plan.take("burst", 5, site="engine_step")
    assert f is not None and int(f.arg) == 4
    assert plan.take("nan", 3) is not None
    # spec() round-trips both sites
    spec = FaultPlan.parse(
        "engine_step=5:burst=4;step=1:io_error").spec()
    rt = FaultPlan.parse(spec)
    assert rt.take("burst", 5, site="engine_step") is not None
    assert rt.take("io_error", 1) is not None


def test_fault_plan_rejects_unknown_serving_kind():
    with pytest.raises(ValueError, match="engine_step"):
        FaultPlan.parse("engine_step=3:nan")      # training-only kind
    with pytest.raises(ValueError, match="unknown fault kind"):
        FaultPlan.parse("step=3:wedge")           # serving-only kind


def test_shed_config_from_config():
    assert ShedConfig.from_config(None) is None
    assert ShedConfig.from_config({"enabled": False}) is None
    cfg = ShedConfig.from_config({"max_queue_depth": 4, "rate": 2.0})
    assert cfg.max_queue_depth == 4 and cfg.rate == 2.0
    with pytest.raises(ValueError, match="unknown shed config"):
        ShedConfig.from_config({"max_depth": 4})
    with pytest.raises(ValueError, match="unknown supervisor config"):
        SupervisorConfig.from_config({"timeout": 1})


# ---------------------------------------------------------------------------
# admission / shedding decision logic (host-only scheduler stand-in)
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


def _sched(page_size=4, num_pages=16, num_slots=2, pages_per_slot=4):
    geom = PageGeometry(page_size=page_size, num_pages=num_pages,
                        num_slots=num_slots, pages_per_slot=pages_per_slot)
    cache = PagedKVCache(_ModelStub(), geom)
    return Scheduler(cache, SchedulerConfig(prefill_chunk=page_size))


def _queued(sched, priority=0, arrival=0.0):
    req = Request(prompt_tokens=[1, 2, 3], max_new_tokens=4,
                  arrival_time=arrival, priority=priority)
    sched.submit(req)
    return req


def test_admission_displaces_lowest_priority_on_full_queue():
    sched = _sched()
    gate = AdmissionController(ShedConfig(max_queue_depth=2))
    r1 = _queued(sched, priority=0, arrival=0.0)
    r2 = _queued(sched, priority=0, arrival=1.0)
    # a higher-priority arrival displaces the WORST queued request:
    # lowest priority, newest arrival among equals
    hi = _queued(sched, priority=1, arrival=2.0)
    admitted, victims = gate.on_submit(sched, hi, 2.0)
    assert admitted and victims == [r2]
    sched.cancel(r2, "shed", RequestState.SHED)
    # an equal-priority arrival into a full queue sheds ITSELF
    lo = _queued(sched, priority=0, arrival=3.0)
    admitted, victims = gate.on_submit(sched, lo, 3.0)
    assert not admitted and victims == [lo]
    assert r1.state is RequestState.WAITING     # older peer untouched


def test_shed_pass_enforces_bound_and_slo_burn():
    sched = _sched(num_slots=2)
    gate = AdmissionController(
        ShedConfig(max_queue_depth=4, slo_burn_threshold=1.0))
    reqs = [_queued(sched, arrival=float(i)) for i in range(6)]
    # queue bound only: 6 queued, bound 4 -> 2 victims, newest first
    victims = gate.shed_pass(sched, burn=0.0, level=0)
    assert victims == [reqs[5], reqs[4]]
    # burn at threshold: trim down to num_slots (keep 2 of 6)
    victims = gate.shed_pass(sched, burn=1.0, level=0)
    assert len(victims) == 4
    assert reqs[0] not in victims and reqs[1] not in victims
    # evicted in-flight work (holds generated tokens) is never sheddable
    reqs[0].generated = [9]
    assert reqs[0] not in gate.shed_pass(sched, burn=1.0, level=4)


def test_degradation_ladder_hysteresis_and_events():
    from dla_tpu.telemetry.flight_recorder import FlightRecorder
    rec = FlightRecorder(capacity=32)
    lad = DegradationLadder(ShedConfig(degrade_high=0.8, degrade_low=0.3,
                                       degrade_patience=2), recorder=rec)
    # escalation needs `patience` CONSECUTIVE high-pressure steps
    assert [lad.update(0.9), lad.update(0.2), lad.update(0.9)] == [0, 0, 0]
    assert lad.update(0.9) == 1
    assert lad.update(0.5) == 1                 # mid band holds steady
    assert [lad.update(0.9) for _ in range(8)] == [1, 2, 2, 3, 3, 4, 4, 4]
    assert lad.no_coschedule and lad.shrink_batch
    assert [lad.update(0.1) for _ in range(4)] == [4, 3, 3, 2]
    kinds = [e["kind"] for e in rec.events]
    assert kinds.count("degradation") == 6      # one event per rung move


def test_allocator_reclaim_cached_flushes_to_free_pool():
    a = PageAllocator(8)
    evicted = []
    a.retain_hook = lambda p: True              # park released pages
    a.evict_hook = evicted.append
    held = a.alloc(3)
    a.free(held[:2])
    assert a.free_count == 4 and a.cached_count == 2
    assert a.reclaim_cached() == 2              # ladder rung 1
    assert a.cached_count == 0 and a.free_count == 6
    assert sorted(evicted) == sorted(held[:2])  # index unhooked too
    assert a.cache_evictions == 2
    assert a.reclaim_cached() == 0              # idempotent when empty
    assert a.refcount(held[2]) == 1             # live pages untouched


# ---------------------------------------------------------------------------
# engine-level: gate, queue timeouts, draining healthz, ladder under load
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def serve_setup():
    from dla_tpu.generation.engine import GenerationConfig
    from dla_tpu.models.config import CacheArray, get_model_config
    from dla_tpu.models.transformer import Transformer
    cfg = get_model_config("tiny")
    model = Transformer(cfg)
    params = model.init(jax.random.key(7))
    # greedy, run-to-length: the replay bit-identity assertions need
    # deterministic sampling and a fixed token budget
    gen = GenerationConfig(max_new_tokens=10, do_sample=False,
                           eos_token_id=-1, pad_token_id=0)
    return model, params, gen


def _engine(serve_setup, clock=None, **cfg_kw):
    model, params, gen = serve_setup
    kw = dict(page_size=4, num_pages=32, num_slots=2, max_model_len=32)
    kw.update(cfg_kw)
    extra = {"now": clock} if clock is not None else {}
    return ServingEngine(model, params, gen, ServingConfig(**kw), **extra)


def _prompts(n, seed=5, length=6):
    # uniform length, as the traffic of one client: nothing here depends
    # on it (one chunk shape compiles once whatever the lengths)
    rs = np.random.RandomState(seed)
    return [list(rs.randint(3, 500, (length,))) for _ in range(n)]


def test_engine_token_bucket_sheds_at_gate(serve_setup):
    t = {"now": 0.0}
    eng = _engine(serve_setup, clock=lambda: t["now"],
                  shed={"rate": 1.0, "burst": 1})
    p = _prompts(3)
    r1 = eng.submit(p[0], 4, arrival_time=0.0)
    r2 = eng.submit(p[1], 4, arrival_time=0.0)   # bucket empty: shed
    assert eng.result(r1).state is RequestState.WAITING
    assert eng.result(r2).state is RequestState.SHED
    assert eng.result(r2).finish_reason == "shed"
    assert eng.metrics.requests_shed.value == 1
    t["now"] = 2.0
    r3 = eng.submit(p[2], 4, arrival_time=2.0)   # refilled: admitted
    assert eng.result(r3).state is RequestState.WAITING
    results = eng.run_until_drained(max_steps=500)
    assert results[r1].state is RequestState.FINISHED
    assert results[r3].state is RequestState.FINISHED
    assert any(e["kind"] == "request_shed" for e in eng.recorder.events)
    eng.scheduler.assert_consistent()
    eng.close()


def test_queued_deadline_expiry_counts_queue_timeouts(serve_setup):
    t = {"now": 0.0}
    eng = _engine(serve_setup, clock=lambda: t["now"], num_slots=1)
    p = _prompts(3)
    r_run = eng.submit(p[0], 5, deadline_s=1.0)
    r_queued = eng.submit(p[1], 5, deadline_s=0.5)  # one slot: waits
    eng.submit(p[2], 5)
    eng.step()
    t["now"] = 2.0
    eng.step()
    # both timed out, but only the never-admitted one is a QUEUE
    # timeout — the admission-pressure signal, distinct from slow decode
    assert eng.result(r_run).state is RequestState.TIMEOUT
    assert eng.result(r_queued).state is RequestState.TIMEOUT
    assert eng.metrics.requests_timed_out.value == 2
    assert eng.metrics.queue_timeouts.value == 1
    eng.run_until_drained(max_steps=500)
    eng.close()


def test_healthz_serves_draining_503(serve_setup):
    eng = _engine(serve_setup, metrics_port=0)
    port = eng.metrics_server.port
    url = f"http://127.0.0.1:{port}/healthz"
    with urllib.request.urlopen(url, timeout=5) as resp:
        assert resp.status == 200
    eng.begin_drain()
    with pytest.raises(urllib.error.HTTPError) as exc_info:
        urllib.request.urlopen(url, timeout=5)
    assert exc_info.value.code == 503
    assert exc_info.value.read().decode().strip() == "draining"
    eng.close()


def test_degradation_ladder_engages_under_queue_pressure(serve_setup):
    eng = _engine(serve_setup,
                  shed={"max_queue_depth": 4, "degrade_high": 0.5,
                        "degrade_low": 0.1, "degrade_patience": 1})
    for p in _prompts(12, seed=11):
        eng.submit(p, 4, arrival_time=0.0)
    results = eng.run_until_drained(max_steps=500)
    m = eng.metrics
    assert m.requests_shed.value > 0            # bound enforced
    assert m.degradation_level.peak >= 1        # ladder engaged
    assert all(r.state in TERMINAL_STATES for r in results.values())
    assert any(e["kind"] == "degradation" for e in eng.recorder.events)
    eng.scheduler.assert_consistent()
    eng.close()


# ---------------------------------------------------------------------------
# the Supervisor: chaos, replay determinism, breaker
# ---------------------------------------------------------------------------

def _supervised(serve_setup, plan, engines, max_restarts=3, **cfg_kw):
    def factory():
        eng = _engine(serve_setup, fault_plan=plan, **cfg_kw)
        engines.append(eng)
        return eng
    return Supervisor(factory, SupervisorConfig(
        watchdog_timeout_s=0.05, watchdog_poll_s=0.01,
        max_restarts=max_restarts))


def test_supervisor_chaos_replay_is_bit_identical(serve_setup):
    """The acceptance gate: wedge + device error + NaN logits across one
    supervised run. Every request terminal, COMPLETED greedy outputs
    bit-identical to a fault-free run, exactly one restart per injected
    fault (breaker untripped), decode compiles pinned at 1 per build."""
    prompts = _prompts(6, seed=0)

    eng = _engine(serve_setup)
    base_rids = [eng.submit(p, 10) for p in prompts]
    base = eng.run_until_drained(max_steps=500)
    baseline = [list(base[r].generated) for r in base_rids]
    eng.close()

    engines = []
    plan = ("engine_step=2:wedge:0.3;engine_step=4:device_error;"
            "engine_step=6:nan_logits")
    sup = _supervised(serve_setup, plan, engines)
    rids = [sup.submit(p, 10) for p in prompts]
    results = sup.run(max_steps=500)
    sup.close()

    assert sup.failures == ["wedge", "device_error", "nan_logits"]
    assert sup.restarts == 3 and not sup.tripped
    for i, rid in enumerate(rids):
        req = results[rid]
        assert req.state is RequestState.FINISHED
        assert list(req.generated) == baseline[i]   # bit-identical
    # static-shape invariant holds per engine build
    assert [e.decode_compiles for e in engines] == [1] * len(engines)
    assert [e.prefill_chunk_compiles for e in engines] == \
        [1] * len(engines)
    final = engines[-1]
    assert final.metrics.supervisor_restarts.value == 3
    assert final.metrics.replayed_requests.value == sup.replayed
    assert final.metrics.breaker_open.value == 0.0


def test_supervisor_chaos_with_chunked_prefill_cache(serve_setup):
    """Same chaos through the chunked-prefill + prefix-cache engine:
    replay stays bit-identical and the chunk compile pins at 1/build."""
    prompts = _prompts(4, seed=3, length=8)
    eng = _engine(serve_setup, prefill_chunk=4, prefix_cache=True)
    base_rids = [eng.submit(p, 8) for p in prompts]
    base = eng.run_until_drained(max_steps=500)
    baseline = [list(base[r].generated) for r in base_rids]
    eng.close()

    engines = []
    plan = "engine_step=3:device_error;engine_step=5:nan_logits"
    sup = _supervised(serve_setup, plan, engines,
                      prefill_chunk=4, prefix_cache=True)
    rids = [sup.submit(p, 8) for p in prompts]
    results = sup.run(max_steps=500)
    sup.close()
    assert sup.restarts == 2 and not sup.tripped
    for i, rid in enumerate(rids):
        assert results[rid].state is RequestState.FINISHED
        assert list(results[rid].generated) == baseline[i]
    assert [e.prefill_chunk_compiles for e in engines] == \
        [1] * len(engines)


def test_supervisor_chaos_with_speculative_decode(serve_setup):
    """Same chaos through the speculative (draft/verify) engine: a wedge
    and a device error land mid-round, replay stays bit-identical to a
    fault-free speculative run, draft/verify compiles pin at 1 per
    build, and the page-pool partition invariant holds after the
    restarts — rolled-back draft tails never leak pages."""
    prompts = _prompts(4, seed=9)
    spec = {"enabled": True, "k": 3, "draft": "self"}
    eng = _engine(serve_setup, speculative=spec)
    base_rids = [eng.submit(p, 12) for p in prompts]
    base = eng.run_until_drained(max_steps=500)
    baseline = [list(base[r].generated) for r in base_rids]
    eng.close()

    engines = []
    # speculative decode finishes in few engine steps (K+1 commits per
    # round), so the faults sit early and the 12-token budget keeps
    # every build mid-round long enough for its fault to land
    plan = "engine_step=1:wedge:0.3;engine_step=2:device_error"
    sup = _supervised(serve_setup, plan, engines, speculative=spec)
    rids = [sup.submit(p, 12) for p in prompts]
    results = sup.run(max_steps=500)
    sup.close()

    assert sup.failures == ["wedge", "device_error"]
    assert sup.restarts == 2 and not sup.tripped
    for i, rid in enumerate(rids):
        req = results[rid]
        assert req.state is RequestState.FINISHED
        assert list(req.generated) == baseline[i]   # bit-identical
    assert [e.spec_draft_compiles for e in engines] == [1] * len(engines)
    assert [e.spec_verify_compiles for e in engines] == [1] * len(engines)
    final = engines[-1]
    final.scheduler.assert_consistent()     # no page leaks after restart
    assert final.cache.allocator.used_count == 0
    assert final.metrics.supervisor_restarts.value == 2


def test_supervisor_burst_fault_invokes_hook(serve_setup):
    engines = []
    bursts = []
    sup = _supervised(serve_setup, "engine_step=1:burst=3", engines)
    sup.on_burst = bursts.append
    sup.submit(_prompts(1)[0], 4)
    sup.run(max_steps=200)
    sup.close()
    assert bursts == [3]
    assert sup.restarts == 0


def test_supervisor_burst_default_submits_low_priority(serve_setup):
    engines = []
    sup = _supervised(serve_setup, "engine_step=1:burst=2", engines,
                      shed={"max_queue_depth": 64})
    rid = sup.submit(_prompts(1)[0], 4)
    results = sup.run(max_steps=200)
    sup.close()
    assert len(results) == 3                    # 1 real + 2 synthetic
    assert results[rid].state is RequestState.FINISHED
    synth = [r for k, r in results.items() if k != rid]
    assert all(r.priority == -1 for r in synth)
    assert all(r.state in TERMINAL_STATES for r in synth)


def test_supervisor_breaker_trips_and_drains(serve_setup):
    """Restart budget exhausted: the breaker trips, the rebuilt engine
    comes up draining (healthz 503 `draining`, breaker gauge 1), and a
    further failure resolves all in-flight work terminally as SHED —
    the client sees final statuses, never a hang."""
    engines = []
    plan = ("engine_step=1:device_error;engine_step=1:device_error;"
            "engine_step=1:device_error")
    sup = _supervised(serve_setup, plan, engines, max_restarts=1,
                      metrics_port=0)
    rids = [sup.submit(p, 10) for p in _prompts(4, seed=2)]
    results = sup.run(max_steps=500)
    assert sup.tripped
    assert sup.restarts >= 2
    final = engines[-1]
    assert final.draining
    assert final.metrics.breaker_open.value == 1.0
    assert all(results[r].state in TERMINAL_STATES for r in rids)
    assert any(results[r].state is RequestState.SHED for r in rids)
    port = final.metrics_server.port
    with pytest.raises(urllib.error.HTTPError) as exc_info:
        urllib.request.urlopen(
            f"http://127.0.0.1:{port}/healthz", timeout=5)
    assert exc_info.value.code == 503
    assert exc_info.value.read().decode().strip() == "draining"
    sup.close()


@pytest.mark.slow
def test_supervisor_chaos_soak(serve_setup):
    """Soak: repeated fault waves (every kind, plus bursts) over a
    larger request population. The invariants that must survive
    arbitrary fault interleaving: zero lost requests, zero hangs, and
    scheduler/allocator consistency on every surviving engine."""
    engines = []
    plan = ";".join(
        [f"engine_step={s}:wedge:0.2" for s in (2, 30)]
        + [f"engine_step={s}:device_error" for s in (6, 40)]
        + [f"engine_step={s}:nan_logits" for s in (10,)]
        + [f"engine_step={s}:burst=4" for s in (4, 20)])
    sup = _supervised(serve_setup, plan, engines, max_restarts=10,
                      shed={"max_queue_depth": 16})
    rids = [sup.submit(p, 8, priority=i % 3)
            for i, p in enumerate(_prompts(16, seed=4))]
    results = sup.run(max_steps=2000)
    sup.close()
    assert all(r.state in TERMINAL_STATES for r in results.values())
    assert not sup.tripped
    completed = [r for r in rids
                 if results[r].state is RequestState.FINISHED]
    assert completed                            # real work got through
    assert all(len(results[r].generated) == 8 for r in completed)
    engines[-1].scheduler.assert_consistent()
