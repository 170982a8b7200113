"""Cross-host federation tests (serving/federation.py): gossip-beat
discovery with TTL staleness, placement over gateway-fronted fleets
that stays BIT-IDENTICAL — greedy and explicitly-seeded — to the
in-process FleetRouter on the same trace, journal replay under ``net=``
wire chaos with zero lost requests, mid-stream MigrationTicket handoff
over the wire, and the cross-process acceptance run: two subprocess
gateway-fronted fleets behind a FederatedRouter reproduce the
in-process streams exactly, and killing one fleet MID-STREAM loses
nothing (orphaned streams re-place and replay bit-identically)."""
import signal
import subprocess
import sys
import time
from pathlib import Path

import jax
import numpy as np
import pytest

from dla_tpu.resilience.faults import FaultPlan
from dla_tpu.serving import (
    FederatedRouter,
    FederationConfig,
    FleetConfig,
    FleetRouter,
    GossipBeater,
    SamplingParams,
    ServingConfig,
    ServingEngine,
    ServingGateway,
)

REPO_ROOT = Path(__file__).resolve().parent.parent
MAX_NEW = 4
PAGE = 4
SEEDED = dict(temperature=0.9, top_p=0.95, top_k=0, seed=77,
              do_sample=True)


@pytest.fixture(scope="module")
def serve_setup():
    from dla_tpu.generation.engine import GenerationConfig
    from dla_tpu.models.config import get_model_config
    from dla_tpu.models.transformer import Transformer
    cfg = get_model_config("tiny")
    model = Transformer(cfg)
    params = model.init(jax.random.key(7))
    gen = GenerationConfig(max_new_tokens=16, do_sample=False,
                           eos_token_id=-1, pad_token_id=0)
    return model, params, gen


def _factory(serve_setup):
    model, params, gen = serve_setup

    def factory(slot):
        return ServingEngine(model, params, gen, ServingConfig(
            page_size=PAGE, num_pages=64, num_slots=2, max_model_len=32,
            prefill_chunk=PAGE, prefix_cache=True,
            fault_plan=""))
    return factory


def _prompts(families=3, per_family=3, seed=11):
    rs = np.random.RandomState(seed)
    prompts = []
    for _ in range(families):
        head = [int(t) for t in rs.randint(3, 500, (PAGE,))]
        for _ in range(per_family):
            prompts.append(head + [int(t)
                                   for t in rs.randint(3, 500, (2,))])
    return prompts


def _reference(serve_setup, prompts, new_tokens=MAX_NEW, sampling=None):
    """In-process FleetRouter outputs for the same trace — the streams
    federation must reproduce over the wire."""
    router = FleetRouter(_factory(serve_setup), FleetConfig(engines=2))
    params = ([None] * len(prompts) if sampling is None
              else [SamplingParams(**sampling)] * len(prompts))
    rids = [router.submit(p, new_tokens, sampling=s)
            for p, s in zip(prompts, params)]
    results = router.run_until_drained(max_steps=5000)
    return [list(results[r].generated) for r in rids]


def _wait_live(fed, n, timeout_s=300.0):
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        if len(fed.live_peers()) >= n:
            return
        time.sleep(0.05)
    pytest.fail(f"never saw {n} live peers; have {fed.live_peers()}")


# ---------------------------------------------------------------------------
# in-process (gateways + router all in this process)
# ---------------------------------------------------------------------------

def test_gossip_discovery_and_ttl_staleness(serve_setup, tmp_path):
    cfg = FederationConfig(lease_ttl_s=0.6, beat_interval_s=0.1)
    gw = ServingGateway(_factory(serve_setup)(0))
    beater = GossipBeater(gw, tmp_path, "solo", cfg)
    fed = FederatedRouter(tmp_path, cfg)
    try:
        _wait_live(fed, 1, timeout_s=30)
        peer = fed.live_peers()[0]
        assert peer["name"] == "solo"
        assert peer["url"] == gw.url
        assert fed.metrics.snapshot()[
            "serving/federation/gossip_beats"] >= 1
        # stop the heartbeat: the peer goes stale one TTL later and is
        # never placed on again (counted, not crashed on)
        beater.stop()
        time.sleep(cfg.lease_ttl_s + 0.3)
        assert fed.live_peers() == []
        assert fed.metrics.snapshot()[
            "serving/federation/stale_peers"] >= 1
    finally:
        beater.stop()
        gw.close()


def test_federated_streams_bit_identical_to_fleet(serve_setup, tmp_path):
    prompts = _prompts()
    ref_greedy = _reference(serve_setup, prompts)
    ref_seeded = _reference(serve_setup, prompts, sampling=SEEDED)

    factory = _factory(serve_setup)
    gws = [ServingGateway(FleetRouter(factory, FleetConfig(engines=2)))
           for _ in range(2)]
    beaters = [GossipBeater(g, tmp_path, n) for g, n in zip(gws, "ab")]
    fed = FederatedRouter(tmp_path, FederationConfig())
    try:
        _wait_live(fed, 2)
        fids = [fed.submit(p, MAX_NEW) for p in prompts]
        out = fed.results(timeout_s=300)
        assert [out[f].tokens for f in fids] == ref_greedy
        assert all(out[f].state == "finished" for f in fids)
        assert fed.requests_lost == 0
        # per-request fold_in(seed, k) sampling is peer-independent, so
        # an EXPLICIT seed is bit-identical across hosts too
        fids = [fed.submit(p, MAX_NEW, sampling=SEEDED)
                for p in prompts]
        out = fed.results(timeout_s=300)
        assert [out[f].tokens for f in fids] == ref_seeded
        snap = fed.metrics.snapshot()
        assert snap["serving/federation/routed_remote"] == \
            2 * len(prompts)
        assert snap["serving/federation/stale_peers"] == 0
    finally:
        for b in beaters:
            b.stop()
        for g in gws:
            g.close()


def test_net_chaos_replays_with_zero_loss(serve_setup, tmp_path):
    prompts = _prompts()
    ref = _reference(serve_setup, prompts)
    factory = _factory(serve_setup)
    gws = [ServingGateway(FleetRouter(factory, FleetConfig(engines=2)))
           for _ in range(2)]
    beaters = [GossipBeater(g, tmp_path, n) for g, n in zip(gws, "ab")]
    plan = FaultPlan.parse("net=3:delay:0.01;net=5:drop;net=8:disconnect")
    fed = FederatedRouter(tmp_path, FederationConfig(), fault_plan=plan)
    try:
        _wait_live(fed, 2)
        fids = [fed.submit(p, MAX_NEW) for p in prompts]
        out = fed.results(timeout_s=300)
        # a dropped op and a torn stream each cost a replay, never a
        # request — and the replayed stream is the SAME stream
        assert [out[f].tokens for f in fids] == ref
        assert fed.requests_lost == 0
        assert fed.replayed >= 1
        assert not plan.pending()      # every armed fault fired
    finally:
        for b in beaters:
            b.stop()
        for g in gws:
            g.close()


def test_migrate_midstream_over_wire_bit_identical(serve_setup,
                                                   tmp_path):
    prompt = _prompts(families=1, per_family=1, seed=3)[0]
    ref = _reference(serve_setup, [prompt], new_tokens=10)[0]
    factory = _factory(serve_setup)
    slow = FleetRouter(factory, FleetConfig(engines=1))
    orig_step = slow.step

    def slow_step():
        time.sleep(0.06)     # keep the stream open long enough to move
        return orig_step()
    slow.step = slow.poll = slow_step
    gw_a = ServingGateway(slow)
    gw_b = ServingGateway(FleetRouter(factory, FleetConfig(engines=1)))
    beaters = [GossipBeater(gw_a, tmp_path, "a"),
               GossipBeater(gw_b, tmp_path, "b")]
    fed = FederatedRouter(tmp_path, FederationConfig())
    try:
        _wait_live(fed, 2)
        fed.results(timeout_s=300)
        # catch a request mid-stream on the slow peer, then ship it —
        # serialized KV ticket out of a, installed into b, stream
        # re-attached with a catch-up — and the total stream must be
        # what it would have been had it never moved
        fid = None
        for _ in range(6):
            f = fed.submit(prompt, 10)
            fr = fed._requests[f]
            deadline = time.monotonic() + 60
            while time.monotonic() < deadline:
                if fr.peer == "a" and fr.remote_rid is not None \
                        and len(fr.tokens) >= 2 and fr.state == "pending":
                    fid = f
                    break
                if fr.state != "pending":
                    break
                time.sleep(0.01)
            if fid is not None:
                break
            fed.results(timeout_s=300)
        assert fid is not None, "never caught a mid-stream request"
        fed.migrate(fid, "b")
        out = fed.results(timeout_s=300)[fid]
        assert out.state == "finished"
        assert out.peer == "b"
        assert out.tokens == ref
        assert fed.requests_lost == 0
        assert fed.metrics.snapshot()[
            "serving/federation/handoff_bytes"] > 0
    finally:
        for b in beaters:
            b.stop()
        gw_a.close()
        gw_b.close()


# ---------------------------------------------------------------------------
# cross-process acceptance: two subprocess fleets behind the router
# ---------------------------------------------------------------------------

def test_cross_process_fleets_bit_identical_and_kill_safe(
        serve_setup, tmp_path):
    """The ISSUE's acceptance bar, one launch, two phases: (1) the same
    seeded trace through two SUBPROCESS gateway-fronted fleets produces
    token streams bit-identical to the in-process FleetRouter — greedy
    AND explicitly-seeded; (2) SIGKILL one fleet mid-trace and nothing
    is lost — orphaned streams re-place on the survivor and replay to
    the same tokens."""
    sys.path.insert(0, str(REPO_ROOT))
    from _cpuhost import cpu_child_env

    prompts = _prompts()
    ref_greedy = _reference(serve_setup, prompts, new_tokens=8)
    ref_seeded = _reference(serve_setup, prompts, new_tokens=8,
                            sampling=SEEDED)

    env = cpu_child_env(1, str(REPO_ROOT))
    procs = {}
    fed = FederatedRouter(tmp_path, FederationConfig())
    try:
        for name in ("a", "b"):
            procs[name] = subprocess.Popen(
                [sys.executable,
                 str(REPO_ROOT / "tests" / "_gateway_worker.py"),
                 str(tmp_path), name, "25"],
                env=env, cwd=str(REPO_ROOT),
                stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
                text=True)
        _wait_live(fed, 2, timeout_s=600)

        # phase 1: wire == in-process, greedy and seeded
        fids = [fed.submit(p, 8) for p in prompts]
        out = fed.results(timeout_s=600)
        assert [out[f].tokens for f in fids] == ref_greedy
        fids = [fed.submit(p, 8, sampling=SEEDED) for p in prompts]
        out = fed.results(timeout_s=600)
        assert [out[f].tokens for f in fids] == ref_seeded
        assert fed.requests_lost == 0

        # phase 2: kill one fleet MID-STREAM
        fids = [fed.submit(p, 8) for p in prompts]
        victim = None
        deadline = time.monotonic() + 300
        while victim is None and time.monotonic() < deadline:
            for f in fids:
                fr = fed._requests[f]
                if fr.state == "pending" and fr.peer in procs \
                        and len(fr.tokens) >= 1:
                    victim = fr.peer
                    break
            time.sleep(0.01)
        assert victim is not None, "no request was caught mid-stream"
        procs[victim].send_signal(signal.SIGKILL)
        out = fed.results(timeout_s=600)
        assert [out[f].tokens for f in fids] == ref_greedy
        assert all(out[f].state == "finished" for f in fids)
        assert fed.requests_lost == 0
        assert fed.replayed >= 1
    finally:
        for p in procs.values():
            p.kill()
        for p in procs.values():
            try:
                p.communicate(timeout=30)
            except subprocess.TimeoutExpired:
                pass
