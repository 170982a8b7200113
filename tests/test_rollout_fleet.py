"""Elastic sampler fleet tests (rollout/actor_fleet): broadcast-tree
refit fanout (all members, zero recompiles, wedged member retired
without stalling), lease-based lose-a-sampler-not-the-run reassignment
regenerating bit-identically from journaled (prompt, seed) pairs,
per-trajectory (heterogeneous) staleness tagging, and the chaos
acceptance — an N=4 async fleet run that loses one sampler mid-rollout
produces rollouts and final params bit-identical to a planned N=3
run."""
import threading
import time

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from dla_tpu.generation.engine import GenerationConfig, build_generate_fn
from dla_tpu.models.config import get_model_config
from dla_tpu.models.transformer import Transformer
from dla_tpu.ops.sampling import derive_rollout_seeds
from dla_tpu.resilience.faults import FaultPlan
from dla_tpu.rollout import (
    RolloutMetrics,
    SamplerFleet,
    SamplerFleetConfig,
    SamplerFleetMetrics,
    TrajectoryGroup,
    WeightRefitter,
    apply_staleness_correction,
    build_rollout_pipeline,
    make_staleness_corrector,
    shard_trajectory_groups,
)
from dla_tpu.rollout.pipeline import RolloutPipeline
from dla_tpu.serving.fleet import broadcast_waves
from dla_tpu.serving.server import ServingConfig

MAX_NEW = 5


@pytest.fixture(scope="module")
def model_and_params():
    cfg = get_model_config("tiny")
    model = Transformer(cfg)
    return model, model.init(jax.random.key(7))


@pytest.fixture(scope="module")
def prompt_batch():
    rs = np.random.RandomState(3)
    prompts = [list(rs.randint(3, 500, (n,))) for n in (6, 4, 9, 5)]
    width = max(len(p) for p in prompts)
    ids = np.zeros((len(prompts), width), np.int32)
    mask = np.zeros_like(ids)
    for i, p in enumerate(prompts):
        ids[i, :len(p)] = p
        mask[i, :len(p)] = 1
    return ids, mask


def _serving_cfg(**kw):
    base = dict(page_size=4, num_pages=64, num_slots=3,
                max_model_len=32, fault_plan="")
    base.update(kw)
    return ServingConfig(**base)


def _gen(**kw):
    base = dict(max_new_tokens=MAX_NEW, do_sample=True, temperature=0.9,
                top_p=0.9, top_k=8, eos_token_id=2, pad_token_id=0)
    base.update(kw)
    return GenerationConfig(**base)


def _batch_reference(model, params, gen, ids, mask, seeds):
    fn = jax.jit(build_generate_fn(model, gen, group_size=1,
                                   per_request_seeds=True))
    return fn(params, jnp.asarray(ids), jnp.asarray(mask),
              jnp.asarray(seeds, jnp.uint32))


def _assert_parity(ref, out):
    """Tokens/masks bit-identical to the batch path; logps to float32
    ulp (paged and contiguous attention round differently — same
    tolerance test_rollout pins for the single engine). Fleet-vs-fleet
    comparisons (the chaos acceptance) assert FULL bit identity
    instead, logps included."""
    for key in ("response_mask", "response_tokens", "sequence_mask",
                "sequences", "lengths"):
        assert np.array_equal(np.asarray(ref[key]),
                              np.asarray(out[key])), key
    rmask = np.asarray(ref["response_mask"])
    np.testing.assert_allclose(
        np.asarray(out["response_logps"]) * rmask,
        np.asarray(ref["response_logps"]) * rmask,
        atol=1e-5, rtol=0)


# ---------------------------------------------------------------------------
# pure pieces: wave schedule, fault grammar, sharding, config
# ---------------------------------------------------------------------------

def test_broadcast_waves_depth_not_n():
    # root holds the payload; coverage multiplies by (1 + branch)/wave
    assert broadcast_waves(4, 2) == [[0, 1], [2, 3]]
    assert broadcast_waves(1, 2) == [[0]]
    assert broadcast_waves(7, 2) == [[0, 1], [2, 3, 4, 5, 6]]
    assert broadcast_waves(0, 2) == []
    # depth grows logarithmically: 64 members in 4 waves at branch 2
    assert len(broadcast_waves(64, 2)) == 4
    with pytest.raises(ValueError):
        broadcast_waves(4, 0)
    covered = [i for w in broadcast_waves(13, 3) for i in w]
    assert covered == list(range(13))


def test_sampler_fault_grammar_roundtrip():
    plan = FaultPlan.parse(
        "sampler=1:rollout_step=2:lost;sampler=0:rollout_step=0:slow:0.2")
    assert len(plan.entries) == 2
    by_kind = {f.kind: f for f in plan.entries}
    lost, slow = by_kind["lost"], by_kind["slow"]
    assert (lost.site, lost.host, lost.step, lost.kind) == \
        ("sampler", 1, 2, "lost")
    assert (slow.site, slow.host, slow.step, slow.kind, slow.arg) == \
        ("sampler", 0, 0, "slow", 0.2)
    assert FaultPlan.parse(plan.spec()).spec() == plan.spec()
    # one-shot take, disjoint from the other five scopes
    assert plan.take("lost", 2, site="sampler") is lost
    assert plan.take("lost", 2, site="sampler") is None
    assert plan.take("slow", 5, site="host") is None
    with pytest.raises(ValueError):        # must be rollout_step=
        FaultPlan.parse("sampler=1:step=2:lost")
    with pytest.raises(ValueError):        # not a sampler kind
        FaultPlan.parse("sampler=1:rollout_step=2:wedge")


def test_shard_trajectory_groups_deterministic():
    def tg(g):
        return TrajectoryGroup(group=g, member=0, version=0, epoch=0,
                               rows={})
    # completion order scrambled; sharding must not care
    groups = [tg(g) for g in (5, 0, 3, 6, 1, 4, 2)]
    shards = shard_trajectory_groups(groups, 3)
    assert [[g.group for g in s] for s in shards] == \
        [[0, 1, 2], [3, 4], [5, 6]]
    assert shard_trajectory_groups([], 2) == [[], []]
    with pytest.raises(ValueError):
        shard_trajectory_groups(groups, 0)


def test_fleet_config_validation():
    cfg = SamplerFleetConfig.from_config(None)
    assert cfg.samplers == 2 and cfg.min_samplers == 1
    assert SamplerFleetConfig.from_config(
        {"samplers": 4, "lease_ttl_s": 0.5}).samplers == 4
    with pytest.raises(ValueError, match="unknown ppo.rollout.fleet"):
        SamplerFleetConfig.from_config({"smaplers": 4})
    with pytest.raises(ValueError):
        SamplerFleetConfig(samplers=0)
    with pytest.raises(ValueError):
        SamplerFleetConfig(samplers=2, min_samplers=3)


def test_fleet_metrics_snapshot_names():
    assert set(SamplerFleetMetrics().snapshot()) == {
        "rollout/fleet/samplers_active",
        "rollout/fleet/refit_fanout_ms",
        "rollout/fleet/retired_samplers",
        "rollout/fleet/reassigned_rollouts",
        "rollout/fleet/trajectory_queue_depth",
    }


# ---------------------------------------------------------------------------
# parity + refit fanout
# ---------------------------------------------------------------------------

def test_fleet_parity_refit_fanout_versions(model_and_params,
                                            prompt_batch):
    """An N=3 fleet (uneven 4-groups-over-3 split) reproduces the
    seeded batch path bit-identically; one publish_params fans out to
    every member over the broadcast tree with zero recompiles, and
    ``row_versions`` carries the stamped version."""
    model, params = model_and_params
    ids, mask = prompt_batch
    gen = _gen()
    seeds = derive_rollout_seeds(123, len(ids))
    ref = _batch_reference(model, params, gen, ids, mask, seeds)

    fleet = SamplerFleet(model, params, gen, _serving_cfg(),
                         SamplerFleetConfig(samplers=3))
    try:
        out = fleet.generate(ids, mask, seeds)
        _assert_parity(ref, out)
        assert np.asarray(out["row_versions"]).tolist() == [0] * len(ids)

        # same-tree refit through the shared WeightRefitter surface:
        # every member lands on version 1, outputs reproduce
        refitter = WeightRefitter(fleet, lambda: params)
        refitter.refit(version=1)
        assert [m.version for m in fleet.active()] == [1, 1, 1]
        assert fleet.version == 1
        out1 = fleet.generate(ids, mask, seeds)
        _assert_parity(ref, out1)
        assert np.asarray(out1["row_versions"]).tolist() == [1] * len(ids)

        # perturbed tree changes outputs; compile counters stay pinned
        bumped = jax.tree.map(lambda x: x * 1.01, params)
        refitter.refit(bumped, version=2)
        out2 = fleet.generate(ids, mask, seeds)
        assert not np.array_equal(np.asarray(ref["response_logps"]),
                                  np.asarray(out2["response_logps"]))
        for m in fleet.active():
            assert m.engine.engine.decode_compiles == 1
        snap = fleet.fleet_metrics.snapshot()
        assert snap["rollout/fleet/samplers_active"] == 3
        assert snap["rollout/fleet/refit_fanout_ms"] > 0
        assert snap["rollout/fleet/retired_samplers"] == 0
        # validation errors surface per member, not silently swallowed
        assert fleet.metrics.snapshot()["rollout/rollouts"] == 3
    finally:
        fleet.close()


def test_refit_timeout_retires_member_without_stalling(model_and_params,
                                                       prompt_batch):
    """A member whose executor is wedged misses its publish deadline;
    the fanout retires it after the bounded retries instead of
    stalling the learner, and the survivor finishes the next rollout
    with full parity."""
    model, params = model_and_params
    ids, mask = prompt_batch
    gen = _gen()
    seeds = derive_rollout_seeds(123, len(ids))
    ref = _batch_reference(model, params, gen, ids, mask, seeds)

    fleet = SamplerFleet(
        model, params, gen, _serving_cfg(),
        SamplerFleetConfig(samplers=2, refit_timeout_s=0.15,
                           refit_retries=1, retire_after_failures=1))
    try:
        wedged = fleet.active()[1]
        wedged.pool.submit(time.sleep, 4.0)      # occupy its executor
        t0 = time.monotonic()
        fleet.publish_params(params, version=1)
        wall = time.monotonic() - t0
        # bounded by (1 + retries) * timeout per member, NOT the wedge
        assert wall < 2.0, f"fanout stalled {wall:.2f}s on wedged member"
        assert wedged.retired
        snap = fleet.fleet_metrics.snapshot()
        assert snap["rollout/fleet/retired_samplers"] == 1
        assert snap["rollout/fleet/samplers_active"] == 1
        assert fleet.active()[0].version == 1

        out = fleet.generate(ids, mask, seeds)
        _assert_parity(ref, out)
        assert np.asarray(out["row_versions"]).tolist() == [1] * len(ids)
    finally:
        fleet.close()


# ---------------------------------------------------------------------------
# lose a sampler, not the run
# ---------------------------------------------------------------------------

def test_sampler_lost_reassigned_bit_identical(model_and_params,
                                               prompt_batch):
    """``sampler=1:rollout_step=0:lost`` silences member 1 mid-rollout;
    the collector detects the stale lease, retires it, reassigns its
    journaled (prompt, seed) groups to the survivor — and the rollout
    arrays come out bit-identical to the fault-free reference. With
    ``regrow``, the next rollout respawns to target size."""
    model, params = model_and_params
    ids, mask = prompt_batch
    gen = _gen()
    seeds = derive_rollout_seeds(123, len(ids))
    ref = _batch_reference(model, params, gen, ids, mask, seeds)

    fleet = SamplerFleet(
        model, params, gen,
        _serving_cfg(fault_plan="sampler=1:rollout_step=0:lost"),
        SamplerFleetConfig(samplers=2, lease_ttl_s=0.3, regrow=True))
    try:
        out = fleet.generate(ids, mask, seeds)
        _assert_parity(ref, out)
        snap = fleet.fleet_metrics.snapshot()
        assert snap["rollout/fleet/retired_samplers"] == 1
        assert snap["rollout/fleet/reassigned_rollouts"] >= 1
        assert snap["rollout/fleet/samplers_active"] == 1

        # regrow: back to target size, and the respawned member samples
        # from the CURRENT tree — next rollout still bit-identical
        out2 = fleet.generate(ids, mask, seeds)
        _assert_parity(ref, out2)
        assert fleet.fleet_metrics.snapshot()[
            "rollout/fleet/samplers_active"] == 2
    finally:
        fleet.close()


def test_sampler_slow_completes_without_retire(model_and_params,
                                               prompt_batch):
    """``slow`` lags a member below the lease TTL: an early-warning
    path, not a death — nothing retires, output parity holds."""
    model, params = model_and_params
    ids, mask = prompt_batch
    gen = _gen()
    seeds = derive_rollout_seeds(123, len(ids))
    ref = _batch_reference(model, params, gen, ids, mask, seeds)

    fleet = SamplerFleet(
        model, params, gen,
        _serving_cfg(fault_plan="sampler=0:rollout_step=0:slow:0.01"),
        SamplerFleetConfig(samplers=2, lease_ttl_s=5.0))
    try:
        out = fleet.generate(ids, mask, seeds)
        _assert_parity(ref, out)
        snap = fleet.fleet_metrics.snapshot()
        assert snap["rollout/fleet/retired_samplers"] == 0
        assert snap["rollout/fleet/samplers_active"] == 2
    finally:
        fleet.close()


# ---------------------------------------------------------------------------
# cross-rollout isolation: stale producers can never corrupt a rollout
# ---------------------------------------------------------------------------

def test_stale_queue_entries_never_leak_across_rollouts(model_and_params,
                                                        prompt_batch):
    """A retired-but-alive member may leave emissions on the trajectory
    queue between rollouts; ``generate`` drains leftovers before
    dispatching and the collector discards any group not tagged (this
    rollout, current owner). The poisoned entries below carry empty
    rows — if any were ever seated, assembly would crash or parity
    would break."""
    model, params = model_and_params
    ids, mask = prompt_batch
    gen = _gen()
    seeds = derive_rollout_seeds(123, len(ids))
    ref = _batch_reference(model, params, gen, ids, mask, seeds)

    fleet = SamplerFleet(model, params, gen, _serving_cfg(),
                         SamplerFleetConfig(samplers=2))
    try:
        for g in range(3):
            fleet._traj_q.put(TrajectoryGroup(
                group=g, member=0, version=9, epoch=0, rows={},
                rollout=-1))
        out = fleet.generate(ids, mask, seeds)
        _assert_parity(ref, out)
        assert np.asarray(out["row_versions"]).tolist() == [0] * len(ids)
    finally:
        fleet.close()


def test_collect_rejects_stale_rollout_and_foreign_owner(model_and_params):
    """``_collect`` accepts a group only from its current owner for the
    current rollout index: a stale-rollout emission and one from a
    member whose groups were reassigned away are both discarded rather
    than seated via first-arrival."""
    model, params = model_and_params
    gen = _gen()
    fleet = SamplerFleet(model, params, gen, _serving_cfg(),
                         SamplerFleetConfig(samplers=1))
    try:
        slot = fleet.active()[0].slot
        stale = TrajectoryGroup(group=0, member=slot, version=0, epoch=0,
                                rows={}, rollout=99)
        foreign = TrajectoryGroup(group=0, member=slot + 1, version=0,
                                  epoch=0, rows={}, rollout=3)
        good = TrajectoryGroup(group=0, member=slot, version=0, epoch=0,
                               rows={}, rollout=3)
        for tg in (stale, foreign, good):
            fleet._traj_q.put(tg)
        done = fleet._collect(3, 1, {0: slot}, (4, MAX_NEW))
        assert done[0] is good
    finally:
        fleet.close()


def test_retired_member_emit_drops_instead_of_spinning(model_and_params):
    """A member retired while blocked on a full queue must drop its
    group and release its executor thread — not spin re-filling the
    bounded queue with garbage for the rest of the run."""
    model, params = model_and_params
    gen = _gen()
    fleet = SamplerFleet(model, params, gen, _serving_cfg(),
                         SamplerFleetConfig(samplers=1, traj_queue_cap=1))
    try:
        m = fleet.active()[0]
        fleet._traj_q.put(TrajectoryGroup(group=0, member=m.slot,
                                          version=0, epoch=0, rows={},
                                          rollout=0))   # queue now full
        fleet._retire(m, "test")
        t = threading.Thread(target=fleet._emit, args=(m, 1, {}, 0),
                             daemon=True)
        t.start()
        t.join(timeout=5.0)
        assert not t.is_alive(), "_emit spun on a retired member"
        assert fleet._traj_q.qsize() == 1   # nothing new enqueued
    finally:
        fleet.close()


# ---------------------------------------------------------------------------
# heterogeneous per-trajectory staleness
# ---------------------------------------------------------------------------

def test_heterogeneous_staleness_per_trajectory(model_and_params,
                                                prompt_batch):
    """Members refit at different learner versions inside ONE batch:
    the staleness/IS machinery must act per trajectory. Rows from the
    current-version member keep weight exactly 1; only the laggard's
    rows get the truncated-IS correction — different from the old
    per-batch path, which would have corrected every row. Advantages
    stay finite throughout."""
    model, params = model_and_params
    ids, mask = prompt_batch
    gen = _gen()
    seeds = derive_rollout_seeds(123, len(ids))

    fleet = SamplerFleet(model, params, gen, _serving_cfg(),
                         SamplerFleetConfig(samplers=2))
    try:
        # refit ONLY member 0 to the bumped tree at version 2 — the
        # shape a fanout-failed member leaves behind (it keeps its old
        # weights and old tag)
        bumped = jax.tree.map(lambda x: x * 1.05, params)
        m0 = fleet.active()[0]
        m0.pool.submit(fleet._publish_one, m0, bumped, False, 2).result()
        out = fleet.generate(ids, mask, seeds)
        # round-robin: even groups -> member 0 (fresh), odd -> member 1
        versions = np.asarray(out["row_versions"])
        assert versions.tolist() == [2, 0, 2, 0]

        # the pipeline helper turns tags into the per-trajectory vector
        pipe = RolloutPipeline.__new__(RolloutPipeline)
        pipe._state_lock = threading.Lock()
        pipe._updates = 2            # learner is at update 2
        worst = pipe._attach_row_staleness(out)
        stale = np.asarray(out["staleness_updates"])
        assert stale.tolist() == [0, 2, 0, 2] and worst == 2

        corr = make_staleness_corrector(model, is_clip=2.0)
        w = np.asarray(corr(bumped, out))
        assert np.all(np.isfinite(w)) and np.all(w <= 2.0)
        # laggard rows sampled under OLD weights: ratio visibly != 1
        assert np.any(np.abs(w[stale > 0] - 1.0) > 1e-4)

        # per-trajectory gating (the train_rlhf path): fresh rows are
        # weight 1 EXACTLY; the old per-batch path corrected them too
        w_traj = np.asarray(jnp.where(jnp.asarray(stale) > 0,
                                      jnp.asarray(w), jnp.float32(1.0)))
        assert np.all(w_traj[stale == 0] == 1.0)
        assert not np.array_equal(w_traj, w)
        adv = apply_staleness_correction(
            jnp.ones((len(w_traj), 3)), jnp.asarray(w_traj))
        assert np.all(np.isfinite(np.asarray(adv)))

        # sharding carries the heterogeneous tags through untouched
        tgs = [TrajectoryGroup(group=g, member=g % 2,
                               version=int(versions[g]), epoch=0, rows={})
               for g in range(4)]
        shards = shard_trajectory_groups(tgs, 2)
        assert [[t.version for t in s] for s in shards] == [[2, 0], [2, 0]]
    finally:
        fleet.close()


# ---------------------------------------------------------------------------
# chaos acceptance: elastic run == planned run
# ---------------------------------------------------------------------------

def _learner_loop(model, params, gen, ids, mask, *, samplers, fault_plan,
                  rollouts=3):
    """A deterministic async-fleet learner loop: consume rollout k,
    derive the next params deterministically FROM the rollout (so final
    params pin every intermediate rollout bit-for-bit), notify. Returns
    (rollout outputs, final params, per-member decode compiles, fleet
    metric snapshot)."""
    def sample_fn(idx):
        return ids, mask, derive_rollout_seeds(9000 + idx, len(ids))

    pipe = build_rollout_pipeline(
        model, params, gen, sample_fn,
        rows=len(ids), prompt_width=ids.shape[1], mode="async",
        max_staleness_updates=2,
        serving={"page_size": 4, "fault_plan": fault_plan},
        fleet={"samplers": samplers, "lease_ttl_s": 0.3})
    assert pipe.deterministic_refit
    try:
        outs = []
        p = params
        for k in range(rollouts):
            out, staleness = pipe.get(k)
            assert staleness <= 2
            # zero lost trajectory groups: every row came home
            assert np.asarray(out["response_tokens"]).shape[0] == len(ids)
            outs.append({k: np.asarray(v) for k, v in out.items()})
            # the "update": a deterministic function of the rollout
            seen = int(np.asarray(out["response_tokens"]).sum()
                       + np.asarray(out["lengths"]).sum())
            scale = np.float32(1.0 + 1e-4 * (seen % 13))
            p = jax.tree.map(lambda x, s=scale: x * s, p)
            pipe.notify_updates(1, params=p)
        compiles = sorted(
            (m.engine.engine.prefill_chunk_compiles,
             m.engine.engine.decode_compiles)
            for m in pipe.rollout._samplers
            if m.engine.engine.decode_compiles)
        snap = pipe.rollout.fleet_metrics.snapshot()
        return outs, p, compiles, snap
    finally:
        pipe.close()


def test_chaos_acceptance_elastic_equals_planned(model_and_params,
                                                 prompt_batch):
    """THE acceptance property: an N=4 async fleet run that loses
    sampler 1 mid-rollout (``sampler=`` plan) completes every rollout
    with zero lost trajectory groups, regenerates the reassigned groups
    bit-identically from the journal, and lands on final params
    bit-identical to a planned N=3 run — with decode/prefill compile
    counters at one per engine build in both runs."""
    model, params = model_and_params
    gen = _gen()
    # 8 groups over 4 members = 2 per member: the killed member's one
    # kill-budget group leaves its SECOND group in flight — the
    # reassignment path must fire, not just the retirement
    rs = np.random.RandomState(11)
    prompts = [list(rs.randint(3, 500, (n,)))
               for n in (6, 4, 9, 5, 7, 3, 8, 5)]
    width = max(len(p) for p in prompts)
    ids = np.zeros((len(prompts), width), np.int32)
    mask = np.zeros_like(ids)
    for i, p in enumerate(prompts):
        ids[i, :len(p)] = p
        mask[i, :len(p)] = 1

    chaos = _learner_loop(model, params, gen, ids, mask, samplers=4,
                          fault_plan="sampler=1:rollout_step=1:lost")
    planned = _learner_loop(model, params, gen, ids, mask, samplers=3,
                            fault_plan="")

    c_outs, c_params, c_compiles, c_snap = chaos
    p_outs, p_params, p_compiles, p_snap = planned
    assert c_snap["rollout/fleet/retired_samplers"] == 1
    assert c_snap["rollout/fleet/reassigned_rollouts"] >= 1
    assert c_snap["rollout/fleet/samplers_active"] == 3
    assert p_snap["rollout/fleet/retired_samplers"] == 0

    # every rollout bit-identical across the two topologies
    for k, (co, po) in enumerate(zip(c_outs, p_outs)):
        for key in ("response_tokens", "response_mask", "sequences",
                    "sequence_mask", "response_logps", "lengths"):
            assert np.array_equal(co[key], po[key]), (k, key)
    # ... so the final params are too
    c_leaves = jax.tree_util.tree_leaves(c_params)
    p_leaves = jax.tree_util.tree_leaves(p_params)
    assert len(c_leaves) == len(p_leaves)
    for cl, pl in zip(c_leaves, p_leaves):
        assert np.array_equal(np.asarray(cl), np.asarray(pl))
    # decode and the one prefill chunk compiled exactly once per engine
    # build, elastic or planned, whatever prompt widths reassignment
    # moved between members
    assert all(c == (1, 1) for c in c_compiles + p_compiles)


# ---------------------------------------------------------------------------
# bench: fanout bounded by tree depth, zero steps lost
# ---------------------------------------------------------------------------

def test_bench_rollout_fleet_depth_bound_and_zero_loss():
    """The bench A/B the fanout exists for: at N=4 branch=2 the
    broadcast refit pays ~2 per-member delays (tree depth) where the
    serial baseline pays ~4 (N) — and the chaos leg loses zero learner
    steps to a sampler death."""
    import bench
    row = bench.run_rollout_fleet_bench()
    assert row["metric"] == "rollout_fleet_fanout_speedup"
    d = row["detail"]
    # wall time bounded by tree depth, not N: ideal ratio N/waves = 2
    assert row["value"] > 1.4
    assert d["broadcast_refit_ms"] < d["serial_refit_ms"]
    assert d["fanout_waves"] == 2 and d["samplers"] == 4
    assert d["steps_lost_to_sampler_death"] == 0
    assert d["outputs_identical_n1_n4"]
    assert d["retired_samplers"] == 1 and d["reassigned_rollouts"] >= 1


# ---------------------------------------------------------------------------
# pipeline close ordering (satellite regression)
# ---------------------------------------------------------------------------

class _BlockingRollout:
    """Minimal rollout double whose generate() is instant — so the
    generator thread races ahead and blocks on the depth-1 queue's
    put — and whose close() records whether the generator had already
    exited (the ordering the fix guarantees)."""

    def __init__(self):
        self.metrics = RolloutMetrics()
        self.stop_requested = False
        self.generator_alive_at_close = None
        self._thread_ref = None

    def generate(self, ids, mask, seeds, max_new=None):
        return {"response_tokens": np.zeros((2, 2), np.int32),
                "response_mask": np.ones((2, 2), np.int32)}

    def request_stop(self):
        self.stop_requested = True

    def close(self):
        t = self._thread_ref
        self.generator_alive_at_close = bool(t and t.is_alive())


def test_close_releases_blocked_generator():
    """Regression: close() must release a generator thread blocked on
    the depth-1 queue BEFORE tearing the engine down — closing the
    supervisor under a live generator was a deadlock."""
    roll = _BlockingRollout()
    pipe = RolloutPipeline(roll, lambda i: (np.zeros((2, 2), np.int32),
                                            np.ones((2, 2), np.int32),
                                            [0, 1]),
                           mode="async")
    out, staleness = pipe.get(0)
    assert staleness == 0
    deadline = time.monotonic() + 10.0
    while not pipe._q.full() and time.monotonic() < deadline:
        time.sleep(0.01)
    assert pipe._q.full(), "generator never refilled the queue"
    # generator is now (or is about to be) blocked in the queue put
    roll._thread_ref = pipe._thread
    t0 = time.monotonic()
    pipe.close(timeout=5.0)
    assert time.monotonic() - t0 < 5.0, "close() hit its deadline"
    assert roll.stop_requested
    assert roll.generator_alive_at_close is False, \
        "engine closed while the generator thread was still alive"


def test_close_releases_deterministic_handoff_wait():
    """Same ordering guarantee for a generator parked in the
    deterministic-refit handoff wait (no notify ever arrives)."""
    roll = _BlockingRollout()
    pipe = RolloutPipeline(roll, lambda i: (np.zeros((2, 2), np.int32),
                                            np.ones((2, 2), np.int32),
                                            [0, 1]),
                           mode="async", deterministic_refit=True)
    out, _ = pipe.get(0)                 # rollout 0 needs no handoff
    time.sleep(0.1)                      # generator enters the wait
    roll._thread_ref = pipe._thread
    t0 = time.monotonic()
    pipe.close(timeout=5.0)
    assert time.monotonic() - t0 < 5.0
    assert roll.generator_alive_at_close is False
