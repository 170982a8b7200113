"""Disaggregated RLHF rollout tests (dla_tpu/rollout): sync-mode bit
parity with the seeded ``build_generate_fn`` batch path, in-place
weight refit with pinned compile counters, async staleness bookkeeping
(stale-use + discard-regenerate), and mid-rollout supervisor restarts
replaying to bit-identical outputs."""
import time

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from dla_tpu.generation.engine import GenerationConfig, build_generate_fn
from dla_tpu.models.config import get_model_config
from dla_tpu.models.transformer import Transformer
from dla_tpu.ops.sampling import derive_rollout_seeds
from dla_tpu.rollout import (
    RolloutEngine,
    RolloutMetrics,
    WeightRefitter,
    apply_staleness_correction,
    build_rollout_pipeline,
    make_staleness_corrector,
)
from dla_tpu.serving.server import ServingConfig

MAX_NEW = 5


@pytest.fixture(scope="module")
def model_and_params():
    cfg = get_model_config("tiny")
    model = Transformer(cfg)
    return model, model.init(jax.random.key(7))


@pytest.fixture(scope="module")
def prompt_batch():
    """Right-padded [B, P] prompt ids/mask — the batch path's layout
    (what encode_prompt_batch produces in the trainer)."""
    rs = np.random.RandomState(3)
    prompts = [list(rs.randint(3, 500, (n,))) for n in (6, 4, 9, 5)]
    width = max(len(p) for p in prompts)
    ids = np.zeros((len(prompts), width), np.int32)
    mask = np.zeros_like(ids)
    for i, p in enumerate(prompts):
        ids[i, :len(p)] = p
        mask[i, :len(p)] = 1
    return ids, mask


def _serving_cfg(G=1, **kw):
    base = dict(page_size=4, num_pages=64, num_slots=3,
                max_model_len=32)
    if G > 1:
        # G-groups share prompt pages through the prefix cache
        base.update(prefill_chunk=4, prefix_cache=True)
    base.update(kw)
    return ServingConfig(**base)


def _batch_reference(model, params, gen, ids, mask, seeds, G=1):
    fn = jax.jit(build_generate_fn(model, gen, group_size=G,
                                   per_request_seeds=True))
    return fn(params, jnp.asarray(ids), jnp.asarray(mask),
              jnp.asarray(seeds, jnp.uint32))


def _assert_parity(ref, out):
    """Tokens and masks bit-identical; logps to float32 ulp (paged and
    contiguous attention round differently)."""
    rmask = np.asarray(ref["response_mask"])
    assert np.array_equal(rmask, np.asarray(out["response_mask"]))
    assert np.array_equal(
        np.asarray(ref["response_tokens"]) * rmask,
        np.asarray(out["response_tokens"]) * rmask)
    smask = np.asarray(ref["sequence_mask"])
    assert np.array_equal(smask, np.asarray(out["sequence_mask"]))
    assert np.array_equal(np.asarray(ref["sequences"]) * smask,
                          np.asarray(out["sequences"]) * smask)
    np.testing.assert_allclose(
        np.asarray(out["response_logps"]) * rmask,
        np.asarray(ref["response_logps"]) * rmask,
        atol=1e-5, rtol=0)


# ---------------------------------------------------------------------------
# sync-mode bit parity with the seeded batch path
# ---------------------------------------------------------------------------

def test_rollout_parity_greedy(model_and_params, prompt_batch):
    model, params = model_and_params
    ids, mask = prompt_batch
    gen = GenerationConfig(max_new_tokens=MAX_NEW, do_sample=False,
                           eos_token_id=2, pad_token_id=0)
    seeds = derive_rollout_seeds(123, len(ids))
    ref = _batch_reference(model, params, gen, ids, mask, seeds)
    roll = RolloutEngine(model, params, gen, _serving_cfg())
    out = roll.generate(ids, mask, seeds)
    roll.close()
    _assert_parity(ref, out)
    snap = roll.metrics.snapshot()
    assert snap["rollout/rollouts"] == 1
    assert snap["rollout/slot_steps_per_token"] > 0


def test_rollout_parity_sampled(model_and_params, prompt_batch):
    """temperature + top-p + top-k: the serving engine's per-request
    (seed, token-index)-keyed sampler reproduces the batch path's
    stream exactly."""
    model, params = model_and_params
    ids, mask = prompt_batch
    gen = GenerationConfig(max_new_tokens=6, do_sample=True,
                           temperature=0.9, top_p=0.9, top_k=8,
                           eos_token_id=2, pad_token_id=0)
    seeds = derive_rollout_seeds(123, len(ids))
    ref = _batch_reference(model, params, gen, ids, mask, seeds)
    roll = RolloutEngine(model, params, gen, _serving_cfg())
    out = roll.generate(ids, mask, seeds)
    roll.close()
    _assert_parity(ref, out)


def test_rollout_parity_grouped_prefix_cache(model_and_params,
                                             prompt_batch):
    """G = samples_per_prompt > 1: G seeded copies per prompt, prompt
    pages aliased through the prefix cache — still bit-identical to the
    batch path's in-graph group_size expansion."""
    model, params = model_and_params
    ids, mask = prompt_batch
    G = 2
    gen = GenerationConfig(max_new_tokens=MAX_NEW, do_sample=True,
                           temperature=1.1, top_p=0.8, top_k=0,
                           eos_token_id=2, pad_token_id=0)
    seeds = derive_rollout_seeds(123, len(ids) * G)
    ref = _batch_reference(model, params, gen, ids, mask, seeds, G=G)
    roll = RolloutEngine(model, params, gen, _serving_cfg(G=G),
                         samples_per_prompt=G)
    out = roll.generate(ids, mask, seeds)
    roll.close()
    _assert_parity(ref, out)
    assert np.asarray(out["response_tokens"]).shape[0] == len(ids) * G


def test_rollout_seed_count_validated(model_and_params, prompt_batch):
    model, params = model_and_params
    ids, mask = prompt_batch
    gen = GenerationConfig(max_new_tokens=MAX_NEW, do_sample=False,
                           eos_token_id=2, pad_token_id=0)
    with pytest.raises(ValueError):
        RolloutEngine(model, params, gen, _serving_cfg(),
                      samples_per_prompt=0)
    roll = RolloutEngine(model, params, gen, _serving_cfg(),
                         samples_per_prompt=2)
    with pytest.raises(ValueError):        # need B * G seeds
        roll.generate(ids, mask, derive_rollout_seeds(1, len(ids)))
    with pytest.raises(ValueError):        # max_new must cover every row
        roll.generate(ids, mask, derive_rollout_seeds(1, len(ids) * 2),
                      max_new=[MAX_NEW] * len(ids))
    roll.close()


# ---------------------------------------------------------------------------
# in-place weight refit
# ---------------------------------------------------------------------------

def test_refit_zero_recompiles_then_donation(model_and_params,
                                             prompt_batch):
    """The refit contract end to end: same-tree refit changes nothing
    and recompiles nothing; a perturbed tree changes the outputs and
    STILL recompiles nothing; a donated refit frees the old tree's
    device buffers and the engine keeps working."""
    model, params = model_and_params
    ids, mask = prompt_batch
    gen = GenerationConfig(max_new_tokens=MAX_NEW, do_sample=False,
                           eos_token_id=2, pad_token_id=0)
    seeds = derive_rollout_seeds(7, len(ids))
    roll = RolloutEngine(model, params, gen, _serving_cfg())
    out0 = roll.generate(ids, mask, seeds)
    assert roll.engine.decode_compiles == 1
    pc = roll.engine.prefill_chunk_compiles
    assert pc == 1

    # refit the SAME params: identical outputs, zero recompiles
    refitter = WeightRefitter(roll, lambda: params)
    ms = refitter.refit()
    assert ms >= 0
    out1 = roll.generate(ids, mask, seeds)
    assert np.array_equal(np.asarray(out0["response_tokens"]),
                          np.asarray(out1["response_tokens"]))
    assert np.array_equal(np.asarray(out0["response_logps"]),
                          np.asarray(out1["response_logps"]))
    assert roll.engine.decode_compiles == 1
    assert roll.engine.prefill_chunk_compiles == pc
    assert roll.metrics.refits.value == 1
    assert roll.metrics.refit_ms.value >= 0

    # perturbed tree (same structure/shapes/dtypes): outputs change,
    # compile counters still pinned
    bumped = jax.tree_util.tree_map(lambda x: x * 1.01, params)
    refitter.refit(bumped)
    out2 = roll.generate(ids, mask, seeds)
    assert not np.array_equal(np.asarray(out0["response_logps"]),
                              np.asarray(out2["response_logps"]))
    assert roll.engine.decode_compiles == 1
    assert roll.engine.prefill_chunk_compiles == pc

    # donated refit: the OLD (bumped) tree's buffers are freed eagerly;
    # the engine runs on the fresh tree and reproduces out0
    fresh = jax.tree_util.tree_map(lambda x: x * 1.0, params)
    WeightRefitter(roll, lambda: fresh, donate=True).refit()
    assert any(leaf.is_deleted()
               for leaf in jax.tree_util.tree_leaves(bumped))
    out3 = roll.generate(ids, mask, seeds)
    assert np.array_equal(np.asarray(out0["response_tokens"]),
                          np.asarray(out3["response_tokens"]))
    assert roll.engine.decode_compiles == 1
    roll.close()


def test_publish_params_rejects_mismatched_tree(model_and_params):
    """A refit that would silently retrace must raise instead."""
    model, params = model_and_params
    gen = GenerationConfig(max_new_tokens=MAX_NEW, do_sample=False,
                           eos_token_id=2, pad_token_id=0)
    roll = RolloutEngine(model, params, gen, _serving_cfg())
    with pytest.raises(ValueError):        # structure mismatch
        roll.publish_params({"not": "the tree"})
    with pytest.raises(ValueError):        # dtype mismatch
        roll.publish_params(jax.tree_util.tree_map(
            lambda x: x.astype(jnp.float16), params))
    roll.close()


# ---------------------------------------------------------------------------
# pipeline: sync pacing + staleness correction
# ---------------------------------------------------------------------------

def test_pipeline_sync_on_policy(model_and_params, prompt_batch):
    """Sync mode: staleness is always 0 and the truncated-IS corrector
    returns weights ~1 for on-policy rollouts."""
    model, params = model_and_params
    ids, mask = prompt_batch
    gen = GenerationConfig(max_new_tokens=MAX_NEW, do_sample=True,
                           temperature=1.0, eos_token_id=2,
                           pad_token_id=0)

    def sample_fn(idx):
        return ids, mask, derive_rollout_seeds(1000 + idx, len(ids))

    pipe = build_rollout_pipeline(model, params, gen, sample_fn,
                                  rows=len(ids),
                                  prompt_width=ids.shape[1],
                                  mode="sync",
                                  serving={"page_size": 4})
    out, staleness = pipe.get(0, params=params)
    assert staleness == 0
    corr = make_staleness_corrector(model, is_clip=2.0)
    w = np.asarray(corr(params, out))
    np.testing.assert_allclose(w, 1.0, atol=1e-3)
    assert np.all(w <= 2.0)

    adv2 = apply_staleness_correction(jnp.ones((len(ids), 3)),
                                      jnp.asarray(w))
    assert adv2.shape == (len(ids), 3)
    adv1 = apply_staleness_correction(jnp.full((len(ids),), 2.0),
                                      jnp.asarray(w))
    np.testing.assert_allclose(np.asarray(adv1), 2.0 * w, atol=1e-6)
    pipe.close()


def _wait_queue_full(pipe, timeout=120.0):
    deadline = time.time() + timeout
    while time.time() < deadline:
        if pipe._q.full():
            return
        time.sleep(0.01)
    raise AssertionError("generator thread never filled the queue")


def test_pipeline_async_staleness_bound(model_and_params, prompt_batch):
    """Async mode bookkeeping: on-policy consumption, bounded-stale
    consumption (stale_rollouts), and discard-regenerate when the
    queued rollout exceeds max_staleness_updates."""
    model, params = model_and_params
    ids, mask = prompt_batch
    gen = GenerationConfig(max_new_tokens=MAX_NEW, do_sample=True,
                           temperature=1.0, eos_token_id=2,
                           pad_token_id=0)

    def sample_fn(idx):
        return ids, mask, derive_rollout_seeds(2000 + idx, len(ids))

    pipe = build_rollout_pipeline(model, params, gen, sample_fn,
                                  rows=len(ids),
                                  prompt_width=ids.shape[1],
                                  mode="async",
                                  max_staleness_updates=1,
                                  serving={"page_size": 4})
    try:
        out0, st0 = pipe.get(0, params=params)
        assert st0 == 0
        assert np.asarray(out0["response_tokens"]).shape[0] == len(ids)

        # rollout 1 was generated before these updates: stale by 1,
        # inside the bound -> used with correction
        _wait_queue_full(pipe)
        pipe.notify_updates(1, params=params)
        out1, st1 = pipe.get(1, params=params)
        assert st1 == 1
        assert pipe.metrics.stale_rollouts.value == 1

        # three more updates push the queued rollout past the bound:
        # discarded, refit, regenerated inline -> comes back on-policy
        _wait_queue_full(pipe)
        pipe.notify_updates(3, params=params)
        out2, st2 = pipe.get(2, params=params)
        assert st2 == 0
        assert pipe.metrics.discarded_rollouts.value == 1
        assert np.asarray(out2["response_mask"]).sum() > 0

        with pytest.raises(RuntimeError):   # strict in-order consumption
            pipe.get(7)
    finally:
        pipe.close()


def test_async_handoff_survives_learner_donation(model_and_params,
                                                 prompt_batch):
    """The trainer's jitted update donates its input params
    (``donate_argnums=(0, 1)``), deleting the old buffers in place —
    the very buffers a by-reference async handoff would leave the
    generator thread reading mid-generation ("Array has been
    deleted", reproduced via train_rlhf with ``mode: async``). Pin:
    the pipeline snapshots every tree crossing the thread boundary,
    so deleting the learner's copy after handoff changes nothing."""
    model, params = model_and_params
    ids, mask = prompt_batch
    gen = GenerationConfig(max_new_tokens=MAX_NEW, do_sample=False,
                           eos_token_id=2, pad_token_id=0)

    def sample_fn(idx):
        return ids, mask, derive_rollout_seeds(3000 + idx, len(ids))

    # the learner's live tree: handed over, then "donated" (deleted)
    learner_tree = jax.tree.map(jnp.copy, params)
    pipe = build_rollout_pipeline(model, learner_tree, gen, sample_fn,
                                  rows=len(ids),
                                  prompt_width=ids.shape[1],
                                  mode="async",
                                  max_staleness_updates=1,
                                  serving={"page_size": 4})
    try:
        out0, _ = pipe.get(0, params=learner_tree)
        assert np.asarray(out0["response_tokens"]).shape[0] == len(ids)
        _wait_queue_full(pipe)
        pipe.notify_updates(1, params=learner_tree)
        # the donated update step: the learner's old buffers die NOW,
        # possibly while the generator is still decoding rollout 2
        for leaf in jax.tree_util.tree_leaves(learner_tree):
            leaf.delete()
        out1, st1 = pipe.get(1)          # generated pre-update: stale 1
        assert st1 == 1
        # rollout 2's version snapshot races the notify (0 or 1, both in
        # bound) — the pin is that generation proceeds on owned buffers
        out2, st2 = pipe.get(2)
        assert st2 <= 1
        assert np.asarray(out2["response_mask"]).sum() > 0
    finally:
        pipe.close()


def test_pipeline_rejects_unknown_mode(model_and_params, prompt_batch):
    model, params = model_and_params
    ids, mask = prompt_batch
    gen = GenerationConfig(max_new_tokens=MAX_NEW, do_sample=False,
                           eos_token_id=2, pad_token_id=0)
    with pytest.raises(ValueError):
        build_rollout_pipeline(model, params, gen, lambda i: None,
                               rows=len(ids),
                               prompt_width=ids.shape[1],
                               mode="overlapped")


def test_build_rollout_pipeline_geometry(model_and_params):
    """The derived serving geometry always fits the rollout: a whole
    prompt+response window per slot, pool covers all slots + trash
    page, prefix cache defaulted ON for G > 1."""
    model, params = model_and_params
    gen = GenerationConfig(max_new_tokens=MAX_NEW, do_sample=False,
                           eos_token_id=2, pad_token_id=0)
    pipe = build_rollout_pipeline(model, params, gen, lambda i: None,
                                  rows=4, prompt_width=9,
                                  samples_per_prompt=2,
                                  serving={"page_size": 4})
    cfg = pipe.rollout.cfg
    assert cfg.page_size == 4
    assert cfg.max_model_len == 16          # ceil4(9 + 5)
    assert cfg.num_slots == 4               # min(rows, 8)
    assert cfg.num_pages == 4 * 4 + 1       # slots * pages/slot + trash
    assert cfg.prefix_cache and cfg.prefill_chunk == 4
    pipe.close()


# ---------------------------------------------------------------------------
# mid-rollout faults + supervisor restart
# ---------------------------------------------------------------------------

def test_mid_rollout_restart_bit_identical(model_and_params,
                                           prompt_batch):
    """rollout_step=0:device_error kills the engine mid-generation; the
    supervisor rebuilds and replays, and the rollout completes with the
    fault-free outputs (tokens exact, logps to float32 ulp)."""
    model, params = model_and_params
    ids, mask = prompt_batch
    gen = GenerationConfig(max_new_tokens=MAX_NEW, do_sample=False,
                           eos_token_id=2, pad_token_id=0)
    seeds = derive_rollout_seeds(42, len(ids))

    base_roll = RolloutEngine(model, params, gen, _serving_cfg())
    base = base_roll.generate(ids, mask, seeds)
    base_roll.close()

    roll = RolloutEngine(
        model, params, gen,
        _serving_cfg(fault_plan="rollout_step=0:device_error"),
        supervisor=True)
    out = roll.generate(ids, mask, seeds)
    assert roll.supervisor.restarts >= 1
    roll.close()

    rmask = np.asarray(base["response_mask"])
    assert np.array_equal(rmask, np.asarray(out["response_mask"]))
    assert np.array_equal(
        np.asarray(base["response_tokens"]) * rmask,
        np.asarray(out["response_tokens"]) * rmask)
    np.testing.assert_allclose(
        np.asarray(out["response_logps"]) * rmask,
        np.asarray(base["response_logps"]) * rmask,
        atol=1e-5, rtol=0)


# ---------------------------------------------------------------------------
# metrics + bench
# ---------------------------------------------------------------------------

def test_rollout_metrics_snapshot_names():
    """The rollout/* panel matches the CATALOG (check_metric_names
    gates the docs table; this pins the runtime side)."""
    snap = RolloutMetrics().snapshot()
    assert set(snap) == {
        "rollout/rollouts", "rollout/gen_tokens_per_s",
        "rollout/slot_steps_per_token",
        "rollout/padding_waste_recovered",
        "rollout/refits", "rollout/refit_ms",
        "rollout/staleness_updates", "rollout/stale_rollouts",
        "rollout/discarded_rollouts",
    }


def test_bench_rollout_recovers_padding_waste():
    """The A/B the subsystem exists for: on a long-tail response-length
    mix, continuous batching spends measurably fewer slot-steps per
    generated token than the fixed-shape batch path."""
    import bench
    row = bench.run_rollout_bench()
    assert row["metric"] == "rollout_padding_waste_recovered"
    d = row["detail"]
    assert 0.0 < row["value"] < 1.0
    assert (d["serving_slot_steps_per_token"]
            < d["batch_slot_steps_per_token"])
