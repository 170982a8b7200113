"""Latent attention + routed experts with a shared expert (the mistral4
block): the program against the plain float32 reference the benchmark
keeps (``perfbench/reference/mistral4_block.py``), at tiny widths in
float32 on the CPU, on seeded random weights.

Tolerance, one for all: both sides compute in float32 but in another
order (the engine's absorbed attention multiplies ``wkv_b`` onto the query
and reads cached rows; the reference expands every key; the engine sorts
(token, choice) pairs into a grouped matmul, the reference weights every
held expert's output for every token), so they differ by a few float32
ulps of the largest activation per layer: 1e-5 to 1e-4 on logits of size
5 to 10. ``TOL`` = 5e-4 is a few times that, and far under what a dropped
token, a skipped shared expert, a wrong rotary pairing, a missing query
scale or a wrong softmax scale moves a logit: the last test shows the
gentlest of them, the query scale of 1.07 to 1.11, at 0.04 (80 x TOL).
"""
import dataclasses
import importlib.util
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dla_tpu.generation.engine import GenerationConfig
from dla_tpu.models.config import get_model_config
from dla_tpu.models.transformer import Transformer
from dla_tpu.ops.rotary import position_query_scale, rotary_angles
from dla_tpu.serving import ServingConfig, ServingEngine

TOL = 5e-4
REF_PATH = (Path(__file__).resolve().parents[1] / "perfbench" / "reference"
            / "mistral4_block.py")


@pytest.fixture(scope="module")
def ref():
    spec = importlib.util.spec_from_file_location("mistral4_block", REF_PATH)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def hf_keys(cfg):
    """The Hugging Face key names the reference reads, from a ModelConfig."""
    return {
        "num_hidden_layers": cfg.num_layers,
        "num_attention_heads": cfg.num_heads,
        "qk_nope_head_dim": cfg.qk_nope_head_dim,
        "qk_rope_head_dim": cfg.qk_rope_head_dim,
        "v_head_dim": cfg.v_head_dim, "kv_lora_rank": cfg.kv_lora_rank,
        "rms_norm_eps": cfg.rms_norm_eps,
        "num_experts_per_tok": cfg.num_experts_per_token,
        "routed_scaling_factor": cfg.moe_routed_scale,
        "rope_parameters": {**(cfg.rope_scaling or {}),
                            "rope_theta": cfg.rope_theta},
    }


def lively(params, seed=0):
    """``init``'s N(0, 0.02) weights leave a 64-wide toy nearly linear
    (attention uniform, router undecided). Scale the matrices to unit
    gain, the embedding to unit rows and draw the norm weights around 1,
    so the softmaxes are sharp and a misplaced norm weight would show."""
    rng = np.random.default_rng(seed)

    def go(path, x):
        name = str(getattr(path[-1], "key", path[-1]))
        if name == "embedding":
            return x * 50.0
        if name.endswith("norm"):
            return jnp.asarray(
                1.0 + 0.3 * rng.standard_normal(x.shape), x.dtype)
        return x * 6.0
    return jax.tree_util.tree_map_with_path(go, params)


def reference_logits(ref, model, params, ids, experts=None):
    layers = params["layers"]
    hidden = ref.hidden_states(
        np.asarray(ids), params["embed"]["embedding"],
        lambda l: {k: layers[k][l] for k in ref.LAYER_LEAVES},
        params["final_norm"], hf_keys(model.cfg), experts=experts)
    return np.asarray(ref.logits(hidden, params["lm_head"]))


@pytest.fixture(scope="module")
def tiny():
    model = Transformer(get_model_config("tiny-mla-moe"))
    return model, lively(model.init(jax.random.key(0)))


def serve(model, params, prompts, max_new, **cfg_kw):
    """Every prompt to ``max_new`` greedy tokens through the paged engine
    (chunked prefill, then decode). Returns the finished requests and the
    engine's metrics snapshot."""
    gen = GenerationConfig(max_new_tokens=max_new, do_sample=False,
                           eos_token_id=-1)
    kw = dict(page_size=4, num_pages=96, num_slots=3, max_model_len=64,
              prefill_chunk=8)
    kw.update(cfg_kw)
    eng = ServingEngine(model, params, gen, ServingConfig(**kw))
    try:
        rids = [eng.submit(p, max_new) for p in prompts]
        while eng.has_work():
            eng.step()
        return [eng.result(r) for r in rids], eng.metrics.snapshot()
    finally:
        eng.close()


def assert_engine_matches(ref, model, params, prompts, results,
                          experts=None):
    """The engine's record of each generated token (the token and its
    log-probability, from the logits the step sampled) against the
    reference's teacher-forced forward over prompt + answer."""
    for prompt, res in zip(prompts, results):
        answer = list(res.generated)
        logits = reference_logits(ref, model, params,
                                  (prompt + answer)[:-1], experts)
        rows = logits[len(prompt) - 1:]
        logp = np.asarray(jax.nn.log_softmax(rows, axis=-1))
        at = np.arange(len(answer))
        np.testing.assert_allclose(
            np.asarray(res.generated_logprobs), logp[at, answer],
            atol=TOL, rtol=0)
        # the greedy token is the reference's best, to the same tolerance
        assert np.all(rows.max(-1) - rows[at, answer] <= TOL)


# ---------------------------------------------------------------- (a) apply

def test_apply_matches_reference(ref, tiny):
    """Full-sequence forward (expanded attention, the training path's
    capacity dispatch with room for every token) against the reference."""
    model, params = tiny
    ids = np.random.default_rng(1).integers(3, 500, size=(2, 48))
    got = np.asarray(model.apply(params, jnp.asarray(ids)))
    for b in range(2):
        want = reference_logits(ref, model, params, ids[b])
        assert np.abs(want).max() > 3.0        # the weights are lively
        np.testing.assert_allclose(got[b], want, atol=TOL, rtol=0)


# ------------------------------------------------- (b) the serving engine

def test_engine_chunked_prefill_and_paged_decode_match_reference(ref, tiny):
    """Chunks of 8 under prompts of 21, 9 and 30 tokens, three slots of
    unequal length decoding together, 47 positions against an original
    context of 16: the absorbed form over the latent pool, the chunk lane
    and the dropless routing are held to the expanded reference."""
    model, params = tiny
    rng = np.random.default_rng(2)
    prompts = [[int(t) for t in rng.integers(3, 500, size=n)]
               for n in (21, 9, 30)]
    results, snap = serve(model, params, prompts, max_new=12)
    assert_engine_matches(ref, model, params, prompts, results)
    # one latent row a token a layer: (16 + 8) float32 numbers x 2 layers
    assert snap["serving/kv_bytes_per_token"] == (16 + 8) * 4 * 2
    assert snap["serving/moe/expert_assignments"] > 0
    assert (snap["serving/moe/experts_hit"]
            <= snap["serving/moe/expert_assignments"])


@pytest.mark.parametrize("chunk, length, chunks", [
    (None, 13, 1), (8, 22, 3)], ids=["under_one_chunk", "three_chunks"])
def test_prompt_of_n_chunks_matches_reference(ref, tiny, chunk, length,
                                              chunks):
    """The chunk lane is the one way in: a prompt shorter than the chunk
    (``prefill_chunk`` unset: as wide as the window) and one that takes
    three chunks, the last of them part full, both write the latent pool
    the absorbed decode reads."""
    model, params = tiny
    rng = np.random.default_rng(3)
    prompt = [int(t) for t in rng.integers(3, 500, size=length)]
    results, snap = serve(model, params, [prompt], max_new=6,
                          prefill_chunk=chunk)
    assert snap["serving/prefill/chunks"] == chunks
    assert_engine_matches(ref, model, params, [prompt], results)


# ------------------------------------------------- (c) the shares add up

def test_expert_shares_add_up_to_the_uncut_layer(ref, tiny):
    """Four chips of two experts each (ids 0..1, 2..3, 4..5, 6..7): what
    each share's layer gives, the shared expert counted once, adds up to
    the uncut layer, in the program and in the reference, and each share
    of the program is the reference's."""
    model, params = tiny
    cfg = model.cfg
    layer = {k: v[0] for k, v in params["layers"].items()}
    h = jnp.asarray(np.random.default_rng(4).standard_normal((1, 24, 64)),
                    jnp.float32)

    def proj(name, inp):
        return inp @ layer[name]

    def program(first, count):
        share = Transformer(dataclasses.replace(
            cfg, moe_first_expert=first, moe_experts_held=count))
        w = {**layer, **{k: layer[k][first:first + count]
                         for k in ("w_gate", "w_up", "w_down")}}
        out, stats = share._mlp(w, h, proj, dropless=True)
        return np.asarray(out[0]), np.asarray(stats)

    def reference(first, count):
        w = {**layer, **{k: layer[k][first:first + count]
                         for k in ("w_gate", "w_up", "w_down")}}
        routed, shared = ref.expert_layer(h[0], w, hf_keys(cfg),
                                          experts=(first, count))
        return np.asarray(routed), np.asarray(shared)

    whole, whole_stats = program(0, 8)
    routed_all, shared = reference(0, 8)
    np.testing.assert_allclose(whole, routed_all + shared, atol=TOL, rtol=0)
    assert whole_stats[1] == 24 * cfg.num_experts_per_token

    parts, landed = [], 0
    for first in (0, 2, 4, 6):
        got, stats = program(first, 2)
        routed, _ = reference(first, 2)
        np.testing.assert_allclose(got, routed + shared, atol=TOL, rtol=0)
        parts.append(got - shared)
        landed += int(stats[1])
    assert landed == 24 * cfg.num_experts_per_token   # every pair, once
    np.testing.assert_allclose(sum(parts) + shared, whole, atol=TOL, rtol=0)


def test_engine_serves_one_share_like_the_reference(ref):
    """The whole path on a share: an engine holding experts 2..5 of 8
    against the reference given the same share."""
    cfg = dataclasses.replace(get_model_config("tiny-mla-moe"),
                              moe_first_expert=2, moe_experts_held=4)
    model = Transformer(cfg)
    params = lively(model.init(jax.random.key(5)), seed=5)
    assert params["layers"]["w_gate"].shape[1] == 4
    assert params["layers"]["router"].shape[-1] == 8
    rng = np.random.default_rng(5)
    prompts = [[int(t) for t in rng.integers(3, 500, size=n)]
               for n in (17, 11)]
    results, snap = serve(model, params, prompts, max_new=8)
    assert_engine_matches(ref, model, params, prompts, results,
                          experts=(2, 4))
    # some choices landed elsewhere and added nothing here
    pairs = snap["serving/decode_steps"] * 2 * cfg.num_layers \
        * cfg.num_experts_per_token
    assert 0 < snap["serving/moe/expert_assignments"] < pairs


# ----------------------------------------------------- (d) skewed routing

def test_skewed_routing_drops_nothing(ref, tiny):
    """Every token's first choice is one expert (all embeddings share a
    large component that the router's column 3 reads), at a chunk of 8
    tokens and at decode. GShard capacity at these sizes is 3 slots an
    expert; the dropless path must still match the reference."""
    model, params = tiny
    common = jnp.asarray(
        np.random.default_rng(6).standard_normal((64,)), jnp.float32)
    params = jax.tree.map(lambda x: x, params)
    params["embed"] = {
        "embedding": params["embed"]["embedding"] + 4.0 * common}
    # mlp_norm keeps the direction (its weights are near 1): column 3
    # scores it far above the other columns' O(1) logits
    router = params["layers"]["router"] * 0.1
    params["layers"] = {**params["layers"], "router": router.at[:, :, 3].set(
        4.0 * common / jnp.linalg.norm(common))}
    rng = np.random.default_rng(7)
    prompts = [[int(t) for t in rng.integers(3, 500, size=n)]
               for n in (16, 24, 8)]
    results, snap = serve(model, params, prompts, max_new=6)
    assert_engine_matches(ref, model, params, prompts, results)
    # the skew is real: at decode three rows send six pairs to four
    # experts at most, so few experts take the pairs
    assert (snap["serving/moe/expert_assignments"]
            >= 1.4 * snap["serving/moe/experts_hit"])
    ids = np.asarray(prompts[1])[None]
    hidden = np.asarray(ref.hidden_states(
        ids[0], params["embed"]["embedding"],
        lambda l: {k: params["layers"][k][l] for k in ref.LAYER_LEAVES},
        params["final_norm"], hf_keys(model.cfg)))
    assert np.all(np.isfinite(hidden))


# ------------------------------------------- (e) YaRN and the query scale

def test_yarn_and_query_scale_follow_the_reference(ref, tiny):
    model, params = tiny
    cfg = model.cfg
    keys = hf_keys(cfg)
    rp = ref.rope_parameters(keys)
    # frequencies: the angle at position 1 is the frequency itself
    cos, sin = rotary_angles(jnp.asarray([[1]]), cfg.qk_rope_head_dim,
                             cfg.rope_theta, scaling=cfg.rope_scaling)
    inv = np.asarray(ref.yarn_inv_freq(rp, cfg.qk_rope_head_dim))
    np.testing.assert_allclose(np.asarray(cos)[0, 0], np.cos(inv), atol=1e-6)
    np.testing.assert_allclose(np.asarray(sin)[0, 0], np.sin(inv), atol=1e-6)
    plain = 1.0 / cfg.rope_theta ** (np.arange(0, 8, 2) / 8)
    assert not np.allclose(inv, plain)      # YaRN interpolates some of them
    # softmax scale: head_dim^-0.5 * (0.1 * mscale_all_dim * ln 4 + 1)^2
    m = 0.1 * np.log(4.0) + 1.0
    assert model._softmax_scale == pytest.approx(16 ** -0.5 * m * m)
    assert ref.softmax_scale(keys) == pytest.approx(model._softmax_scale)
    # the query scale is the identity below the original 16 positions
    got = np.asarray(position_query_scale(
        jnp.asarray([0, 15, 16, 31, 32, 47]), cfg.rope_scaling))
    np.testing.assert_allclose(
        got, [1, 1, 1 + 0.1 * np.log(2), 1 + 0.1 * np.log(2),
              1 + 0.1 * np.log(3), 1 + 0.1 * np.log(3)], rtol=1e-6)


def test_positions_beyond_the_original_context_need_the_query_scale(
        ref, tiny):
    """48 positions over an original context of 16: with the scale the
    program is the reference (test_apply); without it the logits past
    position 16 move by 80 times the tolerance, and those before it
    not at all. What the tolerance is for."""
    model, params = tiny
    scaling = {k: v for k, v in model.cfg.rope_scaling.items()
               if k != "llama_4_scaling_beta"}
    unscaled = Transformer(
        dataclasses.replace(model.cfg, rope_scaling=scaling))
    ids = np.random.default_rng(8).integers(3, 500, size=(48,))
    want = reference_logits(ref, model, params, ids)
    got = np.asarray(unscaled.apply(params, jnp.asarray(ids)[None]))[0]
    np.testing.assert_allclose(got[:16], want[:16], atol=TOL, rtol=0)
    assert np.abs(got[16:] - want[16:]).max() > 40 * TOL
