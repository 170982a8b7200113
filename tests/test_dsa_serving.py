"""Learned sparse attention (DSA) inside latent attention, sigmoid-routed
experts with a correction bias after a leading dense layer (the GLM-5
block): the paged engine against the plain float32 reference the
benchmark keeps (``perfbench/reference/glm5_block.py``), at tiny widths in
float32 on the CPU, on seeded random weights. The indexer keeps the top 8
of contexts of 30 to 65 tokens, so the selection prunes. One engine
serves the comparisons (``served``): each engine compiles its programs
anew, seconds on the CPU.

Tolerance, one for all: both sides compute in float32 but in another
order (the engine gathers the selected rows out of pages and sorts
(token, choice) pairs into a grouped matmul; the reference indexes its
rows and weights every held expert's output for every token), so they
differ by a few float32 ulps of the largest activation per layer: 1e-5
to 1e-4 on logits of size 5 to 10. ``TOL`` = 5e-4 is a few times that;
a wrong selection moves the log-probabilities by 0.1 and more (the
``wrong_selection_last_k`` case), and an index score that differs in its
last bit can swap two nearly equal columns, which the seeds here do not
meet.
"""
import dataclasses
import importlib.util
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dla_tpu.generation.engine import GenerationConfig
from dla_tpu.models.config import get_model_config
from dla_tpu.models.hf_import import hf_config_to_model_config
from dla_tpu.models.transformer import Transformer
from dla_tpu.ops.moe import route_top_k
from dla_tpu.serving import ServingConfig, ServingEngine

TOL = 5e-4
ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def ref():
    spec = importlib.util.spec_from_file_location(
        "glm5_block", ROOT / "perfbench" / "reference" / "glm5_block.py")
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
        "index_n_heads": cfg.index_n_heads,
        "index_head_dim": cfg.index_head_dim, "index_topk": cfg.index_topk,
        "rms_norm_eps": cfg.rms_norm_eps,
        "first_k_dense_replace": cfg.first_k_dense,
        "num_experts_per_tok": cfg.num_experts_per_token,
        "routed_scaling_factor": cfg.moe_routed_scale,
        "rope_parameters": {"rope_theta": cfg.rope_theta},
    }


def lively(model, seed=0):
    """Seeded weights of ``init``'s shapes, drawn on the host (compiling
    ``init`` takes seconds): N(0, 0.02)-like weights leave a 64-wide toy
    nearly linear (attention uniform, index scores undecided), so the
    matrices get a gain near 1, the embedding unit rows, and the norm
    weights, the index norm's bias and the router's correction bias are
    drawn away from their defaults, so that a misplaced one would show."""
    rng = np.random.default_rng(seed)

    def go(path, leaf):
        name = str(getattr(path[-1], "key", path[-1]))
        x = rng.standard_normal(leaf.shape)
        if name.endswith("norm"):
            x = 1.0 + 0.3 * x
        elif name in ("wi_k_norm_bias", "router_bias"):
            x = 0.3 * x
        elif name != "embedding":
            x = 0.12 * x
        return jnp.asarray(x.astype(leaf.dtype))
    return jax.tree_util.tree_map_with_path(
        go, jax.eval_shape(model.init, jax.random.key(0)))


@pytest.fixture(scope="module")
def tiny():
    model = Transformer(get_model_config("tiny-dsa"))
    return model, lively(model)


def serve(model, params, prompts, max_new=4):
    """The prompts one after another through one paged engine with the
    prefix cache on (chunked prefill, then decode); returns the finished
    requests and the engine's metrics snapshot."""
    gen = GenerationConfig(max_new_tokens=max_new, do_sample=False,
                           eos_token_id=-1)
    eng = ServingEngine(model, params, gen, ServingConfig(
        page_size=4, num_pages=128, num_slots=2, max_model_len=96,
        prefill_chunk=16, prefix_cache=True))
    try:
        results = []
        for p in prompts:
            rid = eng.submit(p, max_new)
            while eng.has_work():
                eng.step()
            results.append(eng.result(rid))
        return results, eng.metrics.snapshot()
    finally:
        eng.close()


def reference_logprobs(ref, model, params, prompt, answer, select="top"):
    """The reference's log-probability of each answer token, teacher
    forced over prompt + answer."""
    layer = ref.layer_getter(params, hf_keys(model.cfg))
    hidden, _ = ref.hidden_states(
        np.asarray((prompt + answer)[:-1]), params["embed"]["embedding"],
        layer, params["final_norm"], hf_keys(model.cfg), select=select)
    rows = np.asarray(ref.logits(hidden[len(prompt) - 1:],
                                 params["lm_head"]))
    logp = np.asarray(jax.nn.log_softmax(rows, axis=-1))
    return logp[np.arange(len(answer)), answer]


def prompts_of(seed, *lengths):
    rng = np.random.default_rng(seed)
    return [[int(t) for t in rng.integers(3, 500, size=n)] for n in lengths]


@pytest.fixture(scope="module")
def served(tiny):
    """A 48-token document then two questions on it (9 and 14 tokens),
    one request after the other through an engine with the prefix cache
    on, four greedy tokens each: the first prefills the document in
    three chunks of 16, the second finds its pages in the cache."""
    model, params = tiny
    doc, q1, q2 = prompts_of(2, 48, 9, 14)
    prompts = [doc + q1, doc + q2]
    results, snap = serve(model, params, prompts)
    return prompts, results, snap


# --------------------------------------------- the engine and the reference

#: request of ``served``, the reference's selection, and whether the
#: engine's record has to match that reference (else it has to miss it)
CASES = {
    # chunked prefill and paged decode, the indexer's top 8 of up to 65
    # columns, against the reference's selection and masked softmax
    "first_request": (0, "top", True),
    # the document's pages, latent rows and index keys alike, come from
    # the prefix cache, and what the engine computes on them is the
    # reference's over the whole sequence
    "prefix_cache_hit": (1, "top", True),
    # the last 8 columns in place of the indexer's top 8: far outside the
    # tolerance of the engine's record, so the comparison catches an
    # engine that reads them
    "wrong_selection_last_k": (1, "last", False),
}


@pytest.mark.parametrize("case", CASES)
def test_engine_against_reference(ref, tiny, served, case):
    model, params = tiny
    prompts, results, snap = served
    i, select, match = CASES[case]
    got = np.asarray(results[i].generated_logprobs)
    want = reference_logprobs(ref, model, params, prompts[i],
                              list(results[i].generated), select=select)
    if match:
        np.testing.assert_allclose(got, want, atol=TOL, rtol=0)
    else:
        assert np.max(np.abs(got - want)) > 100 * TOL
    # a token stores a latent row (16 + 8) and an index key (16), float32,
    # in each of 3 layers; the second request's document came from the
    # cache; the indexer pruned
    assert snap["serving/kv_bytes_per_token"] == (16 + 8 + 16) * 4 * 3
    assert snap["serving/prefix_cache/hit_tokens"] == 48
    assert snap["serving/prompt_tokens"] == sum(map(len, prompts))
    assert snap["serving/dsa/selected_tokens"] < \
        snap["serving/dsa/live_tokens"]


def test_topk_over_the_context_is_dense_latent_attention(tiny):
    """With index_topk at least the window, sparse attention over a paged
    pool selects every column up to the query and is the plain causal
    softmax of latent attention over the window's rows, computed here by
    hand: one query a slot (the decode step) and a chunk of eight, in
    one compiled program."""
    model, _ = tiny
    wide = Transformer(dataclasses.replace(model.cfg, index_topk=1024))
    cfg = wide.cfg
    rng = np.random.default_rng(5)
    b, heads, width, page = 2, cfg.num_heads, cfg.latent_row_width_, 4
    tables = np.asarray([[3, 0, 5, 1], [2, 6, 4, 7]], np.int32)
    window = tables.shape[1] * page
    pools = tuple(rng.standard_normal((2, 8, page, 1, w)).astype(np.float32)
                  for w in (width, cfg.index_head_dim))
    rows = pools[0][1][tables].reshape(b, window, width)
    cases = []
    for queries in (1, 8):
        cases.append(tuple(
            rng.standard_normal((b, queries) + shape).astype(np.float32)
            for shape in ((heads, width),
                          (cfg.index_n_heads, cfg.index_head_dim),
                          (cfg.index_n_heads,)))
            + (np.asarray([[11], [15]], np.int32)
               - np.arange(queries, dtype=np.int32)[::-1],))
    outs = jax.device_get(jax.jit(lambda cases, pools: [
        wide._sparse_latent_attend(*case, pools, 1, jnp.asarray(tables))
        for case in cases])(cases, pools))
    for (q_lat, _, _, positions), (out, idx) in zip(cases, outs):
        for i, t in np.ndindex(positions.shape):
            p = int(positions[i, t])
            assert sorted(idx[i, t, :p + 1]) == list(range(p + 1))
            logits = q_lat[i, t] @ rows[i, :p + 1].T * wide._softmax_scale
            probs = np.exp(logits - logits.max(-1, keepdims=True))
            probs /= probs.sum(-1, keepdims=True)               # [H, p + 1]
            np.testing.assert_allclose(out[i, t], probs @ rows[i, :p + 1],
                                       atol=TOL, rtol=0)


# ------------------------------------------------------------ the experts

def test_sigmoid_router_with_bias_by_hand():
    """Sigmoid scores, the choice by score + bias, the weights the chosen
    scores without the bias over their sum, times the scale: where the
    bias reorders the scores, the choice follows the bias and the
    weights do not."""
    h = jnp.asarray([[1.0, 0.0]], jnp.float32)
    router = jnp.asarray([[2.0, 1.0, 0.0, -1.0], [0.0] * 4], jnp.float32)
    bias = jnp.asarray([0.0, 0.0, 0.5, 0.0], jnp.float32)
    _, top_e, top_w = jax.jit(lambda *a: route_top_k(
        *a[:2], 2, 2.5, "sigmoid", a[2]))(h, router, bias)
    s = 1.0 / (1.0 + np.exp(-np.asarray([2.0, 1.0, 0.0, -1.0])))
    # 0.5 + 0.5 beats 0.73 and 0.88 + 0 only just loses to it
    assert list(np.asarray(top_e[0])) == [2, 0]
    np.testing.assert_allclose(np.asarray(top_w[0]),
                               2.5 * s[[2, 0]] / s[[2, 0]].sum(), rtol=1e-6)


def test_expert_shares_add_up_to_the_uncut_layer(ref, tiny):
    """Two chips of four experts each (ids 0..3, 4..7): what each share's
    layer gives in the program, the shared expert counted once, adds up
    to the uncut reference layer."""
    model, params = tiny
    cfg = model.cfg
    layer = {k: np.asarray(v)[0] for k, v in params["layers"].items()}
    h = jnp.asarray(np.random.default_rng(4).standard_normal((1, 24, 64)),
                    jnp.float32)

    def share(w, first, count):
        """A chip's weights: the routed experts ``first`` ..
        ``first + count - 1``, everything else whole."""
        return {**w, **{k: w[k][first:first + count]
                        for k in ("w_gate", "w_up", "w_down")}}

    def both(w):
        """The two chips' layers in the program, and the uncut reference
        layer, in one compiled program."""
        def proj(name, inp):
            return inp @ w[name]
        parts = [Transformer(dataclasses.replace(
            cfg, moe_experts_held=4, moe_first_expert=first))._mlp(
                share(w, first, 4), h, proj, dropless=True)[0][0]
            for first in (0, 4)]
        return parts, ref.expert_layer(h[0], w, hf_keys(cfg),
                                       experts=(0, 8))

    assert Transformer(dataclasses.replace(
        cfg, moe_experts_held=4)).cfg.experts_held_ == 4
    parts, (routed, shared) = jax.jit(both)(layer)
    parts, routed, shared = (np.asarray(parts), np.asarray(routed),
                             np.asarray(shared))
    # each chip adds the shared expert once of its own: counted once
    np.testing.assert_allclose(parts.sum(0) - shared, routed + shared,
                               atol=TOL, rtol=0)


# ------------------------------------------------------------- the import

def test_hf_import_reads_the_glm5_keys():
    """The benchmark's GLM-5 file imports: sigmoid scores with the
    correction bias, one leading dense layer, the indexer's keys, the
    held share of the experts; group-limited routing is still refused."""
    hf = json.loads((ROOT / "perfbench" / "configs" /
                     "glm5_serve_d5_ep16.json").read_text())
    cfg = hf_config_to_model_config(hf, dtype="bfloat16",
                                    param_dtype="bfloat16")
    assert (cfg.moe_scoring, cfg.first_k_dense, cfg.index_topk,
            cfg.index_n_heads, cfg.index_head_dim) == ("sigmoid", 1, 2048,
                                                        32, 128)
    assert cfg.index_rope_interleave and cfg.rope_interleave
    assert (cfg.num_experts, cfg.experts_held_, cfg.moe_first_expert,
            cfg.moe_routed_scale) == (256, 16, 0, 2.5)
    assert cfg.latent_row_width_ == 640 and cfg.rope_theta == 1e6
    model = Transformer(cfg)
    assert model.cache_rows() == ((1, 640), (1, 128))
    with pytest.raises(NotImplementedError, match="group-limited"):
        hf_config_to_model_config({**hf, "n_group": 8, "topk_group": 4})
