"""Plain reference of the Mistral Small 4 decoder block (Hugging Face
``model_type: mistral4``; the config lineage is DeepSeek-V2/V3's: arXiv
2405.04434 section 2.1 for the latent attention, arXiv 2309.00071 for
YaRN): pre-RMSNorm blocks, multi-head latent attention in its expanded
form, a softmax router over every published expert with the top
``num_experts_per_tok`` renormalised, routed SwiGLU experts beside one
shared SwiGLU expert, an untied output head. Straightforward
``jax.numpy`` in float32 under ``matmul_precision "highest"``: no kernel,
no cache, no batching, one sequence at a time, independent of ``dla_tpu``.

The equations (x is the block input after ``attn_norm``, H heads)::

    cq = rms_norm(x Wq_a);  q = cq Wq_b -> H x [q_nope | q_rope]
    [ckv | kr] = x Wkv_a;   ckv <- rms_norm(ckv);  q_rope, kr <- rope(., pos)
    [k_nope_h | v_h] = ckv Wkv_b
    score_h(t, s) = scale * (q_nope_h(t) . k_nope_h(s) + q_rope_h(t) . kr(s))
    out = concat_h(softmax_causal(score_h) v_h) Wo
    g = softmax(x Wr);  the k largest, renormalised to sum 1, times
    routed_scaling_factor;  y = sum_e w_e swiglu_e(x) + swiglu_shared(x)

Conventions the published config does not spell out (the configuration
file lists each under ``assumed``): the softmax router with no correction
bias; ``scale = qk_head_dim^-0.5 * m^2`` with ``m = 0.1 * mscale_all_dim
* ln(factor) + 1`` and RoPE's own attention factor ``m(mscale) /
m(mscale_all_dim)``; ``rope_interleave`` pairs dimensions ``(2i, 2i+1)``;
``llama_4_scaling_beta`` scales the query of position p by ``1 + beta *
ln(1 + floor(p / original_max_position_embeddings))``.

The share of a deployment: ``experts=(first, count)`` names the routed
experts whose weights were handed in (``w_gate`` has ``count`` of them).
The router still scores all ``n_routed_experts_published`` outputs;
choices that land on an expert outside the share add nothing, and that
partial sum (plus the shared expert, which every chip computes) goes on
to the next layer. By default every expert is held.

Memory: queries are taken ``Q_BLOCK`` at a time so the scores of an
8,192-token sequence fit beside 10 GiB of served weights, and each
expert's weights are upcast one expert at a time. Neither changes a
number: every row's softmax still runs over its whole causal prefix.
"""
from __future__ import annotations

import functools
import math
from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

F32 = jnp.float32
Q_BLOCK = 512

#: leaves of one block, as the caller hands them in (stored type; upcast here)
LAYER_LEAVES = (
    "attn_norm", "wq_a", "q_norm", "wq_b", "wkv_a", "kv_norm", "wkv_b", "wo",
    "mlp_norm", "router", "w_gate", "w_up", "w_down",
    "ws_gate", "ws_up", "ws_down")


@jax.jit
def take_layer(stacked: Dict, l) -> Dict:
    """Block l's weights out of leaves stacked along a leading layer axis
    (how the caller stores them), in one jitted call."""
    return {k: jax.lax.dynamic_index_in_dim(v, l, 0, keepdims=False)
            for k, v in stacked.items()}


def _rms_norm(x, weight, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * weight


def _mscale(factor: float, m: float) -> float:
    return 1.0 if factor <= 1.0 else 0.1 * m * math.log(factor) + 1.0


def rope_parameters(cfg: Dict) -> Dict:
    rp = dict(cfg.get("rope_parameters") or cfg.get("rope_scaling") or {})
    rp.setdefault("rope_theta", cfg.get("rope_theta", 10000.0))
    return rp


def yarn_inv_freq(rp: Dict, dim: int):
    """YaRN's frequencies for a rotary slice of ``dim``: dimensions that
    turn more than ``beta_fast`` times over the original context keep
    their frequency, those under ``beta_slow`` turns divide it by
    ``factor``, a linear ramp between (arXiv 2309.00071, section 3.2)."""
    theta = float(rp["rope_theta"])
    inv = 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=F32) / dim))
    if str(rp.get("rope_type") or rp.get("type") or "default") != "yarn":
        return inv
    factor = float(rp["factor"])
    orig = float(rp["original_max_position_embeddings"])

    def turns_dim(n):
        return dim * math.log(orig / (n * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(turns_dim(float(rp.get("beta_fast") or 32))), 0)
    high = min(math.ceil(turns_dim(float(rp.get("beta_slow") or 1))), dim - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=F32) - low) / (high - low),
                    0.0, 1.0)
    return inv / factor * ramp + inv * (1.0 - ramp)


def softmax_scale(cfg: Dict) -> float:
    rp = rope_parameters(cfg)
    dqk = int(cfg["qk_nope_head_dim"]) + int(cfg["qk_rope_head_dim"])
    m = _mscale(float(rp.get("factor", 1.0)),
                float(rp.get("mscale_all_dim") or 0.0))
    return dqk ** -0.5 * m * m


def _rope(x, positions, inv_freq, attn_factor):
    """x [T, H, d]: the pair (x[2i], x[2i+1]) turns by positions * inv_freq[i],
    in place."""
    ang = positions.astype(F32)[:, None] * inv_freq[None, :]
    cos = (jnp.cos(ang) * attn_factor)[:, None, :]
    sin = (jnp.sin(ang) * attn_factor)[:, None, :]
    even, odd = x[..., 0::2], x[..., 1::2]
    out = jnp.stack([even * cos - odd * sin, odd * cos + even * sin], -1)
    return out.reshape(x.shape)


def _swiglu(h, gate, up, down):
    return (jax.nn.silu(h @ gate) * (h @ up)) @ down


def _experts(h, w: Dict, top_k, routed_scale, first):
    """h [T, D] (after ``mlp_norm``) -> (what the held routed experts add,
    what the shared expert adds), each [T, D]. Called under "highest"."""
    probs = jax.nn.softmax(h @ w["router"].astype(F32), axis=-1)   # [T, E]
    top_p, top_e = jax.lax.top_k(probs, top_k)
    top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True) * routed_scale
    held = w["w_gate"].shape[0]
    # [T, held]: the weight each held expert's output gets per token
    combine = jnp.sum(
        top_p[..., None] * (top_e[..., None]
                            == first + jnp.arange(held)[None, None, :]),
        axis=1)

    def expert(acc, xs):
        gate, up, down, c = xs
        y = _swiglu(h, gate.astype(F32), up.astype(F32), down.astype(F32))
        return acc + c[:, None] * y, None

    routed, _ = jax.lax.scan(
        expert, jnp.zeros_like(h),
        (w["w_gate"], w["w_up"], w["w_down"], combine.T))
    shared = _swiglu(h, w["ws_gate"].astype(F32), w["ws_up"].astype(F32),
                     w["ws_down"].astype(F32))
    return routed, shared


def expert_layer(h, w: Dict, cfg: Dict,
                 experts: Optional[Tuple[int, int]] = None):
    """The expert layer alone: h [T, D] float32 (after ``mlp_norm``) ->
    (routed [T, D], shared [T, D]) for the share ``experts`` = (first,
    count) whose weights ``w`` holds. The shares of a deployment add up:
    the routed parts of all of them, and the shared part once, are the
    uncut layer."""
    with jax.default_matmul_precision("highest"):
        return _experts(
            jnp.asarray(h, F32), w, int(cfg["num_experts_per_tok"]),
            float(cfg.get("routed_scaling_factor", 1.0)),
            int(experts[0]) if experts else 0)


@functools.partial(jax.jit, static_argnames=(
    "heads", "nope", "rope", "vdim", "rank", "eps", "scale", "top_k",
    "routed_scale", "first", "beta", "orig", "attn_factor"))
def _block(x, w: Dict, positions, inv_freq, *, heads, nope, rope, vdim, rank,
           eps, scale, top_k, routed_scale, first, beta, orig, attn_factor):
    with jax.default_matmul_precision("highest"):
        small = {k: v.astype(F32) for k, v in w.items()
                 if k not in ("w_gate", "w_up", "w_down")}
        t, _ = x.shape
        h = _rms_norm(x, small["attn_norm"], eps)
        cq = _rms_norm(h @ small["wq_a"], small["q_norm"], eps)
        q = (cq @ small["wq_b"]).reshape(t, heads, nope + rope)
        kv = h @ small["wkv_a"]
        ckv = _rms_norm(kv[:, :rank], small["kv_norm"], eps)
        kr = _rope(kv[:, None, rank:], positions, inv_freq, attn_factor)
        q_rope = _rope(q[..., nope:], positions, inv_freq, attn_factor)
        kvb = (ckv @ small["wkv_b"]).reshape(t, heads, nope + vdim)
        k = jnp.concatenate(
            [kvb[..., :nope], jnp.broadcast_to(kr, (t, heads, rope))], -1)
        v = kvb[..., nope:]
        q = jnp.concatenate([q[..., :nope], q_rope], -1)
        if beta:
            q = q * (1.0 + beta * jnp.log1p(jnp.floor(
                positions.astype(F32) / orig)))[:, None, None]

        pad = (-t) % Q_BLOCK
        qb = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(
            -1, Q_BLOCK, heads, nope + rope)
        idx = jnp.arange(t + pad).reshape(-1, Q_BLOCK)

        def attend(_, xs):
            qi, rows = xs
            scores = jnp.einsum("qhd,shd->hqs", qi, k) * scale
            seen = jnp.arange(t)[None, :] <= rows[:, None]      # causal
            probs = jax.nn.softmax(
                jnp.where(seen[None], scores, -jnp.inf), axis=-1)
            return None, jnp.einsum("hqs,shd->qhd", probs, v)

        _, attn = jax.lax.scan(attend, None, (qb, idx))
        attn = attn.reshape(t + pad, heads * vdim)[:t]
        x = x + attn @ small["wo"]

        h = _rms_norm(x, small["mlp_norm"], eps)
        routed, shared = _experts(h, w, top_k, routed_scale, first)
        return x + routed + shared


def hidden_states(tokens, embedding, layer: Callable[[int], Dict],
                  final_norm, cfg: Dict,
                  experts: Optional[Tuple[int, int]] = None):
    """[T] token ids -> [T, D] float32 after the final norm. ``layer(l)``
    gives block l's weights (``LAYER_LEAVES``); ``cfg`` uses the Hugging
    Face key names; ``experts`` = (first, count) of the routed experts in
    ``w_gate`` / ``w_up`` / ``w_down`` (default: all of them)."""
    tokens = jnp.asarray(tokens, jnp.int32)
    positions = jnp.arange(tokens.shape[0], dtype=jnp.int32)
    rp = rope_parameters(cfg)
    rope = int(cfg["qk_rope_head_dim"])
    factor = float(rp.get("factor", 1.0))
    yarn = str(rp.get("rope_type") or rp.get("type")) == "yarn"
    attn_factor = 1.0
    if yarn and rp.get("mscale") and rp.get("mscale_all_dim"):
        attn_factor = (_mscale(factor, float(rp["mscale"]))
                       / _mscale(factor, float(rp["mscale_all_dim"])))
    elif yarn:
        attn_factor = _mscale(factor, 1.0)
    first = int(experts[0]) if experts else 0
    x = jnp.take(embedding, tokens, axis=0).astype(F32)
    for l in range(int(cfg["num_hidden_layers"])):
        x = _block(
            x, layer(l), positions, yarn_inv_freq(rp, rope),
            heads=int(cfg["num_attention_heads"]),
            nope=int(cfg["qk_nope_head_dim"]), rope=rope,
            vdim=int(cfg["v_head_dim"]), rank=int(cfg["kv_lora_rank"]),
            eps=float(cfg["rms_norm_eps"]), scale=softmax_scale(cfg),
            top_k=int(cfg["num_experts_per_tok"]),
            routed_scale=float(cfg.get("routed_scaling_factor", 1.0)),
            first=first, beta=float(rp.get("llama_4_scaling_beta") or 0.0),
            orig=float(rp.get("original_max_position_embeddings") or 1.0),
            attn_factor=float(attn_factor))
    return _rms_norm(x, final_norm.astype(F32), float(cfg["rms_norm_eps"]))


@jax.jit
def logits(hidden_rows, lm_head):
    """[N, D] float32 rows -> [N, V] float32 logits."""
    with jax.default_matmul_precision("highest"):
        return hidden_rows @ lm_head.astype(F32)
