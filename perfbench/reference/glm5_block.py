"""Plain reference of the GLM-5 decoder (Hugging Face ``model_type:
glm_moe_dsa``; the key names are DeepSeek-V3.2's, whose ``Indexer`` and
sparse latent attention it follows: arXiv 2405.04434 section 2.1 for the
latent attention, the DeepSeek-V3.2-Exp report for the indexer).
Straightforward ``jax.numpy`` in float32 under ``matmul_precision
"highest"``: no kernel, no cache, no batching, one sequence at a time,
independent of ``dla_tpu``.

The equations (every layer; x the block input, H heads, Hi indexer
heads, d_i the indexer's head size, k = index_topk)::

    h = rms_norm(x);  cq = rms_norm(h Wq_a);  q = cq Wq_b -> H x [q_nope | q_rope]
    [ckv | kr] = h Wkv_a;  ckv <- rms_norm(ckv);  q_rope, kr <- rope(., pos)
    [k_nope_h | v_h] = ckv Wkv_b
    qi = cq Wi_q -> Hi x d_i;  ki = layer_norm(h Wi_k);  both rope'd on
         their first qk_rope_head_dim dims
    w = h Wi_w * Hi^-0.5
    I(t, s) = sum_h w_h d_i^-0.5 relu(qi_h(t) . ki(s)),  s <= t
    S_t = the k columns of largest I(t, .) (all of them when t < k)
    score_h(t, s) = (q_nope_h(t) . k_nope_h(s) + q_rope_h(t) . kr(s))
                    * qk_head_dim^-0.5,  softmax over s in S_t alone
    x <- x + concat_h(sum_s p_h(t, s) v_h(s)) Wo
    h = rms_norm(x)
    layer < first_k_dense_replace:  x <- x + swiglu(h)   (intermediate_size)
    else:  s = sigmoid(h Wr) over every published expert (float32); the
           top num_experts_per_tok of s + e_score_correction_bias chosen;
           g = the chosen s over their sum * routed_scaling_factor;
           x <- x + sum_e g_e swiglu_e(h) + swiglu_shared(h)

Departures, none of which changes a number in exact arithmetic: the
scores are computed in the absorbed form, q_nope_h . k_nope_h(s) =
(q_nope_h Wk_h^T) . ckv(s) and the output sum_s p v_h(s) = (sum_s p
ckv(s)) Wv_h, because the expanded keys and values of a 40k-token
sequence are 4.7 GB in float32; rope pairs are adjacent dimensions
(``rope_interleave`` / ``indexer_rope_interleave``); the index keys'
LayerNorm takes DeepSeek-V3.2's eps of 1e-6; the Hadamard rotation and
the fp8 rounding DeepSeek-V3.2 applies to qi and ki are left out (the
rotation is orthogonal; the rounding is an inference shortcut);
multi-token prediction is not part of the main model's logits. The
configuration file lists each under ``assumed``.

The share of a deployment: ``experts=(first, count)`` names the routed
experts whose weights were handed in. The router still scores every
published expert; choices that land outside the share add nothing, and
that partial sum, plus the shared expert (which every chip computes),
goes on to the next layer. ``expert_layer`` gives the two parts apart.

``lowp=True`` is the reading that sets the limits of ``correct``: every
matmul operand and every cached row (latent rows and index keys) rounded
to e4m3 (a scale a row), the nearest precision under the configuration's
bfloat16. ``select="last"`` is the wrong engine the limits must also
refuse: each query attends the last k columns instead of the indexer's
top k.

Memory: every per-token projection runs ``T_BLOCK`` tokens at a time and
every query's indexer scores, selection and attention ``Q_BLOCK`` queries
at a time, so a 40k-token sequence fits beside the served weights; each
expert's weights are upcast one expert at a time. None changes a number.
"""
from __future__ import annotations

import functools
from typing import Callable, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

F32 = jnp.float32
Q_BLOCK = 128
T_BLOCK = 2048
INDEX_NORM_EPS = 1e-6
#: the leaves every layer has, and those of each kind of feed-forward
ATTN_LEAVES = ("attn_norm", "wq_a", "q_norm", "wq_b", "wkv_a", "kv_norm",
               "wkv_b", "wo", "wi_q", "wi_k", "wi_k_norm", "wi_k_norm_bias",
               "wi_w", "mlp_norm")
DENSE_LEAVES = ("w_gate", "w_up", "w_down")
EXPERT_LEAVES = ("router", "router_bias", "w_gate", "w_up", "w_down",
                 "ws_gate", "ws_up", "ws_down")


@jax.jit
def _take(stacked: Dict, l) -> Dict:
    return {k: jax.lax.dynamic_index_in_dim(v, l, 0, keepdims=False)
            for k, v in stacked.items()}


def layer_getter(params: Dict, cfg: Dict) -> Callable[[int], Dict]:
    """``layer(l)`` over the program's tree: the leading dense layers
    live in ``params["dense_layers"]``, the expert layers in
    ``params["layers"]`` (index l - first_k_dense_replace)."""
    k = int(cfg.get("first_k_dense_replace") or 0)
    dense = {n: params["dense_layers"][n]
             for n in ATTN_LEAVES + DENSE_LEAVES} if k else None
    moe = {n: params["layers"][n] for n in ATTN_LEAVES + EXPERT_LEAVES}

    def layer(l: int) -> Dict:
        if l < k:
            return _take(dense, jnp.asarray(l, jnp.int32))
        return _take(moe, jnp.asarray(l - k, jnp.int32))
    return layer


def _e4m3(x, axis):
    """Round to float8 e4m3 with one scale along ``axis``."""
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 448.0 + 1e-30
    return (x / scale).astype(jnp.float8_e4m3fn).astype(F32) * scale


def _mm(a, w, lowp):
    if lowp:
        a, w = _e4m3(a, -1), _e4m3(w, 0)
    return a @ w


def _rms_norm(x, weight, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * weight


def _layer_norm(x, weight, bias, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * weight + bias


def _rope(x, positions, theta):
    """x [T, H, d]: the pair (x[2i], x[2i+1]) turns by positions *
    theta^(-2i/d), in place."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=F32) / d))
    ang = positions.astype(F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin, odd * cos + even * sin],
                     -1).reshape(x.shape)


def _swiglu(h, gate, up, down, lowp):
    return _mm(jax.nn.silu(_mm(h, gate, lowp)) * _mm(h, up, lowp), down,
               lowp)


def _experts(h, w: Dict, top_k, routed_scale, first, lowp):
    """h [T, D] (after ``mlp_norm``) -> (what the held routed experts add,
    what the shared expert adds), each [T, D]."""
    scores = jax.nn.sigmoid(_mm(h, w["router"].astype(F32), lowp))
    _, top_e = jax.lax.top_k(scores + w["router_bias"].astype(F32), top_k)
    top_s = jnp.take_along_axis(scores, top_e, axis=-1)
    top_s = top_s / (jnp.sum(top_s, axis=-1, keepdims=True) + 1e-20)
    top_s = top_s * routed_scale
    held = w["w_gate"].shape[0]
    combine = jnp.sum(
        top_s[..., None] * (top_e[..., None]
                            == first + jnp.arange(held)[None, None, :]),
        axis=1)                                                 # [T, held]

    def expert(acc, xs):
        gate, up, down, c = xs
        y = _swiglu(h, gate.astype(F32), up.astype(F32), down.astype(F32),
                    lowp)
        return acc + c[:, None] * y, None

    routed, _ = jax.lax.scan(
        expert, jnp.zeros_like(h),
        (w["w_gate"], w["w_up"], w["w_down"], combine.T))
    shared = _swiglu(h, w["ws_gate"].astype(F32), w["ws_up"].astype(F32),
                     w["ws_down"].astype(F32), lowp)
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
            int(experts[0]) if experts else 0, False)


def _blocks(x, size):
    """[T, ...] -> [T / size, size, ...] (T a multiple of size)."""
    return x.reshape((-1, size) + x.shape[1:])


@functools.partial(jax.jit, static_argnames=(
    "dims", "moe", "top_k", "routed_scale", "first", "lowp", "select",
    "n_sets"))
def _block(x, w: Dict, set_at, *, dims, moe, top_k, routed_scale,
           first, lowp, select, n_sets):
    (heads, nope, rope, vdim, rank, hi, di, k, eps, theta, scale) = dims
    with jax.default_matmul_precision("highest"):
        small = {n: v.astype(F32) for n, v in w.items()
                 if n not in ("w_gate", "w_up", "w_down")}
        t = x.shape[0]
        tb = min(T_BLOCK, t)
        k = min(k, t)
        positions = jnp.arange(t, dtype=jnp.int32)

        def row(x_b):
            """One block of tokens: what a cache would keep of them and
            what their queries start from."""
            x_b, pos = x_b
            h = _rms_norm(x_b, small["attn_norm"], eps)
            cq = _rms_norm(_mm(h, small["wq_a"], lowp), small["q_norm"], eps)
            kv = _mm(h, small["wkv_a"], lowp)
            ckv = _rms_norm(kv[:, :rank], small["kv_norm"], eps)
            kr = _rope(kv[:, None, rank:], pos, theta)[:, 0]
            ki = _layer_norm(_mm(h, small["wi_k"], lowp), small["wi_k_norm"],
                             small["wi_k_norm_bias"], INDEX_NORM_EPS)
            ki = jnp.concatenate(
                [_rope(ki[:, None, :rope], pos, theta)[:, 0], ki[:, rope:]],
                -1)
            wts = _mm(h, small["wi_w"], lowp) * hi ** -0.5 * di ** -0.5
            if lowp:        # the cached rows, in the lower precision
                ckv, kr, ki = _e4m3(ckv, -1), _e4m3(kr, -1), _e4m3(ki, -1)
            return cq, ckv, kr, ki, wts

        _, (cq, ckv, kr, ki, wts) = jax.lax.scan(
            lambda c, xs: (c, row(xs)), None,
            (_blocks(x, tb), _blocks(positions, tb)))
        cq, ckv, kr, ki, wts = (a.reshape((t,) + a.shape[2:])
                                for a in (cq, ckv, kr, ki, wts))
        wkv_b = small["wkv_b"].reshape(rank, heads, nope + vdim)
        cols = jnp.arange(t)

        def attend(_, xs):
            cq_b, w_b, pos = xs                                # [Q, ...]
            q = _mm(cq_b, small["wq_b"], lowp).reshape(-1, heads, nope + rope)
            q_nope, q_rope = q[..., :nope], _rope(q[..., nope:], pos, theta)
            qi = _mm(cq_b, small["wi_q"], lowp).reshape(-1, hi, di)
            qi = jnp.concatenate([_rope(qi[..., :rope], pos, theta),
                                  qi[..., rope:]], -1)
            index = jnp.einsum("qhd,sd->qhs", qi, ki)
            index = jnp.einsum("qhs,qh->qs", jax.nn.relu(index), w_b)
            seen = cols[None, :] <= pos[:, None]                 # causal
            index = jnp.where(seen, index, -jnp.inf)
            if select == "last":
                sel = pos[:, None] - jnp.arange(k)[None, :]
                ok = sel >= 0
                sel = jnp.maximum(sel, 0)
            else:
                top, sel = jax.lax.top_k(index, k)               # [Q, k]
                ok = jnp.isfinite(top)
            c_s, r_s = ckv[sel], kr[sel]                         # [Q, k, .]
            q_lat = jnp.einsum("qhn,rhn->qhr", q_nope, wkv_b[..., :nope])
            if lowp:
                q_lat, q_rope = _e4m3(q_lat, -1), _e4m3(q_rope, -1)
            logits = (jnp.einsum("qhr,qkr->qhk", q_lat, c_s)
                      + jnp.einsum("qhe,qke->qhk", q_rope, r_s)) * scale
            probs = jax.nn.softmax(
                jnp.where(ok[:, None, :], logits, -jnp.inf), axis=-1)
            u = jnp.einsum("qhk,qkr->qhr", probs, c_s)
            o = jnp.einsum("qhr,rhv->qhv", u, wkv_b[..., nope:])
            return None, (_mm(o.reshape(o.shape[0], heads * vdim),
                              small["wo"], lowp), sel)

        _, (attn, sel) = jax.lax.scan(
            attend, None, (_blocks(cq, Q_BLOCK), _blocks(wts, Q_BLOCK),
                           _blocks(positions, Q_BLOCK)))
        x = x + attn.reshape(t, -1)
        sets = sel.reshape(t, -1)[set_at] if n_sets else None

        def ffn(_, x_b):
            h = _rms_norm(x_b, small["mlp_norm"], eps)
            if not moe:
                return None, x_b + _swiglu(
                    h, w["w_gate"].astype(F32), w["w_up"].astype(F32),
                    w["w_down"].astype(F32), lowp)
            routed, shared = _experts(h, w, top_k, routed_scale, first, lowp)
            return None, x_b + routed + shared

        _, x = jax.lax.scan(ffn, None, _blocks(x, tb))
        return x.reshape(t, -1), sets


def hidden_states(tokens, embedding, layer: Callable[[int], Dict],
                  final_norm, cfg: Dict,
                  experts: Optional[Tuple[int, int]] = None,
                  lowp: bool = False, select: str = "top",
                  sets_at: Sequence[int] = ()):
    """[T] token ids -> ([T, D] float32 after the final norm, [layers]
    of [len(sets_at), k] the columns each query at ``sets_at`` attends).
    ``layer(l)`` gives block l's weights; ``cfg`` uses the Hugging Face
    key names; ``experts`` = (first, count) of the routed experts handed
    in. The ids are padded to whole blocks: a pad after the sequence
    changes no earlier row (every query's window ends at its own
    position, and the feed-forward is a token's own)."""
    tokens = jnp.asarray(tokens, jnp.int32)
    n = tokens.shape[0]
    size = Q_BLOCK if n <= T_BLOCK else T_BLOCK
    tokens = jnp.pad(tokens, (0, -n % size))
    rp = dict(cfg.get("rope_parameters") or {})
    theta = float(rp.get("rope_theta", cfg.get("rope_theta", 10000.0)))
    nope, rope = int(cfg["qk_nope_head_dim"]), int(cfg["qk_rope_head_dim"])
    dims = (int(cfg["num_attention_heads"]), nope, rope,
            int(cfg["v_head_dim"]), int(cfg["kv_lora_rank"]),
            int(cfg["index_n_heads"]), int(cfg["index_head_dim"]),
            int(cfg["index_topk"]), float(cfg["rms_norm_eps"]), theta,
            float(nope + rope) ** -0.5)
    set_at = jnp.asarray(list(sets_at) or [0], jnp.int32)
    first = int(experts[0]) if experts else 0
    dense = int(cfg.get("first_k_dense_replace") or 0)
    x = jnp.take(embedding, tokens, axis=0).astype(F32)
    sets = []
    for l in range(int(cfg["num_hidden_layers"])):
        x, s = _block(
            x, layer(l), set_at,
            dims=dims, moe=l >= dense,
            top_k=int(cfg["num_experts_per_tok"]),
            routed_scale=float(cfg.get("routed_scaling_factor", 1.0)),
            first=first, lowp=bool(lowp), select=select,
            n_sets=len(sets_at))
        sets.append(s)
    return _rms_norm(x[:n], final_norm.astype(F32),
                     float(cfg["rms_norm_eps"])), sets


@functools.partial(jax.jit, static_argnames=("lowp",))
def logits(hidden_rows, lm_head, lowp: bool = False):
    """[N, D] float32 rows -> [N, V] float32 logits."""
    with jax.default_matmul_precision("highest"):
        return _mm(hidden_rows, lm_head.astype(F32), lowp)
