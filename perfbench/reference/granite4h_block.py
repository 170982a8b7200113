"""Plain reference of the Granite 4.0-H decoder (Hugging Face ``model_type:
granitemoehybrid``, ``modeling_granitemoehybrid.py``; its state-space layer
is Mamba-2, arXiv:2405.21060). Straightforward ``jax.numpy`` in float32
under ``matmul_precision "highest"``: a sequential scan over time for the
Mamba-2 layers (the recurrence, not the chunked SSD form the program runs),
masked attention over the whole prefix, no cache, no batching, one
sequence at a time, independent of ``dla_tpu``.

The equations (every layer l; d = hidden_size, r = residual_multiplier)::

    x_0 = E[ids] * embedding_multiplier
    x <- x + r * Mixer_l(RMSNorm(x))
    x <- x + r * (MoE(h) + Shared(h)),  h = RMSNorm(x)
    logits = RMSNorm(x) E^T / logits_scaling          (the head is tied)

    layer_types[l] == "attention"
            causal grouped-query attention, H query heads of dh = d / H
            over KH key / value heads, no bias, no positional encoding,
            scores times attention_multiplier
    layer_types[l] == "mamba"   (Mamba-2, one group)
            [z | xBC | dt] = h W_in;  xBC <- silu(conv(xBC) + b)
            [x | B | C] = xBC;  x -> [heads, head_dim]
            dt <- softplus(dt + dt_bias) a head;  A = -exp(A_log) a head
            S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t     [H, P, N]
            y_t = S_t C_t + D * x_t
            out = (RMSNorm(y * silu(z)) * w_norm) W_out    (over all d_inner)
    MoE(h)  router logits h W_r [E] in float32, the top k, softmax over
            the chosen logits; expert e: (silu(h Wg_e) * (h Wu_e)) Wd_e
    Shared  (silu(h Wsg) * (h Wsu)) Wsd

Departures from ``modeling_granitemoehybrid.py`` that the builder knows
of, none of which changes a number with the same weights: linear weights
are stored ``[in, out]`` (HF ``[out, in]``); HF's ``input_linear`` of an
expert (and of the shared MLP) is ``[gate | up]`` along its output, stored
here as two matrices ``w_gate`` / ``w_up`` (``ws_gate`` / ``ws_up``); the
depthwise convolution's taps are ``conv_w [K, channels]``, oldest first
(HF ``conv1d.weight [channels, 1, K]``); the gated norm's weight is
``ssm_norm``; the float32 here is the reference's own choice (HF runs the
projections in the checkpoint's bfloat16).

The share of a deployment: ``experts=(first, count)`` names the routed
experts whose weights were handed in. The router still scores every
published expert; choices that land outside the share add nothing, and
that partial sum, plus the shared expert (which every chip computes), goes
on to the next layer. ``expert_layer`` gives the two parts apart, so that
the shares of a deployment can be summed against the uncut layer.

``lowp=True`` is the reading that sets the limits of ``correct``: the same
forward with every matmul operand and every key and value row rounded to
e4m3 (a scale a row) and the recurrent state rounded to bfloat16 after
every token: the nearest precisions under the configuration's bfloat16
weights, activations and pages and float32 state.

Memory: ``take_layer`` hands over one layer of the program's tree at a
time, so the float32 copy is of one layer (at 36 held experts 1.9 GB);
queries are taken ``Q_BLOCK`` at a time; each expert's weights are upcast
one expert at a time. None changes a number.
"""
from __future__ import annotations

import functools
import re
from typing import Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

F32 = jnp.float32
Q_BLOCK = 512
#: leaves upcast one expert at a time, not with the rest of the layer
EXPERT_LEAVES = ("w_gate", "w_up", "w_down")


# ------------------------------------------------------------- the layout

def layer_kinds(cfg: Dict) -> List[str]:
    """``attention`` | ``mamba`` of every layer (HF ``layer_types``)."""
    kinds = list(cfg["layer_types"])
    assert len(kinds) == int(cfg["num_hidden_layers"])
    return kinds


def take_layer(layers: Dict[str, Dict], l: int) -> Dict:
    """Layer l's weights out of the program's tree: a dict of stacks named
    ``<first layer>s<stride>_<mixer>``, stack i of which is layer ``first
    + i * stride``."""
    for key, stack in layers.items():
        m = re.match(r"(\d+)s(\d+)_", key)
        first, stride = int(m.group(1)), int(m.group(2))
        i, rest = divmod(l - first, stride)
        reps = next(iter(stack.values())).shape[0]
        if rest == 0 and 0 <= i < reps:
            return {k: v[i] for k, v in stack.items()}
    raise KeyError(f"no layer {l} in {sorted(layers)}")


# ---------------------------------------------------------------- rounding

def _e4m3(x, axis):
    """Round to float8 e4m3 with one scale along ``axis``."""
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 448.0 + 1e-30
    return (x / scale).astype(jnp.float8_e4m3fn).astype(F32) * scale


def _mm(a, w, lowp):
    if lowp:
        a, w = _e4m3(a, -1), _e4m3(w, 0)
    return a @ w


def _row(x, lowp):
    return _e4m3(x, -1) if lowp else x


def _state(s, lowp):
    # reduce_precision, not a round trip through bfloat16: XLA:TPU may
    # drop a convert pair (excess precision), it keeps this op
    return (jax.lax.reduce_precision(s, exponent_bits=8, mantissa_bits=7)
            if lowp else s)


# ------------------------------------------------------------------ blocks

def _rms_norm(x, weight, eps):
    return x * jax.lax.rsqrt(
        jnp.mean(x * x, axis=-1, keepdims=True) + eps) * weight


def mamba2_step(state, x, dt, a, b_in, c_out, d_skip):
    """One token of the recurrence: state [H, P, N], x [H, P], dt and A
    [H], B and C [N], D [H]. Returns (new state, y [H, P])."""
    state = (jnp.exp(dt * a)[:, None, None] * state
             + (dt[:, None] * x)[..., None] * b_in[None, None, :])
    return state, state @ c_out + d_skip[:, None] * x


def mamba2(h, w: Dict, cfg: Dict, lowp: bool = False, gate: bool = True,
           n_valid=None):
    """[T, D] -> (the mixer's output [T, D], the state [H, P, N] after the
    first ``n_valid`` tokens (default: all T); the tokens after them move
    no state). ``gate=False`` leaves the gate out of the gated norm (a
    deliberately wrong mixer, for tests)."""
    t, d = h.shape
    n_valid = t if n_valid is None else n_valid
    di = int(cfg["mamba_expand"]) * d
    nh, n = int(cfg["mamba_n_heads"]), int(cfg["mamba_d_state"])
    hp = di // nh
    conv = di + 2 * n
    zxd = _mm(h, w["in_proj"], lowp)
    z, xbc, dt = zxd[:, :di], zxd[:, di:di + conv], zxd[:, di + conv:]
    k = w["conv_w"].shape[0]
    padded = jnp.concatenate([jnp.zeros((k - 1, conv), F32), xbc])
    xbc = jax.nn.silu(sum(padded[i:i + t] * w["conv_w"][i]
                          for i in range(k)) + w["conv_b"])
    x = xbc[:, :di].reshape(t, nh, hp)
    b_in, c_out = xbc[:, di:di + n], xbc[:, di + n:]
    dt = jax.nn.softplus(dt + w["dt_bias"])
    a = -jnp.exp(w["a_log"])

    def step(state, xs):
        x_t, dt_t, b_t, c_t, i = xs
        new, y = mamba2_step(state, x_t, dt_t, a, b_t, c_t, w["d_skip"])
        return jnp.where(i < n_valid, _state(new, lowp), state), y

    last, y = jax.lax.scan(step, jnp.zeros((nh, hp, n), F32),
                           (x, dt, b_in, c_out, jnp.arange(t)))
    y = y.reshape(t, di)
    g = y * jax.nn.silu(z) if gate else y
    eps = float(cfg["rms_norm_eps"])
    return _mm(_rms_norm(g, w["ssm_norm"], eps), w["out_proj"], lowp), last


def attention(q, k, v, scale):
    """q [T, H, dh], k and v [T, KH, dh], causal over the whole prefix,
    scores times ``scale``. Returns [T, H * dh]."""
    t, heads, dh = q.shape
    share = heads // k.shape[1]
    k, v = jnp.repeat(k, share, axis=1), jnp.repeat(v, share, axis=1)
    pad = (-t) % Q_BLOCK
    qb = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(
        -1, Q_BLOCK, heads, dh)
    idx = jnp.arange(t + pad).reshape(-1, Q_BLOCK)
    cols = jnp.arange(t)

    def attend(_, xs):
        qi, rows = xs
        scores = jnp.einsum("qhd,shd->hqs", qi, k) * scale
        seen = cols[None, :] <= rows[:, None]
        probs = jax.nn.softmax(
            jnp.where(seen[None], scores, -jnp.inf), axis=-1)
        return None, jnp.einsum("hqs,shd->qhd", probs, v)

    _, out = jax.lax.scan(attend, None, (qb, idx))
    return out.reshape(t + pad, heads * dh)[:t]


def _swiglu(h, gate, up, down, lowp):
    return _mm(jax.nn.silu(_mm(h, gate, lowp)) * _mm(h, up, lowp), down,
               lowp)


def _experts(h, w: Dict, top_k: int, first: int, lowp: bool):
    """h [T, D] (after the second norm) -> (what the held routed experts
    add, what the shared expert adds), each [T, D]."""
    logits = _mm(h, w["router"].astype(F32), lowp)                # [T, E]
    top_l, top_e = jax.lax.top_k(logits, top_k)
    top_p = jax.nn.softmax(top_l, axis=-1)
    held = w["w_gate"].shape[0]
    combine = jnp.sum(                                           # [T, held]
        top_p[..., None] * (top_e[..., None]
                            == first + jnp.arange(held)[None, None, :]),
        axis=1)

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
    """The expert layer alone: h [T, D] float32 (after the second norm) ->
    (routed [T, D], shared [T, D]) for the share ``experts`` = (first,
    count) whose weights ``w`` holds. The shares of a deployment add up:
    the routed parts of all of them, and the shared part once, are the
    uncut layer."""
    with jax.default_matmul_precision("highest"):
        return _experts(jnp.asarray(h, F32), w,
                        int(cfg["num_experts_per_tok"]),
                        int(experts[0]) if experts else 0, False)


@functools.partial(jax.jit, static_argnames=(
    "kind", "heads", "kheads", "eps", "scale", "residual", "top_k", "first",
    "mamba", "lowp", "gate"))
def _block(x, w: Dict, n_valid, *, kind, heads, kheads, eps, scale,
           residual, top_k, first, mamba, lowp, gate):
    """One layer: (x after it, a Mamba-2 layer's state after ``n_valid``
    tokens or a 0 for attention). ``mamba``: the Mamba-2 sizes as a tuple
    of (key, value) pairs (hashable for jit)."""
    with jax.default_matmul_precision("highest"):
        small = {k: v.astype(F32) for k, v in w.items()
                 if k not in EXPERT_LEAVES}
        t, d = x.shape
        dh = d // heads
        h = _rms_norm(x, small["norm1"], eps)
        state = jnp.zeros((), F32)
        if kind == "mamba":
            out, state = mamba2(h, small, dict(mamba, rms_norm_eps=eps), lowp,
                                gate, n_valid)
        else:
            q = _mm(h, small["wq"], lowp).reshape(t, heads, dh)
            k = _row(_mm(h, small["wk"], lowp), lowp).reshape(t, kheads, dh)
            v = _row(_mm(h, small["wv"], lowp), lowp).reshape(t, kheads, dh)
            out = _mm(attention(_row(q, lowp), k, v, scale), small["wo"],
                      lowp)
        x = x + residual * out
        h = _rms_norm(x, small["norm2"], eps)
        routed, shared = _experts(
            h, {**small, **{k: w[k] for k in EXPERT_LEAVES}}, top_k, first,
            lowp)
        return x + residual * (routed + shared), state


def hidden_states(tokens, embedding, layer: Callable[[int], Dict],
                  final_norm, cfg: Dict,
                  experts: Optional[Tuple[int, int]] = None,
                  lowp: bool = False, gate: bool = True,
                  n_valid: Optional[int] = None, states: bool = False):
    """[T] token ids -> [T, D] float32 after the final norm. ``layer(l)``
    gives layer l's weights (``take_layer`` of the program's tree);
    ``cfg`` uses the Hugging Face key names; ``experts`` = (first, count)
    of the routed experts held (default: all of them). ``states``: also
    each Mamba-2 layer's state [H, P, N] after the first ``n_valid``
    tokens (default: all), in layer order; the ids after them are
    padding."""
    tokens = jnp.asarray(tokens, jnp.int32)
    n_valid = jnp.int32(len(tokens) if n_valid is None else n_valid)
    eps = float(cfg["rms_norm_eps"])
    d = int(cfg["hidden_size"])
    heads = int(cfg["num_attention_heads"])
    scale = float(cfg.get("attention_multiplier") or (d // heads) ** -0.5)
    mamba = tuple((k, cfg[k]) for k in (
        "mamba_expand", "mamba_n_heads", "mamba_d_state"))
    x = (jnp.take(embedding, tokens, axis=0).astype(F32)
         * float(cfg.get("embedding_multiplier", 1.0)))
    last = []
    for l, kind in enumerate(layer_kinds(cfg)):
        x, state = _block(x, layer(l), n_valid, kind=kind, heads=heads,
                   kheads=int(cfg["num_key_value_heads"]), eps=eps,
                   scale=scale,
                   residual=float(cfg.get("residual_multiplier", 1.0)),
                   top_k=int(cfg["num_experts_per_tok"]),
                   first=int(experts[0]) if experts else 0, mamba=mamba,
                   lowp=lowp, gate=gate)
        if kind == "mamba":
            last.append(state)
    hidden = _rms_norm(x, jnp.asarray(final_norm).astype(F32), eps)
    return (hidden, last) if states else hidden


@functools.partial(jax.jit, static_argnames=("scaling", "lowp"))
def logits(hidden_rows, embedding, scaling: float = 1.0, lowp: bool = False):
    """[N, D] float32 rows -> [N, V] float32 logits: the tied head, over
    ``logits_scaling``; ``lowp`` rounds both operands to e4m3 as every
    other matmul of the low-precision reading."""
    with jax.default_matmul_precision("highest"):
        table = embedding.astype(F32)
        if lowp:
            hidden_rows, table = _e4m3(hidden_rows, -1), _e4m3(table, -1)
        return hidden_rows @ table.T / scaling
