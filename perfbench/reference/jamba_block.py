"""Plain reference of the Jamba decoder at ``num_experts`` 1 (Hugging Face
``model_type: jamba``, ``modeling_jamba.py``; the architecture is Jamba's,
arXiv:2403.19887; its state-space layer is Mamba-1, arXiv:2312.00752, with
three RMSNorms of Jamba's own inside it). Straightforward ``jax.numpy`` in
float32 under ``matmul_precision "highest"``: a sequential scan over time
for the state-space layers, masked attention over the whole prefix, no
cache, no batching, one sequence at a time, independent of ``dla_tpu``.

The equations (every layer l; d = hidden_size)::

    x <- x + Mixer_l(RMSNorm(x));   x <- x + MLP(RMSNorm(x))
    MLP(h) = (silu(h Wg) * (h Wu)) Wd
    a final RMSNorm, then the head = the embedding transposed (tied)

    l % attn_layer_period == attn_layer_offset
            causal full attention: H query heads of dh = d / H over KH key /
            value heads (KH = 1: multi-query), no bias, no rotary,
            scale dh^-0.5
    every other l
            Mamba-1:  [x | z] = h W_in;  x <- silu(conv(x) + b)
            [dt | B | C] = x W_x
            dt <- RMSNorm_dt(dt);  B <- RMSNorm_B(B);  C <- RMSNorm_C(C)
            Delta = softplus(dt W_dt + b_dt)
            S_t = exp(Delta_t A) * S_{t-1} + (Delta_t x_t) B_t^T
            y_t = S_t C_t + D * x_t;  out = (y * silu(z)) W_out

No positional encoding anywhere. Departures from ``modeling_jamba.py``
that the builder knows of, none of which changes a number with the same
weights: linear weights are stored ``[in, out]`` (HF ``[out, in]``); the
depthwise convolution's taps are ``conv_w [K, d_inner]``, oldest first (HF
``conv1d.weight [d_inner, 1, K]``); ``dt_proj``'s bias is ``dt_bias``; the
float32 here is HF's own choice for the recurrence only (HF runs the
projections in the checkpoint's bfloat16); HF's ``num_experts`` > 1 puts a
routed MLP in the layers ``expert_layer_period`` / ``_offset`` choose,
which at ``num_experts`` 1, the published value, choose nothing (every
layer builds the dense ``JambaMLP``). The layer rule, the three inner
norms, ``head_dim`` and "no rotary" are listed with their sources in the
configuration file under ``assumed`` (the catalog's ``config`` does not
carry the order of the layer types).

``lowp=True`` is the reading that sets the limits of ``correct``: the same
forward with every matmul operand and every key and value row rounded to
e4m3 (a scale a row) and the recurrent state rounded to bfloat16 after
every token: the nearest precisions under the configuration's bfloat16
pages and float32 state.

Memory: queries are taken ``Q_BLOCK`` at a time, so 33,792 tokens fit
(scores ``[20, 512, T]``). That changes no number: every row's softmax
still runs over its whole causal prefix.
"""
from __future__ import annotations

import functools
import re
from typing import Callable, Dict, List

import jax
import jax.numpy as jnp

F32 = jnp.float32
Q_BLOCK = 512


# ------------------------------------------------------------- the layout

def layer_kinds(cfg: Dict) -> List[str]:
    """``attention`` | ``ssm`` of every layer, from the Hugging Face keys
    (``JambaConfig.layers_block_type``)."""
    period = int(cfg["attn_layer_period"])
    offset = int(cfg["attn_layer_offset"])
    return ["attention" if l % period == offset else "ssm"
            for l in range(int(cfg["num_hidden_layers"]))]


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
    return s.astype(jnp.bfloat16).astype(F32) if lowp else s


# ------------------------------------------------------------------ blocks

def _rms_norm(x, weight, eps):
    return x * jax.lax.rsqrt(
        jnp.mean(x * x, axis=-1, keepdims=True) + eps) * weight


def mamba_step(state, x, dt, a, b_in, c_out, d_skip):
    """One token of the recurrence: state [d, N], x and dt [d], A [d, N],
    B and C [N]. Returns (new state, y [d])."""
    state = jnp.exp(dt[:, None] * a) * state \
        + (dt * x)[:, None] * b_in[None, :]
    return state, state @ c_out + d_skip * x


def mamba(h, w: Dict, eps: float, lowp: bool = False):
    """[T, D] -> the mixer's output [T, D]."""
    t = h.shape[0]
    di, n = w["a_log"].shape
    r = w["dt_proj"].shape[0]
    xz = _mm(h, w["in_proj"], lowp)
    x, z = xz[:, :di], xz[:, di:]
    k = w["conv_w"].shape[0]
    padded = jnp.concatenate([jnp.zeros((k - 1, di), F32), x])
    x = jax.nn.silu(sum(padded[i:i + t] * w["conv_w"][i] for i in range(k))
                    + w["conv_b"])
    dbc = _mm(x, w["x_proj"], lowp)
    dt = _rms_norm(dbc[:, :r], w["dt_norm"], eps)
    b_in = _rms_norm(dbc[:, r:r + n], w["b_norm"], eps)
    c_out = _rms_norm(dbc[:, r + n:], w["c_norm"], eps)
    dt = jax.nn.softplus(_mm(dt, w["dt_proj"], lowp) + w["dt_bias"])
    a = -jnp.exp(w["a_log"])

    def step(state, xs):
        x_t, dt_t, b_t, c_t = xs
        state, y = mamba_step(state, x_t, dt_t, a, b_t, c_t, w["d_skip"])
        return _state(state, lowp), y

    _, y = jax.lax.scan(step, jnp.zeros((di, n), F32), (x, dt, b_in, c_out))
    return _mm(y * jax.nn.silu(z), w["out_proj"], lowp)


def attention(q, k, v):
    """q [T, H, dh], k and v [T, KH, dh], causal over the whole prefix.
    Returns [T, H * dh]."""
    t, heads, dh = q.shape
    share = heads // k.shape[1]
    k, v = jnp.repeat(k, share, axis=1), jnp.repeat(v, share, axis=1)
    scale = dh ** -0.5
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


@functools.partial(jax.jit, static_argnames=(
    "kind", "heads", "kheads", "eps", "lowp"))
def _block(x, w: Dict, *, kind, heads, kheads, eps, lowp):
    """One layer."""
    with jax.default_matmul_precision("highest"):
        w = {k: v.astype(F32) for k, v in w.items()}
        t, d = x.shape
        dh = d // heads
        h = _rms_norm(x, w["norm1"], eps)
        if kind == "ssm":
            out = mamba(h, w, eps, lowp)
        else:
            q = _mm(h, w["wq"], lowp).reshape(t, heads, dh)
            k = _row(_mm(h, w["wk"], lowp), lowp).reshape(t, kheads, dh)
            v = _row(_mm(h, w["wv"], lowp), lowp).reshape(t, kheads, dh)
            out = _mm(attention(_row(q, lowp), k, v), w["wo"], lowp)
        x = x + out
        h = _rms_norm(x, w["norm2"], eps)
        ff = jax.nn.silu(_mm(h, w["w_gate"], lowp)) * _mm(h, w["w_up"], lowp)
        return x + _mm(ff, w["w_down"], lowp)


def hidden_states(tokens, embedding, layer: Callable[[int], Dict],
                  final_norm, cfg: Dict, lowp: bool = False):
    """[T] token ids -> [T, D] float32 after the final norm. ``layer(l)``
    gives layer l's weights (``take_layer`` of the program's tree, whose
    leaves carry every size the blocks need); ``final_norm`` is the
    weight; ``cfg`` uses the Hugging Face key names."""
    tokens = jnp.asarray(tokens, jnp.int32)
    heads = int(cfg["num_attention_heads"])
    kheads = int(cfg["num_key_value_heads"])
    eps = float(cfg["rms_norm_eps"])
    x = jnp.take(embedding, tokens, axis=0).astype(F32)
    for l, kind in enumerate(layer_kinds(cfg)):
        x = _block(x, layer(l), kind=kind, heads=heads, kheads=kheads,
                   eps=eps, lowp=lowp)
    return _rms_norm(x, jnp.asarray(final_norm).astype(F32), eps)


@jax.jit
def logits(hidden_rows, embedding):
    """[N, D] float32 rows -> [N, V] float32 logits (the tied head)."""
    with jax.default_matmul_precision("highest"):
        return hidden_rows @ embedding.astype(F32).T
