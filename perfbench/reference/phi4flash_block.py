"""Plain reference of the Phi-4-mini-flash-reasoning decoder (Hugging Face
``model_type: phi4flash``; the architecture is SambaY, arXiv:2507.06607
figure 1; its attention is the Differential Transformer's, arXiv:2410.05258;
its state-space layer is Mamba-1, arXiv:2312.00752). Straightforward
``jax.numpy`` in float32 under ``matmul_precision "highest"``: a sequential
scan over time for the state-space layers, masked attention over the whole
sequence, no cache, no batching, one sequence at a time, independent of
``dla_tpu``.

The equations (n = num_hidden_layers, d = hidden_size; every layer l)::

    x <- x + Mixer_l(LN1_l(x));   x <- x + MLP_l(LN2_l(x))
    LN: LayerNorm with weight and bias;  MLP(h) = (silu(h Wg) * (h Wu)) Wd
    a final LN, then the head = the embedding transposed

    l even, l <= n/2    Mamba-1:  [x | z] = h W_in;  x <- silu(conv4(x) + b)
                        [dt | B | C] = x W_x;  Delta = softplus(dt W_dt + b_dt)
                        S_t = exp(Delta_t A) * S_{t-1} + (Delta_t x_t) B_t^T
                        y_t = S_t C_t + D * x_t;  out = (y * silu(z)) W_out
                        (layer n/2 also hands y on as the memory m)
    l odd, l < n/2      differential attention, causal, window
    l = n/2 + 1         differential attention, causal, full (its keys and
                        values are the ones the layers below it read)
    l even, l > n/2+1   gated memory unit: (m * silu(h W_in)) W_out
    l odd, l > n/2+1    differential attention with its own W_q, W_o,
                        lambdas and sub-norm over layer (n/2+1)'s keys
                        and values, causal, full

    differential attention: query heads pair as (2j, 2j+1) = (Q1_j, Q2_j),
    key heads the same way, the two value heads of a pair concatenated;
    query pair j reads key/value pair j // (query pairs / key pairs);
    O_j = (softmax(s Q1 K1^T) - lambda softmax(s Q2 K2^T)) V,  s = dh^-0.5
    lambda = exp(lq1 . lk1) - exp(lq2 . lk2) + lambda_init(l)
    lambda_init(l) = 0.8 - 0.6 exp(-0.3 l)
    O_j <- RMSNorm(O_j) * (1 - lambda_init);  out = concat_j(O_j) W_o + b_o

No positional encoding anywhere. Departures from the published modeling
file, where the builder knows of one, are listed in the configuration
file under ``assumed`` (the catalog's ``config`` carries no Mamba size,
no layer rule and no word on the attention's form): the Mamba sizes are
the family's defaults; the memory is tapped before the gate; ``W1``'s
halves are stored apart as ``w_gate`` / ``w_up`` (immaterial with random
weights); the fused ``Wqkv`` is stored as ``wq`` / ``wk`` / ``wv``.

``lowp=True`` is the reading that sets the limits of ``correct``: the same
forward with every matmul operand and every key and value row rounded to
e4m3 (a scale a row) and the recurrent state rounded to bfloat16 after
every token: the nearest precisions under the configuration's bfloat16
pages and float32 state.

Memory: queries are taken ``Q_BLOCK`` at a time. That changes no number:
every row's softmax still runs over its whole causal prefix.
"""
from __future__ import annotations

import functools
import math
import re
from typing import Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

F32 = jnp.float32
Q_BLOCK = 512


# ------------------------------------------------------------- the layout

def layer_kinds(cfg: Dict) -> List[Tuple[str, Optional[int]]]:
    """(mixer, window) of every layer, from the Hugging Face keys:
    ``ssm`` | ``attention`` (window or None) | ``gmu`` | ``cross``."""
    n = int(cfg["num_hidden_layers"])
    every = int(cfg.get("mb_per_layer", 2))
    half = n // 2
    out: List[Tuple[str, Optional[int]]] = []
    for l in range(n):
        ssm_pos = l % every == 0
        if l <= half:
            out.append(("ssm", None) if ssm_pos
                       else ("attention", int(cfg["sliding_window"])))
        elif l == half + 1:
            out.append(("attention", None))
        else:
            out.append(("gmu", None) if ssm_pos else ("cross", None))
    return out


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

def _layer_norm(x, weight, bias, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * weight + bias


def _rms_norm(x, weight, eps):
    return x * jax.lax.rsqrt(
        jnp.mean(x * x, axis=-1, keepdims=True) + eps) * weight


def lambda_init(l: int) -> float:
    return 0.8 - 0.6 * math.exp(-0.3 * l)


def mamba_step(state, x, dt, a, b_in, c_out, d_skip):
    """One token of the recurrence: state [d, N], x and dt [d], A [d, N],
    B and C [N]. Returns (new state, y [d])."""
    state = jnp.exp(dt[:, None] * a) * state \
        + (dt * x)[:, None] * b_in[None, :]
    return state, state @ c_out + d_skip * x


def mamba(h, w: Dict, lowp: bool = False):
    """[T, D] -> (the mixer's output [T, D], the memory y [T, d_inner])."""
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
    dt = jax.nn.softplus(_mm(dbc[:, :r], w["dt_proj"], lowp) + w["dt_bias"])
    a = -jnp.exp(w["a_log"])

    def step(state, xs):
        x_t, dt_t, b_t, c_t = xs
        state, y = mamba_step(state, x_t, dt_t, a, b_t, c_t, w["d_skip"])
        return _state(state, lowp), y

    _, y = jax.lax.scan(step, jnp.zeros((di, n), F32),
                        (x, dt, dbc[:, r:r + n], dbc[:, r + n:]))
    return _mm(y * jax.nn.silu(z), w["out_proj"], lowp), y


def differential_attention(q, k, v, lam, lam0, subln, window, eps):
    """q [T, H, dh], k and v [S, KH, dh] with S = T (self or cross onto
    the same tokens), causal, ``window`` or None. Returns [T, H * dh]."""
    t, heads, dh = q.shape
    kheads = k.shape[1]
    pairs, kpairs = heads // 2, kheads // 2
    share = pairs // kpairs
    q = q.reshape(t, pairs, 2, dh)
    k = jnp.repeat(k.reshape(t, kpairs, 2, dh), share, axis=1)
    v = jnp.repeat(v.reshape(t, kpairs, 2 * dh), share, axis=1)
    scale = dh ** -0.5
    pad = (-t) % Q_BLOCK
    qb = jnp.pad(q, ((0, pad), (0, 0), (0, 0), (0, 0))).reshape(
        -1, Q_BLOCK, pairs, 2, dh)
    idx = jnp.arange(t + pad).reshape(-1, Q_BLOCK)
    cols = jnp.arange(t)

    def attend(_, xs):
        qi, rows = xs
        scores = jnp.einsum("qpcd,spcd->pcqs", qi, k) * scale
        seen = cols[None, :] <= rows[:, None]
        if window is not None:
            seen = seen & (cols[None, :] > rows[:, None] - window)
        probs = jax.nn.softmax(
            jnp.where(seen[None, None], scores, -jnp.inf), axis=-1)
        mix = probs[:, 0] - lam * probs[:, 1]                  # [P, Q, S]
        return None, jnp.einsum("pqs,spd->qpd", mix, v)

    _, out = jax.lax.scan(attend, None, (qb, idx))
    out = out.reshape(t + pad, pairs, 2 * dh)[:t]
    out = _rms_norm(out, subln, eps) * (1.0 - lam0)
    return out.reshape(t, heads * dh)


def _lambda(w: Dict, lam0: float):
    return (jnp.exp(jnp.sum(w["lambda_q1"] * w["lambda_k1"]))
            - jnp.exp(jnp.sum(w["lambda_q2"] * w["lambda_k2"])) + lam0)


@functools.partial(jax.jit, static_argnames=(
    "kind", "window", "heads", "kheads", "eps", "lowp"))
def _block(x, w: Dict, memory, shared, lam0, *, kind, window, heads,
           kheads, eps, lowp):
    """One layer (``lam0`` = lambda_init of its depth). Returns (x,
    memory, shared): the memory and the shared keys and values as this
    layer leaves them for the ones above."""
    with jax.default_matmul_precision("highest"):
        w = {k: v.astype(F32) for k, v in w.items()}
        t, d = x.shape
        dh = d // heads

        def norm(name):
            if name + "_bias" in w:
                return _layer_norm(x, w[name], w[name + "_bias"], eps)
            return _rms_norm(x, w[name], eps)

        h = norm("norm1")
        if kind == "ssm":
            out, memory = mamba(h, w, lowp)
        elif kind == "gmu":
            out = _mm(memory * jax.nn.silu(_mm(h, w["gmu_in"], lowp)),
                      w["gmu_out"], lowp)
        else:
            q = (_mm(h, w["wq"], lowp) + w["wq_bias"]).reshape(t, heads, dh)
            if kind == "attention":
                k = _row(_mm(h, w["wk"], lowp) + w["wk_bias"], lowp)
                v = _row(_mm(h, w["wv"], lowp) + w["wv_bias"], lowp)
                k, v = (k.reshape(t, kheads, dh), v.reshape(t, kheads, dh))
                if window is None:
                    shared = (k, v)
            else:
                k, v = shared
            att = differential_attention(
                _row(q, lowp) if lowp else q, k, v, _lambda(w, lam0), lam0,
                w["subln"], window, eps)
            out = _mm(att, w["wo"], lowp) + w["wo_bias"]
        x = x + out
        h = norm("norm2")
        ff = jax.nn.silu(_mm(h, w["w_gate"], lowp)) * _mm(h, w["w_up"], lowp)
        return x + _mm(ff, w["w_down"], lowp), memory, shared


def hidden_states(tokens, embedding, layer: Callable[[int], Dict],
                  final_norm, cfg: Dict, lowp: bool = False):
    """[T] token ids -> [T, D] float32 after the final norm. ``layer(l)``
    gives layer l's weights (``take_layer`` of the program's tree);
    ``final_norm`` is (weight, bias); ``cfg`` uses the Hugging Face key
    names plus the state-space sizes under ``assumed``-backed keys
    (``take_layer``'s leaves carry every size the blocks need)."""
    tokens = jnp.asarray(tokens, jnp.int32)
    t = tokens.shape[0]
    d = int(cfg["hidden_size"])
    heads = int(cfg["num_attention_heads"])
    kheads = int(cfg["num_key_value_heads"])
    eps = float(cfg["layer_norm_eps"])
    x = jnp.take(embedding, tokens, axis=0).astype(F32)
    memory = None
    shared = None
    for l, (kind, window) in enumerate(layer_kinds(cfg)):
        w = layer(l)
        if memory is None:
            memory = jnp.zeros((t, w["a_log"].shape[0]), F32)
            dh = d // heads
            shared = (jnp.zeros((t, kheads, dh), F32),) * 2
        x, memory, shared = _block(
            x, w, memory, shared, jnp.asarray(lambda_init(l), F32),
            kind=kind, window=window, heads=heads, kheads=kheads, eps=eps,
            lowp=lowp)
    weight, bias = final_norm
    return _layer_norm(x, weight.astype(F32), bias.astype(F32), eps)


@jax.jit
def logits(hidden_rows, embedding):
    """[N, D] float32 rows -> [N, V] float32 logits (the tied head)."""
    with jax.default_matmul_precision("highest"):
        return hidden_rows @ embedding.astype(F32).T
