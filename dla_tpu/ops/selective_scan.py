"""Selective state-space scans: Mamba-1 (arXiv:2312.00752 section 3.2)
and, below, Mamba-2 (``ssd_step`` / ``ssd_chunk``).

Per channel d and state index n, with a per-token step size ``dt``::

    S_t[n, d] = exp(dt_t[d] * A[n, d]) * S_{t-1}[n, d] + dt_t[d] * x_t[d] * B_t[n]
    y_t[d]    = sum_n S_t[n, d] * C_t[n] + D[d] * x_t[d]

Two forms that agree with each other: :func:`selective_scan_step` is one
recurrence step (a decode step: one token a sequence), and
:func:`selective_scan_chunk` runs T tokens from a given state and returns
the state at the end (a prefill chunk, or a whole sequence from zeros).
The state is float32 and laid out ``[..., N, d_inner]``: the channel axis
is the minor one, so a row is a whole number of 128-lane vectors on the
TPU (``[d_inner, N]`` with N = 16 would pad every row eightfold). ``exp``
and the products that feed the state run in float32 whatever the
activation dtype; the caller's matmuls stay in the activation dtype.

``dt`` is the step size after ``softplus``. A token whose ``dt`` is 0
leaves the state as it was (``exp(0) = 1`` and nothing is added), which
is how callers mask pad tokens and rows that are not running.

The chunk form is a sequential ``lax.scan`` over blocks of ``block``
tokens with an associative scan inside each block: ``T / block`` loop
iterations of log2(block) levels each, and temporaries of ``block`` states
at a time, not T (a chunk of 256 tokens at d_inner 5,120 and N 16 would
hold 84 MB a tensor at once).
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

F32 = jnp.float32
DEFAULT_BLOCK = 16


def selective_scan_step(
    x: jnp.ndarray,        # [B, d]    the token's conv output (after silu)
    dt: jnp.ndarray,       # [B, d]    step size, float32, after softplus
    a: jnp.ndarray,        # [N, d]    A = -exp(A_log), float32
    b_in: jnp.ndarray,     # [B, N]
    c_out: jnp.ndarray,    # [B, N]
    d_skip: jnp.ndarray,   # [d]
    state: jnp.ndarray,    # [B, N, d] float32
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """One recurrence step. Returns (y [B, d] float32, new state)."""
    xf, dt = x.astype(F32), dt.astype(F32)
    decay = jnp.exp(dt[:, None, :] * a[None])                 # [B, N, d]
    state = decay * state + (dt * xf)[:, None, :] * b_in.astype(F32)[:, :, None]
    y = jnp.einsum("bnd,bn->bd", state, c_out.astype(F32))
    return y + d_skip.astype(F32) * xf, state


def _block_size(t: int, block: int) -> int:
    """The largest power of two that divides ``t`` and is at most
    ``block`` (1 for an odd length: the scan is then sequential)."""
    size = 1
    while size * 2 <= block and t % (size * 2) == 0:
        size *= 2
    return size


def selective_scan_chunk(
    x: jnp.ndarray,        # [B, T, d]
    dt: jnp.ndarray,       # [B, T, d] float32, after softplus; 0 = no-op
    a: jnp.ndarray,        # [N, d]
    b_in: jnp.ndarray,     # [B, T, N]
    c_out: jnp.ndarray,    # [B, T, N]
    d_skip: jnp.ndarray,   # [d]
    state: jnp.ndarray,    # [B, N, d] float32, the state before token 0
    block: int = DEFAULT_BLOCK,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """T steps from ``state``. Returns (y [B, T, d] float32, the state
    after the last token)."""
    bsz, t, d = x.shape
    n = a.shape[0]
    size = _block_size(t, block)
    blocks = t // size

    def split(v):          # [B, T, ...] -> [blocks, B, size, ...]
        return jnp.moveaxis(
            v.reshape((bsz, blocks, size) + v.shape[2:]), 1, 0)

    def combine(left, right):
        # two stretches of the recurrence S <- decay * S + add, composed
        (dl, al), (dr, ar) = left, right
        return dl * dr, dr * al + ar

    def body(s0, xs):
        xb, dtb, bb, cb = xs                                  # [B, size, .]
        xf, dtf = xb.astype(F32), dtb.astype(F32)
        decay = jnp.exp(dtf[:, :, None, :] * a[None, None])   # [B,size,N,d]
        add = (dtf * xf)[:, :, None, :] * bb.astype(F32)[..., None]
        cum_decay, cum_add = jax.lax.associative_scan(
            combine, (decay, add), axis=1)
        states = cum_decay * s0[:, None] + cum_add            # [B,size,N,d]
        y = jnp.einsum("btnd,btn->btd", states, cb.astype(F32))
        return states[:, -1], y + d_skip.astype(F32) * xf

    state, ys = jax.lax.scan(
        body, state.astype(F32),
        (split(x), split(dt), split(b_in), split(c_out)))
    return jnp.moveaxis(ys, 0, 1).reshape(bsz, t, d), state


# --------------------------------------------------------------- Mamba-2
#
# Mamba-2 (arXiv:2405.21060, "state space duality"): each of H heads has
# ONE scalar decay a token and a state matrix [P, N] (P = head_dim), and B
# and C are shared by the heads of a group (one group here)::
#
#     S_t[h] = exp(dt_t[h] * A[h]) * S_{t-1}[h] + dt_t[h] * x_t[h] (x) B_t
#     y_t[h] = S_t[h] C_t + D[h] * x_t[h]
#
# The scalar decay makes a block of L tokens a masked matrix product (the
# SSD form): within the block y = (L o C B^T) (dt x) with L[i, j] =
# exp(cum_i - cum_j) for i >= j, plus the state from before the block read
# through C and decayed by exp(cum_i); the state after the block is the
# decayed one plus sum_j exp(cum_L - cum_j) dt_j x_j (x) B_j. The blocks
# follow one another in a ``lax.scan``. Its products run at float32
# "highest" precision: the state feeds every later token.


def ssd_step(
    x: jnp.ndarray,        # [B, H, P]  the token's x (after conv + silu)
    dt: jnp.ndarray,       # [B, H]     step size, float32, after softplus
    a: jnp.ndarray,        # [H]        A = -exp(A_log), float32
    b_in: jnp.ndarray,     # [B, N]
    c_out: jnp.ndarray,    # [B, N]
    d_skip: jnp.ndarray,   # [H]
    state: jnp.ndarray,    # [B, H, P, N] float32
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """One Mamba-2 recurrence step, elementwise in float32. Returns (y [B,
    H, P] float32, new state)."""
    xf, dt = x.astype(F32), dt.astype(F32)
    decay = jnp.exp(dt * a[None])                                 # [B, H]
    state = (decay[:, :, None, None] * state
             + (dt[:, :, None] * xf)[..., None]
             * b_in.astype(F32)[:, None, None, :])
    y = jnp.sum(state * c_out.astype(F32)[:, None, None, :], axis=-1)
    return y + d_skip.astype(F32)[None, :, None] * xf, state


def ssd_chunk(
    x: jnp.ndarray,        # [B, T, H, P]
    dt: jnp.ndarray,       # [B, T, H] float32, after softplus; 0 = no-op
    a: jnp.ndarray,        # [H]
    b_in: jnp.ndarray,     # [B, T, N]
    c_out: jnp.ndarray,    # [B, T, N]
    d_skip: jnp.ndarray,   # [H]
    state: jnp.ndarray,    # [B, H, P, N] float32, the state before token 0
    block: int = 256,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """T Mamba-2 steps from ``state`` in the chunked (SSD) form, blocks of
    ``block`` tokens (the last one padded with tokens whose ``dt`` is 0,
    which move nothing). Returns (y [B, T, H, P] float32, the state after
    the last token)."""
    bsz, t, h, p = x.shape
    size = min(block, t)
    pad = (-t) % size
    if pad:
        def grow(v):
            return jnp.pad(v, ((0, 0), (0, pad)) + ((0, 0),) * (v.ndim - 2))
        x, dt, b_in, c_out = grow(x), grow(dt), grow(b_in), grow(c_out)
    blocks = (t + pad) // size
    hi = jax.lax.Precision.HIGHEST
    causal = jnp.tril(jnp.ones((size, size), bool))[None, None]
    a, d_skip = a.astype(F32), d_skip.astype(F32)

    def split(v):          # [B, T, ...] -> [blocks, B, size, ...]
        return jnp.moveaxis(
            v.reshape((bsz, blocks, size) + v.shape[2:]), 1, 0)

    def body(s0, xs):
        # heads lead every product as a batch dimension, so the state
        # keeps its [B, H, P, N] layout through the block (a product that
        # puts P before H makes XLA relay the whole state pool out and
        # back around a chunk program)
        xb, dtb, bb, cb = xs
        xf = jnp.moveaxis(xb.astype(F32), 2, 1)                 # [B,H,L,P]
        dtb = jnp.moveaxis(dtb.astype(F32), 2, 1)               # [B, H, L]
        bb, cb = bb.astype(F32), cb.astype(F32)
        cum = jnp.cumsum(dtb * a[None, :, None], axis=2)        # [B, H, L]
        # token j's contribution reaches token i >= j decayed by
        # exp(cum_i - cum_j) <= 1 (the exponent is masked before exp)
        seg = cum[:, :, :, None] - cum[:, :, None, :]           # [B,H,i,j]
        decay = jnp.where(causal, jnp.exp(jnp.where(causal, seg, 0.0)), 0.0)
        scores = jnp.einsum("bin,bjn->bij", cb, bb, precision=hi)
        w = decay * scores[:, None] * dtb[:, :, None, :]
        y = jnp.einsum("bhij,bhjp->bhip", w, xf, precision=hi)
        y = y + jnp.exp(cum)[..., None] * jnp.einsum(
            "bhpn,bin->bhip", s0, cb, precision=hi)
        to_end = jnp.exp(cum[:, :, -1:] - cum) * dtb            # [B, H, L]
        s1 = (jnp.exp(cum[:, :, -1])[:, :, None, None] * s0
              + jnp.einsum("bhjp,bjn->bhpn", to_end[..., None] * xf, bb,
                           precision=hi))
        y = y + d_skip[None, :, None, None] * xf
        return s1, jnp.moveaxis(y, 1, 2)                        # [B,L,H,P]

    state, ys = jax.lax.scan(
        body, state.astype(F32),
        (split(x), split(dt), split(b_in), split(c_out)))
    y = jnp.moveaxis(ys, 0, 1).reshape(bsz, t + pad, h, p)
    return y[:, :t], state


def causal_conv_step(
    x: jnp.ndarray,        # [B, T, d]  the conv's input (before silu)
    tail: jnp.ndarray,     # [B, K-1, d] the K-1 inputs before token 0
    weight: jnp.ndarray,   # [K, d]     depthwise taps, oldest first
    bias: jnp.ndarray,     # [d]
    n_real: jnp.ndarray,   # [B] int32: rows 0..n_real-1 of x are real
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Causal depthwise convolution of width K over ``tail ++ x``.
    Returns (conv output [B, T, d] in x's dtype, before the activation;
    the new tail: the K-1 inputs that end at the last real token, which
    is ``tail`` itself where ``n_real`` is 0)."""
    k = weight.shape[0]
    t = x.shape[1]
    seq = jnp.concatenate([tail.astype(x.dtype), x], axis=1)  # [B,K-1+T,d]
    out = sum(seq[:, i:i + t].astype(F32) * weight[i].astype(F32)
              for i in range(k)) + bias.astype(F32)
    idx = n_real[:, None] + jnp.arange(k - 1, dtype=jnp.int32)[None, :]
    new_tail = jnp.take_along_axis(seq, idx[:, :, None], axis=1)
    return out.astype(x.dtype), new_tail.astype(tail.dtype)
