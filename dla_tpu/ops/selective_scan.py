"""Selective state-space scan (Mamba-1, arXiv:2312.00752 section 3.2).

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
