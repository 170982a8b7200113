"""Fused int8 weight-only matmul (Pallas TPU).

``models.transformer.quantize_weights`` stores rollout weights as int8
with per-output-channel fp32 scales. The plain XLA consumption path
(``_weight``: convert * scale -> matmul) is written hoping XLA fuses the
dequantization into the dot — measured on chip (r5, tools/profile_decode
+ sweep_decode) it does NOT: XLA materializes the dequantized bf16
matrix in HBM, so int8 weights READ MORE bytes than bf16 ones
(int8 read + bf16 write + bf16 read ≈ 2.5x) and the b64 rollout decode
ran 4.7x off roofline. This kernel does the convert in VMEM where it
belongs: each grid step DMAs an int8 weight block, converts to bf16 in
registers (lossless: |w| <= 127 is exactly representable), runs the MXU
dot with fp32 accumulation, and applies the per-channel scale to the
PRODUCT — so HBM weight traffic is the int8 bytes and nothing else.

Decode (M = batch) visits each weight byte exactly once per step; the
x block is revisited across the N grid so it stays resident in VMEM.

Forward-only by design: quantized trees exist for rollout decode
(RLHF's hot loop) and never take gradients.
"""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

# default tile sizes; N tiles are lane-dim multiples of 128, M tiles
# sublane multiples of the bf16 tile (16). N defaults big: at decode
# (M = batch) each grid step is ~a microsecond of DMA, so per-step
# fixed overhead dominates with narrow tiles — 2048 cuts a 349M
# model's decode projection stack from ~540 to ~170 grid steps;
# _pick_blocks shrinks it back down when K is too large for VMEM.
DEFAULT_BLOCK_M = 256
DEFAULT_BLOCK_N = 2048


def _kernel(x_ref, w_ref, s_ref, o_ref):
    # x [bm, K] bf16; w [K, bn] int8; s [1, bn] fp32
    acc = jnp.dot(x_ref[...], w_ref[...].astype(jnp.bfloat16),
                  preferred_element_type=jnp.float32)
    o_ref[...] = (acc * s_ref[...]).astype(o_ref.dtype)


# VMEM block budget: the pipeline's x, w and out buffers must fit
# under Mosaic's 16 MiB scoped-VMEM default with room for its own stack
_VMEM_BUDGET = 14 * 1024 * 1024


def _pick_blocks(m: int, k: int, n: int, block_m: int, block_n: int):
    """Shrink (bm, bn) until the working set fits VMEM. Every blocked
    operand is double-buffered by the pipeline — x too, whenever the M
    grid has more than one block (Mosaic's scoped allocation for
    bm=256, K=14336, bn=128 is 2*7.0 + 2*1.75 + 0.125 = 17.63 MiB,
    over the limit a single-x count let through). bn shrinks first —
    smaller bn only adds grid steps; smaller bm re-reads the WEIGHTS
    once per M block, which is the traffic this kernel exists to
    minimize."""
    bm = min(block_m, max(16, -(-m // 16) * 16))  # sublane-align small M
    bn = min(block_n, max(128, -(-n // 128) * 128))  # lane-align small N

    def fits(bm, bn):
        return 2 * (bm * k * 2 + k * bn + bm * bn * 2) <= _VMEM_BUDGET

    while not fits(bm, bn) and bn > 128:
        bn //= 2
    while not fits(bm, bn) and bm > 16:
        bm = max(16, bm // 2)
    if not fits(bm, bn):
        raise ValueError(
            f"int8_matmul cannot tile K={k} into VMEM even at "
            f"bm={bm}, bn={bn}; K-blocking is not implemented")
    return bm, bn


@partial(jax.jit, static_argnames=("block_m", "block_n", "interpret"))
def _int8_matmul_2d(x, w, wscale, block_m: int, block_n: int,
                    interpret: bool):
    m, k = x.shape
    _, n = w.shape
    bm, block_n = _pick_blocks(m, k, n, block_m, block_n)
    pad_m = (-m) % bm
    if pad_m:
        x = jnp.pad(x, ((0, pad_m), (0, 0)))
    grid = ((m + pad_m) // bm, pl.cdiv(n, block_n))
    out = pl.pallas_call(
        _kernel,
        out_shape=jax.ShapeDtypeStruct((m + pad_m, n), jnp.bfloat16),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, k), lambda i, j: (i, 0)),
            pl.BlockSpec((k, block_n), lambda i, j: (0, j)),
            pl.BlockSpec((1, block_n), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, block_n), lambda i, j: (i, j)),
        interpret=interpret,
    )(x.astype(jnp.bfloat16), w, wscale.astype(jnp.float32))
    return out[:m] if pad_m else out


def int8_matmul(
    x: jnp.ndarray,        # [..., K] activations (any float dtype)
    w: jnp.ndarray,        # [K, N] int8
    wscale: jnp.ndarray,   # [1, N] or [N] fp32 per-output-channel scales
    *,
    block_m: int = DEFAULT_BLOCK_M,
    block_n: int = DEFAULT_BLOCK_N,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """x @ (w * wscale) with the dequantization fused into the kernel.

    Returns bf16 [..., N] (the activation dtype of every quantized-tree
    consumer). K is never blocked (no accumulation machinery); instead
    ``_pick_blocks`` shrinks bn, then bm, until one (K, bn) int8 weight
    block plus the (bm, K) activation block fit the VMEM budget — 70B
    shapes (K=28672) land at bn=128 with no caller involvement.
    """
    if w.dtype != jnp.int8:
        raise ValueError(f"int8_matmul needs int8 weights, got {w.dtype}")
    if interpret is None:
        interpret = jax.devices()[0].platform == "cpu"
    if wscale.ndim == 1:
        wscale = wscale[None, :]
    lead = x.shape[:-1]
    k = x.shape[-1]
    out = _int8_matmul_2d(x.reshape(-1, k), w, wscale,
                          block_m, block_n, bool(interpret))
    return out.reshape(*lead, w.shape[1])
