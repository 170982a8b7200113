"""Pallas TPU decode-attention kernel with in-VMEM KV dequantization.

Single-token attention over the KV cache is THE bandwidth-bound loop of
rollout decode (the reference consumes it through HF generate,
src/training/train_rlhf.py:123-124). The XLA path
(ops.attention.decode_attention) runs at the HBM roofline for bf16
caches, but the int8 cache path dequantizes with convert*scale OUTSIDE
the attention — measured on chip (r5, tools/sweep_decode.py) XLA does
not fuse that into the einsums and materializes a bf16 copy of the
cache per layer per step, making int8 KV a REGRESSION (b64: 3.77
ms/token vs bf16's 2.71). This kernel reads the int8 bytes from HBM,
dequantizes in VMEM, and runs the online-softmax attention in one pass —
the cache's HBM traffic is the int8 bytes and nothing else.

Shape/layout choices (layout = the cache's native [B, S, K, D]):
  - grid (B, S/block_s); KV blocks DMA'd as contiguous [bs, K*D] rows
    (all kv heads of a position together — full-stride rows, no
    128-byte strided pickup);
  - a static unrolled loop over the K kv heads inside the kernel, one
    MXU dot per head: q [Gp, D] x k [bs, D]^T, fp32 accumulation;
  - GQA query groups padded to Gp=8 sublanes (padded rows are zeros ->
    finite garbage, sliced off by the wrapper);
  - the just-computed token's k/v join the softmax as an extra column
    at grid step 0 (same joint-softmax semantics as decode_attention:
    the cache is attended UN-updated, the caller writes it once);
  - additive bias [B, S] carries validity+causality+window, computed
    once per decode step by the caller and shared by every layer.

Forward-only (decode never takes gradients).
"""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
DEFAULT_BLOCK_S = 512
GP = 8  # query-group sublane padding


def _body(lb_ref, q_ref, kn_ref, vn_ref, bias_ref, k_ref, v_ref, ks_ref,
          vs_ref, o_ref, m_ref, l_ref, acc_ref, *, kheads, dh, bs, s,
          scale, softcap=0.0):
    si = pl.program_id(1)
    ns = pl.num_programs(1)

    def cap(x):
        # gemma-2 logit softcapping: cap * tanh(x / cap), applied to the
        # SCALED scores before masking (decode_attention's order)
        if not softcap:
            return x
        return softcap * jnp.tanh(x / softcap)

    @pl.when(si == 0)
    def _init():
        # the new token joins as the first softmax column: delta == 0 is
        # causal and inside any window, so it is always unmasked
        for kh in range(kheads):
            rows = slice(kh * GP, (kh + 1) * GP)
            dcol = slice(kh * dh, (kh + 1) * dh)
            q = q_ref[0, rows, :].astype(jnp.float32)       # [Gp, D]
            kn = kn_ref[0, :, dcol].astype(jnp.float32)     # [1, D]
            # a [Gp, D] x [1, D]^T dot is a one-column matmul Mosaic
            # cannot lower; widen to fp32 first (bf16 products are
            # exact in fp32) and reduce along the lanes instead
            s_self = cap(jnp.sum(q * kn, axis=1, keepdims=True)
                         * scale)                           # [Gp, 1]
            m_ref[rows, :] = jnp.broadcast_to(s_self, (GP, 128))
            l_ref[rows, :] = jnp.ones((GP, 128), jnp.float32)
            acc_ref[rows, :] = jnp.broadcast_to(
                vn_ref[0, :, dcol].astype(jnp.float32), (GP, dh))

    # blocks past the cache fill level are SKIPPED outright: their index
    # maps clamp to the last active block (no DMA on a revisited block)
    # and the compute is gated off here — decode's cache read traffic
    # scales with the actual fill, not the preallocated S
    @pl.when(si <= lb_ref[0])
    def _process():
        # columns past min(S, kv_fill) are garbage loads (ragged tail
        # padding, or cache tail not yet written — possibly NaN) —
        # scores must be REPLACED, not bias-added (NaN + NEG_INF is
        # still NaN), and garbage V rows must be zeroed (exp()
        # underflow gives p == 0, but 0 * NaN = NaN inside the dot)
        bound = jnp.minimum(jnp.int32(s), lb_ref[1])
        col = si * bs + jax.lax.broadcasted_iota(jnp.int32, (1, bs), 1)
        colmask = col < bound                               # [1, bs]
        bias = jnp.where(colmask, bias_ref[0], 0.0)        # [1, bs]
        vrow = si * bs + jax.lax.broadcasted_iota(jnp.int32, (bs, 1), 0)
        vmask = vrow < bound                                # [bs, 1]

        for kh in range(kheads):
            rows = slice(kh * GP, (kh + 1) * GP)
            dcol = slice(kh * dh, (kh + 1) * dh)
            q = q_ref[0, rows, :]                           # [Gp, D]
            k_blk = k_ref[0, :, dcol]                       # [bs, D]
            v_blk = v_ref[0, :, dcol]
            if ks_ref is not None:
                k_blk = (k_blk.astype(jnp.float32)
                         * ks_ref[0, kh, :][:, None]).astype(jnp.bfloat16)
                v_blk = (v_blk.astype(jnp.float32)
                         * vs_ref[0, kh, :][:, None]).astype(jnp.bfloat16)
            v_blk = jnp.where(vmask, v_blk, jnp.zeros_like(v_blk))
            s_blk = cap(jax.lax.dot_general(
                q, k_blk, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale)  # [Gp, bs]
            s_blk = jnp.where(colmask, s_blk + bias, NEG_INF)

            m_old = m_ref[rows, :1]                          # [Gp, 1]
            l_old = l_ref[rows, :1]
            m_new = jnp.maximum(m_old,
                                jnp.max(s_blk, axis=1, keepdims=True))
            p = jnp.exp(s_blk - m_new)                       # [Gp, bs]
            corr = jnp.exp(m_old - m_new)                    # [Gp, 1]
            l_new = l_old * corr + jnp.sum(p, axis=1, keepdims=True)
            m_ref[rows, :] = jnp.broadcast_to(m_new, (GP, 128))
            l_ref[rows, :] = jnp.broadcast_to(l_new, (GP, 128))
            pv = jax.lax.dot_general(
                p.astype(v_blk.dtype), v_blk, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)          # [Gp, D]
            acc_ref[rows, :] = acc_ref[rows, :] * corr + pv

    @pl.when(si == ns - 1)
    def _fin():
        o_ref[0, :, :] = acc_ref[...] / l_ref[:, :1]


@partial(jax.jit, static_argnames=("scale", "block_s", "interpret",
                                   "softcap"))
def _call(q3, kn2, vn2, bias, kc, vc, ks, vs, kv_fill, scale, block_s,
          interpret, softcap=0.0):
    b, khgp, dh = q3.shape
    kheads = khgp // GP
    s = kc.shape[1]
    khd = kc.shape[2]
    bs = min(block_s, max(128, -(-s // 128) * 128))
    ns = pl.cdiv(s, bs)
    # last S-block holding a potentially-valid cache column: KV blocks
    # past it clamp their index maps to it (a revisited block is not
    # re-DMA'd) and skip their compute — traffic follows the fill level.
    # The raw fill rides along so the kernel can hard-mask the unwritten
    # tail WITHIN the last block (bias alone cannot kill NaN garbage).
    fill = kv_fill.astype(jnp.int32).reshape(())
    last_blk = jnp.stack([jnp.clip((fill - 1) // bs, 0, ns - 1), fill])

    def clamp(si, lb):
        return jnp.minimum(si, lb[0])

    in_specs = [
        pl.BlockSpec((1, khgp, dh), lambda bi, si, lb: (bi, 0, 0)),
        # per-row operands ride a unit middle dim: a (1, n) block over a
        # [B, n] array breaks Mosaic's tiling rule (second-to-last block
        # dim must be 8-divisible or the array's own), (1, 1, n) over
        # [B, 1, n] satisfies it
        pl.BlockSpec((1, 1, khd), lambda bi, si, lb: (bi, 0, 0)),
        pl.BlockSpec((1, 1, khd), lambda bi, si, lb: (bi, 0, 0)),
        pl.BlockSpec((1, 1, bs),
                     lambda bi, si, lb: (bi, 0, clamp(si, lb))),
        pl.BlockSpec((1, bs, khd),
                     lambda bi, si, lb: (bi, clamp(si, lb), 0)),
        pl.BlockSpec((1, bs, khd),
                     lambda bi, si, lb: (bi, clamp(si, lb), 0)),
    ]
    args = [q3, kn2[:, None, :], vn2[:, None, :], bias[:, None, :], kc, vc]
    quant = ks is not None
    if quant:
        in_specs += [
            pl.BlockSpec((1, kheads, bs),
                         lambda bi, si, lb: (bi, 0, clamp(si, lb))),
            pl.BlockSpec((1, kheads, bs),
                         lambda bi, si, lb: (bi, 0, clamp(si, lb))),
        ]
        args += [ks, vs]

    kw = dict(kheads=kheads, dh=dh, bs=bs, s=s, scale=scale,
              softcap=softcap)
    if quant:
        def kernel(lb_ref, q_ref, kn_ref, vn_ref, bias_ref, k_ref, v_ref,
                   ks_ref, vs_ref, o_ref, m_ref, l_ref, acc_ref):
            _body(lb_ref, q_ref, kn_ref, vn_ref, bias_ref, k_ref, v_ref,
                  ks_ref, vs_ref, o_ref, m_ref, l_ref, acc_ref, **kw)
    else:
        def kernel(lb_ref, q_ref, kn_ref, vn_ref, bias_ref, k_ref, v_ref,
                   o_ref, m_ref, l_ref, acc_ref):
            _body(lb_ref, q_ref, kn_ref, vn_ref, bias_ref, k_ref, v_ref,
                  None, None, o_ref, m_ref, l_ref, acc_ref, **kw)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, ns),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, khgp, dh),
                               lambda bi, si, lb: (bi, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((khgp, 128), jnp.float32),   # m
            pltpu.VMEM((khgp, 128), jnp.float32),   # l
            pltpu.VMEM((khgp, dh), jnp.float32),    # acc
        ],
    )
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((b, khgp, dh), jnp.float32),
        grid_spec=grid_spec,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(last_blk, *args)


def flash_decode_attention(
    q: jnp.ndarray,        # [B, 1, H, D]
    k_cache: jnp.ndarray,  # [B, S, K, D] bf16 or int8
    v_cache: jnp.ndarray,
    k_new: jnp.ndarray,    # [B, 1, K, D]
    v_new: jnp.ndarray,
    *,
    kv_valid: Optional[jnp.ndarray] = None,     # [B, S]
    q_positions: Optional[jnp.ndarray] = None,  # [B, 1]
    kv_positions: Optional[jnp.ndarray] = None,  # [B, S]
    bias: Optional[jnp.ndarray] = None,         # [B, S] fp32 additive
    k_scale: Optional[jnp.ndarray] = None,  # [B, K, S] fp32 (int8 cache)
    v_scale: Optional[jnp.ndarray] = None,
    kv_fill: Optional[jnp.ndarray] = None,  # scalar: valid cols < fill
    softmax_scale: Optional[float] = None,
    window: Optional[int] = None,
    logit_softcap: float = 0.0,
    block_s: int = DEFAULT_BLOCK_S,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """Drop-in for ops.attention.decode_attention (same semantics: joint
    softmax over the un-updated cache plus the new token's k/v, cache
    written by the caller). int8 caches pass their per-(position, head)
    scales — K-MAJOR [B, K, S], the decode cache's storage layout, so no
    transpose traffic rides the per-layer hot loop — and are dequantized
    in VMEM. Masking comes either as a precomputed additive ``bias``
    [B, S] (0 = attend, NEG_INF = masked; callers looping over layers
    build it ONCE per decode step) or as kv_valid/positions/window from
    which it is built here. ``kv_fill`` (scalar int32) promises every
    valid cache column sits below it: KV blocks past the fill level are
    neither read from HBM nor computed, so a right-sized caller (the
    decode engine: fill = prompt_width + step) pays for the cache it
    has actually written, not the preallocated max_new_tokens worth.
    Returns [B, 1, H, D] in v_new.dtype."""
    b, t, h, d = q.shape
    assert t == 1, "flash_decode_attention is single-token by construction"
    _, s, kheads, _ = k_cache.shape
    g = h // kheads
    if g > GP:
        raise ValueError(f"GQA group {g} exceeds the kernel's sublane "
                         f"pad {GP}; use the XLA decode_attention path")
    scale = softmax_scale if softmax_scale is not None else d ** -0.5
    if interpret is None:
        interpret = jax.devices()[0].platform == "cpu"

    # [B, K*Gp, D] query with zero-padded group rows (padded rows see
    # bias-only scores -> finite garbage, sliced off below)
    q4 = q.reshape(b, kheads, g, d).astype(jnp.bfloat16)
    q4 = jnp.pad(q4, ((0, 0), (0, 0), (0, GP - g), (0, 0)))
    q3 = q4.reshape(b, kheads * GP, d)

    if bias is None:
        if kv_valid is None or q_positions is None or kv_positions is None:
            raise ValueError("pass bias= or all of kv_valid/q_positions/"
                             "kv_positions")
        delta = q_positions - kv_positions              # [B, S]
        mask = kv_valid.astype(bool) & (delta >= 0)
        if window is not None:
            mask = mask & (delta < window)
        bias = jnp.where(mask, 0.0, NEG_INF).astype(jnp.float32)

    kc = k_cache.reshape(b, s, kheads * d)
    vc = v_cache.reshape(b, s, kheads * d)
    kn2 = k_new.reshape(b, kheads * d).astype(jnp.bfloat16)
    vn2 = v_new.reshape(b, kheads * d).astype(jnp.bfloat16)
    ks = vs = None
    if k_cache.dtype == jnp.int8:
        if k_scale is None or v_scale is None:
            raise ValueError("int8 cache needs k_scale/v_scale")
        ks = k_scale.astype(jnp.float32)
        vs = v_scale.astype(jnp.float32)

    if kv_fill is None:
        kv_fill = jnp.asarray(s, jnp.int32)  # no bound known: read all
    out = _call(q3, kn2, vn2, bias, kc, vc, ks, vs, kv_fill,
                float(scale), int(block_s), bool(interpret),
                float(logit_softcap))
    out = out.reshape(b, kheads, GP, d)[:, :, :g, :]
    return out.reshape(b, 1, h, d).astype(v_new.dtype)
