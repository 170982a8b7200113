"""Attention ops.

``causal_attention`` is the XLA-fused reference implementation: einsum QK^T
-> masked softmax (fp32) -> einsum with V. XLA fuses the mask+softmax into
the matmuls well on TPU; the Pallas flash kernel
(dla_tpu.ops.flash_attention) replaces it for long sequences where the
[B, H, T, T] score materialization no longer fits HBM, and ring attention
(dla_tpu.ops.ring_attention) extends it over the ``sequence`` mesh axis.

Replaces: HF attention internals + the optional flash-attention path the
reference only gestures at (reference src/models/base_model.py:39-40 — the
flag merely sets use_cache=False).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

NEG_INF = -1e30  # large-but-finite: -inf breaks softmax rows that are fully masked
# query-block size for chunked_causal_attention; the dispatch gate in
# models/transformer.py keys off this same constant
DEFAULT_Q_CHUNK = 512


def causal_attention(
    q: jnp.ndarray,  # [B, T, H, D]
    k: jnp.ndarray,  # [B, S, K, D]   K = num kv heads (GQA when K < H)
    v: jnp.ndarray,  # [B, S, K, D]
    *,
    kv_segment_mask: Optional[jnp.ndarray] = None,  # [B, T, S] extra mask (1=attend)
    q_positions: Optional[jnp.ndarray] = None,  # [B, T] absolute positions
    kv_positions: Optional[jnp.ndarray] = None,  # [B, S]
    causal: bool = True,
    softmax_scale: Optional[float] = None,
    window: Optional[int] = None,  # sliding window: attend (q-window, q]
    logit_softcap: float = 0.0,    # gemma-2: cap*tanh(scores/cap) pre-mask
) -> jnp.ndarray:
    """Grouped-query causal attention. Returns [B, T, H, D].

    Causality is evaluated on absolute positions so the same op serves
    full-sequence training (q_positions == kv_positions == arange) and
    single-token decode against a KV cache (q_positions = current step).
    ``window`` adds mistral-style sliding-window attention (HF
    ``sliding_window``): token q attends only kv positions in
    (q - window, q]. Position-based, so it is decode-correct too —
    and it may be a TRACED scalar (gemma-2's alternating-layer window
    rides the layer scan as data).
    """
    b, t, h, d = q.shape
    _, s, kheads, _ = k.shape
    groups = h // kheads
    scale = softmax_scale if softmax_scale is not None else d ** -0.5

    qg = q.reshape(b, t, kheads, groups, d)
    # scores [B, K, G, T, S] — fp32 out of the MXU (bf16 operands with
    # fp32 accumulation), so softmax numerics match ring/flash/fused_ce
    scores = jnp.einsum("btkgd,bskd->bkgts", qg, k,
                        preferred_element_type=jnp.float32) * scale
    if logit_softcap:
        scores = logit_softcap * jnp.tanh(scores / logit_softcap)

    if window is not None and not causal:
        raise ValueError("window implements causal sliding-window "
                         "semantics (q - window, q]; causal=False with a "
                         "window would silently attend the whole future")
    mask = None
    if causal or window is not None:
        if q_positions is None:
            q_positions = jnp.arange(t)[None, :]
        if kv_positions is None:
            kv_positions = jnp.arange(s)[None, :]
        delta = q_positions[:, :, None] - kv_positions[:, None, :]  # [B,T,S]
        mask = delta >= 0 if causal else None
        if window is not None:
            win = delta < window
            mask = win if mask is None else (mask & win)
    if kv_segment_mask is not None:
        seg = kv_segment_mask.astype(bool)
        mask = seg if mask is None else (mask & seg)
    if mask is not None:
        scores = jnp.where(mask[:, None, None, :, :], scores, NEG_INF)

    weights = jnp.exp(scores - jnp.max(scores, axis=-1, keepdims=True))
    weights = weights / (jnp.sum(weights, axis=-1, keepdims=True) + 1e-30)
    out = jnp.einsum("bkgts,bskd->btkgd", weights.astype(v.dtype), v)
    return out.reshape(b, t, h, d)


def chunked_causal_attention(
    q: jnp.ndarray,  # [B, T, H, D]
    k: jnp.ndarray,  # [B, S, K, D]
    v: jnp.ndarray,  # [B, S, K, D]
    *,
    kv_segment_mask: Optional[jnp.ndarray] = None,  # [B, T, S]
    q_positions: Optional[jnp.ndarray] = None,      # [B, T]
    kv_positions: Optional[jnp.ndarray] = None,     # [B, S]
    softmax_scale: Optional[float] = None,
    window=None,                     # static int or traced scalar
    logit_softcap: float = 0.0,
    q_chunk: int = DEFAULT_Q_CHUNK,
    kv_valid: Optional[jnp.ndarray] = None,         # [B, S] 1 = attend
    q_segments: Optional[jnp.ndarray] = None,       # [B, T] packed ids
    kv_segments: Optional[jnp.ndarray] = None,      # [B, S]
) -> jnp.ndarray:
    """causal_attention computed one query block at a time: peak live
    scores are [B, H, q_chunk, S] instead of [B, H, T, T].

    This is the O(T)-memory path for models the Pallas flash kernel
    cannot serve (gemma-2: softcapping / per-layer windows / custom
    scale) — without it their training forward+backward materializes
    quadratic score tensors, the same class of blowup ops.fused_ce
    exists to kill on the loss side. The scan body is jax.checkpoint-ed
    so the BACKWARD also recomputes per chunk rather than saving every
    chunk's weights (which would re-materialize the full [B, H, T, S]).
    A T that doesn't divide into chunks is PADDED up (pad query rows
    compute garbage nothing consumes; outputs sliced back to T), so the
    O(T * chunk) bound holds for every length.

    Masking comes in two forms: a caller-materialized ``kv_segment_mask``
    [B, T, S] (itself O(T^2) bytes — fine at moderate T), or the FACTORED
    1-D metadata ``kv_valid`` / ``q_segments`` / ``kv_segments``, from
    which each chunk's [B, C, S] mask slab is built inside the
    checkpointed body — nothing quadratic ever lives, the ring kernel's
    own trick. The two are mutually exclusive; semantics match
    causal_attention exactly.
    """
    b, t, h, d = q.shape
    if kv_segment_mask is not None and (
            kv_valid is not None or q_segments is not None
            or kv_segments is not None):
        raise ValueError("pass kv_segment_mask OR factored "
                         "kv_valid/q_segments/kv_segments, not both")
    if (q_segments is None) != (kv_segments is None):
        raise ValueError("q_segments and kv_segments must be passed "
                         "together (a one-sided segment restriction "
                         "would be silently dropped)")

    def factored_mask_slab(qseg_c, rows):
        """[B, rows, S] mask from the 1-D metadata for one query chunk."""
        slab = None
        if kv_valid is not None:
            slab = jnp.broadcast_to(
                kv_valid[:, None, :].astype(bool),
                (b, rows, kv_valid.shape[1]))
        if qseg_c is not None and kv_segments is not None:
            same = qseg_c[:, :, None] == kv_segments[:, None, :]
            slab = same if slab is None else (slab & same)
        return slab

    if t <= q_chunk:
        mc = kv_segment_mask
        if mc is None and (kv_valid is not None or q_segments is not None):
            mc = factored_mask_slab(q_segments, t)
        return causal_attention(
            q, k, v, kv_segment_mask=mc,
            q_positions=q_positions, kv_positions=kv_positions,
            softmax_scale=softmax_scale, window=window,
            logit_softcap=logit_softcap)
    if q_positions is None:
        q_positions = jnp.broadcast_to(jnp.arange(t)[None, :], (b, t))
    pad = (-t) % q_chunk
    tp = t + pad
    if pad:
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
        # pad rows get in-range causal positions; their outputs are
        # garbage that the final slice drops
        q_positions = jnp.pad(q_positions, ((0, 0), (0, pad)),
                              constant_values=0)
        if kv_segment_mask is not None:
            kv_segment_mask = jnp.pad(
                kv_segment_mask, ((0, 0), (0, pad), (0, 0)),
                constant_values=1)
        if q_segments is not None:
            q_segments = jnp.pad(q_segments, ((0, 0), (0, pad)),
                                 constant_values=0)
    nc = tp // q_chunk
    q_c = q.reshape(b, nc, q_chunk, h, d).transpose(1, 0, 2, 3, 4)
    pos_c = q_positions.reshape(b, nc, q_chunk).transpose(1, 0, 2)
    xs = [q_c, pos_c]
    if kv_segment_mask is not None:
        xs.append(kv_segment_mask.reshape(
            b, nc, q_chunk, kv_segment_mask.shape[-1]
        ).transpose(1, 0, 2, 3))
    if q_segments is not None:
        xs.append(q_segments.reshape(b, nc, q_chunk).transpose(1, 0, 2))

    def body(_, chunk_xs):
        qc, pc = chunk_xs[0], chunk_xs[1]
        if kv_segment_mask is not None:
            mc = chunk_xs[2]
        else:
            qseg_c = chunk_xs[2] if q_segments is not None else None
            mc = factored_mask_slab(qseg_c, q_chunk)
        out = causal_attention(
            qc, k, v, kv_segment_mask=mc, q_positions=pc,
            kv_positions=kv_positions, softmax_scale=softmax_scale,
            window=window, logit_softcap=logit_softcap)
        return None, out

    _, outs = jax.lax.scan(jax.checkpoint(body), None, tuple(xs))
    return outs.transpose(1, 0, 2, 3, 4).reshape(b, tp, h, d)[:, :t]


def block_decode_attention(
    q: jnp.ndarray,       # [B, G, H, D]  the block's queries
    k_cache: jnp.ndarray,  # [B, S, K, D]  cache BEFORE this block's write
    v_cache: jnp.ndarray,
    k_new: jnp.ndarray,    # [B, G, K, D]  the block's keys (rotary applied)
    v_new: jnp.ndarray,
    *,
    kv_valid: jnp.ndarray,        # [B, S] valid cache columns (1=attend)
    q_positions: jnp.ndarray,     # [B, G] absolute position per query
    kv_positions: jnp.ndarray,    # [B, S] logical position per cache column
    softmax_scale: Optional[float] = None,
    window: Optional[int] = None,
    logit_softcap: float = 0.0,
) -> jnp.ndarray:
    """decode_attention generalized from one query token to a block of
    G: joint softmax over the un-updated cache PLUS the block's own
    keys (intra-block causal on absolute positions), WITHOUT writing
    the cache — the caller writes all G columns once, outside the
    layer loop. This is the verification step of speculative decoding
    (score G draft tokens in ONE forward) and degenerates to
    decode_attention semantics at G = 1. Returns [B, G, H, D]."""
    b, g, h, d = q.shape
    _, s, kheads, _ = k_cache.shape
    groups = h // kheads
    scale = softmax_scale if softmax_scale is not None else d ** -0.5

    qg = q.reshape(b, g, kheads, groups, d)
    # [B, K, Gr, G, S] scores against the existing cache
    scores = jnp.einsum("bgkrd,bskd->bkrgs", qg, k_cache,
                        preferred_element_type=jnp.float32) * scale
    # [B, K, Gr, G, G] scores against the block's own keys
    self_scores = jnp.einsum("bgkrd,btkd->bkrgt", qg, k_new,
                             preferred_element_type=jnp.float32) * scale
    if logit_softcap:
        scores = logit_softcap * jnp.tanh(scores / logit_softcap)
        self_scores = logit_softcap * jnp.tanh(
            self_scores / logit_softcap)

    delta = q_positions[:, :, None] - kv_positions[:, None, :]  # [B,G,S]
    mask = kv_valid[:, None, :].astype(bool) & (delta >= 0)
    if window is not None:
        mask = mask & (delta < window)
    scores = jnp.where(mask[:, None, None, :, :], scores, NEG_INF)
    sdelta = q_positions[:, :, None] - q_positions[:, None, :]  # [B,G,G]
    smask = sdelta >= 0
    if window is not None:
        smask = smask & (sdelta < window)
    self_scores = jnp.where(smask[:, None, None, :, :], self_scores,
                            NEG_INF)

    joint = jnp.concatenate([scores, self_scores], axis=-1)
    joint = joint - jnp.max(joint, axis=-1, keepdims=True)
    weights = jnp.exp(joint)
    weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    w_cache = weights[..., :s].astype(v_cache.dtype)
    w_self = weights[..., s:].astype(v_new.dtype)
    out = jnp.einsum("bkrgs,bskd->bgkrd", w_cache, v_cache)
    out = out + jnp.einsum("bkrgt,btkd->bgkrd", w_self, v_new)
    return out.reshape(b, g, h, d)


def blockwise_paged_attention(
    q: jnp.ndarray,        # [B, T, H, D]  the chunk's queries
    k_pool: jnp.ndarray,   # [L, pages, page, ...]  a row = K * D numbers
    v_pool: jnp.ndarray,
    layer,                 # scalar index into the pools' leading axis
    block_tables: jnp.ndarray,  # [B, pages/slot]  physical page ids
    context: jnp.ndarray,  # [B] int32  cached columns before the chunk
    k_new: jnp.ndarray,    # [B, T, K, D]  the chunk's keys
    v_new: jnp.ndarray,
    *,
    q_positions: jnp.ndarray,   # [B, T] absolute position per query
    block_pages: int,
    softmax_scale: Optional[float] = None,
) -> jnp.ndarray:
    """``block_decode_attention`` over each row's paged prefix, WITHOUT
    gathering the row's window: the cached columns are read in blocks of
    ``block_pages`` whole pages up to the live context, with an online
    soft-max. Row b's column c lives at offset ``c % page`` of page
    ``block_tables[b, c // page]``, sits at position c and is attended
    iff ``c < context[b]`` (what ``PagedKVCache`` keeps for a prefilling
    slot: ``valid`` a prefix of length ``start``, every query of the
    chunk at or past it), so validity, causality and position are one
    compare; no window, no soft-cap.

    The walk is a ``lax.fori_loop`` whose trip count is computed in the
    program, ``ceil(max(context) / block columns)``: a block past every
    row's context is neither gathered nor scored, and a context of 0 runs
    none. A block gathers its pages of ``layer`` alone (the reason
    ``Transformer._paged_layers`` gathers a layer at a time holds here
    too), scores [B, K, G, T, block] in float32, masks ``column <
    context`` and folds into the running row maximum, row sum and
    un-normalised float32 output. The chunk's own keys and values come
    last (causal by absolute position: pad tokens carry later positions
    than every real query), and the output is normalised once. Operands
    are as ``block_decode_attention`` has them: the weights are cast to
    the value dtype for the value product, which accumulates in float32.
    The same mathematics, rounding apart.

    One bound serves every row, so this is for programs of one slot or
    few (a prefill chunk). The one-token step of many slots with as many
    lengths keeps its whole-window gather (``decode_attention``): bounded
    by the longest row it would read nearly everything, and what it wants
    is the per-slot page walk of ops/paged_attention.py. Returns
    [B, T, H, D]."""
    b, t, h, d = q.shape
    kheads = k_new.shape[2]
    groups = h // kheads
    page = k_pool.shape[2]
    cols = block_pages * page
    scale = softmax_scale if softmax_scale is not None else d ** -0.5
    # whole blocks: pad entries name the trash page, and their columns
    # lie past every context
    n_blocks = -(-block_tables.shape[1] // block_pages)
    tables = jnp.pad(block_tables, (
        (0, 0), (0, n_blocks * block_pages - block_tables.shape[1])))
    qg = q.reshape(b, t, kheads, groups, d).transpose(0, 2, 3, 1, 4)
    context = context.astype(jnp.int32)

    def fold(carry, scores, mask, values):
        """One block of masked scores [B, K, G, T, S] and its values
        [B, S, K, D] into (row maximum, row sum, output)."""
        m, l, acc = carry
        scores = jnp.where(mask, scores, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(scores, axis=-1))
        # a row with nothing to attend yet keeps m at NEG_INF: its
        # weights are zeroed by the mask, not by the exponential
        p = jnp.where(mask, jnp.exp(scores - m_new[..., None]), 0.0)
        alpha = jnp.exp(m - m_new)
        l = alpha * l + jnp.sum(p, axis=-1)
        acc = alpha[..., None] * acc + jnp.einsum(
            "bkgts,bskd->bkgtd", p.astype(values.dtype), values,
            preferred_element_type=jnp.float32)
        return m_new, l, acc

    def block(j, carry):
        pages = jax.lax.dynamic_slice_in_dim(
            tables, j * block_pages, block_pages, axis=1)
        k_blk = k_pool[layer, pages].reshape(b, cols, kheads, d)
        v_blk = v_pool[layer, pages].reshape(b, cols, kheads, d)
        scores = jnp.einsum("bkgtd,bskd->bkgts", qg, k_blk,
                            preferred_element_type=jnp.float32) * scale
        column = j * cols + jnp.arange(cols, dtype=jnp.int32)
        mask = column[None, :] < context[:, None]                # [B, S]
        return fold(carry, scores, mask[:, None, None, None, :], v_blk)

    stats = (b, kheads, groups, t)
    carry = (jnp.full(stats, NEG_INF, jnp.float32),
             jnp.zeros(stats, jnp.float32),
             jnp.zeros(stats + (d,), jnp.float32))
    trips = jnp.minimum(-(-jnp.max(context) // cols), n_blocks)
    carry = jax.lax.fori_loop(0, trips, block, carry)

    self_scores = jnp.einsum("bkgtd,bskd->bkgts", qg, k_new,
                             preferred_element_type=jnp.float32) * scale
    smask = q_positions[:, :, None] >= q_positions[:, None, :]   # [B,T,T]
    _, l, acc = fold(carry, self_scores, smask[:, None, None], v_new)
    out = (acc / l[..., None]).astype(v_new.dtype)
    return out.transpose(0, 3, 1, 2, 4).reshape(b, t, h, d)


def decode_attention(
    q: jnp.ndarray,       # [B, 1, H, D]  the current token's query
    k_cache: jnp.ndarray,  # [B, S, K, D]  cache BEFORE this step's write
    v_cache: jnp.ndarray,  # [B, S, K, D]
    k_new: jnp.ndarray,    # [B, 1, K, D]  this token's key (rotary applied)
    v_new: jnp.ndarray,    # [B, 1, K, D]
    *,
    kv_valid: jnp.ndarray,        # [B, S] valid cache columns (1=attend)
    q_positions: jnp.ndarray,     # [B, 1] absolute position of the token
    kv_positions: jnp.ndarray,    # [B, S] logical position per cache column
    softmax_scale: Optional[float] = None,
    window: Optional[int] = None,
    logit_softcap: float = 0.0,
) -> jnp.ndarray:
    """Single-token attention over an un-updated KV cache plus the
    just-computed key/value, WITHOUT writing the cache.

    The decode hot loop is HBM-bound; inserting ``k_new`` into the cache
    before attending forces a [B, S, K, D] copy per layer per step (the
    round-3 decode path paid this twice: once for the in-loop
    dynamic_update_slice, once re-emitting the cache through the layer
    scan). Instead the new token's score column is concatenated to the
    *score* matrix — [B, K, G, 1, S+1] floats, not KV bytes — and the
    output is the jointly-softmaxed mix of the cache values and
    ``v_new``. The caller writes the cache once, outside the layer loop.

    The new token always attends to itself (delta 0: causal and inside
    any window); cache columns are masked by validity, causality, and the
    optional sliding window on logical positions. Returns [B, 1, H, D].
    """
    b, t, h, d = q.shape
    assert t == 1, "decode_attention is single-token by construction"
    _, s, kheads, _ = k_cache.shape
    groups = h // kheads
    scale = softmax_scale if softmax_scale is not None else d ** -0.5

    qg = q.reshape(b, kheads, groups, d)
    # [B, K, G, S] scores against the existing cache (fp32 accumulation)
    scores = jnp.einsum("bkgd,bskd->bkgs", qg, k_cache,
                        preferred_element_type=jnp.float32) * scale
    if logit_softcap:
        scores = logit_softcap * jnp.tanh(scores / logit_softcap)
    delta = q_positions - kv_positions            # [B, S]
    mask = kv_valid.astype(bool) & (delta >= 0)
    if window is not None:
        mask = mask & (delta < window)
    scores = jnp.where(mask[:, None, None, :], scores, NEG_INF)
    # [B, K, G, 1] the new token's self-score
    self_score = jnp.einsum("bkgd,bkd->bkg", qg, k_new[:, 0],
                            preferred_element_type=jnp.float32
                            )[..., None] * scale
    if logit_softcap:
        self_score = logit_softcap * jnp.tanh(self_score / logit_softcap)

    joint = jnp.concatenate([scores, self_score], axis=-1)  # [B,K,G,S+1]
    joint = joint - jnp.max(joint, axis=-1, keepdims=True)
    weights = jnp.exp(joint)
    weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    w_cache = weights[..., :s].astype(v_cache.dtype)
    w_self = weights[..., s:].astype(v_new.dtype)           # [B,K,G,1]
    out = jnp.einsum("bkgs,bskd->bkgd", w_cache, v_cache)
    out = out + w_self * v_new[:, 0][:, :, None, :]
    return out.reshape(b, 1, h, d)
