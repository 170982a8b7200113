"""Pallas TPU paged decode attention: one token a slot against a
block-paged KV pool, reading the slot's LIVE pages and nothing else.

The XLA paged step (``Transformer._paged_layers``) gathers every slot's
whole window out of the pool, a layer at a time, whatever its fill, and
attends over all of it: on a v5e the two window gathers were 22% of the
dense decode step and the scores over dead columns came on top (PERF.md,
PR 32 / PR 35). This kernel walks the block table instead: per slot it
copies pages ``0 .. ceil(length / page) - 1`` of one layer of the key
and value pools from HBM into VMEM, several pages a step and double
buffered, and runs the online-softmax attention over them in one pass.

Semantics are ``ops.attention.decode_attention``'s over the gathered
window, for a window whose column ``c`` holds position ``c`` and is valid
iff ``c < length`` (what ``PagedKVCache`` keeps for a running slot):
  - the cache is attended UN-updated and this token's key / value join
    as one more softmax column (always attended: delta 0); the caller
    writes the pool, as before;
  - cached column ``c`` is attended iff ``c < length`` and
    ``length - c < window``; scale, then softcap, then mask;
  - bf16 operands into the MXU with float32 accumulation, softmax in
    float32.

Layout choices:
  - the pools go in whole, ``[L, pages, page, K, D]`` as stored, in HBM
    (``pl.ANY``), with the layer as a scalar operand: slicing a layer out
    of the scan's carry, or reshaping a page to ``[page, K * D]``, would
    copy the pool (the tiled minor dims are ``(K, D)``);
  - a page lands in VMEM as ``[page, K, 128]`` per 128 lanes of the
    head (Mosaic's strided load wants a 128-lane base: a 256-wide head
    is two copies a page and two partial products a head); head ``k``
    is rows ``k, k + K, ...`` of the ``[tokens * K, 128]`` view, a
    strided load; 16-bit pages hold two heads to a 32-bit word and the
    pair stays packed through the MXU (``_word_rows``: jax's ragged
    paged attention kernel splits it with a shift and a mask, which
    cost more than the second head's masked scores);
  - grid ``(slots,)``; inside, a loop over blocks of ``pages_per_block``
    pages with a dynamic trip count, the next block (or the next slot's
    first) in flight while this one is computed; a page past the live
    count is neither copied nor waited for, and its stale buffer is
    masked (scores replaced, values zeroed: garbage may be NaN);
  - the GQA group padded to 8 sublanes (padded rows are zero queries:
    finite garbage the wrapper slices off).

Forward only (decode never takes gradients). This module imports Pallas
at module level, which costs about a second of host time: import it
inside the function that needs it (``Transformer.paged_decode_kernel``
does), never from a module a serving or training process imports at
start. What it costs a start beside the import is its trace, by the jnp
call: keep loops over pages rolled and a head's body in one function.
"""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
LANES = 128
GP = 8                      # query-group sublane padding
# VMEM for the two pools' double buffers: 32 pages a block at Mistral's
# [16, 8, 128] bf16 page. Larger blocks amortise the per-block work (16
# pages: 2.29 ms a step of the decode-heavy cell's 16 layers, 32: 1.93;
# PERF.md, PR 35) and waste MXU work on a slot's last, partial block
BUFFER_BYTES = 4 << 20
NO_WINDOW = 2 ** 30         # Transformer._layer_window's unreachable bound


def _cdiv(x, n: int):
    """ceil(x / n) for a traced x >= 0. (``lax.div`` and the shifts below,
    not ``//`` and ``%``: floor division traces to eight jitted calls,
    and the kernel's trace is set-up time on every start.)"""
    return jax.lax.div(x + (n - 1), jnp.int32(n))


def _shift(x, n: int):
    """x // n for a traced x >= 0 and n a power of two."""
    return jax.lax.shift_right_logical(x, jnp.int32(n.bit_length() - 1))


def _word_rows(ref, word: int, kheads: int, hpw: int, dtype):
    """The heads that share 32-bit word ``word`` of every token's rows,
    still packed: ``[tokens * hpw, 128]`` whose row ``hpw * t + e`` is 128
    lanes of head ``hpw * word + e`` of token ``t``, out of ``ref``, the
    ``[tokens * K / hpw, 128]`` view of a buffer as 32-bit words
    (``_kernel.words``). One strided load of words; nothing is
    unpacked (splitting a word's two 16-bit heads cost a shift, a mask
    and two converts a vreg; packed, the second head rides the same MXU
    weights and its scores are masked out of the first's rows)."""
    tokens = ref.shape[0] * hpw // kheads
    rows = ref[pl.ds(word, tokens, stride=kheads // hpw), :]
    return rows if hpw == 1 else pltpu.bitcast(rows, dtype)


@partial(jax.jit, static_argnames=("scale", "softcap"))
def _attend_word(q, k_blk, v_blk, m_old, l_old, acc, mask, vmask, *,
                 scale: float, softcap: float):
    """One online-softmax step for the heads of one word against one
    block. ``q`` / ``k_blk`` / ``v_blk`` / ``acc``: one entry per 128
    lanes of the head; ``q`` [gr, 128], ``k_blk`` / ``v_blk`` [hpw * bk,
    128] packed (``_word_rows``), ``acc`` [gr, 128] float32; ``m_old`` /
    ``l_old`` [gr, 1]; ``mask`` [gr, hpw * bk] the columns a row attends,
    ``vmask`` [hpw * bk, 1] the live ones. Returns the new maximum and
    sum, lane-broadcast for their stores, and the new ``acc``.

    A jitted function so that the kernel, which unrolls the words of a
    token's heads for the scheduler's sake, traces this body once and
    binds it once a word: traced a word at a time, the kernel's trace
    was 0.9 s of every start on the chip's host (PERF.md, PR 35)."""
    s_blk = None                                         # [gr, hpw * bk]
    for q_c, k_c in zip(q, k_blk):
        part = jax.lax.dot_general(
            q_c.astype(k_c.dtype), k_c, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        s_blk = part if s_blk is None else s_blk + part
    s_blk = s_blk * scale
    if softcap:
        # gemma-2's logit softcapping on the SCALED scores, before the
        # mask (decode_attention's order)
        s_blk = softcap * jnp.tanh(s_blk / softcap)
    # scores of a dead column, or of the word's other head, are REPLACED
    # (garbage may be NaN; NaN + mask is NaN)
    s_blk = jnp.where(mask, s_blk, NEG_INF)
    m_new = jnp.maximum(m_old, jnp.max(s_blk, axis=1, keepdims=True))
    p = jnp.exp(s_blk - m_new)
    corr = jnp.exp(m_old - m_new)
    l_new = l_old * corr + jnp.sum(p, axis=1, keepdims=True)
    p = p.astype(v_blk[0].dtype)
    new_acc = []
    for a_c, v_c in zip(acc, v_blk):
        # garbage values zeroed: p is 0 there, and 0 * NaN = NaN
        v_c = jnp.where(vmask, v_c, jnp.zeros_like(v_c))
        new_acc.append(a_c * corr + jax.lax.dot_general(
            p, v_c, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32))             # [gr, 128]
    lanes = (m_new.shape[0], LANES)
    return (jnp.broadcast_to(m_new, lanes), jnp.broadcast_to(l_new, lanes),
            new_acc)


def _kernel(tables_ref, lens_ref, meta_ref,              # scalar prefetch
            q_ref, kn_ref, vn_ref, k_hbm, v_hbm,          # inputs
            o_ref,                                        # output
            k_buf, v_buf, sems, buf_ref, m_ref, l_ref, acc_ref,  # scratch
            *, scale: float, softcap: float, pages_per_slot: int):
    b = pl.program_id(0)
    nslots = pl.num_programs(0)
    _, nd, ppb, page, kheads, _ = k_buf.shape    # nd = D / 128 lanes
    bk = ppb * page
    hpw = 4 // k_buf.dtype.itemsize      # heads to a 32-bit word
    gr = hpw * GP                        # query rows of a word's heads
    layer, window = meta_ref[0], meta_ref[1]
    pools = ((k_hbm, k_buf, 0), (v_hbm, v_buf, 1))

    def live(slot):
        return jnp.minimum(lens_ref[slot], pages_per_slot * page)

    def blocks(slot):
        # a slot of length 0 still takes one (fully masked) block, so
        # that every grid step finds its first block in flight
        return jnp.maximum(_cdiv(live(slot), bk), 1)

    def live_pages(slot, blk):
        """Live pages of block ``blk``: they are its first ones."""
        return jnp.clip(_cdiv(live(slot), page) - blk * ppb, 0, ppb)

    def start(slot, blk, buf):
        """Start the copies of block ``blk``'s live pages into buffer
        ``buf``. A rolled loop over the live pages: unrolled, the
        descriptors of 16 pages at every site were half of the kernel's
        trace and lowering time, which every process start pays
        (PERF.md, PR 35)."""
        def one(j, _):
            pid = tables_ref[slot * pages_per_slot + blk * ppb + j]
            for hbm, vmem, which in pools:
                for c in range(nd):
                    src = hbm.at[layer, pid]
                    if nd > 1:
                        src = src.at[:, :, pl.ds(c * LANES, LANES)]
                    pltpu.make_async_copy(
                        src, vmem.at[buf, c, j], sems.at[which, buf]).start()
            return ()
        jax.lax.fori_loop(0, live_pages(slot, blk), one, ())

    def wait(slot, blk, buf):
        """Wait for what ``start`` started. Every copy into a buffer
        signals the buffer's semaphore with its bytes: a full block (the
        usual one) is waited for with one descriptor a pool, the size of
        the whole buffer; a partial block a page at a time."""
        n = live_pages(slot, blk)

        @pl.when(n == ppb)
        def _full():
            for _, vmem, which in pools:
                pltpu.make_async_copy(vmem.at[buf], vmem.at[buf],
                                      sems.at[which, buf]).wait()

        @pl.when(n < ppb)
        def _partial():
            def one(j, _):
                for _, vmem, which in pools:
                    for c in range(nd):
                        pltpu.make_async_copy(
                            vmem.at[buf, c, j], vmem.at[buf, c, j],
                            sems.at[which, buf]).wait()
                return ()
            jax.lax.fori_loop(0, n, one, ())

    def lanes(c):
        return slice(c * LANES, (c + 1) * LANES)

    def cap(x):
        return softcap * jnp.tanh(x / softcap) if softcap else x

    @pl.when(b == 0)
    def _first():
        buf_ref[0] = 0
        start(0, 0, 0)

    # the new token is the first softmax column: delta 0 is causal and
    # inside any window, so it is never masked (``kn`` / ``vn`` come with
    # each head's row repeated over its group's sublanes)
    s_self = cap(jnp.sum(q_ref[0] * kn_ref[0], axis=1, keepdims=True)
                 * scale)                                   # [K * Gp, 1]
    m_ref[...] = jnp.broadcast_to(s_self, m_ref.shape)
    l_ref[...] = jnp.ones(l_ref.shape, jnp.float32)
    acc_ref[...] = vn_ref[0]

    length = live(b)
    nblk = blocks(b)
    buf0 = buf_ref[0]

    def block(i, _):
        cur = (buf0 + i) & 1

        # in flight while this block is computed: the slot's next
        # block, or after its last the next slot's first
        more = i + 1 < nblk

        @pl.when(jnp.logical_or(more, b + 1 < nslots))
        def _next():
            start(jnp.where(more, b, jnp.minimum(b + 1, nslots - 1)),
                  jnp.where(more, i + 1, 0), 1 - cur)

        wait(b, i, cur)
        # column j of a word's [gr, hpw * bk] scores: token j // hpw,
        # head j % hpw of the word; row r belongs to head r // Gp of it
        col = jax.lax.broadcasted_iota(jnp.int32, (gr, hpw * bk), 1)
        row = jax.lax.broadcasted_iota(jnp.int32, (gr, hpw * bk), 0)
        tok = i * bk + _shift(col, hpw)
        mask = (tok < length) & (length - tok < window)
        if hpw > 1:
            mask &= (col & (hpw - 1)) == _shift(row, GP)
        vtok = i * bk + _shift(jax.lax.broadcasted_iota(
            jnp.int32, (hpw * bk, 1), 0), hpw)
        vmask = vtok < length                                # [hpw * bk, 1]

        def words(buf):
            """A buffer of this block as 32-bit words, one view per 128
            lanes of the head: ``[tokens * K / hpw, 128]``."""
            views = [buf.at[cur, c].reshape(bk * kheads, LANES)
                     for c in range(nd)]
            return views if hpw == 1 else [
                v.bitcast(jnp.uint32) for v in views]
        k_words, v_words = words(k_buf), words(v_buf)
        # the words of a token's heads, unrolled: their products are
        # independent and the scheduler spreads them over the MXUs
        for w in range(kheads // hpw):
            rows = slice(w * gr, (w + 1) * gr)
            m_new, l_new, acc = _attend_word(
                [q_ref[0, rows, lanes(c)] for c in range(nd)],
                [_word_rows(ref, w, kheads, hpw, k_buf.dtype)
                 for ref in k_words],
                [_word_rows(ref, w, kheads, hpw, v_buf.dtype)
                 for ref in v_words],
                m_ref[rows, :1], l_ref[rows, :1],
                [acc_ref[rows, lanes(c)] for c in range(nd)], mask, vmask,
                scale=scale, softcap=softcap)
            m_ref[rows, :] = m_new
            l_ref[rows, :] = l_new
            for c in range(nd):
                acc_ref[rows, lanes(c)] = acc[c]
        return ()

    jax.lax.fori_loop(0, nblk, block, ())
    buf_ref[0] = (buf0 + nblk) & 1
    o_ref[0, :, :] = acc_ref[...] / l_ref[:, :1]


@partial(jax.jit, static_argnames=("scale", "softcap", "pages_per_block",
                                   "interpret"))
def _call(q3, kn, vn, k_pool, v_pool, tables, lengths, meta, *, scale,
          softcap, pages_per_block, interpret):
    b, khgp, dh = q3.shape
    _, _, page, kheads, _ = k_pool.shape
    pages_per_slot = tables.shape[1]
    ppb = max(min(pages_per_block, pages_per_slot), 1)

    def per_slot(shape):
        return pl.BlockSpec((1,) + shape, lambda bi, *_: (bi, 0, 0))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b,),
        in_specs=[per_slot((khgp, dh)), per_slot((khgp, dh)),
                  per_slot((khgp, dh)),
                  pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=per_slot((khgp, dh)),
        scratch_shapes=[
            pltpu.VMEM((2, dh // LANES, ppb, page, kheads, LANES),
                       k_pool.dtype),
            pltpu.VMEM((2, dh // LANES, ppb, page, kheads, LANES),
                       v_pool.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.SMEM((1,), jnp.int32),            # buffer in flight
            pltpu.VMEM((khgp, LANES), jnp.float32),   # m
            pltpu.VMEM((khgp, LANES), jnp.float32),   # l
            pltpu.VMEM((khgp, dh), jnp.float32),    # acc
        ])
    return pl.pallas_call(
        partial(_kernel, scale=scale, softcap=softcap,
                pages_per_slot=pages_per_slot),
        out_shape=jax.ShapeDtypeStruct((b, khgp, dh), jnp.float32),
        grid_spec=grid_spec,
        # slots run in order: each starts the next one's first copy
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="paged_decode_attention",
    )(tables.reshape(-1), lengths, meta, q3, kn, vn, k_pool, v_pool)


def paged_decode_attention(
    q: jnp.ndarray,             # [B, H, D] this token's queries
    k_pool: jnp.ndarray,        # [L, pages, page, K, D] as stored
    v_pool: jnp.ndarray,
    block_tables: jnp.ndarray,  # [B, pages/slot] int32 physical page ids
    lengths: jnp.ndarray,       # [B] int32 cached tokens = this query's pos
    k_new: jnp.ndarray,         # [B, K, D] this token's key (rotary applied)
    v_new: jnp.ndarray,
    *,
    layer,                      # int32 scalar, may be traced
    window=None,                # None, int, or a traced int32 scalar
    softmax_scale: Optional[float] = None,
    logit_softcap: float = 0.0,
    pages_per_block: Optional[int] = None,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """``decode_attention`` over slot b's first ``lengths[b]`` cached
    columns, read page by page through ``block_tables[b]`` out of layer
    ``layer`` of the pools, plus this token's own key and value; the
    pools are not written. Pages past ``ceil(lengths[b] / page)`` are
    not read; their table entries may point anywhere inside the pool
    (the trash page). Returns [B, H, D] in ``v_new``'s dtype."""
    b, h, d = q.shape
    kheads = k_pool.shape[3]
    g = h // kheads
    if g > GP:
        raise ValueError(f"GQA group {g} exceeds the kernel's sublane "
                         f"pad {GP}; use the gather path")
    if d % LANES or (kheads * k_pool.dtype.itemsize) % 4:
        raise ValueError(
            f"head width {d} must be a multiple of 128 and the kv heads "
            f"({kheads} of {k_pool.dtype}) must fill 32-bit words")
    scale = softmax_scale if softmax_scale is not None else d ** -0.5
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    if pages_per_block is None:
        page_bytes = k_pool.shape[2] * kheads * d * k_pool.dtype.itemsize
        pages_per_block = max(BUFFER_BYTES // (4 * page_bytes), 1)
    # [B, K * Gp, D] per-slot operands in float32 containers (their
    # 8-row slices then sit on whole tiles; the values are the model's:
    # the kernel casts to the page dtype before the MXU): the queries
    # with their group rows zero-padded, this token's key and value with
    # each head's row repeated over its group's rows. (Plain reshapes,
    # one concatenate and two broadcasts: ``jnp.pad`` and ``jnp.repeat``
    # trace to ten times as many calls, and the trace is set-up time.)
    q4 = q.reshape(b, kheads, g, d).astype(jnp.float32)
    if g < GP:
        q4 = jnp.concatenate(
            [q4, jnp.zeros((b, kheads, GP - g, d), jnp.float32)], axis=2)

    def per_group_row(x, dtype):
        x = x.astype(dtype).astype(jnp.float32)[:, :, None, :]
        return jnp.broadcast_to(x, (b, kheads, GP, d)).reshape(
            b, kheads * GP, d)
    meta = jnp.stack([
        jnp.asarray(layer, jnp.int32).reshape(()),
        jnp.asarray(NO_WINDOW if window is None else window,
                    jnp.int32).reshape(())])
    out = _call(q4.reshape(b, kheads * GP, d),
                per_group_row(k_new, k_pool.dtype),
                per_group_row(v_new, v_pool.dtype),
                k_pool, v_pool, block_tables.astype(jnp.int32),
                lengths.astype(jnp.int32), meta,
                scale=float(scale), softcap=float(logit_softcap),
                pages_per_block=int(pages_per_block),
                interpret=bool(interpret))
    out = out.reshape(b, kheads, GP, d)[:, :, :g, :]
    return out.reshape(b, h, d).astype(v_new.dtype)
