"""``ops.attention.blockwise_paged_attention`` (a chunk's queries over the
row's paged prefix, read in blocks up to the live context with an online
soft-max) against ``block_decode_attention`` over the gathered window:
the same mathematics, rounding apart.

Tolerances. Float32 against float32 differ by the order of the sums (a
running maximum and one division at the end against a soft-max over the
whole row): read 4e-7 on outputs of size 1, held to 1e-5. In bfloat16 the
two round their weights at different points (normalised against
un-normalised): read 0.016, held to the 0.08 ``tests/test_jamba_serving.py``
holds a bfloat16 engine to.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dla_tpu.ops.attention import (
    block_decode_attention,
    blockwise_paged_attention,
)

TOL = {"float32": 1e-5, "bfloat16": 0.08}
PAGE, TABLE_PAGES, BLOCK_PAGES, T, D, LAYERS = 4, 12, 2, 8, 16, 3
BLOCK = PAGE * BLOCK_PAGES                     # 8 columns a block
WINDOW = PAGE * TABLE_PAGES                    # 48 columns a row
#: (key / value heads, query heads a key head)
SHAPES = {"grouped_query": (2, 2), "multi_query": (1, 20)}


def _inputs(dtype, shape, rows, layer=1, seed=0):
    """Pools of ``LAYERS`` layers whose pages are all different, rows'
    block tables that are a shuffle of the pool's pages (page 0 is the
    trash page), and a chunk's queries, keys and values."""
    kheads, groups = SHAPES[shape]
    keys = jax.random.split(jax.random.key(seed), 5)
    pages = rows * TABLE_PAGES + 1
    pools = [jax.random.normal(k, (LAYERS, pages, PAGE, kheads * D), dtype)
             for k in keys[:2]]
    q = jax.random.normal(keys[2], (rows, T, kheads * groups, D), dtype)
    k_new, v_new = (jax.random.normal(k, (rows, T, kheads, D), dtype)
                    for k in keys[3:])
    tables = 1 + np.random.RandomState(seed).permutation(
        pages - 1).reshape(rows, TABLE_PAGES)
    return pools, layer, jnp.asarray(tables, jnp.int32), q, k_new, v_new


def _gathered(pools, layer, tables, q, k_new, v_new, context, positions):
    """``block_decode_attention`` over every row's whole gathered window,
    as the chunk program called it before the walk."""
    rows = q.shape[0]
    heads = (rows, WINDOW, k_new.shape[2], D)
    window = jnp.arange(WINDOW, dtype=jnp.int32)
    return block_decode_attention(
        q, pools[0][layer, tables].reshape(heads),
        pools[1][layer, tables].reshape(heads), k_new, v_new,
        kv_valid=window[None] < context[:, None], q_positions=positions,
        kv_positions=jnp.broadcast_to(window, (rows, WINDOW)),
        softmax_scale=D ** -0.5)


def _walked(pools, layer, tables, q, k_new, v_new, context, positions,
            block_pages=BLOCK_PAGES):
    return jax.jit(lambda *a: blockwise_paged_attention(
        *a, q_positions=positions, block_pages=block_pages,
        softmax_scale=D ** -0.5))(
        q, pools[0], pools[1], layer, tables, context, k_new, v_new)


def _gap(a, b):
    return float(jnp.abs(a.astype(jnp.float32)
                         - b.astype(jnp.float32)).max())


def _chunk(contexts):
    """Contexts [B] and the chunk's positions [B, T]: a column's position
    is its index, pads included (they come after every real query)."""
    context = jnp.asarray(contexts, jnp.int32)
    return context, context[:, None] + jnp.arange(T, dtype=jnp.int32)[None]


@pytest.mark.parametrize("dtype", sorted(TOL))
@pytest.mark.parametrize("shape", sorted(SHAPES))
@pytest.mark.parametrize("contexts", [
    (0,), (1,), (BLOCK - 1,), (BLOCK,), (BLOCK + 1,), (WINDOW - T,),
    (WINDOW,), (3, 29), (40, 0)],
    ids=lambda c: "context_" + "_".join(map(str, c)))
def test_walk_matches_the_gathered_window(dtype, shape, contexts):
    """No block, one column, a column short of a block, a block, a block
    and one, the longest context a chunk of the engine meets, the whole
    window, and two rows of different contexts (the shorter row sits out
    the later blocks with nothing to attend)."""
    args = _inputs(jnp.dtype(dtype), shape, len(contexts))
    context, positions = _chunk(contexts)
    want = _gathered(*args, context, positions)
    got = _walked(*args, context, positions)
    assert got.shape == want.shape and got.dtype == want.dtype
    assert _gap(got, want) < TOL[dtype]


@pytest.mark.parametrize("dtype", sorted(TOL))
@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_pad_tokens_mask_themselves(dtype, shape):
    """A ragged last chunk: 3 real tokens, 5 pads at the later positions.
    Every real query reads what the gathered form reads, and the pads'
    keys, however large, reach no real query."""
    pools, layer, tables, q, k_new, v_new = _inputs(
        jnp.dtype(dtype), shape, 1, seed=3)
    context, positions = _chunk((13,))
    want = _gathered(pools, layer, tables, q, k_new, v_new, context,
                     positions)
    got = _walked(pools, layer, tables, q, k_new, v_new, context, positions)
    assert _gap(got, want) < TOL[dtype]
    loud = _walked(pools, layer, tables, q, k_new.at[:, 3:].mul(50.0),
                   v_new.at[:, 3:].add(7.0), context, positions)
    assert _gap(loud[:, :3], want[:, :3]) < TOL[dtype]
    assert _gap(loud[:, 3:], want[:, 3:]) > 1.0


@pytest.mark.parametrize("block_pages", [1, 5, TABLE_PAGES])
def test_any_whole_number_of_pages_a_block(block_pages):
    """A page a block, a block that does not divide the table (the last
    one runs over pad entries) and the whole table in one."""
    args = _inputs(jnp.float32, "multi_query", 2, seed=5)
    context, positions = _chunk((WINDOW, 17))
    want = _gathered(*args, context, positions)
    got = _walked(*args, context, positions, block_pages=block_pages)
    assert _gap(got, want) < TOL["float32"]


@pytest.mark.parametrize("context", [0, 5, BLOCK, 2 * BLOCK + 1])
def test_a_block_past_the_context_is_not_read(context):
    """Pages past the last block the context reaches hold NaN: a block
    that ran over them would give NaN whatever its mask (0 x NaN in the
    value product), as the gathered form does."""
    pools, layer, tables, q, k_new, v_new = _inputs(
        jnp.float32, "multi_query", 1, seed=7)
    ctx, positions = _chunk((context,))
    want = _gathered(pools, layer, tables, q, k_new, v_new, ctx, positions)
    live_pages = -(-context // BLOCK) * BLOCK_PAGES
    dead = np.asarray(tables)[0, live_pages:]
    poisoned = [p.at[:, dead].set(jnp.nan) for p in pools]
    got = _walked(poisoned, layer, tables, q, k_new, v_new, ctx, positions)
    assert _gap(got, want) < TOL["float32"]
    if live_pages < TABLE_PAGES:
        assert not np.isfinite(np.asarray(_gathered(
            poisoned, layer, tables, q, k_new, v_new, ctx, positions))).all()


def test_the_trip_count_is_computed_in_the_program():
    """One program for every context: a ``while`` whose bound is traced,
    whose body holds the gather, and no score as wide as the window."""
    args = _inputs(jnp.float32, "multi_query", 1)
    context, positions = _chunk((9,))
    pools, layer, tables, q, k_new, v_new = args
    text = jax.jit(lambda ctx, pos: blockwise_paged_attention(
        q, pools[0], pools[1], layer, tables, ctx, k_new, v_new,
        q_positions=pos, block_pages=BLOCK_PAGES)).lower(
        context, positions).as_text()
    assert "stablehlo.while" in text
    body = text[text.index("stablehlo.while"):]
    assert "stablehlo.gather" in body or "dynamic_gather" in body
    # the scores: [B, K, G, T, block] and [B, K, G, T, T], never the window
    assert f"x{T}x{BLOCK}xf32" in text
    assert f"x{T}x{WINDOW}xf32" not in text
    assert f"x{T}x{WINDOW + T}xf32" not in text


def test_layer_picks_the_layers_rows():
    """The same tables over another layer of the pools read other rows."""
    pools, _, tables, q, k_new, v_new = _inputs(jnp.float32, "grouped_query",
                                                1)
    context, positions = _chunk((20,))
    outs = [_walked(pools, jnp.int32(l), tables, q, k_new, v_new, context,
                    positions) for l in range(LAYERS)]
    for l in range(LAYERS):
        assert _gap(outs[l], _gathered(pools, l, tables, q, k_new, v_new,
                                       context, positions)) < TOL["float32"]
    assert _gap(outs[0], outs[1]) > 1e-2
