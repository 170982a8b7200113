"""Pallas TPU selective scan for a prefill chunk: the chunk form of the
Mamba-1 recurrence (``ops/selective_scan.py``) with the layer's state
held in VMEM from the chunk's first token to its last.

``selective_scan_chunk`` is XLA's: a ``lax.scan`` over blocks of 16
tokens with an associative scan inside each, whose ``decay``, ``add``
and cumulative tensors (``f32[1, 16, N, d_inner]``, 5 MB each at d_inner
5,120) live in HBM: on a v5e 32 us a block, 26.5 ms for the 26 layers of
a 512-token chunk, 5% of what the vector unit allows (PERF.md, PR 36 /
PR 37). Nothing in the recurrence needs the state in HBM between two
tokens of a chunk: a layer's state is ``f32[16, 5120]`` = 320 KB. This
kernel reads ``x``, ``dt``, ``B``, ``C`` once, writes ``y`` and the
final state once, and steps through the tokens one at a time with the
state in registers: 2.9 ms for the same 26 layers (PERF.md, PR 37).

Same mathematics and precisions as ``selective_scan_step`` applied token
by token (``exp``, the products and the state in float32; ``dt = 0``
leaves the state as it was); sequential, so nearer the reference than
the associative form.

Layout choices:
  - the state tile of a grid step is ``[N, 1024]`` float32, N on
    sublanes and channels on lanes, as the cache stores it (``[N,
    d_inner]``): 16 vregs at N = 16, carried through the token loop as
    a value; a token's ``dt`` and ``dt * x`` are one row of their
    ``[tokens, 1024]`` blocks, loaded replicated over the N sublanes
    (whole rows: Mosaic does not take a lane slice of such a load);
  - ``B_t`` and ``C_t`` are ``[N]`` vectors the state wants along
    sublanes, the same for every channel: the wrapper broadcasts them
    over 128 lanes outside the kernel (``[T, N, 128]`` in the
    activation dtype: 2 MB a layer at 512 tokens in bfloat16), so a
    token's is one aligned tile and no relayout runs per token;
  - grid ``(batch, token blocks, channel tiles)``, channel tiles
    innermost: the ``B`` / ``C`` block of a token block is fetched once
    and the state of every channel tile waits in a VMEM scratch
    ``[tiles, N, 1024]`` for the next token block;
  - ``y_t = sum_n S_t * C_t`` is a sublane reduction, stored a row at a
    time; ``D * x`` is added to the whole block after the loop;
  - the token loop is rolled, eight tokens written out an iteration:
    the loop is not software-pipelined, and one token an iteration
    leaves its chain of latencies uncovered (6.5 ms for the 26 layers
    against 4.4 in the microbenchmark, which adds 1.5 of its own).

Forward only: no VJP (``HybridStack.forward``, the path a backward pass
would take, keeps XLA's form). Nothing here names Pallas at module
level; :func:`pallas` imports it on first use, and the model that will
run the kernel starts that import early (``Transformer``'s constructor).
Keep the trace small: one rolled loop over tokens, traced once.
"""
from __future__ import annotations

from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from dla_tpu.utils.compile_cache import cached_bytecode
from dla_tpu.utils.profiling import startup_span

F32 = jnp.float32
LANES = 128
#: channels a grid step carries through its token loop (16 state vregs
#: at N = 16): 512 and 1,280 read the same within 3%, 256 half as fast
#: again (PERF.md, PR 37)
LANE_TILE = 1024
#: a chunk's length has to be a multiple of this (a bfloat16 tile's
#: sublanes); the token block is the largest power of two up to
#: MAX_TOKEN_BLOCK that divides it (64 to 512 read the same: 128 keeps
#: the double-buffered blocks at 5 MB of VMEM in bfloat16, 8 in float32)
TOKEN_ALIGN = 16
MAX_TOKEN_BLOCK = 128
#: tokens written out a loop iteration; divides TOKEN_ALIGN
UNROLL = 8
#: Mamba-1's state size, the largest the tiles are sized for
MAX_STATE = 16


def pallas():
    """Pallas and its TPU dialect, imported on first use: about a second
    from source on the chip's host, 0.4 s from the bytecode kept beside
    the compile cache (PERF.md, PR 35)."""
    with startup_span("startup_kernel_import",
                      module="jax.experimental.pallas.tpu"), \
            cached_bytecode():
        from jax.experimental import pallas as pl
        from jax.experimental.pallas import tpu as pltpu
    return pl, pltpu


def takes(t: int, d_inner: int, n: int) -> bool:
    """Whether the kernel takes a chunk of ``t`` tokens over ``d_inner``
    channels and a state of ``n`` a channel (N under 8 pads its
    sublanes; over MAX_STATE the state tile and the ``B`` / ``C`` blocks
    outgrow the registers and the VMEM they were sized for)."""
    return (t > 1 and t % TOKEN_ALIGN == 0 and d_inner % LANES == 0
            and n <= MAX_STATE)


def _token_block(t: int) -> int:
    size = TOKEN_ALIGN
    while size * 2 <= MAX_TOKEN_BLOCK and t % (size * 2) == 0:
        size *= 2
    return size


def _lane_tile(d: int) -> int:
    tile = LANE_TILE
    while d % tile:
        tile -= LANES
    return tile


def _kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, dskip_ref, s0_ref,   # in
            y_ref, s_out_ref,                                        # out
            state_ref, dtx_ref):                                     # scratch
    pl, _ = pallas()
    tb, dj = pl.program_id(1), pl.program_id(2)
    tokens, width = dt_ref.shape[1:]
    n = a_ref.shape[0]
    tiles = width // LANES

    @pl.when(tb == 0)
    def _first():
        state_ref[dj] = s0_ref[0]

    x = x_ref[0].astype(F32)
    dtx_ref[...] = dt_ref[0] * x
    a = a_ref[...]

    def row(ref, t):
        # one token's row over the N sublanes: a replicated load (a
        # lane slice of it is not implemented in Mosaic: whole rows)
        return jnp.broadcast_to(ref[pl.ds(t, 1), :], (n, width))

    def wide(ref, t):
        # [N, 128], the same in every lane -> [N, width]: the same vregs
        return jnp.concatenate([ref[0, t].astype(F32)] * tiles, axis=1)

    def token(t, s):
        s = (jnp.exp(row(dt_ref.at[0], t) * a) * s
             + row(dtx_ref, t) * wide(b_ref, t))
        y_ref[0, pl.ds(t, 1), :] = jnp.sum(
            s * wide(c_ref, t), axis=0, keepdims=True)
        return s

    def group(g, s):
        # written out: the next token's loads and exp do not wait for
        # this token's reduction and store
        for u in range(UNROLL):
            s = token(g * UNROLL + u, s)
        return s

    state_ref[dj] = jax.lax.fori_loop(0, tokens // UNROLL, group,
                                      state_ref[dj])
    s_out_ref[0] = state_ref[dj]
    y_ref[0] = y_ref[0] + dskip_ref[...] * x


@partial(jax.jit, static_argnames=("interpret",))
def _call(x, dt, a, b_wide, c_wide, d_skip, state, *, interpret):
    pl, pltpu = pallas()
    bsz, t, d = x.shape
    n = a.shape[0]
    tokens, width = _token_block(t), _lane_tile(d)
    block = pl.BlockSpec
    grid_spec = pl.GridSpec(
        grid=(bsz, t // tokens, d // width),
        in_specs=[
            block((1, tokens, width), lambda b, i, j: (b, i, j)),    # x
            block((1, tokens, width), lambda b, i, j: (b, i, j)),    # dt
            block((n, width), lambda b, i, j: (0, j)),               # A
            block((1, tokens, n, LANES), lambda b, i, j: (b, i, 0, 0)),
            block((1, tokens, n, LANES), lambda b, i, j: (b, i, 0, 0)),
            block((1, width), lambda b, i, j: (0, j)),               # D
            block((1, n, width), lambda b, i, j: (b, 0, j)),         # state
        ],
        out_specs=[
            block((1, tokens, width), lambda b, i, j: (b, i, j)),    # y
            # written at every token block, the last one's stands
            block((1, n, width), lambda b, i, j: (b, 0, j)),
        ],
        scratch_shapes=[
            pltpu.VMEM((d // width, n, width), F32),    # every tile's state
            pltpu.VMEM((tokens, width), F32),           # dt * x
        ])
    return pl.pallas_call(
        _kernel,
        out_shape=[jax.ShapeDtypeStruct((bsz, t, d), F32),
                   jax.ShapeDtypeStruct((bsz, n, d), F32)],
        grid_spec=grid_spec,
        # token blocks run in order (the state crosses them) and the
        # scratch holds one row's state: rows in order too
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary")),
        interpret=interpret,
        name="selective_scan_chunk",
    )(x, dt, a, b_wide, c_wide, d_skip, state)


def selective_scan_chunk_kernel(
    x: jnp.ndarray,        # [B, T, d]
    dt: jnp.ndarray,       # [B, T, d] float32, after softplus; 0 = no-op
    a: jnp.ndarray,        # [N, d]
    b_in: jnp.ndarray,     # [B, T, N]
    c_out: jnp.ndarray,    # [B, T, N]
    d_skip: jnp.ndarray,   # [d]
    state: jnp.ndarray,    # [B, N, d] float32, the state before token 0
    *,
    interpret: Optional[bool] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """``selective_scan_chunk``'s contract: T steps from ``state``,
    returns (y [B, T, d] float32, the state after the last token)."""
    bsz, t, d = x.shape
    n = a.shape[0]
    if not takes(t, d, n):
        raise ValueError(
            f"a chunk of {t} tokens at d_inner {d}, N {n}: the kernel "
            f"takes T a multiple of {TOKEN_ALIGN}, d_inner of {LANES}, N "
            f"up to {MAX_STATE}; use selective_scan_chunk")
    if interpret is None:
        interpret = jax.default_backend() != "tpu"

    def wide(v):
        return jnp.broadcast_to(v[..., None], (bsz, t, n, LANES))
    return _call(x, dt.astype(F32), a.astype(F32), wide(b_in), wide(c_out),
                 d_skip.astype(F32).reshape(1, d), state.astype(F32),
                 interpret=bool(interpret))
