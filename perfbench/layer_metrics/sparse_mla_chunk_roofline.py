"""The whole prefill-chunk program's share of its roofline, for latent
attention with a learned sparse indexer and routed experts: the larger of
its matmul operations (``costs_dsa_mla.chunk_flops``: the chunks' mean
count of real tokens through every weight, the held experts they chose,
their indexer scores against the mean context and their attention over
the rows they select, the head for one row) over the chip's bf16 peak,
and its least bytes (``chunk_bytes``: every weight held once, the index
keys of the context and the rows read) over the HBM bandwidth; over the
chunk program's busy median."""
from perfbench.lib import costs_dsa_mla, program_scopes, xplane

LAYER = "kernels"
UNIT = "%"
BETTER = "higher"
MOVES = "itl_p99_ms"
SOURCE = "device_trace"
DRIVERS = ('serve_closed_loop_shared_docs',)


def read(ctx):
    if ctx.trace is None or ctx.peaks is None \
            or "index_topk" not in ctx.config:
        return None
    tokens = program_scopes.chunk_span_mean(ctx, "nvalid")
    start = program_scopes.chunk_span_mean(ctx, "context")
    step = xplane.program_busy_median(
        ctx.trace, ctx.trace_window, ctx.programs["prefill_chunk"])
    if not tokens or start is None or not step:
        return None
    context = start + tokens / 2.0      # a query's mean columns
    least = max(
        costs_dsa_mla.chunk_flops(ctx.config, tokens, context)
        / ctx.peaks["bf16_flops_per_s"],
        costs_dsa_mla.chunk_bytes(ctx.config, tokens, start + tokens)
        / ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least / step
