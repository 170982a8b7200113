"""The whole prefill-chunk program's share of its roofline, for a decoder
of state-space layers beside attention layers with rows of their own: the
larger of its matmul operations (``costs_ssm_attn.chunk_flops``: the
chunks' mean count of real tokens through every matrix, their attention
against the mean context cached before them, the head for one row) over
the chip's bf16 peak, and its least bytes (``chunk_bytes``: every weight
once, the slot's live rows, its state read and written) over the HBM
bandwidth; over the chunk program's busy median."""
from perfbench.lib import costs_ssm_attn, program_scopes, xplane

LAYER = "kernels"
UNIT = "%"
BETTER = "higher"
MOVES = "itl_p99_ms"
SOURCE = "device_trace"
DRIVERS = ('serve_closed_loop_ssm_attn',)


def read(ctx):
    if (ctx.trace is None or ctx.peaks is None
            or ctx.config.get("model_type") != "jamba"):
        return None
    tokens = program_scopes.chunk_span_mean(ctx, "nvalid")
    context = program_scopes.chunk_span_mean(ctx, "context")
    step = xplane.program_busy_median(
        ctx.trace, ctx.trace_window, ctx.programs["prefill_chunk"])
    if not tokens or context is None or not step:
        return None
    least = max(
        costs_ssm_attn.chunk_flops(ctx.config, tokens, context)
        / ctx.peaks["bf16_flops_per_s"],
        costs_ssm_attn.chunk_bytes(ctx.config, context)
        / ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least / step
