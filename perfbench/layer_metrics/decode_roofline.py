"""The decode step's share of its roofline: the least time one step could
take (``costs.decode_step_bytes``: bf16 matmul weights once plus the keys
and values the running slots hold, averaged over the window's steps,
over the chip's published HBM bandwidth) over ``decode_step_device_ms``.
Bandwidth bounds it: at 16 slots a step's operations (2 per weight per
slot) take a fifteenth of the time its bytes take on a v5e."""
from perfbench.lib import costs, xplane

LAYER = "kernels"
UNIT = "%"
BETTER = "higher"
MOVES = "serve_tok_s"
SOURCE = "device_trace"
DRIVERS = ('serve_closed_loop',)


def read(ctx):
    live = ctx.samples.get("live_context_tokens")
    if ctx.trace is None or ctx.peaks is None or not live:
        return None
    step = xplane.program_busy_median(
        ctx.trace, ctx.trace_window, ctx.programs["decode"])
    if step is None:
        return None
    least = (costs.decode_step_bytes(ctx.config, sum(live) / len(live))
             / ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least / step
