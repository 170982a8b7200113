"""The decode step's share of its roofline, for a decoder of state-space
layers beside attention layers with rows of their own: the least time one
step could take (``costs_ssm_attn.decode_step_bytes``: every weight once,
each attention layer's rows of the live tokens, the running slots'
recurrent state read and written; averaged over the window's steps, over
the chip's published HBM bandwidth) over the decode program's busy median.
Bandwidth bounds it: at 16 slots a weight is used 16 times."""
from perfbench.lib import costs_ssm_attn, xplane

LAYER = "kernels"
UNIT = "%"
BETTER = "higher"
MOVES = "itl_p99_ms"
SOURCE = "device_trace"
DRIVERS = ('serve_closed_loop_ssm_attn',)


def read(ctx):
    live = ctx.samples.get("live_context_tokens")
    slots = ctx.samples.get("running_slots")
    if (ctx.trace is None or ctx.peaks is None or not live or not slots
            or ctx.config.get("model_type") != "jamba"):
        return None
    step = xplane.program_busy_median(
        ctx.trace, ctx.trace_window, ctx.programs["decode"])
    if not step:
        return None

    def mean(xs):
        return sum(xs) / len(xs)
    least = (costs_ssm_attn.decode_step_bytes(
        ctx.config, mean(live), mean(slots)) / ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least / step
