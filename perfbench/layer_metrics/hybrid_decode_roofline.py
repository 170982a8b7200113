"""The decode step's share of its roofline, for a decoder of state-space
layers, window and full attention and cross-attention onto one cache: the
least time one step could take (``costs_hybrid.decode_step_bytes``: every
weight once, the full layer's rows of the live tokens once for each of
its readers, the window layers' rows inside the window, the running
slots' recurrent state read and written; averaged over the window's
steps, over the chip's published HBM bandwidth) over the decode program's
busy median. Bandwidth bounds it: at 64 slots a weight is used 64 times."""
from perfbench.lib import costs_hybrid, xplane

LAYER = "kernels"
UNIT = "%"
BETTER = "higher"
MOVES = "itl_p99_ms"
SOURCE = "device_trace"
DRIVERS = ('serve_closed_loop_hybrid',)


def read(ctx):
    live = ctx.samples.get("live_context_tokens")
    inside = ctx.samples.get("window_tokens")
    slots = ctx.samples.get("running_slots")
    if (ctx.trace is None or ctx.peaks is None or not live or not inside
            or not slots or ctx.config.get("model_type") != "phi4flash"):
        return None
    step = xplane.program_busy_median(
        ctx.trace, ctx.trace_window, ctx.programs["decode"])
    if step is None:
        return None

    def mean(xs):
        return sum(xs) / len(xs)
    least = (costs_hybrid.decode_step_bytes(
        ctx.config, mean(live), mean(inside), mean(slots))
        / ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least / step
