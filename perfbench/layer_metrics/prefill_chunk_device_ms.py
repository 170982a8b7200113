"""Median device-busy time inside one execution of the jitted prefill-chunk
step."""
from perfbench.lib import xplane

LAYER = "model step"
UNIT = "ms"
BETTER = "lower"
MOVES = "itl_p99_ms"
SOURCE = "device_trace"
DRIVERS = ('serve_closed_loop',)


def read(ctx):
    if ctx.trace is None:
        return None
    secs = xplane.program_busy_median(
        ctx.trace, ctx.trace_window, ctx.programs["prefill_chunk"])
    return None if secs is None else 1e3 * secs
