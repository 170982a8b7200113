"""Median device-busy time inside one execution of the jitted decode step."""
from perfbench.lib import xplane

LAYER = "model step"
UNIT = "ms"
BETTER = "lower"
MOVES = "serve_tok_s"
SOURCE = "device_trace"
DRIVERS = ('serve_closed_loop',)


def read(ctx):
    if ctx.trace is None:
        return None
    secs = xplane.program_busy_median(
        ctx.trace, ctx.trace_window, ctx.programs["decode"])
    return None if secs is None else 1e3 * secs
