"""Median time between the end of a decode step on the device and the start
of the next step program (prefill chunk or decode): the engine's host loop
(scheduling, sampling bookkeeping, argument transfer) as the device sees
it."""
from perfbench.lib import stats, xplane

LAYER = "engine host loop"
UNIT = "ms"
BETTER = "lower"
MOVES = "itl_p99_ms"
SOURCE = "device_trace"
DRIVERS = ('serve_closed_loop',)


def read(ctx):
    if ctx.trace is None:
        return None
    gaps = xplane.gaps_after(
        ctx.trace.devices[0], ctx.programs["decode"], ctx.trace_window,
        then="|".join(ctx.programs.values()))
    return 1e3 * stats.median(gaps) if gaps else None
