"""Median device-busy time inside one execution of the jitted train step
(the trace's ``XLA Modules`` event of ``jit__train_step``), first device."""
from perfbench.lib import xplane

LAYER = "model step"
UNIT = "ms"
BETTER = "lower"
MOVES = "train_tok_s_chip"
SOURCE = "device_trace"
DRIVERS = ('train_packed',)


def read(ctx):
    if ctx.trace is None:
        return None
    secs = xplane.program_busy_median(
        ctx.trace, ctx.trace_window, ctx.programs["train_step"])
    return None if secs is None else 1e3 * secs
