"""Share of the traced window in which a collective ran on a device and no
compute did (mean over devices): what the mesh costs that overlap does
not hide."""
from perfbench.lib import xplane

LAYER = "parallel"
UNIT = "%"
BETTER = "lower"
MOVES = "train_tok_s_chip"
SOURCE = "device_trace"
DRIVERS = ('train_packed',)


def read(ctx):
    if ctx.trace is None or len(ctx.trace.devices) < 2:
        return None
    t0, t1 = ctx.trace_window
    return (100.0 * xplane.collective_exposed_seconds(ctx.trace, ctx.trace_window)
            / (t1 - t0))
