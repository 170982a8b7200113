"""1 - the union of the device-op intervals over the traced window, mean
over devices."""
from perfbench.lib import xplane

LAYER = "device"
UNIT = "%"
BETTER = "lower"
MOVES = "train_tok_s_chip"
SOURCE = "device_trace"
DRIVERS = ('train_packed',)


def read(ctx):
    if ctx.trace is None:
        return None
    return 100.0 * xplane.idle_share(ctx.trace, ctx.trace_window)
