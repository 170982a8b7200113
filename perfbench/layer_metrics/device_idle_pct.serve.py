"""1 - the union of the device-op intervals over the traced window, mean
over devices."""
from perfbench.lib import xplane

LAYER = "device"
UNIT = "%"
BETTER = "lower"
MOVES = "itl_p99_ms"
SOURCE = "device_trace"
DRIVERS = ('serve_closed_loop',)


def read(ctx):
    if ctx.trace is None:
        return None
    return 100.0 * xplane.idle_share(ctx.trace, ctx.trace_window)
