"""Time of the Pallas flash-attention kernels over device-busy time. In the
trace a Mosaic kernel is a custom call whose target is ``tpu_custom_call``;
the train step holds no other Pallas kernel than flash attention's forward
and its two backward passes."""
from perfbench.lib import xplane

#: what marks a Mosaic kernel's device event (see xplane.short_name)
KERNELS = r"tpu_custom_call"

LAYER = "kernels"
UNIT = "%"
BETTER = "lower"
MOVES = "train_tok_s_chip"
SOURCE = "device_trace"
DRIVERS = ('train_packed',)


def read(ctx):
    if ctx.trace is None:
        return None
    secs = xplane.name_seconds(ctx.trace, ctx.trace_window, KERNELS)
    if secs <= 0.0:
        return None        # no such kernel in the trace: nothing to read
    return 100.0 * secs / xplane.busy_seconds(ctx.trace, ctx.trace_window)
