"""Median device-busy time, inside one execution of the jitted train step, of
the leaf operations of the forward pass (``jvp(...)`` in the operation's
``op_name``, outside any ``transpose``). First device; ``spans.classify``
holds the rule."""
from perfbench.lib import spans

LAYER = "model step"
UNIT = "ms"
BETTER = "lower"
MOVES = "train_tok_s_chip"
SOURCE = "device_trace"
DRIVERS = ('train_packed',)


def read(ctx):
    return spans.scope_median_ms(ctx, "forward")
