"""Median device-busy time, inside one execution of the jitted train step, of
the leaf operations of the trainer's ``optimizer`` named scope: the optax
update, the parameter apply and the guard's select. First device;
``spans.classify`` holds the rule."""
from perfbench.lib import spans

LAYER = "model step"
UNIT = "ms"
BETTER = "lower"
MOVES = "train_tok_s_chip"
SOURCE = "device_trace"
DRIVERS = ('train_packed',)


def read(ctx):
    return spans.scope_median_ms(ctx, "optimizer")
