"""Median device-busy time, inside one execution of the jitted train step, of
the leaf operations of the trainer's ``step_metrics`` named scope: the
gradient norm and the in-graph collector's parameter and update norms, each
a pass over a whole f32 tree, that leave the step with its metrics. First
device; ``spans.classify`` holds the rule."""
from perfbench.lib import spans

LAYER = "model step"
UNIT = "ms"
BETTER = "lower"
MOVES = "train_tok_s_chip"
SOURCE = "device_trace"
DRIVERS = ('train_packed',)


def read(ctx):
    return spans.scope_median_ms(ctx, "metrics")
