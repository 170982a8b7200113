"""Median ``train_metrics_fetch`` span: the trainer converting the step's
metrics tree to host floats after the loss, while the device has nothing
to run."""
from perfbench.lib import spans

LAYER = "trainer"
UNIT = "ms"
BETTER = "lower"
MOVES = "train_tok_s_chip"
SOURCE = "program_span"
DRIVERS = ('train_packed',)


def read(ctx):
    return spans.span_median_ms(ctx, "train_metrics_fetch")
