"""Median ``train_h2d`` span: reshaping the next batch and putting it on
the device (``place_batch``), between two steps."""
from perfbench.lib import spans

LAYER = "trainer"
UNIT = "ms"
BETTER = "lower"
MOVES = "train_tok_s_chip"
SOURCE = "program_span"
DRIVERS = ('train_packed',)


def read(ctx):
    return spans.span_median_ms(ctx, "train_h2d")
