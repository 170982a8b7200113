"""Median seconds a step waited for its batch: the trainer's own ``StepClock``
``data_wait`` segment around ``next(batches)``, read per step."""
from perfbench.lib import stats

LAYER = "data"
UNIT = "ms"
BETTER = "lower"
MOVES = "train_tok_s_chip"
SOURCE = "program_span"
DRIVERS = ('train_packed',)


def read(ctx):
    waits = ctx.samples.get("data_wait_ms")
    return stats.median(waits) if waits else None
