"""Median wall time of a step on the harness's clock, from the end of one
loss fetch to the end of the next."""
from perfbench.lib import stats

LAYER = "trainer"
UNIT = "ms"
BETTER = "lower"
MOVES = "train_tok_s_chip"
SOURCE = "host_clock"
DRIVERS = ('train_packed',)


def read(ctx):
    walls = ctx.samples.get("step_wall_ms")
    return stats.median(walls) if walls else None
