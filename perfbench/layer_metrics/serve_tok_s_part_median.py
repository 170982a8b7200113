"""Median of the token rates of the window's consecutive whole 5-second
parts: a steadier statistic beside ``serve_tok_s``, which stays the plain
total over the whole window."""
from perfbench.lib import stats

LAYER = "client side"
UNIT = "tokens/s"
BETTER = "higher"
MOVES = "serve_tok_s"
SOURCE = "host_clock"
DRIVERS = ('serve_closed_loop',)


def read(ctx):
    parts = ctx.samples.get("part_tok_s")
    return stats.median(parts) if parts else None
