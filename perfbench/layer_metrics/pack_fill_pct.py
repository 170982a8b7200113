"""Document tokens over row slots in the steps of the window, counted by the
harness from ``segment_ids > 0``."""

LAYER = "data"
UNIT = "%"
BETTER = "higher"
MOVES = "train_tok_s_chip"
SOURCE = "program_counter"
DRIVERS = ('train_packed',)


def read(ctx):
    slots = ctx.counters.get("row_slots")
    return 100.0 * ctx.counters["tokens"] / slots if slots else None
