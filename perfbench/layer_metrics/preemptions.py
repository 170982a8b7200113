"""``serving/preemptions`` inside the window, as a count."""

LAYER = "KV pool"
UNIT = "count"
BETTER = "lower"
MOVES = "itl_p99_ms"
SOURCE = "program_counter"
DRIVERS = ('serve_closed_loop',)


def read(ctx):
    return ctx.counters.get("preemptions")
