"""Requests that got a token in an engine step over the engine's slots,
averaged over the window's steps."""

LAYER = "scheduler"
UNIT = "%"
BETTER = "higher"
MOVES = "itl_p99_ms"
SOURCE = "program_counter"
DRIVERS = ('serve_closed_loop',)


def read(ctx):
    running = ctx.samples.get("running_slots")
    if not running:
        return None
    return 100.0 * sum(running) / len(running) / ctx.counters["num_slots"]
