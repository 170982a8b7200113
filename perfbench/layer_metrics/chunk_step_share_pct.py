"""``serving/prefill/chunks`` over engine steps in the window: the share of
steps that carried a prefill chunk, which says in which mode of the
step-time distribution the 99th percentile of the token gaps sits."""

LAYER = "scheduler"
UNIT = "%"
BETTER = "lower"
MOVES = "itl_p99_ms"
SOURCE = "program_counter"
DRIVERS = ('serve_closed_loop',)


def read(ctx):
    steps = ctx.counters.get("engine_steps")
    return 100.0 * ctx.counters["prefill_chunks"] / steps if steps else None
