"""``serving/page_occupancy_peak``: the fullest the page pool got, set-up
included."""

LAYER = "KV pool"
UNIT = "%"
BETTER = "lower"
MOVES = "itl_p99_ms"
SOURCE = "program_counter"
DRIVERS = ('serve_closed_loop',)


def read(ctx):
    peak = ctx.counters.get("page_occupancy_peak")
    return None if peak is None else 100.0 * peak
