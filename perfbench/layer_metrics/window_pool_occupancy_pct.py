"""``serving/window_page_occupancy_peak``: the most of the window pool's
pages that slots held at once, over the pages the pool has (a ring of
ceil((window + chunk) / page) + 1 pages a slot), set-up included. What
keeps it under 100 is the release of pages from behind the window."""

LAYER = "KV pool"
UNIT = "%"
BETTER = "lower"
MOVES = "itl_p99_ms"
SOURCE = "program_counter"
DRIVERS = ('serve_closed_loop_hybrid',)


def read(ctx):
    peak = ctx.counters.get("window_page_occupancy_peak")
    return 100.0 * peak if peak else None
