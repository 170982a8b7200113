"""Median time a request waited in the queue: from its ``serve_req_submit``
mark to its ``serve_req_admit`` mark, over the requests that have both
inside the traced window. A request admitted early in the window whose
submit fell before it is not counted, so the first second of the window
under-counts long waits."""
from perfbench.lib import spans

LAYER = "scheduler"
UNIT = "ms"
BETTER = "lower"
MOVES = "itl_p99_ms"
SOURCE = "program_span"
DRIVERS = ('serve_closed_loop',)


def read(ctx):
    return spans.request_median_ms(ctx, "submit", "admit")
