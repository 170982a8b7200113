"""Median time from a request's admission to its first token (its
``serve_req_admit`` and ``serve_req_first_token`` marks): the chunked
prefill's service time, over the requests that have both marks inside the
traced window. With ``request_queue_wait_ms`` it splits TTFT."""
from perfbench.lib import spans

LAYER = "scheduler"
UNIT = "ms"
BETTER = "lower"
MOVES = "itl_p99_ms"
SOURCE = "program_span"
DRIVERS = ('serve_closed_loop',)


def read(ctx):
    return spans.request_median_ms(ctx, "admit", "first_token")
