"""Median over the requests whose first token fell inside the window of
first-token time minus submit time, on the harness's clock. ISSUE 25 wanted
it end to end; it is a per-layer number because it cannot be bounded: two
runs of one seed agree to 0.4%, but the order of the same 16 prompts decides
which of them meet at the one-chunk-a-step prefill lane, and six seeds read
780 to 934 ms (my chip runs, PR 25)."""
from perfbench.lib import stats

LAYER = "client side"
UNIT = "ms"
BETTER = "lower"
MOVES = "itl_p99_ms"
SOURCE = "host_clock"
DRIVERS = ('serve_closed_loop',)


def read(ctx):
    ttft = ctx.samples.get("ttft_ms")
    return stats.median(ttft) if ttft else None
