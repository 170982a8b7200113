"""90th percentile of first-token time minus submit time on the harness's
clock. Recorded, not judged: about a hundred requests a window leave
ten beyond it."""
from perfbench.lib import stats

LAYER = "client side"
UNIT = "ms"
BETTER = "lower"
MOVES = "itl_p99_ms"
SOURCE = "host_clock"
DRIVERS = ('serve_closed_loop',)


def read(ctx):
    ttft = ctx.samples.get("ttft_ms")
    return stats.percentile(ttft, 90.0) if ttft else None
