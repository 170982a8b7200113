"""KV columns the decode steps read over the tokens their running slots
held (``read_tokens`` / ``live_tokens`` of the ``serve_decode`` spans,
summed across the traced window): 1 is a read bounded by the fill; an
empty slot's window counts as read and holds nothing."""
from perfbench.lib import spans

LAYER = "KV pool"
UNIT = "x"
BETTER = "lower"
MOVES = "itl_p99_ms"
SOURCE = "program_counter"
DRIVERS = ('serve_closed_loop',)


def read(ctx):
    trace = spans.for_context(ctx)
    sums = spans.kv_reads(trace.host) if trace is not None else None
    if not sums or not sums["live_tokens"]:
        return None
    return sums["read_tokens"] / sums["live_tokens"]
