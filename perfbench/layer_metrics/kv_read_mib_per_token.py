"""MiB of KV cache the decode steps read for each token they handed out:
the ``serve_decode`` spans' ``read_tokens`` (columns the step's gathers
read) times the bytes of one cached token, over their ``slots`` (one token
a running slot), summed across the traced window."""
from perfbench.lib import spans

LAYER = "KV pool"
UNIT = "MiB"
BETTER = "lower"
MOVES = "itl_p99_ms"
SOURCE = "program_counter"
DRIVERS = ('serve_closed_loop',)


def read(ctx):
    trace = spans.for_context(ctx)
    sums = spans.kv_reads(trace.host) if trace is not None else None
    if not sums or not sums["slots"]:
        return None
    return (sums["read_tokens"] * spans.kv_bytes_per_token(ctx.config)
            / sums["slots"] / 2 ** 20)
