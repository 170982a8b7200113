"""Median per engine step of the host's own work: the ``serve`` step span
less its ``serve_decode_fetch`` and ``serve_chunk_fetch`` spans, in which
the host waits for the device's result. It is the host's cost whether or
not the device waited for it; ``engine_host_gap_ms`` is the same layer as
the device sees it."""
from perfbench.lib import spans

LAYER = "engine host loop"
UNIT = "ms"
BETTER = "lower"
MOVES = "itl_p99_ms"
SOURCE = "program_span"
DRIVERS = ('serve_closed_loop',)

FETCHES = ("serve_decode_fetch", "serve_chunk_fetch")


def read(ctx):
    trace = spans.for_context(ctx)
    if trace is None or not spans.has(trace.host, "serve_decode_fetch"):
        return None
    whole = [e - s for _, s, e, _ in spans.steps(trace.host, "serve")]
    waits = spans.per_step(trace.host, FETCHES, "serve")
    return spans.median_ms([w - f for w, f in zip(whole, waits)])
