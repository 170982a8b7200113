"""Median per engine step of the host time in the scheduler: the step's
``serve_schedule`` spans (fault poll, deadline expiry, resilience pass,
every page-safety pass) plus its ``serve_admit`` span."""
from perfbench.lib import spans

LAYER = "scheduler"
UNIT = "ms"
BETTER = "lower"
MOVES = "itl_p99_ms"
SOURCE = "program_span"
DRIVERS = ('serve_closed_loop',)


def read(ctx):
    trace = spans.for_context(ctx)
    if trace is None or not spans.has(trace.host, "serve_schedule"):
        return None
    return spans.median_ms(spans.per_step(
        trace.host, ("serve_schedule", "serve_admit"), "serve"))
