"""Median ``serve_decode_args`` span: building the decode step's per-slot
arguments on the host and putting each on the device, once per decode
step."""
from perfbench.lib import spans

LAYER = "engine host loop"
UNIT = "ms"
BETTER = "lower"
MOVES = "itl_p99_ms"
SOURCE = "program_span"
DRIVERS = ('serve_closed_loop',)


def read(ctx):
    return spans.span_median_ms(ctx, "serve_decode_args")
