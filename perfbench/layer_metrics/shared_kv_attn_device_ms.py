"""Median device-busy time, inside one execution of the jitted decode step,
of the leaf operations under the model's ``full_attention`` and
``cross_attention`` scopes: the one request-long pool's writer and its
readers (projections, the gather of every slot's window out of the pool,
differential attention over it, output projections)."""
from perfbench.lib import decode_scopes

LAYER = "model step"
UNIT = "ms"
BETTER = "lower"
MOVES = "itl_p99_ms"
SOURCE = "device_trace"
DRIVERS = ('serve_closed_loop_hybrid',)


def read(ctx):
    return decode_scopes.decode_scope_ms(
        ctx, ("full_attention", "cross_attention"))
