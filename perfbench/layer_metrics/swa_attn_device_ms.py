"""Median device-busy time, inside one execution of the jitted decode step,
of the leaf operations under the model's ``swa_attention`` scope: the
window layers' projections, the gather of the window's pages out of the
window pool, differential attention over them and the write of the fresh
row."""
from perfbench.lib import decode_scopes

LAYER = "model step"
UNIT = "ms"
BETTER = "lower"
MOVES = "itl_p99_ms"
SOURCE = "device_trace"
DRIVERS = ('serve_closed_loop_hybrid',)


def read(ctx):
    return decode_scopes.decode_scope_ms(ctx, ("swa_attention",))
