"""Median device-busy time, inside one execution of the jitted decode step,
of the leaf operations under the model's ``mla_attention`` scope: the query
and latent projections, the absorbed attention over the gathered latent rows
and the output projection. Every layer of the step counts; the engine's
gather of the window out of the pool, and its scatter, lie outside."""
from perfbench.lib import decode_scopes

LAYER = "model step"
UNIT = "ms"
BETTER = "lower"
MOVES = "itl_p99_ms"
SOURCE = "device_trace"
DRIVERS = ('serve_closed_loop_hf',)


def read(ctx):
    return decode_scopes.decode_scope_ms(ctx, ("mla_attention",))
