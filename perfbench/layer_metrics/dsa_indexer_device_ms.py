"""Median device-busy milliseconds, over the traced window's decode
steps, of the leaf operations under the model's ``dsa_indexer`` scope:
the index keys of every slot's window gathered, the indexer's scores and
the exact top-k selection."""
from perfbench.lib import decode_scopes

LAYER = "model step"
UNIT = "ms"
BETTER = "lower"
MOVES = "itl_p99_ms"
SOURCE = "device_trace"
DRIVERS = ('serve_closed_loop_shared_docs',)


def read(ctx):
    return decode_scopes.decode_scope_ms(ctx, ("dsa_indexer",))
