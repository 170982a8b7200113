"""Median device-busy milliseconds, over the traced window's prefill
chunks, of the leaf operations under the model's ``dsa_indexer`` scope:
the slot's index keys gathered, 512 queries' scores against the whole
window and their exact top-k selections."""
from perfbench.lib import program_scopes

LAYER = "model step"
UNIT = "ms"
BETTER = "lower"
MOVES = "itl_p99_ms"
SOURCE = "device_trace"
DRIVERS = ('serve_closed_loop_shared_docs',)


def read(ctx):
    return program_scopes.scope_ms(ctx, "prefill_chunk", ("dsa_indexer",))
