"""Median device-busy time, inside one execution of the jitted decode step,
of the leaf operations under the model's ``moe_experts`` scope: sorting the
(token, choice) pairs by expert, the three grouped matmuls over the held
experts' weights, and the weighted return. Every layer of the step counts.
XLA:TPU renames the grouped matmuls it makes of ``jax.lax.ragged_dot``
(``op_name="ragged-dot-none"``, with a ``ragged-dot-metadata`` call beside
them) and drops the scope, so those two names count as the scope's: nothing
else in the step makes them."""
from perfbench.lib import decode_scopes

LAYER = "model step"
UNIT = "ms"
BETTER = "lower"
MOVES = "itl_p99_ms"
SOURCE = "device_trace"
DRIVERS = ('serve_closed_loop_hf',)


def read(ctx):
    return decode_scopes.decode_scope_ms(
        ctx, ("moe_experts", "ragged-dot-none", "ragged-dot-metadata"))
