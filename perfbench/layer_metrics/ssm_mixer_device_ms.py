"""Median device-busy time, inside one execution of the jitted decode step,
of the leaf operations under the model's ``ssm_mixer`` and ``gmu`` scopes:
the state-space layers' projections, convolution and one recurrence step
over every slot's state, and the gated memory units that gate its output.
Every such layer of the step counts; the blocks' MLPs lie outside."""
from perfbench.lib import decode_scopes

LAYER = "model step"
UNIT = "ms"
BETTER = "lower"
MOVES = "itl_p99_ms"
SOURCE = "device_trace"
DRIVERS = ('serve_closed_loop_hybrid',)


def read(ctx):
    return decode_scopes.decode_scope_ms(ctx, ("ssm_mixer", "gmu"))
