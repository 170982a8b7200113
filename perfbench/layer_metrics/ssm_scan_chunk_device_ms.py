"""Median device-busy time, inside one execution of the jitted prefill-chunk
step, of the leaf operations under the model's ``ssm_scan`` scope: the
selective scans of every state-space layer over the chunk's tokens (the
recurrence alone; the mixers' projections and convolutions lie outside)."""
from perfbench.lib import program_scopes

LAYER = "model step"
UNIT = "ms"
BETTER = "lower"
MOVES = "itl_p99_ms"
SOURCE = "device_trace"
DRIVERS = ('serve_closed_loop_ssm_attn',)


def read(ctx):
    return program_scopes.scope_ms(ctx, "prefill_chunk", ("ssm_scan",))
