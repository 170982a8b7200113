"""The chunk program's selective scans' share of their roofline: the least
time the scans of one chunk could take
(``costs_ssm_attn.scan_chunk_least_seconds`` at the chunks' mean count of
real tokens: x, dt, B, C in and y out at the activation dtype + each
layer's state read and written once, over the chip's HBM bandwidth; or the
recurrence's elementwise float32 operations over the vector unit's peak,
which is the larger and so the bound that holds at d_inner 5,120 x N 16;
that peak is derived, not published: ``costs_ssm_attn.VPU_F32_OPS_PER_S``)
over ``ssm_scan_chunk_device_ms``."""
from perfbench.lib import costs_ssm_attn, program_scopes

LAYER = "kernels"
UNIT = "%"
BETTER = "higher"
MOVES = "itl_p99_ms"
SOURCE = "device_trace"
DRIVERS = ('serve_closed_loop_ssm_attn',)


def read(ctx):
    if ctx.peaks is None or ctx.config.get("model_type") != "jamba":
        return None
    busy_ms = program_scopes.scope_ms(ctx, "prefill_chunk", ("ssm_scan",))
    tokens = program_scopes.chunk_span_mean(ctx, "nvalid")
    if not busy_ms or not tokens:
        return None
    least = max(costs_ssm_attn.scan_chunk_least_seconds(
        ctx.config, tokens, ctx.peaks).values())
    return 100.0 * least * 1e3 / busy_ms
