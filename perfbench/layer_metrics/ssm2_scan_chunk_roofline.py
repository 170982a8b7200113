"""The chunk program's Mamba-2 scans' share of their roofline: the least
time the chunked (SSD) scans of one chunk could take
(``costs_ssm_moe.ssd_chunk_least_seconds`` at the chunks' mean count of
real tokens: the SSD form's matmul operations over the chip's bf16 peak, or
x, B, C and dt in and y out at the activation dtype + each layer's state
read and written once over its HBM bandwidth, whichever is larger) over the
median device-busy time under the ``ssm_scan`` scope of one chunk
program (the scope Mamba-1 scans share: a spec holds one kind or the
other). Read where the configuration has Mamba-2 heads."""
from perfbench.lib import costs_ssm_moe, program_scopes

LAYER = "kernels"
UNIT = "%"
BETTER = "higher"
MOVES = "itl_p99_ms"
SOURCE = "device_trace"
DRIVERS = ('serve_closed_loop_ssm_moe',)


def read(ctx):
    if ctx.peaks is None or "mamba_n_heads" not in ctx.config:
        return None
    busy_ms = program_scopes.scope_ms(ctx, "prefill_chunk", ("ssm_scan",))
    tokens = program_scopes.chunk_span_mean(ctx, "nvalid")
    if not busy_ms or not tokens:
        return None
    least = max(costs_ssm_moe.ssd_chunk_least_seconds(
        ctx.config, tokens, ctx.peaks).values())
    return 100.0 * least * 1e3 / busy_ms
