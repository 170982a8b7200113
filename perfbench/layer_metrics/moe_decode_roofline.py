"""The decode step's share of its roofline, for latent attention and routed
experts: the least time one step could take (``costs_mla_moe.decode_step_
bytes``: attention, shared-expert, router and head weights once, one
expert's weights for every held expert that received a token, by the
program's counter averaged over the window's decode steps, and the latent
rows the running slots hold, over the chip's published HBM bandwidth) over
the decode program's busy median. Bandwidth bounds it: at 32 slots a weight
is used 32 times at most."""
from perfbench.lib import costs_mla_moe, xplane

LAYER = "kernels"
UNIT = "%"
BETTER = "higher"
MOVES = "itl_p99_ms"
SOURCE = "device_trace"
DRIVERS = ('serve_closed_loop_hf',)


def read(ctx):
    live = ctx.samples.get("live_context_tokens")
    steps = ctx.counters.get("decode_steps")
    hit = ctx.counters.get("moe_experts_hit")
    if (ctx.trace is None or ctx.peaks is None or not live or not steps
            or not hit or "kv_lora_rank" not in ctx.config):
        return None
    step = xplane.program_busy_median(
        ctx.trace, ctx.trace_window, ctx.programs["decode"])
    if step is None:
        return None
    least = (costs_mla_moe.decode_step_bytes(
        ctx.config, sum(live) / len(live), hit / steps)
        / ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least / step
