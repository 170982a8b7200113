"""The decode step's share of its roofline, for Mamba-2 layers beside
attention with routed and shared experts in every layer: the least time
one step could take (``costs_ssm_moe.decode_step_bytes``: every weight but
the routed experts' once, the tied head included; one expert's weights for
every held expert that received a token, by the program's counter averaged
over the window's decode steps; the attention layers' rows of the live
tokens; the running slots' recurrent state read and written; over the
chip's published HBM bandwidth) over the decode program's busy median.
Bandwidth bounds it: at 32 slots a weight is used 32 times at most. Read
where the configuration has Mamba-2 heads and routed experts."""
from perfbench.lib import costs_ssm_moe, xplane

LAYER = "kernels"
UNIT = "%"
BETTER = "higher"
MOVES = "itl_p99_ms"
SOURCE = "device_trace"
DRIVERS = ('serve_closed_loop_ssm_moe',)


def read(ctx):
    live = ctx.samples.get("live_context_tokens")
    slots = ctx.samples.get("running_slots")
    steps = ctx.counters.get("decode_steps")
    hit = ctx.counters.get("moe_experts_hit")
    if (ctx.trace is None or ctx.peaks is None or not live or not slots
            or not steps or not hit
            or "mamba_n_heads" not in ctx.config
            or "num_local_experts" not in ctx.config):
        return None
    step = xplane.program_busy_median(
        ctx.trace, ctx.trace_window, ctx.programs["decode"])
    if not step:
        return None

    def mean(xs):
        return sum(xs) / len(xs)
    least = (costs_ssm_moe.decode_step_bytes(
        ctx.config, mean(live), mean(slots), hit / steps)
        / ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least / step
