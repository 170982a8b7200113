"""The decode step's share of its roofline, for latent attention with a
learned sparse indexer and routed experts: the least time one step could
take (``costs_dsa_mla.decode_step_bytes``: the weights every step reads,
one expert's weights for every held expert that received a token by the
program's counter averaged over the window's decode steps, every index
key the running slots' queries scored and the latent rows they read, by
the ``serve_decode`` spans' ``live_tokens`` + ``slots`` and
``selected_tokens`` averaged over the traced window) over the chip's
published HBM bandwidth, over the decode program's busy median."""
from perfbench.lib import costs_dsa_mla, spans, xplane

LAYER = "kernels"
UNIT = "%"
BETTER = "higher"
MOVES = "itl_p99_ms"
SOURCE = "device_trace"
DRIVERS = ('serve_closed_loop_shared_docs',)


def read(ctx):
    steps = ctx.counters.get("decode_steps")
    hit = ctx.counters.get("moe_experts_hit")
    trace = spans.for_context(ctx)
    if (trace is None or ctx.peaks is None or not steps or not hit
            or "index_topk" not in ctx.config):
        return None
    rows = [(int(a["live_tokens"]) + int(a["slots"]),
             int(a["selected_tokens"])) for n, _, _, a in trace.host
            if n == "serve_decode" and "selected_tokens" in a]
    if not rows or not any(s for _, s in rows):
        return None
    step = xplane.program_busy_median(
        ctx.trace, ctx.trace_window, ctx.programs["decode"])
    if step is None:
        return None
    scored = sum(r[0] for r in rows) / len(rows)
    read_rows = sum(r[1] for r in rows) / len(rows)
    least = (costs_dsa_mla.decode_step_bytes(
        ctx.config, scored, read_rows, hit / steps)
        / ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least / step
