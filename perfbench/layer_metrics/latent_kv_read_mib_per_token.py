"""MiB of latent cache the decode steps read for each token they handed
out: the ``serve_decode`` spans' ``read_tokens`` (columns the step's gather
reads) times the bytes one cached token takes over every layer, over their
``slots``, summed across the traced window. The bytes are the pool's own
(the program's gauge ``serving/kv_bytes_per_token``: the stored row, lane
padding included), or where the program has no such gauge the algorithm's
(``costs_mla_moe.kv_bytes_per_token``: kv_lora_rank + qk_rope_head_dim
numbers a layer). ``kv_read_mib_per_token`` is the same quantity for a
dense block's keys and values."""
from perfbench.lib import costs_mla_moe, spans

LAYER = "KV pool"
UNIT = "MiB"
BETTER = "lower"
MOVES = "itl_p99_ms"
SOURCE = "program_counter"
DRIVERS = ('serve_closed_loop_hf',)


def read(ctx):
    if "kv_lora_rank" not in ctx.config:
        return None
    trace = spans.for_context(ctx)
    sums = spans.kv_reads(trace.host) if trace is not None else None
    if not sums or not sums["slots"]:
        return None
    width = {"bfloat16": 2, "float16": 2, "float32": 4}[
        ctx.config["serving"]["dtype"]]
    row = (ctx.counters.get("kv_bytes_per_token")
           or costs_mla_moe.kv_bytes_per_token(ctx.config, width))
    return sums["read_tokens"] * row / sums["slots"] / 2 ** 20
