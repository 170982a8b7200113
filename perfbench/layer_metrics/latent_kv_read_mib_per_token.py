"""MiB of latent cache the decode steps read for each token they handed
out: the ``serve_decode`` spans' ``read_tokens`` (columns the step's gather
reads) times the bytes of one cached latent row over every layer
(``costs_mla_moe.kv_bytes_per_token``: kv_lora_rank + qk_rope_head_dim
numbers a layer), over their ``slots``, summed across the traced window.
``kv_read_mib_per_token`` is the same quantity for a dense block's keys and
values."""
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
    return (sums["read_tokens"]
            * costs_mla_moe.kv_bytes_per_token(ctx.config, width)
            / sums["slots"] / 2 ** 20)
