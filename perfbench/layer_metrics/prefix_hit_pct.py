"""Prompt tokens the prefix cache's pages covered, over the prompt tokens
admitted, in the window (``serving/prefix_cache/hit_tokens`` over
``serving/prompt_tokens``): near 99% while the documents stay cached."""

LAYER = "KV pool"
UNIT = "%"
BETTER = "higher"
MOVES = "itl_p99_ms"
SOURCE = "program_counter"
DRIVERS = ('serve_closed_loop_shared_docs',)


def read(ctx):
    prompt = ctx.counters.get("prompt_tokens")
    hit = ctx.counters.get("prefix_hit_tokens")
    if not prompt or hit is None:
        return None
    return 100.0 * hit / prompt
