"""Model FLOP/s utilisation: the operations forward and backward need per
document token (``costs.train_flops_per_token``: matmuls from the shapes
plus causal attention inside documents and the window; no recomputation,
no embedding lookup, no padding) times tokens/s/chip over the chip's
published bf16 peak. An end-to-end utilisation, not a kernel's roofline."""
from perfbench.lib import costs

LAYER = "trainer"
UNIT = "%"
BETTER = "higher"
MOVES = "train_tok_s_chip"
SOURCE = "host_clock"
DRIVERS = ('train_packed',)


def read(ctx):
    if ctx.peaks is None or "doc_lengths" not in ctx.samples:
        return None
    per_token = costs.train_flops_per_token(
        ctx.config, ctx.samples["doc_lengths"])
    return (100.0 * per_token * ctx.end_to_end["train_tok_s_chip"]
            / ctx.peaks["bf16_flops_per_s"])
