"""Program loss minus the plain float32 reference's loss on the same packed
rows at the same weights, absolute, in nats: what ``correct`` compares."""

LAYER = "model step"
UNIT = "nats"
BETTER = "lower"
MOVES = "train_tok_s_chip"
SOURCE = "program_counter"
DRIVERS = ('train_packed',)


def read(ctx):
    return ctx.counters.get("ref_loss_diff")
