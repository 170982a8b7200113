"""Root mean square, over the checked tokens of the sampled requests, of
the difference between the engine's chosen-token log-probability and the
plain float32 reference's, in nats: what ``correct`` holds to 0.10."""

LAYER = "model step"
UNIT = "nats"
BETTER = "lower"
MOVES = "itl_p99_ms"
SOURCE = "program_counter"
DRIVERS = ('serve_closed_loop',)


def read(ctx):
    return ctx.counters.get("ref_logprob_rms")
