"""MiB of recurrent state one slot holds over every state-space layer,
whatever its length: the program's gauge ``serving/state_bytes_per_slot``
(float32 state and the convolution's tail as the cache manager stores
them)."""

LAYER = "KV pool"
UNIT = "MiB"
BETTER = "lower"
MOVES = "itl_p99_ms"
SOURCE = "program_counter"
DRIVERS = ('serve_closed_loop_hybrid',)


def read(ctx):
    state = ctx.counters.get("state_bytes_per_slot")
    return state / 2 ** 20 if state else None
