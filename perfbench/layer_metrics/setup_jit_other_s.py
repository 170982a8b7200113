"""Lowering and backend-compile seconds before the traced window that no
main-thread start-up span covers, so nothing is counted twice: the
harness's weights ``init``, the sampler, eager one-op programs between
the constructors (JAX's monitoring durations of every jitted function, a
union of intervals on the main thread)."""
from perfbench.lib import startup

LAYER = "start-up"
UNIT = "s"
BETTER = "lower"
MOVES = "setup_s"
SOURCE = "program_counter"
DRIVERS = ('train_packed', 'serve_closed_loop', 'serve_closed_loop_hf',
           'serve_closed_loop_hybrid', 'serve_closed_loop_ssm_attn')


def read(ctx):
    return startup.metric(ctx, "setup_jit_other_s")
