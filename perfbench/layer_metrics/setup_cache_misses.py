"""Persistent compile-cache misses before the traced window, every jitted
function of the process (JAX's ``/jax/compilation_cache/cache_misses``
events, kept with their times by the program's compile accounting). 0 in
a cached run; the first thing to look at when one side of a ``setup_s``
pair reads seconds high."""
from perfbench.lib import startup

LAYER = "start-up"
UNIT = "misses"
BETTER = "lower"
MOVES = "setup_s"
SOURCE = "program_counter"
DRIVERS = ('train_packed', 'serve_closed_loop', 'serve_closed_loop_hf',
           'serve_closed_loop_hybrid', 'serve_closed_loop_ssm_attn')


def read(ctx):
    return startup.metric(ctx, "setup_cache_misses")
