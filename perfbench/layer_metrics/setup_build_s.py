"""The constructors: self seconds, before the traced window, of the main
thread's ``startup_model_build``, ``startup_engine_build`` /
``startup_trainer_build`` and their children (``startup_pool_alloc``,
``startup_state_init``; ``startup_weights`` where a run takes the
product's weights path)."""
from perfbench.lib import startup

LAYER = "start-up"
UNIT = "s"
BETTER = "lower"
MOVES = "setup_s"
SOURCE = "program_span"
DRIVERS = ('train_packed', 'serve_closed_loop', 'serve_closed_loop_hf',
           'serve_closed_loop_hybrid', 'serve_closed_loop_ssm_attn')


def read(ctx):
    return startup.metric(ctx, "setup_build_s")
