"""Backend compile, or its retrieval from the persistent cache, of the step
programs during set-up: the main thread's ``xla_compile`` start-up spans
that began before the traced window (``Lowered.compile()``: tenths of a
second a program on a hit, the whole XLA:TPU compile on a miss)."""
from perfbench.lib import startup

LAYER = "start-up"
UNIT = "s"
BETTER = "lower"
MOVES = "setup_s"
SOURCE = "program_span"
DRIVERS = ('train_packed', 'serve_closed_loop', 'serve_closed_loop_hf',
           'serve_closed_loop_hybrid', 'serve_closed_loop_ssm_attn')


def read(ctx):
    return startup.metric(ctx, "setup_compile_s")
