"""What the critical path paid for Pallas: the main thread's
``startup_kernel_import`` spans before the traced window (its wait, at
trace time, for a kernel module or for the import lock a constructor's
background thread holds; that thread's own span has another thread's name
and is not counted)."""
from perfbench.lib import startup

LAYER = "start-up"
UNIT = "s"
BETTER = "lower"
MOVES = "setup_s"
SOURCE = "program_span"
DRIVERS = ('train_packed', 'serve_closed_loop', 'serve_closed_loop_hf',
           'serve_closed_loop_hybrid', 'serve_closed_loop_ssm_attn')


def read(ctx):
    return startup.metric(ctx, "setup_kernel_import_wait_s")
