"""The five set-up durations (``setup_trace_lower_s``, ``setup_compile_s``,
``setup_kernel_import_wait_s``, ``setup_build_s``, ``setup_jit_other_s``)
over ``setup_s``. The rest is what the program cannot span: the
interpreter and ``import jax`` before it exists, the harness's own work,
and the warm steps."""
from perfbench.lib import startup

LAYER = "start-up"
UNIT = "%"
BETTER = "higher"
MOVES = "setup_s"
SOURCE = "program_span"
DRIVERS = ('train_packed', 'serve_closed_loop', 'serve_closed_loop_hf',
           'serve_closed_loop_hybrid', 'serve_closed_loop_ssm_attn')


def read(ctx):
    return startup.metric(ctx, "setup_attributed_pct")
