"""Trace and lowering of the step programs during set-up: self seconds of
the main thread's ``xla_lower`` start-up spans that began before the
traced window (``IntrospectedFunction``'s ``jitted.lower()``: paid at
every process start, whatever the persistent cache holds; a kernel import
the lowering waited for is ``setup_kernel_import_wait_s``, not this)."""
from perfbench.lib import startup

LAYER = "start-up"
UNIT = "s"
BETTER = "lower"
MOVES = "setup_s"
SOURCE = "program_span"
DRIVERS = ('train_packed', 'serve_closed_loop', 'serve_closed_loop_hf',
           'serve_closed_loop_hybrid', 'serve_closed_loop_ssm_attn')


def read(ctx):
    return startup.metric(ctx, "setup_trace_lower_s")
