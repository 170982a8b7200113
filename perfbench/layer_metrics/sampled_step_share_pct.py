"""Share of the traced window's ``serve_decode`` spans whose
``sampling_slots`` argument is over 0: decode steps with a running
request of temperature > 0, whose sampler filtered and drew over the
whole vocabulary instead of taking the arg-max. 0 says every step of the
cell took the sampler's short branch. Nothing to read where the program
does not emit the argument."""
from perfbench.lib import spans

LAYER = "model step"
UNIT = "%"
BETTER = "lower"
MOVES = "itl_p99_ms"
SOURCE = "program_span"
DRIVERS = ('serve_closed_loop',)


def read(ctx):
    trace = spans.for_context(ctx)
    if trace is None:
        return None
    rows = [int(a["sampling_slots"]) for n, _, _, a in trace.host
            if n == "serve_decode" and "sampling_slots" in a]
    if not rows:
        return None
    return 100.0 * sum(1 for n in rows if n > 0) / len(rows)
