"""The profiler window of a ``--trace 1`` run: a few seconds in the middle
of the measured window, never all of it (traces are large and tracing
slows the host)."""
from __future__ import annotations

import shutil
from pathlib import Path
from typing import Optional, Tuple


class TraceWindow:
    """Call :meth:`tick` with the seconds since the window opened at
    every step boundary; it starts and stops the profiler when due."""

    SPAN_S = 5.0

    def __init__(self, enabled: bool, seconds: float, out_dir: Path):
        self.enabled = enabled
        span = min(self.SPAN_S, seconds / 2.0)
        self.start_at = (seconds - span) / 2.0
        self.stop_at = self.start_at + span
        self.out_dir = Path(out_dir)
        self.active = False
        self.done = False

    def tick(self, t_rel: float) -> None:
        if not self.enabled or self.done:
            return
        import jax
        if not self.active and t_rel >= self.start_at:
            shutil.rmtree(self.out_dir, ignore_errors=True)
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0     # TraceMe spans only
            options.host_tracer_level = 2
            jax.profiler.start_trace(str(self.out_dir),
                                     profiler_options=options)
            self.active = True
        elif self.active and t_rel >= self.stop_at:
            self.close()

    def close(self) -> None:
        if self.active:
            import jax
            jax.profiler.stop_trace()
            self.active = False
            self.done = True

    def load(self):
        """The reduced trace, or None where none was taken."""
        from perfbench.lib import xplane
        if not self.done:
            return None
        path = xplane.find_xplane(str(self.out_dir))
        return xplane.load(path) if path else None
