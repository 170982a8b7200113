"""What a driver is handed (:class:`Bench`) and what a per-layer metric's
reader is handed (:class:`RunContext`)."""
from __future__ import annotations

import dataclasses
import sys
import time
from pathlib import Path
from typing import Any, Dict, List, Optional, Tuple

from perfbench.lib.manifest import Manifest
from perfbench.lib.sut import CompileWatch
from perfbench.lib.tracing import TraceWindow


@dataclasses.dataclass
class Bench:
    manifest: Manifest
    cell: Dict
    config: Dict
    traffic: Dict
    seed: int
    seconds: float
    rehearsal: bool
    t_start: float                 # perf_counter at process start
    attach_s: float                # seconds jax.devices() took
    compiles: CompileWatch
    tracer: TraceWindow
    scratch: Path                  # ignored directory inside the checkout

    def say(self, msg: str) -> None:
        tag = "REHEARSAL " if self.rehearsal else ""
        print(f"{tag}[perfbench {time.perf_counter() - self.t_start:6.1f}s] "
              f"{msg}", file=sys.stderr, flush=True)

    def open_window(self) -> Tuple[float, float]:
        """Set-up ends here. Returns the window's opening time and
        ``setup_s``: process start to now, less the seconds the TPU
        runtime took to come up."""
        self.compiles.open = True
        t0 = time.perf_counter()
        return t0, t0 - self.t_start - self.attach_s

    def close_window(self) -> None:
        self.compiles.open = False
        self.tracer.close()


@dataclasses.dataclass
class RunContext:
    """One run as a per-layer metric's reader sees it. ``counters`` are
    counts and sums over the window, ``samples`` lists of readings,
    ``trace`` the reduced xplane of the traced part (None without
    ``--trace 1``)."""
    cell: Dict
    config: Dict
    traffic: Dict
    chips: int
    window_s: float
    device: Dict
    peaks: Optional[Dict]
    end_to_end: Dict[str, float]
    counters: Dict[str, float]
    samples: Dict[str, List[float]]
    annotations: Tuple[str, ...] = ()
    programs: Dict[str, str] = dataclasses.field(default_factory=dict)
    trace: Any = None
    trace_window: Optional[Tuple[float, float]] = None
