"""Where ``setup_s`` goes, from the program's own start-up records.

No profiler runs while a process sets up (the traced run profiles 5 s in
the middle of the window), so the program keeps its start-up spans in
memory (``dla_tpu.utils.profiling.startup_spans``: the constructors, the
lazy kernel imports, each lowering and each compile of an
``IntrospectedFunction``) and one ``jax.monitoring`` listener keeps the
lowering and backend-compile durations, and the persistent cache's hits
and misses, of every jitted function of the process
(``dla_tpu.telemetry.xla_introspect.compile_events``). The readers run in
the harness's process and read both directly, as ``decode_scopes.py``
reads ``compiled_scopes()``.

Both clocks are ``time.perf_counter_ns()``, which is every step span's
``host_ns``. **The cut** between set-up and the rest is the smallest
``host_ns`` of the traced window's step spans: a window with a compile in
it is already not ``correct``, so any instant inside the window separates
set-up from the reference check's compiles after it.

Everything after :func:`collect` is arithmetic on plain dicts and tuples,
checked in ``perfbench/tests/test_startup.py``. Durations are self times
by nesting on the main thread, so the five of them add up to no more
than ``setup_s``. Where the program keeps no such records (the parent of
the PR that added them), or without a trace (a rehearsal, ``--trace 0``),
every reader returns ``None`` and raises nothing.
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence, Tuple

from perfbench.lib import spans, xplane

MAIN = "MainThread"
LOWER = "xla_lower"
COMPILE = "xla_compile"
IMPORT = "startup_kernel_import"
#: everything a constructor or the weights path spans, children included
BUILD = ("startup_model_build", "startup_engine_build",
         "startup_trainer_build", "startup_pool_alloc",
         "startup_state_init", "startup_weights")
CACHE_MISS = "/jax/compilation_cache/cache_misses"
DURATIONS = ("setup_trace_lower_s", "setup_compile_s",
             "setup_kernel_import_wait_s", "setup_build_s",
             "setup_jit_other_s")

Record = Dict[str, Any]             # name, thread, start_ns, end_ns, args
Event = Tuple[str, int, float, str, str]   # event, t_ns, seconds, fun, thread


def collect() -> Optional[Tuple[List[Record], List[Event]]]:
    """The program's records and compile events so far, or None where
    the program keeps none."""
    try:
        from dla_tpu.telemetry.xla_introspect import compile_events
        from dla_tpu.utils.profiling import startup_spans
    except ImportError:
        return None
    return startup_spans(), [tuple(ev) for ev in compile_events()]


def cut_ns(host: Sequence[spans.Span]) -> Optional[int]:
    """The smallest ``host_ns`` of the traced step spans."""
    at = [int(stats["host_ns"]) for name, _, _, stats in host
          if name in spans.STEP_SPANS and "host_ns" in stats]
    return min(at) if at else None


def main_self_seconds(records: Sequence[Record], cut: int
                      ) -> Dict[str, float]:
    """Self seconds by span name of the main thread's records that began
    before the cut: a record's duration less what the records nested in
    it cover (an import the lowering waited for is the import's, not the
    lowering's). Another thread's records neither count nor subtract."""
    rows = spans._sorted(
        (r["name"], r["start_ns"] * 1e-9, r["end_ns"] * 1e-9, {})
        for r in records if r["thread"] == MAIN and r["start_ns"] < cut)
    _, own = spans.nesting(rows)
    out: Dict[str, float] = {}
    for (name, *_), secs in zip(rows, own):
        out[name] = out.get(name, 0.0) + secs
    return out


def jit_other_seconds(records: Sequence[Record], events: Sequence[Event],
                      cut: int) -> float:
    """Seconds of the main thread's lowering and backend-compile events
    before the cut that no main-thread start-up record covers: the
    harness's weights ``init``, the sampler, eager one-op programs
    between the constructors. Counted as a union of intervals, less the
    records' union, so nothing is counted twice."""
    timed = xplane.union(
        ((t_ns * 1e-9 - seconds, t_ns * 1e-9)
         for _, t_ns, seconds, _, thread in events
         if thread == MAIN and seconds > 0.0 and t_ns < cut))
    covered = xplane.union(
        ((r["start_ns"] * 1e-9, r["end_ns"] * 1e-9) for r in records
         if r["thread"] == MAIN and r["start_ns"] < cut))
    return xplane.total(xplane.subtract(timed, covered))


def cache_misses(events: Sequence[Event], cut: int) -> int:
    """Persistent-cache misses before the cut, on any thread."""
    return sum(1 for event, t_ns, *_ in events
               if event == CACHE_MISS and t_ns < cut)


def reduce(records: Sequence[Record], events: Sequence[Event], cut: int,
           setup_s: float) -> Dict[str, float]:
    """All seven metrics from plain data."""
    own = main_self_seconds(records, cut)
    out = {
        "setup_trace_lower_s": own.get(LOWER, 0.0),
        "setup_compile_s": own.get(COMPILE, 0.0),
        "setup_kernel_import_wait_s": own.get(IMPORT, 0.0),
        "setup_build_s": sum(own.get(name, 0.0) for name in BUILD),
        "setup_jit_other_s": jit_other_seconds(records, events, cut),
        "setup_cache_misses": float(cache_misses(events, cut)),
    }
    out["setup_attributed_pct"] = (
        100.0 * sum(out[name] for name in DURATIONS) / setup_s)
    return out


_READ: Dict[str, Optional[Dict[str, float]]] = {}


def metric(ctx, name: str) -> Optional[float]:
    """One of the seven for a per-layer metric's reader; the reduction is
    made once per run."""
    cell = ctx.cell["name"]
    if cell not in _READ:
        _READ[cell] = _read(ctx)
    got = _READ[cell]
    return None if got is None else got[name]


def _read(ctx) -> Optional[Dict[str, float]]:
    trace = spans.for_context(ctx)
    if trace is None:
        return None
    cut = cut_ns(trace.host)
    found = collect()
    setup_s = float(ctx.end_to_end.get("setup_s") or 0.0)
    if cut is None or found is None or setup_s <= 0.0:
        return None
    records, events = found
    if not records:
        return None
    return reduce(records, events, cut, setup_s)
