"""XLA introspection: retrace attribution + compiled-function accounting.

The telemetry spine measures the host side of a run; this module opens
the XLA layer underneath it. Two blind spots it removes:

- **Why did that recompile happen?** ``jax.jit`` silently re-traces when
  any argument's shape/dtype/structure changes, and ``log_compiles``
  only says *that* it happened. :class:`IntrospectedFunction` wraps a
  jitted entry point, fingerprints every call's argument avals, and on a
  fingerprint change names exactly which argument changed and how
  (``batch['input_ids']: i32[8,16] -> i32[8,32]``) — emitted as a
  ``compile`` flight-recorder event and ``telemetry/xla/*recompiles``
  counters.

- **What did XLA actually lower?** At each compile the wrapper reads
  ``lowered.compile().cost_analysis()`` / ``memory_analysis()`` and
  publishes per-function analytic FLOPs, bytes accessed, and
  argument/output/temp/alias/generated-code memory as always-on
  ``telemetry/xla/<fn>/*`` gauges — the ``tools/scale_rehearsal.py``
  offline pattern promoted into the live registry — plus a roofline
  verdict (compute- vs bandwidth-bound) when given an
  :class:`~dla_tpu.telemetry.mfu.MFUCalculator`.

Zero extra compiles, by construction: the wrapper OWNS dispatch via the
AOT path. The first call for a fingerprint runs ``jitted.lower(args)``
(the ONE trace — the in-body trace-time compile counters tick exactly
once) then ``.compile()``, and every subsequent call with the same
fingerprint dispatches through the cached ``Compiled`` object without
touching the tracing machinery. A changed fingerprint re-lowers, exactly
as plain ``jax.jit`` would have re-traced — same compile count, but now
attributed. A lowering, compile or dispatch error propagates to the
caller: retrying through the raw jitted callable would compile the same
program a second time and report the step as healthy.

Fingerprints deliberately cover structure + shape + dtype, not values:
traced scalars (the guard EMA, fault injectors) change value every step
and must never re-key the cache — mirroring jit's own cache key.

- **What did compiling cost, and did the persistent cache answer?** The
  wrapper times its ``lower()`` and its ``compile()`` apart (start-up
  spans ``xla_lower`` / ``xla_compile``, gauges ``lower_s`` /
  ``compile_s`` / ``cache_hit``), and one ``jax.monitoring`` listener
  (:func:`install_compile_accounting`) counts the persistent cache's
  hits and misses and keeps the lowering and backend-compile durations
  of EVERY jitted function of the process, wrapped or not.
"""
from __future__ import annotations

import dataclasses
import re
import threading
import time
from collections import OrderedDict, deque
from typing import (Any, Callable, Deque, Dict, List, NamedTuple,
                    Optional, Tuple)

import jax

from dla_tpu.telemetry.mfu import MFUCalculator
from dla_tpu.telemetry.registry import Counter, Gauge, MetricRegistry

#: memory_analysis fields published as ``telemetry/xla/<fn>/<name>``.
_MEMORY_FIELDS = (
    ("argument_size_in_bytes", "argument_bytes"),
    ("output_size_in_bytes", "output_bytes"),
    ("temp_size_in_bytes", "temp_bytes"),
    # bytes of arguments the program updates in place (donated inputs
    # XLA aliased to outputs): 0 means every output is a fresh buffer
    ("alias_size_in_bytes", "alias_bytes"),
    ("generated_code_size_in_bytes", "generated_code_bytes"),
    ("peak_memory_in_bytes", "peak_bytes"),
)


def _leaf_sig(x: Any) -> str:
    """One argument leaf's cache-key contribution: ``dtype[shape]`` for
    anything array-like (value changes never re-key, mirroring jit),
    ``repr`` for static leaves (a changed static IS a retrace)."""
    shape = getattr(x, "shape", None)
    dtype = getattr(x, "dtype", None)
    if shape is not None and dtype is not None:
        return f"{dtype}[{','.join(str(d) for d in shape)}]"
    if isinstance(x, (bool, int, float, complex)):
        # python scalars trace as weak-typed () arrays: key on the type,
        # not the value, exactly like jit's weak-type cache key
        return f"weak_{type(x).__name__}[]"
    return f"static:{x!r}"


def fingerprint_args(args: Tuple[Any, ...]) -> Tuple[str, Tuple[Tuple[str, str], ...]]:
    """(treedef string, ((arg path, leaf signature), ...)) — hashable,
    and diffable leaf-by-leaf with human-readable paths."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(args)
    sigs = tuple((f"args{jax.tree_util.keystr(path)}", _leaf_sig(leaf))
                 for path, leaf in flat)
    return (str(treedef), sigs)


def diff_fingerprints(old, new, limit: int = 4) -> List[Dict[str, str]]:
    """Name what changed between two fingerprints: up to ``limit``
    ``{"arg", "old", "new"}`` rows. A structure (treedef / leaf count)
    change is reported as one ``args`` row."""
    if old is None:
        return []
    old_tree, old_sigs = old
    new_tree, new_sigs = new
    changes: List[Dict[str, str]] = []
    if old_tree != new_tree or len(old_sigs) != len(new_sigs):
        return [{"arg": "args", "old": "structure", "new": "structure "
                 f"changed ({len(old_sigs)} -> {len(new_sigs)} leaves)"}]
    for (path, osig), (_, nsig) in zip(old_sigs, new_sigs):
        if osig != nsig:
            changes.append({"arg": path, "old": osig, "new": nsig})
            if len(changes) >= limit:
                break
    return changes


def normalize_cost_analysis(cost: Any) -> Dict[str, float]:
    """``Compiled.cost_analysis()``'s dict under telemetry key names:
    ``{"flops", "bytes_accessed", "transcendentals"}`` (empty when the
    backend reports none)."""
    if not isinstance(cost, dict):
        return {}
    return {
        "flops": float(cost.get("flops", 0.0) or 0.0),
        "bytes_accessed": float(cost.get("bytes accessed", 0.0) or 0.0),
        "transcendentals": float(cost.get("transcendentals", 0.0) or 0.0),
    }


def memory_stats(compiled: Any) -> Dict[str, float]:
    """``memory_analysis()`` fields under their telemetry names; empty
    when the backend does not implement compiled memory stats."""
    try:
        ma = compiled.memory_analysis()
    except Exception:                                 # noqa: BLE001
        return {}
    if ma is None:
        return {}
    out: Dict[str, float] = {}
    for attr, name in _MEMORY_FIELDS:
        v = getattr(ma, attr, None)
        if v is not None:
            out[name] = float(v)
    return out


def live_array_bytes() -> float:
    """Total bytes of every live jax array in this process — the live-HBM
    number (on TPU these buffers are HBM-resident). Read-through at
    snapshot/scrape cadence via a FuncGauge, never per step."""
    try:
        arrays = jax.live_arrays()
    except Exception:                                 # noqa: BLE001
        return 0.0
    total = 0
    for a in arrays:
        try:
            total += int(a.nbytes)
        except Exception:                             # noqa: BLE001
            continue
    return float(total)


def register_live_bytes_gauge(registry: MetricRegistry):
    """``telemetry/xla/live_bytes``: live-array byte total at scrape/log
    cadence (idempotent per registry)."""
    if "telemetry/xla/live_bytes" in registry._instruments:
        return registry.get("telemetry/xla/live_bytes")
    return registry.func_gauge("telemetry/xla/live_bytes", live_array_bytes)


_HLO_INSTRUCTION = re.compile(r"^\s*(ROOT\s+)?%?([\w.\-]+) = ")
_HLO_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$")
_HLO_OP_NAME = re.compile(r'op_name="([^"]*)"')
_HLO_CALLS = re.compile(r"\bcalls=%?([\w.\-]+)")
_HLO_MODULE = re.compile(r"^HloModule\s+([^\s,]+)")


def hlo_scopes(hlo_text: str) -> Dict[str, str]:
    """``Compiled.as_text()`` -> {instruction name: ``op_name``}: the name
    stack JAX recorded for each instruction (``jit(_train_step)/optimizer/
    mul``, ``.../transpose(jvp())/.../checkpoint/rematted_computation/
    dot_general``). A profiler trace names a device event by its
    instruction, so this map is what ties device time to ``jax.named_scope``
    and to JAX's own forward / backward / remat frames. A fusion that
    carries no ``op_name`` of its own takes the root's of the computation
    it calls."""
    scopes: Dict[str, str] = {}
    roots: Dict[str, str] = {}             # computation -> its root's op_name
    unnamed: List[Tuple[str, str]] = []    # (instruction, called computation)
    computation = None
    for line in hlo_text.splitlines():
        m = _HLO_INSTRUCTION.match(line)
        if m is None:
            c = _HLO_COMPUTATION.match(line)
            if c is not None:
                computation = c.group(1)
            continue
        op = _HLO_OP_NAME.search(line)
        if op is not None:
            scopes[m.group(2)] = op.group(1)
            if m.group(1) and computation is not None:
                roots[computation] = op.group(1)
        else:
            call = _HLO_CALLS.search(line)
            if call is not None:
                unnamed.append((m.group(2), call.group(1)))
    for name, called in unnamed:
        if called in roots:
            scopes[name] = roots[called]
    return scopes


#: IntrospectedFunction name -> the executable it compiled last, and the
#: (module name, scopes) read from it on first request
_LATEST_COMPILED: Dict[str, Any] = {}
_SCOPES: Dict[str, Tuple[Any, str, Dict[str, str]]] = {}


def compiled_scopes(module_pattern: str) -> Dict[str, str]:
    """:func:`hlo_scopes` of the program(s) this process compiled through
    an :class:`IntrospectedFunction` whose HLO module name matches the
    pattern (``jit__train_step``). Parsed on first request, never on the
    step path; empty where nothing matches."""
    rx = re.compile(module_pattern)
    out: Dict[str, str] = {}
    for name, compiled in _LATEST_COMPILED.items():
        cached = _SCOPES.get(name)
        if cached is None or cached[0] is not compiled:
            text = compiled.as_text()
            module = _HLO_MODULE.match(text)
            cached = (compiled, module.group(1) if module else name,
                      hlo_scopes(text))
            _SCOPES[name] = cached
        if rx.search(cached[1]):
            out.update(cached[2])
    return out


# ------------------------------------------------ process-wide accounting

TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
TO_MLIR_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_COUNTS = {
    "/jax/compilation_cache/compile_requests_use_cache": "cache_requests",
    "/jax/compilation_cache/cache_hits": "cache_hits",
    "/jax/compilation_cache/cache_misses": "cache_misses",
}
#: the most events kept: the newest (a long-lived process asks what a
#: phase compiled, ``compile_events(since_ns)``; a start is far smaller)
COMPILE_EVENT_CAP = 4096


class CompileEvent(NamedTuple):
    """One duration JAX reported: ``t_ns`` is ``perf_counter_ns`` when it
    fired (the end of the ``seconds`` it measures), on ``thread``."""
    event: str
    t_ns: int
    seconds: float
    fun_name: str
    thread: str


class _Accounting:
    """What the listener adds up. JAX fires every one of these events
    synchronously on the thread that lowers or compiles, so the counts
    are kept per thread as well: a wrapper reads whether ITS compile hit
    the cache from its own thread's counts, and a compile on another
    thread (a sampler's, a fleet member's) cannot be mistaken for it."""

    def __init__(self):
        self.installed = False
        self.reset()

    def reset(self) -> None:
        self.totals = {"cache_requests": 0, "cache_hits": 0,
                       "cache_misses": 0, "backend_compiles": 0,
                       "lower_s": 0.0, "backend_compile_s": 0.0}
        self.events: Deque[CompileEvent] = deque(maxlen=COMPILE_EVENT_CAP)
        self.dropped = 0
        self.local = threading.local()

    def thread_counts(self) -> Dict[str, int]:
        counts = getattr(self.local, "counts", None)
        if counts is None:
            counts = self.local.counts = {
                "cache_requests": 0, "cache_hits": 0, "cache_misses": 0,
                "depth": 0}
        return counts

    # JAX's ``log_elapsed_time`` reports a scalar when a timed region
    # opens and a duration when it closes. Tracing nests (a callee's
    # ``jaxpr_trace_duration`` lies inside its caller's, and inside a
    # lowering that traces a helper), so a plain sum counts seconds
    # twice: only a region that opened at depth 0 is counted and kept.
    def on_open(self, event: str, value: float, **kw) -> None:
        if event == TRACE_EVENT or event == TO_MLIR_EVENT:
            self.thread_counts()["depth"] += 1

    def on_duration(self, event: str, seconds: float, **kw) -> None:
        if event == BACKEND_COMPILE_EVENT:
            self.totals["backend_compiles"] += 1
            self.totals["backend_compile_s"] += seconds
        elif event == TRACE_EVENT or event == TO_MLIR_EVENT:
            counts = self.thread_counts()
            counts["depth"] = max(counts["depth"] - 1, 0)
            if counts["depth"]:
                return
            self.totals["lower_s"] += seconds
        else:
            return
        self._keep(event, float(seconds), str(kw.get("fun_name", "?")))

    def on_event(self, event: str, **kw) -> None:
        key = _CACHE_COUNTS.get(event)
        if key is None:
            return
        self.totals[key] += 1
        self.thread_counts()[key] += 1
        if key != "cache_requests":
            # a hit or a miss is kept with its time too (0 seconds, no
            # name: it fires inside the backend compile it belongs to),
            # so a reader can count those of one phase
            self._keep(event, 0.0, "")

    def _keep(self, event: str, seconds: float, fun_name: str) -> None:
        if len(self.events) == self.events.maxlen:
            self.dropped += 1       # the oldest makes room
        self.events.append(CompileEvent(
            event, time.perf_counter_ns(), seconds, fun_name,
            threading.current_thread().name))


_ACCOUNTING = _Accounting()


def install_compile_accounting() -> None:
    """Register the one ``jax.monitoring`` listener of the process;
    ``utils.compile_cache.enable_compile_cache()`` calls it, as does an
    :class:`IntrospectedFunction` before its first compile. Once only,
    however often it is called."""
    if _ACCOUNTING.installed:
        return
    _ACCOUNTING.installed = True
    jax.monitoring.register_scalar_listener(_ACCOUNTING.on_open)
    jax.monitoring.register_event_duration_secs_listener(
        _ACCOUNTING.on_duration)
    jax.monitoring.register_event_listener(_ACCOUNTING.on_event)


def compile_accounting() -> Dict[str, float]:
    """The totals so far: persistent-cache ``cache_requests`` /
    ``cache_hits`` / ``cache_misses``, ``backend_compiles`` and their
    ``backend_compile_s`` (a retrieval counts as the compile it
    replaced), ``lower_s`` (trace + MLIR lowering, outermost regions
    only) and ``events_dropped``."""
    return dict(_ACCOUNTING.totals, events_dropped=_ACCOUNTING.dropped)


def compile_events(since_ns: int = 0) -> List[CompileEvent]:
    """The outermost trace / lowering regions, every backend compile and
    every persistent-cache hit or miss (``seconds`` 0) JAX reported
    since ``since_ns`` (``perf_counter_ns``), every jitted function of
    the process: the newest ``COMPILE_EVENT_CAP`` of them."""
    return [ev for ev in list(_ACCOUNTING.events) if ev.t_ns >= since_ns]


def reset_compile_accounting() -> None:
    """Zero the totals and forget the events (tests); the listener, once
    installed, stays."""
    _ACCOUNTING.reset()


def publish_compile_accounting(registry: MetricRegistry) -> None:
    """``telemetry/xla/cache_hits``, ``/cache_misses``,
    ``/backend_compile_s``, ``/lower_s`` on ``registry``, as of now."""
    for key in ("cache_hits", "cache_misses", "backend_compile_s",
                "lower_s"):
        set_gauge(registry, f"telemetry/xla/{key}",
                  _ACCOUNTING.totals[key])


@dataclasses.dataclass
class _Entry:
    """One compiled specialization: the AOT executable + its analysis."""
    compiled: Any
    stats: Dict[str, float]


class IntrospectedFunction:
    """Dispatch-owning wrapper around one jitted entry point.

    Call it exactly like the jitted function. Attributes of interest:

    - ``compiles`` / ``recompiles`` — wrapper-observed compile counts
      (recompiles = compiles beyond the first)
    - ``last_event`` — the compile event dict for the most recent
      dispatch, ``None`` when the dispatch hit the cache (the trainer
      reads this to tell attributed from unattributed compile-counter
      ticks)
    - ``stats`` — the latest compile's cost/memory analysis
    - ``step`` — caller-maintained current step, stamped onto events
    """

    def __init__(self, name: str, jitted: Callable, *,
                 registry: Optional[MetricRegistry] = None,
                 recorder: Any = None,
                 mfu_calc: Optional[MFUCalculator] = None,
                 on_compile: Optional[Callable[[Dict[str, Any]], None]] = None,
                 enabled: bool = True,
                 max_entries: int = 16):
        self.name = name
        self.jitted = jitted
        self.registry = registry
        self.recorder = recorder
        self.mfu_calc = mfu_calc
        self.on_compile = on_compile
        self.enabled = enabled
        self.max_entries = max(1, int(max_entries))
        self.step: Optional[int] = None
        self.compiles = 0
        self.recompiles = 0
        self.last_event: Optional[Dict[str, Any]] = None
        self.stats: Dict[str, float] = {}
        self._cache: "OrderedDict[Any, _Entry]" = OrderedDict()
        self._last_fp = None

    # ------------------------------------------------------------- dispatch

    def __call__(self, *args):
        self.last_event = None
        if not self.enabled:
            return self.jitted(*args)
        # fingerprint BEFORE dispatch: donated buffers are dead after
        fp = fingerprint_args(args)
        entry = self._cache.get(fp)
        if entry is None:
            entry = self._compile(fp, args)
        else:
            self._cache.move_to_end(fp)
        self._last_fp = fp
        return entry.compiled(*args)

    def _compile(self, fp, args) -> _Entry:
        # here, not at the top: utils.profiling imports this package
        from dla_tpu.utils.profiling import startup_span
        install_compile_accounting()
        is_recompile = self.compiles > 0
        n = self.compiles + 1
        # the one trace and the lowering: paid at every process start,
        # whatever the persistent cache holds
        with startup_span("xla_lower", fn=self.name, n_compiles=n) as low:
            lowered = self.jitted.lower(*args)
        # the backend compile, or its retrieval from the persistent
        # cache: which, by this thread's counts before and after
        counts = _ACCOUNTING.thread_counts()
        asked, hits = counts["cache_requests"], counts["cache_hits"]
        with startup_span("xla_compile", fn=self.name, n_compiles=n,
                          cache_hit=-1) as comp:
            compiled = lowered.compile()
            cache_hit = (-1 if counts["cache_requests"] == asked
                         else int(counts["cache_hits"] > hits))
            comp.set(cache_hit=cache_hit)
        timing = {"lower_s": low.seconds, "compile_s": comp.seconds,
                  "cache_hit": cache_hit}
        _LATEST_COMPILED[self.name] = compiled    # for compiled_scopes()
        if is_recompile:
            self._emit_compile_event(fp, aot=True, timing=timing)
        self.compiles += 1
        if not is_recompile and self.recorder is not None:
            # first compile is expected, not a recompile: ring event only
            # (last_event stays None so the caller reads it as attributed)
            self.recorder.record("compile", step=self.step, fn=self.name,
                                 first=True, attributed=True,
                                 n_compiles=1, aot=True, **timing)
        stats = dict(timing)
        stats.update(normalize_cost_analysis(
            _safe_cost_analysis(compiled)))
        stats.update(memory_stats(compiled))
        if self.mfu_calc is not None and stats.get("flops"):
            verdict = self.mfu_calc.roofline(
                stats["flops"], stats.get("bytes_accessed", 0.0))
            stats.update({f"roofline_{k}": v for k, v in verdict.items()})
        self.stats = stats
        self._publish(stats)
        entry = _Entry(compiled, stats)
        self._cache[fp] = entry
        while len(self._cache) > self.max_entries:
            self._cache.popitem(last=False)
        return entry

    # -------------------------------------------------------- event plumbing

    def note_unattributed_compile(self, step: Optional[int] = None) -> None:
        """The caller's trace-time compile counter ticked but this wrapper
        saw no fingerprint delta (external jit cache thrash): count and
        record it as an unattributed recompile so it still shows up in
        the ring and the counters."""
        if step is not None:
            self.step = step
        self._emit_compile_event(self._last_fp, aot=False)

    def _emit_compile_event(self, new_fp, aot: bool,
                            timing: Optional[Dict[str, float]] = None
                            ) -> None:
        changes = diff_fingerprints(self._last_fp, new_fp)
        event = {
            "fn": self.name,
            "attributed": bool(changes),
            "changed": changes,
            "n_compiles": self.compiles + 1,
            "aot": aot,
            **(timing or {}),
        }
        self.recompiles += 1
        self.last_event = event
        if self.registry is not None:
            _get_counter(self.registry, "telemetry/xla/recompiles").inc()
            _get_counter(self.registry,
                         f"telemetry/xla/{self.name}/recompiles").inc()
        if self.recorder is not None:
            self.recorder.record("compile", step=self.step, **{
                k: (v if k != "changed" else _changes_text(v))
                for k, v in event.items()})
        if self.on_compile is not None:
            self.on_compile(dict(event, step=self.step))

    def _publish(self, stats: Dict[str, float]) -> None:
        if self.registry is None:
            return
        for key, value in stats.items():
            set_gauge(self.registry,
                      f"telemetry/xla/{self.name}/{key}", value)


def _safe_cost_analysis(compiled: Any) -> Any:
    try:
        return compiled.cost_analysis()
    except Exception:                                 # noqa: BLE001
        return {}


def _changes_text(changes: List[Dict[str, str]]) -> str:
    if not changes:
        return "unattributed (no fingerprint delta)"
    return "; ".join(f"{c['arg']}: {c['old']} -> {c['new']}"
                     for c in changes)


def _get_counter(registry: MetricRegistry, name: str) -> Counter:
    inst = registry._instruments.get(name)
    if inst is None:
        inst = registry.counter(name)
    return inst


def set_gauge(registry: MetricRegistry, name: str, value: float) -> Gauge:
    """Set gauge ``name`` on ``registry``, registering it the first time
    (the ``telemetry/xla/`` family is a dynamic prefix of the CATALOG)."""
    inst = registry._instruments.get(name)
    if inst is None:
        inst = registry.gauge(name)
    inst.set(value)
    return inst
