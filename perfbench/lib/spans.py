"""The program's own spans, read from the traced run's xplane.

``dla_tpu/utils/profiling.py`` emits ``jax.profiler.TraceAnnotation``
events with arguments: step spans (``serve``, ``train``), their phases,
and zero-length marks of the request lifecycle (``serve_req_*``). They sit
in the same file, on the same clock, as the device's operations.
``perfbench/lib/xplane.py`` keeps names and times only, so this module
loads the file a second time, keeping each host event's stats and each
device operation's whole text. Everything after :func:`load` is arithmetic
on plain tuples, checked in ``perfbench/tests/test_spans.py`` without a
chip.

A span is ``(name, start_s, end_s, stats)``. *Program* spans are those
the program emits (their names start with ``serve`` or ``train``); the
runtime's own TraceMe events on the same thread are everything else.

Where the program emits no such span (the parent of the PR that added
them, a rehearsal, ``--trace 0``) every reader here returns ``None`` or
an empty result and raises nothing.
"""
from __future__ import annotations

import dataclasses
import json
import re
from pathlib import Path
from typing import Any, Dict, Iterable, List, Optional, Sequence, Tuple

from perfbench.lib import stats as st
from perfbench.lib import xplane

Span = Tuple[str, float, float, Dict[str, Any]]
Interval = Tuple[float, float]

#: the step span of each loop
STEP_SPANS = ("serve", "train")
#: scopes of the train step's device time, in order of precedence
SCOPES = ("optimizer", "metrics", "remat", "backward", "forward", "unscoped")

BENCH = Path(__file__).resolve().parents[1]


def is_program(name: str) -> bool:
    return name.startswith(STEP_SPANS)


@dataclasses.dataclass
class SpanTrace:
    host: List[Span]                  # the thread that carries the step spans
    ops: List[Tuple[str, float, float]]       # device 0, whole op text
    modules: List[Tuple[str, float, float]]   # device 0, program executions


# ------------------------------------------------------------------ loading

def load(path: str) -> SpanTrace:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    host: List[Span] = []
    most = 0
    devices = {}
    for plane in data.planes:
        if plane.name == "/host:CPU":
            for line in plane.lines:
                evs = [(ev.name, ev.start_ns * 1e-9,
                        (ev.start_ns + ev.duration_ns) * 1e-9, ev)
                       for ev in line.events]
                steps = sum(1 for e in evs if e[0] in STEP_SPANS)
                if steps > most:
                    most = steps
                    # stats are read for program spans only: the thread
                    # holds tens of thousands of runtime events
                    host = [(n, s, e, dict(ev.stats) if is_program(n) else {})
                            for n, s, e, ev in evs]
        elif plane.name.startswith("/device:TPU:"):
            lines = {line.name: line for line in plane.lines}
            devices[plane.name] = [
                _sorted((ev.name, ev.start_ns * 1e-9,
                         (ev.start_ns + ev.duration_ns) * 1e-9)
                        for ev in lines[key].events) if key in lines else []
                for key in ("XLA Ops", "XLA Modules")]
    ops, modules = devices[min(devices)] if devices else ([], [])
    return SpanTrace(_sorted(host), ops, modules)


def _sorted(events: Iterable[tuple]) -> List[tuple]:
    """By start, longer first on ties, so a parent precedes its children."""
    return sorted(events, key=lambda e: (e[1], -e[2]))


_LOADED: Dict[str, SpanTrace] = {}


def trace_dir(cell_name: str) -> Path:
    """Where ``run.py`` has the profiler write a cell's traced window."""
    return BENCH / ".run" / cell_name / "trace"


def for_context(ctx) -> Optional[SpanTrace]:
    """The traced run's spans for a per-layer metric's reader: None
    without a device trace (``--trace 0``, a rehearsal). Loaded once per
    process."""
    if ctx.trace is None:
        return None
    path = xplane.find_xplane(str(trace_dir(ctx.cell["name"])))
    if path is None:
        return None
    if path not in _LOADED:
        _LOADED[path] = load(path)
    return _LOADED[path]


# ------------------------------------------------------------ span algebra

def program_spans(spans: Sequence[Span]) -> List[Span]:
    return [s for s in spans if is_program(s[0])]


def nesting(spans: Sequence[Span]) -> Tuple[List[Optional[int]], List[float]]:
    """For spans of one thread sorted by start (longer first on ties): each
    span's parent (index of the innermost span that holds it, or None) and
    its self time, its duration less what its child spans cover
    (``choosing-metrics`` section 4)."""
    parents: List[Optional[int]] = []
    own: List[float] = []
    stack: List[int] = []
    for i, (_, s, e, _) in enumerate(spans):
        while stack and spans[stack[-1]][2] <= s:
            stack.pop()
        while stack and spans[stack[-1]][2] < e:   # overlaps, not nested
            stack.pop()
        parent = stack[-1] if stack else None
        parents.append(parent)
        own.append(e - s)
        if parent is not None:
            own[parent] -= e - s
        stack.append(i)
    return parents, [max(x, 0.0) for x in own]


def self_times(spans: Sequence[Span]) -> Dict[str, List[float]]:
    """Self seconds of the program's spans by name, one entry per span."""
    prog = program_spans(spans)
    _, own = nesting(prog)
    out: Dict[str, List[float]] = {}
    for (name, *_), secs in zip(prog, own):
        out.setdefault(name, []).append(secs)
    return out


def steps(spans: Sequence[Span], step: str) -> List[Span]:
    return [s for s in spans if s[0] == step]


def per_step(spans: Sequence[Span], names: Sequence[str], step: str,
             own: bool = False) -> List[float]:
    """For each whole ``step`` span, the summed seconds (durations, or
    self times with ``own``) of the spans named ``names`` inside it. A
    step that holds none counts 0; spans outside every step (the edges
    of the traced window) are dropped."""
    prog = program_spans(spans)
    parents, self_s = nesting(prog)
    totals: Dict[int, float] = {i: 0.0 for i, s in enumerate(prog)
                                if s[0] == step}
    for i, (name, s, e, _) in enumerate(prog):
        if name not in names:
            continue
        root = i
        while root is not None and prog[root][0] != step:
            root = parents[root]
        if root is not None:
            totals[root] += self_s[i] if own else e - s
    return [totals[i] for i in sorted(totals)]


def has(spans: Sequence[Span], name: str) -> bool:
    return any(s[0] == name for s in spans)


# -------------------------------------------------------- request lifecycle

def requests(spans: Sequence[Span]) -> Dict[int, Dict[str, Any]]:
    """The ``serve_req_*`` marks grouped by ``rid``: the time of the
    first ``submit``, ``admit``, ``first_token`` and ``finish`` mark seen
    for the request, its count of ``preempt`` marks, and the marks' other
    arguments. A request that was submitted or admitted before the traced
    window simply lacks that key."""
    out: Dict[int, Dict[str, Any]] = {}
    for name, start, _, args in spans:
        if not name.startswith("serve_req_") or "rid" not in args:
            continue
        what = name[len("serve_req_"):]
        req = out.setdefault(int(args["rid"]), {"preempts": 0})
        if what == "preempt":
            req["preempts"] += 1
        elif what not in req:
            req[what] = start
            req.update({k: v for k, v in args.items() if k != "rid"})
    return out


def request_intervals(spans: Sequence[Span], a: str, b: str) -> List[float]:
    """Seconds from mark ``a`` to mark ``b`` for every request that has
    both inside the traced window."""
    return [r[b] - r[a] for r in requests(spans).values()
            if a in r and b in r and r[b] >= r[a]]


# ------------------------------------------------------------------ KV read

def kv_reads(spans: Sequence[Span]) -> Optional[Dict[str, float]]:
    """Sums over the window's ``serve_decode`` spans of their arguments:
    ``slots`` (running slots, one token handed out each), ``live_tokens``
    (tokens those slots hold) and ``read_tokens`` (columns the step's KV
    gathers read). None where no such span carries them."""
    rows = [a for n, _, _, a in spans
            if n == "serve_decode" and "read_tokens" in a]
    if not rows:
        return None
    return {k: float(sum(int(a[k]) for a in rows))
            for k in ("slots", "live_tokens", "read_tokens")}


def kv_bytes_per_token(config: Dict) -> int:
    """Bytes one cached token takes: K and V, every layer, every KV head,
    in the engine's cache dtype."""
    head_dim = int(config.get("head_dim") or
                   config["hidden_size"] // config["num_attention_heads"])
    width = {"bfloat16": 2, "float16": 2, "float32": 4}[
        config["serving"]["dtype"]]
    return (2 * int(config["num_hidden_layers"])
            * int(config["num_key_value_heads"]) * head_dim * width)


# ------------------------------------------------------------ device scopes

_OP_NAME = re.compile(r'op_name="([^"]*)"')
_INSTR = re.compile(r"^\s*(ROOT\s+)?%?([\w.\-]+)\s*=")


def _names_scope(op_name: str, scope: str) -> bool:
    """``scope`` is one frame of the name stack (``a/scope/b``,
    ``jvp(scope)``), not a part of another frame's name."""
    return re.search(rf"(^|[/(]){scope}([/)]|$)", op_name) is not None


def classify(op_name: Optional[str]) -> str:
    """A device operation's scope from the ``op_name`` JAX wrote for it,
    in this order of precedence: ``optimizer`` and ``metrics`` (the
    trainer's named scopes ``optimizer`` and ``step_metrics``), ``remat``
    (the forward pass computed again inside the backward pass: JAX's
    ``rematted_computation`` frame), ``backward`` (``transpose(...)``),
    ``forward`` (``jvp(...)``); anything else, or no name, is
    ``unscoped``. A fusion carries its root's name."""
    if not op_name:
        return "unscoped"
    if _names_scope(op_name, "optimizer"):
        return "optimizer"
    if _names_scope(op_name, "step_metrics"):
        return "metrics"
    if "rematted_computation" in op_name:
        return "remat"
    if "transpose(" in op_name:
        return "backward"
    if "jvp(" in op_name:
        return "forward"
    return "unscoped"


def op_name_of(event_text: str) -> Optional[str]:
    """``op_name`` where the device event's own text carries it."""
    m = _OP_NAME.search(event_text)
    return m.group(1) if m else None


def instruction_name(event_text: str) -> str:
    """The HLO instruction's name from a device event's text
    (``%fusion.5 = ...`` -> ``fusion.5``); other text stays as is."""
    m = _INSTR.match(event_text)
    return m.group(2) if m else event_text


def scope_seconds(ops: Sequence[Tuple[str, float, float]],
                  runs: Sequence[Interval],
                  scopes: Optional[Dict[str, str]] = None
                  ) -> List[Dict[str, float]]:
    """Per whole program execution in ``runs``: device-busy seconds of
    the leaf operations inside it, by scope. A container (``while``,
    ``call``) is never counted, only what runs inside it. ``scopes`` maps
    an instruction's name to its ``op_name`` for traces whose events do
    not carry it."""
    out: List[Dict[str, float]] = []
    leaves = xplane.leaves(ops)
    j = 0
    for lo, hi in sorted(runs):
        acc = {k: [] for k in SCOPES}
        while j < len(leaves) and leaves[j][1] < lo:
            j += 1
        k = j
        while k < len(leaves) and leaves[k][1] < hi:
            text, s, e = leaves[k]
            name = op_name_of(text)
            if name is None and scopes is not None:
                name = scopes.get(instruction_name(text))
            acc[classify(name)].append((s, min(e, hi)))
            k += 1
        out.append({key: xplane.total(xplane.union(iv))
                    for key, iv in acc.items()})
    return out


def scope_map(ctx) -> Optional[Dict[str, str]]:
    """Instruction name -> ``op_name`` of the cell's step program, from
    the program's own record of what it compiled; kept beside the trace
    so ``spans_report.py`` can read it later. None where the program
    keeps no such record."""
    try:
        from dla_tpu.telemetry.xla_introspect import compiled_scopes
    except ImportError:
        return None
    found: Dict[str, str] = {}
    for pattern in ctx.programs.values():
        found.update(compiled_scopes(pattern) or {})
    if not found:
        return None
    (trace_dir(ctx.cell["name"]).parent / "scopes.json").write_text(
        json.dumps(found))
    return found


def saved_scope_map(cell_name: str) -> Optional[Dict[str, str]]:
    path = trace_dir(cell_name).parent / "scopes.json"
    return json.loads(path.read_text()) if path.is_file() else None


_SCOPE_ROWS: Dict[str, Optional[List[Dict[str, float]]]] = {}


def scope_median_ms(ctx, scope: str) -> Optional[float]:
    """Median over the traced window's whole train-step executions of one
    scope's device-busy milliseconds, first device. The split is made
    once per run and serves every scope's reader."""
    cell = ctx.cell["name"]
    if cell not in _SCOPE_ROWS:
        _SCOPE_ROWS[cell] = _scope_rows(ctx)
    rows = _SCOPE_ROWS[cell]
    return 1e3 * st.median([r[scope] for r in rows]) if rows else None


def _scope_rows(ctx) -> Optional[List[Dict[str, float]]]:
    trace = for_context(ctx)
    if trace is None or not trace.ops:
        return None
    rx = re.compile(ctx.programs["train_step"])
    runs = [(s, e) for n, s, e in trace.modules if rx.search(n)
            and s >= ctx.trace_window[0] and e <= ctx.trace_window[1]]
    if not runs:
        return None
    carried = any(op_name_of(t) for t, _, _ in trace.ops[:64])
    scopes = None if carried else scope_map(ctx)
    if not carried and scopes is None:
        return None
    return scope_seconds(trace.ops, runs, scopes)


# ---------------------------------------------------------------- idle time

def innermost_cover(spans: Sequence[Span]) -> List[Tuple[float, float, str]]:
    """The program's spans of one thread as consecutive pieces
    ``(start, end, name)``, each named by the innermost span open in it.
    Time no program span covers is left out."""
    out: List[Tuple[float, float, str]] = []
    stack: List[Span] = []        # open spans, innermost last
    t = 0.0                       # pieces are emitted up to here

    def close(upto: float) -> None:
        nonlocal t
        while stack and stack[-1][2] <= upto:
            name, _, end, _ = stack.pop()
            if end > t:
                out.append((t, end, name))
                t = end

    for span in program_spans(spans):
        close(span[1])
        if stack and span[1] > t:
            out.append((t, span[1], stack[-1][0]))
        t = span[1]
        stack.append(span)
    close(float("inf"))
    return out


def idle_by_span(spans: Sequence[Span], idle: Sequence[Interval]
                 ) -> Dict[str, float]:
    """The device's idle seconds by the innermost program span open at
    the time: each idle interval is cut at the spans' boundaries, so a
    gap that runs across three spans is shared between them. ``outside``
    collects what no program span covers."""
    cover = innermost_cover(spans)
    acc: Dict[str, float] = {}
    j = 0
    for a, b in sorted(idle):
        while j < len(cover) and cover[j][1] <= a:
            j += 1
        k, covered = j, 0.0
        while k < len(cover) and cover[k][0] < b:
            part = min(b, cover[k][1]) - max(a, cover[k][0])
            if part > 0:
                acc[cover[k][2]] = acc.get(cover[k][2], 0.0) + part
                covered += part
            k += 1
        if b - a > covered:
            acc["outside"] = acc.get("outside", 0.0) + (b - a - covered)
    return acc


def device_idle(ops: Sequence[Tuple[str, float, float]],
                window: Interval) -> List[Interval]:
    return xplane.subtract([window], xplane.clip(xplane.busy(ops), *window))


# ----------------------------------------------------------- reader helpers

def median_ms(values: Sequence[float]) -> Optional[float]:
    return 1e3 * st.median(values) if values else None


def span_median_ms(ctx, name: str) -> Optional[float]:
    """Median duration of the traced run's spans named ``name``."""
    trace = for_context(ctx)
    if trace is None:
        return None
    return median_ms([e - s for n, s, e, _ in trace.host if n == name])


def request_median_ms(ctx, a: str, b: str) -> Optional[float]:
    """Median time from mark ``a`` to mark ``b`` over the traced run's
    requests that have both."""
    trace = for_context(ctx)
    if trace is None:
        return None
    return median_ms(request_intervals(trace.host, a, b))
