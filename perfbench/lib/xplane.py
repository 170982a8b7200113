"""Reduction of a profiler trace (``.xplane.pb``) to numbers.

Two steps. :func:`load` turns the file into plain tuples with nothing but
``jax.profiler.ProfileData``. Everything after that is arithmetic on
those tuples, so the tests check it on a synthetic trace without a chip.

Vocabulary: an event is ``(name, start_s, end_s)``. A device's *ops* are
the events of its ``XLA Ops`` line, its *modules* those of ``XLA
Modules`` (one event per execution of a jitted program). *Host* events
are the TraceMe events of the host threads, among them the program's
``TraceAnnotation`` / ``StepTraceAnnotation`` names.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

from perfbench.lib import stats

Event = Tuple[str, float, float]
Interval = Tuple[float, float]

#: HLO instruction names that move data between chips. On the TPU an
#: asynchronous collective shows as a short ``-start`` and a ``-done``
#: that lasts as long as the device waits for the transfer.
COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|all-to-all|"
    r"collective-permute|collective-broadcast|send|recv)")


@dataclasses.dataclass
class DeviceTrace:
    name: str
    ops: List[Event]
    modules: List[Event]


@dataclasses.dataclass
class Trace:
    devices: List[DeviceTrace]
    host: Dict[str, List[Event]]      # thread line name -> events


def find_xplane(trace_dir: str) -> Optional[str]:
    hits = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return hits[-1] if hits else None


def load(path: str) -> Trace:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    devices: List[DeviceTrace] = []
    host: Dict[str, List[Event]] = {}
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            ops: List[Event] = []
            modules: List[Event] = []
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops = _events(line)
                elif line.name == "XLA Modules":
                    modules = _events(line)
            devices.append(DeviceTrace(plane.name, ops, modules))
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                evs = _events(line)
                if evs:
                    host[line.name] = evs
    devices.sort(key=lambda d: d.name)
    return Trace(devices, host)


_HLO = re.compile(r"^%(?P<name>[^ ]+) = (?P<rest>.*)$", re.S)
_SHAPE = re.compile(r"[a-z]+[0-9]*\[[0-9,]*\]")
_TARGET = re.compile(r'custom_call_target="([^"]+)"')


def short_name(text: str) -> str:
    """A device event's name as the reduction uses it. The TPU's op line
    names an event by its whole HLO instruction (``%fusion.5 = pred[768000]
    {...} fusion(...)``): keep the instruction's name and its first output
    shape, and for a custom call its target. Anything else stays as is."""
    m = _HLO.match(text)
    if not m:
        return text
    shape = _SHAPE.search(m.group("rest"))
    target = _TARGET.search(m.group("rest"))
    return " ".join(x for x in (
        m.group("name"), shape.group(0) if shape else "",
        target.group(1) if target else "") if x)


def _events(line) -> List[Event]:
    out = [(short_name(ev.name), ev.start_ns * 1e-9,
            (ev.start_ns + ev.duration_ns) * 1e-9) for ev in line.events]
    out.sort(key=lambda e: (e[1], -e[2]))
    return out


# ------------------------------------------------------------- intervals

def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Merged, sorted, non-overlapping intervals."""
    out: List[Interval] = []
    for a, b in sorted(i for i in intervals if i[1] > i[0]):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def clip(merged: Sequence[Interval], t0: float, t1: float) -> List[Interval]:
    return [(max(a, t0), min(b, t1)) for a, b in merged
            if min(b, t1) > max(a, t0)]


def total(intervals: Iterable[Interval]) -> float:
    return sum(b - a for a, b in intervals)


def subtract(a: Sequence[Interval], b: Sequence[Interval]) -> List[Interval]:
    """The parts of merged intervals ``a`` that merged ``b`` leaves bare."""
    out: List[Interval] = []
    j = 0
    for lo, hi in a:
        cur = lo
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < hi:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < hi:
            out.append((cur, hi))
    return out


def busy(ops: Sequence[Event]) -> List[Interval]:
    """Union of the intervals in which an operation ran."""
    return union((s, e) for _, s, e in ops)


def window_of(trace: Trace) -> Interval:
    """First op start to last op end over all devices: the traced part
    of the run as the devices saw it."""
    starts = [d.ops[0][1] for d in trace.devices if d.ops]
    ends = [max(e for _, _, e in d.ops) for d in trace.devices if d.ops]
    if not starts:
        raise ValueError("the trace holds no device operation")
    return min(starts), max(ends)


def busy_seconds(trace: Trace, window: Interval) -> float:
    """Seconds an operation ran, averaged over the devices."""
    per_device = [total(clip(busy(d.ops), *window)) for d in trace.devices]
    return sum(per_device) / len(per_device)


def idle_share(trace: Trace, window: Interval) -> float:
    return 1.0 - busy_seconds(trace, window) / (window[1] - window[0])


# ------------------------------------------------------------- operations

def self_times(ops: Sequence[Event]) -> Dict[str, float]:
    """Seconds per operation name, a container (``while``, ``call``)
    counting only what its children leave bare. ``ops`` sorted by start,
    longer first on ties."""
    out: Dict[str, float] = {}
    stack: List[List] = []        # [name, end, self seconds]

    def close(upto: float) -> None:
        while stack and stack[-1][1] <= upto:
            name, _, own = stack.pop()
            out[name] = out.get(name, 0.0) + max(own, 0.0)

    for name, s, e in ops:
        close(s)
        if stack:
            stack[-1][2] -= min(e, stack[-1][1]) - s
        stack.append([name, e, e - s])
    close(float("inf"))
    return out


def leaves(ops: Sequence[Event]) -> List[Event]:
    """Operations that hold no other operation."""
    out: List[Event] = []
    for i, (name, s, e) in enumerate(ops):
        nxt = ops[i + 1] if i + 1 < len(ops) else None
        if nxt is None or nxt[1] >= e:
            out.append((name, s, e))
    return out


def op_label(name: str) -> str:
    """A trace's op name as a name the ledger can hold."""
    return re.sub(r"[^A-Za-z0-9_.-]+", "_", name).strip("_")[:64]


def top_ops(trace: Trace, window: Interval, n: int = 10
            ) -> List[Tuple[str, float]]:
    """The operations that took most device time (self time, mean over
    devices), for the result line's ``breakdown``."""
    acc: Dict[str, float] = {}
    for d in trace.devices:
        inside = [(nm, max(s, window[0]), min(e, window[1]))
                  for nm, s, e in d.ops if e > window[0] and s < window[1]]
        for name, secs in self_times(inside).items():
            acc[name] = acc.get(name, 0.0) + secs / len(trace.devices)
    ranked = sorted(acc.items(), key=lambda kv: -kv[1])[:n]
    return [(op_label(k), v) for k, v in ranked]


def name_seconds(trace: Trace, window: Interval, pattern: str) -> float:
    """Seconds of the leaf operations whose name matches, mean over
    devices."""
    rx = re.compile(pattern)
    per_device = []
    for d in trace.devices:
        hit = union((s, e) for nm, s, e in leaves(d.ops) if rx.search(nm))
        per_device.append(total(clip(hit, *window)))
    return sum(per_device) / len(per_device)


# --------------------------------------------------------------- programs

def module_runs(device: DeviceTrace, pattern: str) -> List[Interval]:
    rx = re.compile(pattern)
    return [(s, e) for nm, s, e in device.modules if rx.search(nm)]


def module_busy_seconds(device: DeviceTrace, pattern: str,
                        window: Interval) -> List[float]:
    """Device-busy seconds inside each whole execution of the programs
    whose module name matches, for executions inside the window."""
    merged = busy(device.ops)
    return [total(clip(merged, s, e)) for s, e in module_runs(device, pattern)
            if s >= window[0] and e <= window[1]]


def program_busy_median(trace: Trace, window: Interval, pattern: str
                        ) -> Optional[float]:
    """Median of :func:`module_busy_seconds` on the first device, or None
    where the program did not run whole inside the window."""
    runs = module_busy_seconds(trace.devices[0], pattern, window)
    return stats.median(runs) if runs else None


def gaps_after(device: DeviceTrace, pattern: str, window: Interval,
               then: Optional[str] = None) -> List[float]:
    """For each execution of a program matching ``pattern``, the seconds
    from its end to the start of the next execution of a program matching
    ``then`` (default: the same pattern). With ``then`` naming the step
    programs it is the host's share of a step as the device sees it; the
    small programs of the host loop's own eager operations run inside it
    and count as the host's."""
    first = re.compile(pattern)
    nxt = re.compile(then or pattern)
    runs = sorted((s, e, nm) for nm, s, e in device.modules
                  if first.search(nm) or nxt.search(nm))
    out: List[float] = []
    for (s, e, nm), (s2, e2, nm2) in zip(runs, runs[1:]):
        if (first.search(nm) and nxt.search(nm2)
                and s >= window[0] and e2 <= window[1]):
            out.append(max(s2 - e, 0.0))
    return out


# ------------------------------------------------------------ collectives

def collective_exposed_seconds(trace: Trace, window: Interval) -> float:
    """Seconds in which a collective ran on a device and no compute did,
    mean over devices. Leaves only, so a ``while`` that holds both does
    not hide either."""
    per_device = []
    for d in trace.devices:
        ops = leaves(d.ops)
        coll = union((s, e) for nm, s, e in ops if COLLECTIVE.match(nm))
        comp = union((s, e) for nm, s, e in ops if not COLLECTIVE.match(nm))
        per_device.append(total(clip(subtract(coll, comp), *window)))
    return sum(per_device) / len(per_device)


# -------------------------------------------------------------- idle gaps

def main_host_line(trace: Trace, annotations: Sequence[str]) -> List[Event]:
    """The host thread that carries the program's annotations."""
    best: List[Event] = []
    most = 0
    for events in trace.host.values():
        n = sum(1 for nm, _, _ in events if nm in annotations)
        if n > most:
            best, most = events, n
    return best


def attribute_gaps(trace: Trace, window: Interval,
                   annotations: Sequence[str], n: int = 10
                   ) -> List[Tuple[str, float]]:
    """The device's idle time by what the host was doing: each gap of the
    first device goes to the innermost program annotation and the
    innermost other host call that cover its middle."""
    device = trace.devices[0]
    idle = subtract([window], clip(busy(device.ops), *window))
    host = main_host_line(trace, annotations)
    acc: Dict[str, float] = {}
    open_: List[Event] = []       # host events open at the sweep point
    i = 0
    for a, b in idle:
        mid = 0.5 * (a + b)
        while i < len(host) and host[i][1] <= mid:
            while open_ and open_[-1][2] <= host[i][1]:
                open_.pop()
            open_.append(host[i])
            i += 1
        covering = [nm for nm, _, e in open_ if e > mid]
        note = [nm for nm in covering if nm in annotations]
        call = [nm for nm in covering
                if nm not in annotations and not nm.startswith("$")]
        label = op_label("___".join(
            ([note[-1]] if note else ["outside_annotations"])
            + ([call[-1]] if call else [])))
        acc[label] = acc.get(label, 0.0) + (b - a)
    return sorted(acc.items(), key=lambda kv: -kv[1])[:n]
