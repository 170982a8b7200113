"""Device time of ``jax.named_scope`` frames inside one of a serving cell's
jitted programs: ``decode_scopes.decode_scope_ms`` for any program of the
driver's ``PROGRAMS`` (the prefill chunk above all). The TPU's op events
carry no ``op_name``, so an instruction's scope is joined from the
program's own record of what it compiled
(``xla_introspect.compiled_scopes``), per program: instruction names
(``fusion.5``) repeat between programs. Returns None, and raises nothing,
where the program keeps no such record or has no such scope (a parent of
the PR that named it)."""
from __future__ import annotations

import re
from typing import Dict, List, Optional, Sequence

from perfbench.lib import spans, xplane
from perfbench.lib import stats as st
from perfbench.lib.decode_scopes import _in_scope

_MAPS: Dict[str, Optional[Dict[str, str]]] = {}


def scope_map(ctx, program: str) -> Optional[Dict[str, str]]:
    """instruction name -> ``op_name`` of the program ``PROGRAMS`` calls
    ``program``."""
    pattern = ctx.programs.get(program)
    if pattern is None:
        return None
    if pattern not in _MAPS:
        try:
            from dla_tpu.telemetry.xla_introspect import compiled_scopes
            _MAPS[pattern] = compiled_scopes(pattern) or None
        except ImportError:
            _MAPS[pattern] = None
    return _MAPS[pattern]


def scope_runs_ms(ctx, program: str, frames: Sequence[str]
                  ) -> Optional[List[float]]:
    """For each whole execution of ``program`` inside the traced window,
    the device-busy milliseconds of the leaf operations whose ``op_name``
    holds one of ``frames``, the first of which is the program's own scope
    and has to be there; first device."""
    trace = spans.for_context(ctx)
    scopes = scope_map(ctx, program)
    if trace is None or not trace.ops or not scopes:
        return None
    if not any(_in_scope(op, frames[0]) for op in scopes.values()):
        return None
    named = {name for name, op in scopes.items()
             if any(_in_scope(op, f) for f in frames)}
    rx = re.compile(ctx.programs[program])
    runs = sorted((s, e) for n, s, e in trace.modules if rx.search(n)
                  and s >= ctx.trace_window[0] and e <= ctx.trace_window[1])
    if not runs:
        return None
    leaves = [(s, e) for text, s, e in xplane.leaves(trace.ops)
              if spans.instruction_name(text) in named]
    per_run, j = [], 0
    for lo, hi in runs:
        while j < len(leaves) and leaves[j][0] < lo:
            j += 1
        k = j
        while k < len(leaves) and leaves[k][0] < hi:
            k += 1
        per_run.append(1e3 * xplane.total(xplane.union(
            (s, min(e, hi)) for s, e in leaves[j:k])))
        j = k
    return per_run


def scope_ms(ctx, program: str, frames: Sequence[str]) -> Optional[float]:
    """Median of :func:`scope_runs_ms`."""
    runs = scope_runs_ms(ctx, program, frames)
    return st.median(runs) if runs else None


def chunk_span_mean(ctx, arg: str) -> Optional[float]:
    """Mean over the traced window's ``serve_prefill_chunk`` spans of one
    of their integer arguments (``nvalid``, ``context``); None where no
    span carries it."""
    trace = spans.for_context(ctx)
    if trace is None:
        return None
    values = [int(a[arg]) for n, _, _, a in trace.host
              if n == "serve_prefill_chunk" and arg in a]
    return sum(values) / len(values) if values else None
