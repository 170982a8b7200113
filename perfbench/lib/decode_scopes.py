"""Device time of one ``jax.named_scope`` inside the jitted decode step.

The TPU's op events carry no ``op_name`` (PERF.md section 6), so an
instruction's scope is joined from the program's own record of what it
compiled (``xla_introspect.compiled_scopes``), as the ``train_*_device_ms``
readers do through ``spans.scope_map``, but for the decode program alone:
instruction names (``fusion.5``) repeat between programs, and a serving
cell runs two. Returns None, and raises nothing, where the program keeps
no such record or has no such scope (a parent of the PR that named it)."""
from __future__ import annotations

import re
from typing import Dict, Optional, Sequence

from perfbench.lib import spans, xplane
from perfbench.lib import stats as st

_MAPS: Dict[str, Optional[Dict[str, str]]] = {}


def _in_scope(op_name: str, scope: str) -> bool:
    """``scope`` is one whole frame of the name stack."""
    return re.search(rf"(^|[/(]){re.escape(scope)}([/)]|$)", op_name) \
        is not None


def _decode_scope_map(ctx) -> Optional[Dict[str, str]]:
    pattern = ctx.programs.get("decode")
    if pattern is None:
        return None
    if pattern not in _MAPS:
        try:
            from dla_tpu.telemetry.xla_introspect import compiled_scopes
            _MAPS[pattern] = compiled_scopes(pattern) or None
        except ImportError:
            _MAPS[pattern] = None
    return _MAPS[pattern]


def decode_scope_ms(ctx, frames: Sequence[str]) -> Optional[float]:
    """Median over the traced window's whole decode-step executions of
    the device-busy milliseconds of the leaf operations whose ``op_name``
    holds one of ``frames``, the first of which is the program's own
    scope and has to be there; first device."""
    trace = spans.for_context(ctx)
    scopes = _decode_scope_map(ctx)
    if trace is None or not trace.ops or not scopes:
        return None
    if not any(_in_scope(op, frames[0]) for op in scopes.values()):
        return None
    named = {name for name, op in scopes.items()
             if any(_in_scope(op, f) for f in frames)}
    rx = re.compile(ctx.programs["decode"])
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
        per_run.append(xplane.total(xplane.union(
            (s, min(e, hi)) for s, e in leaves[j:k])))
        j = k
    return 1e3 * st.median(per_run)
