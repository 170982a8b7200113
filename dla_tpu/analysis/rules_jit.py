"""Rules over jit-traced function bodies: retrace hazards, trace-time
side effects, and donated-buffer misuse.

Why these are the first rules (arXiv:2204.06514's compile discipline):
a jitted step that silently retraces turns a 3 ms dispatch into a
multi-second compile *per step shape*; a ``print``/``time.time`` inside
a traced body runs exactly once at trace time and then lies forever; a
donated buffer read after the call aliases freed device memory. All
three are invisible in CPU unit tests and expensive on a v5e-256 pod.
"""
from __future__ import annotations

import ast
from typing import Iterator, List, Set

from dla_tpu.analysis.astutil import (
    ImportMap,
    JitSite,
    dotted,
    find_jit_sites,
    local_names,
)
from dla_tpu.analysis.core import Finding, Project, Rule, register

# ------------------------------------------------------------- retrace

#: Canonical callables with a shape-valued argument -> its positional
#: index (jax.random.split's shape is ``num`` at position 1; the key at
#: position 0 is traced by design).
_SHAPE_FNS = {
    "jax.numpy.zeros": 0, "jax.numpy.ones": 0, "jax.numpy.full": 0,
    "jax.numpy.empty": 0, "jax.numpy.arange": 0, "jax.numpy.linspace": 0,
    "jax.numpy.eye": 0, "numpy.zeros": 0, "numpy.ones": 0,
    "numpy.full": 0, "numpy.empty": 0, "numpy.arange": 0,
    "jax.lax.iota": 1, "jax.lax.broadcasted_iota": 1,
    "jax.random.split": 1,
}
#: Method names whose arguments are shapes.
_SHAPE_METHODS = {"reshape", "broadcast_to"}


def _names_in(node: ast.AST) -> Set[str]:
    return {n.id for n in ast.walk(node) if isinstance(n, ast.Name)
            and isinstance(n.ctx, ast.Load)}


def _is_none_check(test: ast.AST) -> bool:
    """``x is None`` / ``x is not None`` (and boolean combinations of
    them) — the one traced-arg control-flow idiom that is always safe,
    because tracers are never None."""
    if isinstance(test, ast.BoolOp):
        return all(_is_none_check(v) for v in test.values)
    if isinstance(test, ast.UnaryOp) and isinstance(test.op, ast.Not):
        return _is_none_check(test.operand)
    return (isinstance(test, ast.Compare)
            and all(isinstance(op, (ast.Is, ast.IsNot))
                    for op in test.ops))


@register
class RetraceHazardRule(Rule):
    name = "retrace-hazard"
    summary = ("python control flow / shape math / string building on "
               "traced jit arguments not covered by static_argnums")

    def run(self, project: Project) -> Iterator[Finding]:
        for sf in project.py_files():
            imports = sf.imports
            for site in sf.jit_sites:
                yield from self._check_site(sf.rel, site, imports)

    def _check_site(self, rel: str, site: JitSite, imports: ImportMap
                    ) -> Iterator[Finding]:
        traced = set(site.traced_params())
        if not traced:
            return
        fn = site.fn
        for node in ast.walk(fn):
            # (1) python branching on a traced value: trace error or a
            # silent retrace per value once wrapped in static fallbacks
            if isinstance(node, (ast.If, ast.While)):
                test = node.test
                if _is_none_check(test):
                    continue
                hits = sorted(_names_in(test) & traced)
                if hits:
                    yield Finding(
                        self.name, rel, node.lineno,
                        f"python `{'while' if isinstance(node, ast.While) else 'if'}` "
                        f"on traced argument(s) {', '.join(hits)} of jitted "
                        f"`{fn.name}` — mark static via static_argnums/"
                        f"static_argnames or use lax.cond/lax.select")
            elif isinstance(node, ast.Call):
                yield from self._check_shape_call(rel, fn, node, traced,
                                                 imports)
            # (2) f-strings / dict keys from traced values: str(tracer)
            # is baked at trace time (the collector stash bug class)
            elif isinstance(node, ast.FormattedValue):
                hits = sorted(_names_in(node.value) & traced)
                if hits:
                    yield Finding(
                        self.name, rel, node.lineno,
                        f"f-string interpolates traced argument(s) "
                        f"{', '.join(hits)} of jitted `{fn.name}` — the "
                        f"string is frozen at trace time")
            elif isinstance(node, ast.Dict):
                for key in node.keys:
                    if (isinstance(key, ast.Name)
                            and isinstance(key.ctx, ast.Load)
                            and key.id in traced):
                        yield Finding(
                            self.name, rel, key.lineno,
                            f"dict key `{key.id}` is a traced argument of "
                            f"jitted `{fn.name}` — tracer hash is a "
                            f"trace-time constant")

    def _check_shape_call(self, rel: str, fn: ast.FunctionDef,
                          node: ast.Call, traced: Set[str],
                          imports: ImportMap) -> Iterator[Finding]:
        canon = imports.canonical(node.func)
        shape_args: List[ast.AST] = []
        label = canon
        if canon in _SHAPE_FNS:
            idx = _SHAPE_FNS[canon]
            if len(node.args) > idx:
                shape_args = [node.args[idx]]
            for kw in node.keywords:
                if kw.arg in ("shape", "num", "dimension"):
                    shape_args.append(kw.value)
        elif (isinstance(node.func, ast.Attribute)
              and node.func.attr in _SHAPE_METHODS):
            shape_args = list(node.args)
            label = node.func.attr
        elif (isinstance(node.func, ast.Name)
              and node.func.id == "range"):
            shape_args = list(node.args)
            label = "range"
        for arg in shape_args:
            hits = set()
            if isinstance(arg, ast.Name) and arg.id in traced:
                hits = {arg.id}
            elif isinstance(arg, (ast.Tuple, ast.List)):
                hits = {e.id for e in arg.elts
                        if isinstance(e, ast.Name) and e.id in traced}
            if hits:
                yield Finding(
                    self.name, rel, node.lineno,
                    f"traced argument(s) {', '.join(sorted(hits))} of "
                    f"jitted `{fn.name}` used as a shape in `{label}` — "
                    f"shapes must be static (static_argnums or close "
                    f"over the python int)")


# -------------------------------------------------------- side effects

#: Canonical calls that execute once at trace time and never again.
_SIDE_EFFECT_CALLS = {
    "print": "runs once at trace time, then never again",
    "input": "blocks tracing; never runs on device",
    "open": "file I/O at trace time only",
    "time.time": "freezes a single trace-time timestamp into the graph",
    "time.perf_counter": "freezes a trace-time timestamp",
    "time.monotonic": "freezes a trace-time timestamp",
    "time.time_ns": "freezes a trace-time timestamp",
    "time.sleep": "sleeps at trace time only",
    "datetime.datetime.now": "freezes a trace-time timestamp",
    "datetime.datetime.utcnow": "freezes a trace-time timestamp",
}
#: Python-level RNG modules: one trace-time draw becomes a constant —
#: use jax.random with an explicit key instead.
_PY_RANDOM_PREFIXES = ("random.", "numpy.random.")
_MUTATING_METHODS = {"append", "extend", "add", "update", "insert",
                     "setdefault", "pop", "clear", "remove",
                     "appendleft", "popleft", "write"}


@register
class TraceSideEffectRule(Rule):
    name = "trace-side-effect"
    summary = ("host side effects (print/time/random/python-state "
               "mutation) inside jit-traced function bodies")

    def run(self, project: Project) -> Iterator[Finding]:
        for sf in project.py_files():
            imports = sf.imports
            for site in sf.jit_sites:
                yield from self._check_site(sf.rel, site, imports)

    def _check_site(self, rel: str, site: JitSite, imports: ImportMap
                    ) -> Iterator[Finding]:
        fn = site.fn
        locals_ = local_names(fn)
        for node in ast.walk(fn):
            if isinstance(node, (ast.Global, ast.Nonlocal)):
                yield Finding(
                    self.name, rel, node.lineno,
                    f"`{'global' if isinstance(node, ast.Global) else 'nonlocal'}` "
                    f"inside jitted `{fn.name}` — the write happens once "
                    f"at trace time (use the telemetry collector stash "
                    f"side channel if this is a metric)")
            elif isinstance(node, ast.Call):
                canon = imports.canonical(node.func)
                if canon in _SIDE_EFFECT_CALLS:
                    yield Finding(
                        self.name, rel, node.lineno,
                        f"`{canon}` inside jitted `{fn.name}` — "
                        f"{_SIDE_EFFECT_CALLS[canon]} (use jax.debug.print/"
                        f"callback for runtime effects)")
                elif canon and canon.startswith(_PY_RANDOM_PREFIXES):
                    yield Finding(
                        self.name, rel, node.lineno,
                        f"python RNG `{canon}` inside jitted `{fn.name}` "
                        f"— the draw happens once at trace time; thread a "
                        f"jax.random key instead")
                elif (isinstance(node.func, ast.Attribute)
                      and node.func.attr in _MUTATING_METHODS
                      and isinstance(node.func.value, ast.Name)
                      and isinstance(node.func.value.ctx, ast.Load)
                      and node.func.value.id not in locals_
                      and imports.canonical(node.func) == dotted(node.func)):
                    # bare-name receiver that is neither a local nor an
                    # import: a closed-over / module-level container
                    yield Finding(
                        self.name, rel, node.lineno,
                        f"`.{node.func.attr}()` mutates closed-over "
                        f"`{node.func.value.id}` inside jitted "
                        f"`{fn.name}` — trace-time-only python state "
                        f"mutation")
            elif isinstance(node, (ast.Assign, ast.AugAssign)):
                targets = (node.targets if isinstance(node, ast.Assign)
                           else [node.target])
                for t in targets:
                    if (isinstance(t, ast.Attribute)
                            and isinstance(t.value, ast.Name)
                            and t.value.id == "self"):
                        yield Finding(
                            self.name, rel, t.lineno,
                            f"assignment to `self.{t.attr}` inside jitted "
                            f"`{fn.name}` — object state mutates once at "
                            f"trace time, not per step")
                    elif (isinstance(t, ast.Subscript)
                          and isinstance(t.value, ast.Name)
                          and t.value.id not in locals_):
                        yield Finding(
                            self.name, rel, t.lineno,
                            f"subscript store into closed-over "
                            f"`{t.value.id}` inside jitted `{fn.name}` — "
                            f"trace-time-only python state mutation")


# ------------------------------------------------------------ donation

@register
class DonationMisuseRule(Rule):
    name = "donation-misuse"
    summary = ("arguments passed at donate_argnums positions read again "
               "after the jitted call (donated buffers are freed)")

    def run(self, project: Project) -> Iterator[Finding]:
        for sf in project.py_files():
            imports = sf.imports
            donating = self._donating_symbols(sf, imports)
            if donating:
                yield from self._check_calls(sf, donating)

    def _donating_symbols(self, sf, imports: ImportMap):
        """symbol-name -> donate positions, for every binding of a
        jit-with-donation callable in this module: decorated defs,
        ``x = jax.jit(f, donate_argnums=...)``, attribute targets
        (``self._step = jax.jit(...)``) tracked by attribute name, and
        zero-arg factory methods that return one of those."""
        tree = sf.tree
        donating = {}
        sites = sf.jit_sites
        site_by_call = {id(s.call): s for s in sites if s.call is not None}
        for site in sites:
            if not site.donate_positions:
                continue
            # decorated def: callable by its own name
            if site.call in site.fn.decorator_list:
                donating[site.fn.name] = site.donate_positions
        for node in ast.walk(tree):
            if not isinstance(node, ast.Assign):
                continue
            # ``x = jax.jit(..)`` or ``x = jax.jit(..) if cond else None``
            values = ([node.value.body, node.value.orelse]
                      if isinstance(node.value, ast.IfExp) else [node.value])
            for value in values:
                site = site_by_call.get(id(value))
                if site is None or not site.donate_positions:
                    continue
                for t in node.targets:
                    if isinstance(t, ast.Name):
                        donating[t.id] = site.donate_positions
                    elif isinstance(t, ast.Attribute):
                        donating[t.attr] = site.donate_positions
        # factory methods: "def compile_x(self): ... return <donating>"
        for node in ast.walk(tree):
            if not isinstance(node, ast.FunctionDef):
                continue
            for ret in ast.walk(node):
                if not isinstance(ret, ast.Return) or ret.value is None:
                    continue
                key = None
                if isinstance(ret.value, ast.Name):
                    key = ret.value.id
                elif (isinstance(ret.value, ast.Attribute)
                      and isinstance(ret.value.value, ast.Name)
                      and ret.value.value.id == "self"):
                    key = ret.value.attr
                if key in donating:
                    donating[node.name] = donating[key]
        return donating

    def _check_calls(self, sf, donating) -> Iterator[Finding]:
        for fn in [n for n in ast.walk(sf.tree)
                   if isinstance(n, ast.FunctionDef)]:
            # propagate factory results: y = self.compile_x()
            local_donating = dict(donating)
            for node in ast.walk(fn):
                if (isinstance(node, ast.Assign)
                        and isinstance(node.value, ast.Call)):
                    callee = node.value.func
                    key = (callee.attr if isinstance(callee, ast.Attribute)
                           else callee.id if isinstance(callee, ast.Name)
                           else None)
                    if key in donating and not node.value.args:
                        for t in node.targets:
                            if isinstance(t, ast.Name):
                                local_donating[t.id] = donating[key]
            yield from self._check_fn(sf, fn, local_donating)

    @staticmethod
    def _expr_key(node: ast.AST):
        """Stable key for a donated-arg expression we can track: a bare
        name or a dotted attribute chain on one (``self.state``,
        ``c.pools``, ``self.cache.pools``)."""
        attrs = []
        while isinstance(node, ast.Attribute):
            attrs.append(node.attr)
            node = node.value
        if isinstance(node, ast.Name):
            return ".".join([node.id] + attrs[::-1])
        return None

    def _check_fn(self, sf, fn: ast.FunctionDef, donating
                  ) -> Iterator[Finding]:
        # flatten statements in source order with their call / the names
        # they store, then scan forward from each donating call
        events = []     # (lineno, kind, payload)
        for node in ast.walk(fn):
            if isinstance(node, ast.Call):
                callee = node.func
                key = (callee.attr if isinstance(callee, ast.Attribute)
                       else callee.id if isinstance(callee, ast.Name)
                       else None)
                if key in donating:
                    events.append((node.lineno, "call", (node, key)))
            if isinstance(node, (ast.Name, ast.Attribute)) \
                    and isinstance(node.ctx, (ast.Load, ast.Store)):
                expr = self._expr_key(node)
                if expr is not None:
                    kind = ("load" if isinstance(node.ctx, ast.Load)
                            else "store")
                    events.append((node.lineno, kind, expr))
        events.sort(key=lambda e: e[0])

        assigns = {id(n.value): n for n in ast.walk(fn)
                   if isinstance(n, ast.Assign)}
        for lineno, kind, payload in events:
            if kind != "call":
                continue
            call, key = payload
            rebound: Set[str] = set()
            assign = assigns.get(id(call))
            if assign is not None:
                for t in assign.targets:
                    for sub in ast.walk(t):
                        k = self._expr_key(sub)
                        if k:
                            rebound.add(k)
            for pos in donating[key]:
                if pos >= len(call.args):
                    continue
                donated = self._expr_key(call.args[pos])
                if donated is None or donated in rebound:
                    continue
                for l2, k2, p2 in events:
                    if l2 <= lineno:
                        continue
                    if k2 == "store" and p2 == donated:
                        break
                    if k2 == "load" and p2 == donated:
                        yield Finding(
                            self.name, sf.rel, l2,
                            f"`{donated}` was donated to `{key}` at line "
                            f"{lineno} (donate_argnums position {pos}) "
                            f"but is read afterwards — donated buffers "
                            f"are invalidated; rebind the result instead")
                        break
