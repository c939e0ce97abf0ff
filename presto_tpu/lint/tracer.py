"""Tracer-hygiene rules: Python-level inspection of traced values.

A ``@jax.jit``-traced function sees abstract tracers, not arrays.
``bool()``/``float()``/``if`` on a traced value raises a
ConcretizationTypeError — but only when that code path is actually
traced, so a branch for a rare query shape ships broken. ``np.*`` on a
traced value silently falls back to host transfer + concretization.
Unhashable static arguments fail at call time; mutable ones force a
retrace per call (wrong-numbers-not-stack-traces territory, the failure
mode Tailwind-style offload frameworks call out).

Reachability: jit roots are functions wrapped by ``jax.jit`` (decorator
or call form) plus callbacks handed to ``lax.scan``/``while_loop``/
``cond``/``fori_loop``/``vmap``/``shard_map`` (those always trace their
operand). The rule follows calls from the roots through the scoped
modules — plain calls, imported-module attribute calls (``OP.f()``),
and same-module method calls; a ``getattr(self, ...)`` computed
dispatch marks the whole class reachable (the PlanInterpreter
pattern). Host-side driver code in the same files (compile loops,
result transfer) is correctly outside this set.

"Traced value" is detected syntactically: an expression containing a
``jnp.*`` / ``jax.lax.*`` / ``jax.nn.*`` call (minus the dtype-query
functions, which return static metadata). Trace-time-static host work —
dictionary transforms with real numpy, shape math on Python ints — is
deliberately not flagged; that asymmetry is what keeps the rule
enforceable at zero findings.
"""

from __future__ import annotations

import ast
from typing import Iterator

from presto_tpu.lint.core import (Finding, Project, SourceModule,
                                  qual_name, rule, walk_functions)

# directories whose functions run (transitively) under jax tracing
TRACE_SCOPES = (
    "presto_tpu/ops/",
    "presto_tpu/exec/",
    "presto_tpu/expr/",
    # the shard_map path is traced end to end as well
    "presto_tpu/parallel/executor.py",
    "presto_tpu/parallel/exchange.py",
)

# jnp/lax functions that return static metadata, not traced arrays
_STATIC_JNP = {"issubdtype", "iinfo", "finfo", "result_type",
               "promote_types", "can_cast", "dtype", "ndim", "shape"}

_JIT_NAMES = {"jax.jit", "jax.pjit"}
_TRACING_HOFS = {
    "jax.lax.scan", "jax.lax.while_loop", "jax.lax.cond",
    "jax.lax.fori_loop", "jax.lax.switch", "jax.lax.associative_scan",
    "jax.lax.map", "jax.vmap", "jax.pmap", "jax.shard_map",
    "jax.grad", "jax.value_and_grad", "jax.checkpoint",
    "jax.experimental.shard_map.shard_map",
}
_MUTABLE_LITERALS = (ast.List, ast.Dict, ast.Set, ast.ListComp,
                     ast.DictComp, ast.SetComp)


def _resolve(qname: str | None, aliases: dict[str, str]) -> str | None:
    """Expand the leading component of a dotted name through the
    module's imports: ``jnp.where`` -> ``jax.numpy.where``."""
    if qname is None:
        return None
    head, _, rest = qname.partition(".")
    head = aliases.get(head, head)
    return f"{head}.{rest}" if rest else head


def _is_traced_producer(call_qname: str | None) -> bool:
    if call_qname is None:
        return False
    if call_qname.startswith(("jax.numpy.", "jax.lax.", "jax.nn.",
                              "jax.scipy.")):
        return call_qname.rsplit(".", 1)[1] not in _STATIC_JNP
    return False


def _contains_traced(node: ast.AST, aliases: dict[str, str]) -> bool:
    for sub in ast.walk(node):
        if isinstance(sub, ast.Call):
            if _is_traced_producer(
                    _resolve(qual_name(sub.func), aliases)):
                return True
    return False


class _FnUnit:
    def __init__(self, mod: SourceModule, path: tuple[str, ...],
                 node: ast.FunctionDef):
        self.mod = mod
        self.path = path
        self.node = node
        self.name = node.name

    @property
    def key(self) -> tuple:
        return (self.mod.relpath, self.path)

    def own_statements(self) -> Iterator[ast.AST]:
        """Walk the body excluding nested function/class subtrees
        (those are separate units)."""
        stack: list[ast.AST] = list(self.node.body)
        while stack:
            n = stack.pop()
            yield n
            for child in ast.iter_child_nodes(n):
                if not isinstance(child, (ast.FunctionDef,
                                          ast.AsyncFunctionDef,
                                          ast.ClassDef)):
                    stack.append(child)


def _collect_units(mods: list[SourceModule]
                   ) -> dict[tuple, _FnUnit]:
    units: dict[tuple, _FnUnit] = {}
    for mod in mods:
        own = getattr(mod, "_fn_units", None)
        if own is None:
            # cached on the SourceModule: the tracer family and the
            # tracekey rule scope overlapping directories, and the
            # function walk is the expensive half of graph building
            own = mod._fn_units = {
                (mod.relpath, path): _FnUnit(mod, path, fn)
                for path, fn in walk_functions(mod.tree)}
        units.update(own)
    return units


def _jit_static_names(call: ast.Call) -> list[str]:
    names: list[str] = []
    for kw in call.keywords:
        if kw.arg == "static_argnames":
            v = kw.value
            if isinstance(v, ast.Constant) and isinstance(v.value, str):
                names.append(v.value)
            elif isinstance(v, (ast.Tuple, ast.List)):
                names.extend(e.value for e in v.elts
                             if isinstance(e, ast.Constant)
                             and isinstance(e.value, str))
    return names


def _registry_decorators(mod: SourceModule) -> set[str]:
    """Module-local decorator factories that REGISTER the decorated
    function (store it into a dispatch table): their body, or a nested
    deco's body, assigns into a subscript (``TABLE[name] = fn``) or
    appends to a collection. Functions they decorate are invoked
    through the table by traced code, invisibly to the call graph — a
    plain wrapping decorator (timing, caching) does not qualify."""
    out: set[str] = set()
    for node in mod.tree.body:
        if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        for sub in ast.walk(node):
            if isinstance(sub, ast.Assign) and any(
                    isinstance(t, ast.Subscript) for t in sub.targets):
                out.add(node.name)
                break
            if isinstance(sub, ast.Call) and \
                    isinstance(sub.func, ast.Attribute) and \
                    sub.func.attr in ("append", "add", "setdefault",
                                      "register"):
                out.add(node.name)
                break
    return out


def _class_methods(mods: list[SourceModule]
                   ) -> dict[tuple[str, str], list[tuple]]:
    """(relpath, class name) -> method unit keys, from real ClassDefs."""
    out: dict[tuple[str, str], list[tuple]] = {}

    def visit(mod, node, path):
        for child in ast.iter_child_nodes(node):
            if isinstance(child, ast.ClassDef):
                methods = [
                    path + (child.name, m.name)
                    for m in child.body
                    if isinstance(m, (ast.FunctionDef,
                                      ast.AsyncFunctionDef))]
                out.setdefault((mod.relpath, child.name),
                               []).extend(methods)
                visit(mod, child, path + (child.name,))
            elif isinstance(child, (ast.FunctionDef,
                                    ast.AsyncFunctionDef)):
                visit(mod, child, path + (child.name,))
            else:
                visit(mod, child, path)

    for mod in mods:
        visit(mod, mod.tree, ())
    return out


def _find_roots(mods: list[SourceModule], units: dict[tuple, _FnUnit],
                alias_cache: dict[str, dict[str, str]]
                ) -> tuple[set[tuple], list[tuple]]:
    """(root unit keys, [(unit, static_argnames, anchor_call)]) — the
    second list carries static-argument info for jit'd functions."""
    roots: set[tuple] = set()
    statics: list[tuple] = []
    by_name: dict[tuple[str, str], list[_FnUnit]] = {}
    for u in units.values():
        by_name.setdefault((u.mod.relpath, u.name), []).append(u)

    def mark(mod: SourceModule, fname: str,
             static_names: list[str] | None = None,
             call: ast.Call | None = None) -> None:
        for u in by_name.get((mod.relpath, fname), []):
            roots.add(u.key)
            if static_names:
                statics.append((u, static_names, call))

    for mod in mods:
        aliases = alias_cache[mod.relpath]
        registry_decos = _registry_decorators(mod)
        for node in mod.walk():
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                for dec in node.decorator_list:
                    target = dec.func if isinstance(dec, ast.Call) \
                        else dec
                    rq = _resolve(qual_name(target), aliases)
                    # registry decorators (@scalar("add")-style): the
                    # decorated function is called through a dispatch
                    # table by traced code, invisibly to the call graph
                    if isinstance(dec, ast.Call) and \
                            isinstance(dec.func, ast.Name) and \
                            dec.func.id in registry_decos and \
                            rq not in ("functools.partial", "partial"):
                        mark(mod, node.name)
                    if rq in _JIT_NAMES:
                        mark(mod, node.name,
                             _jit_static_names(dec)
                             if isinstance(dec, ast.Call) else None,
                             dec if isinstance(dec, ast.Call) else None)
                    elif rq in ("functools.partial", "partial") and \
                            isinstance(dec, ast.Call) and dec.args:
                        inner = _resolve(qual_name(dec.args[0]),
                                         aliases)
                        if inner in _JIT_NAMES:
                            mark(mod, node.name,
                                 _jit_static_names(dec), dec)
            elif isinstance(node, ast.Call):
                rq = _resolve(qual_name(node.func), aliases)
                if rq in _JIT_NAMES:
                    for a in node.args[:1]:
                        if isinstance(a, ast.Name):
                            mark(mod, a.id, _jit_static_names(node),
                                 node)
                elif rq in _TRACING_HOFS:
                    for a in node.args:
                        if isinstance(a, ast.Name):
                            mark(mod, a.id)
    return roots, statics


class CallGraph:
    """The jit-reachability call graph over one scope set, shared by
    the tracer family and the trace-key provenance rule (tracekey.py):
    parsed function units, import aliases, name-resolution tables, and
    the edge relation. Obtain via :func:`call_graph` (cached per
    project so the two families never re-walk the tree)."""

    def __init__(self, mods: list[SourceModule]):
        self.mods = mods
        self.units = _collect_units(mods)
        self.alias_cache = {m.relpath: m.aliases for m in mods}
        self.mod_by_name: dict[str, SourceModule] = {}
        for m in mods:
            self.mod_by_name[m.modname] = m
            if m.modname.endswith(".__init__"):
                # a package's functions are addressed through the
                # package name (`from presto_tpu import lint`), never
                # through ``.__init__``
                self.mod_by_name[m.modname[:-len(".__init__")]] = m
        self.by_name: dict[tuple[str, str], list[_FnUnit]] = {}
        for u in self.units.values():
            self.by_name.setdefault((u.mod.relpath, u.name),
                                    []).append(u)
        self.classes = _class_methods(mods)

    def named(self, relpath: str, name: str) -> Iterator[_FnUnit]:
        """Units a bare name resolves to in ``relpath``: functions with
        that name, plus every method of a class with that name
        (instantiation makes the whole class live)."""
        yield from self.by_name.get((relpath, name), [])
        for key in self.classes.get((relpath, name), []):
            if (relpath, key) in self.units:
                yield self.units[(relpath, key)]

    def find_roots(self) -> tuple[set[tuple], list[tuple]]:
        return _find_roots(self.mods, self.units, self.alias_cache)

    def resolve_call(self, u: _FnUnit,
                     call: ast.Call) -> Iterator[_FnUnit]:
        """Units one Call node may enter (same resolution the edge
        relation uses; exposed for per-call-site analyses like the
        tracekey argument-taint fixpoint)."""
        aliases = self.alias_cache[u.mod.relpath]
        fn = call.func
        if isinstance(fn, ast.Name):
            if fn.id == "getattr":
                return
            tq = aliases.get(fn.id)
            if tq and "." in tq:
                tmod, _, tname = tq.rpartition(".")
                m = self.mod_by_name.get(tmod)
                if m is not None:
                    yield from self.named(m.relpath, tname)
                    return
            yield from self.named(u.mod.relpath, fn.id)
        elif isinstance(fn, ast.Attribute):
            base = _resolve(qual_name(fn.value), aliases)
            m = self.mod_by_name.get(base) if base else None
            if m is not None:
                yield from self.named(m.relpath, fn.attr)
            else:
                yield from self.named(u.mod.relpath, fn.attr)

    def edges(self, u: _FnUnit) -> Iterator[_FnUnit]:
        """Callees of one unit: plain and imported-module calls,
        same-module method calls by name, class instantiation (all
        methods), bare function references (callbacks passed as
        values), and getattr-computed self dispatch (all sibling
        methods)."""
        class_wide = False
        for stmt in u.own_statements():
            if isinstance(stmt, ast.Name) and \
                    isinstance(stmt.ctx, ast.Load):
                # bare reference: a callback handed to other code
                yield from self.by_name.get((u.mod.relpath, stmt.id),
                                            [])
                continue
            if not isinstance(stmt, ast.Call):
                continue
            fn = stmt.func
            if isinstance(fn, ast.Name) and fn.id == "getattr":
                # computed dispatch: getattr(self, ...) marks every
                # sibling method reachable (PlanInterpreter.run)
                if stmt.args and \
                        isinstance(stmt.args[0], ast.Name) and \
                        stmt.args[0].id == "self":
                    class_wide = True
                continue
            yield from self.resolve_call(u, stmt)
        if class_wide and len(u.path) >= 2:
            prefix = u.path[:-1]
            for other in self.units.values():
                if other.mod is u.mod and len(other.path) == \
                        len(u.path) and other.path[:-1] == prefix:
                    yield other

    def reachable(self, roots: set[tuple]) -> set[tuple]:
        """BFS over the call graph from ``roots``."""
        seen = set(roots)
        frontier = [self.units[k] for k in roots if k in self.units]
        while frontier:
            u = frontier.pop()
            for tgt in self.edges(u):
                if tgt.key not in seen:
                    seen.add(tgt.key)
                    frontier.append(tgt)
        return seen


def call_graph(project: Project,
               scopes: tuple[str, ...]) -> CallGraph:
    """The CallGraph for ``scopes``, cached on the project instance
    (like locks.class_analyses: the data dies with the run instead of
    pinning the parsed package in a module global)."""
    cache = getattr(project, "_callgraph_cache", None)
    if cache is None:
        cache = project._callgraph_cache = {}
    graph = cache.get(scopes)
    if graph is None:
        graph = cache[scopes] = CallGraph(project.in_scope(scopes))
    return graph


def _check_unit(u: _FnUnit, findings: list[Finding],
                aliases: dict[str, str]) -> None:
    def f(node: ast.AST, rule_name: str, msg: str) -> None:
        findings.append(Finding(rule_name, u.mod.relpath, node.lineno,
                                node.col_offset, msg))

    where = f"in jit-reachable `{'.'.join(u.path)}`"
    for node in u.own_statements():
        if isinstance(node, ast.Call):
            rq = _resolve(qual_name(node.func), aliases)
            if rq in ("bool", "int", "float", "complex") and \
                    node.args and _contains_traced(node.args[0],
                                                   aliases):
                f(node, "tracer-concretize",
                  f"{rq}() on a traced value {where} concretizes at "
                  "trace time (use jnp/lax ops or hoist to the host)")
            elif isinstance(node.func, ast.Attribute) and \
                    node.func.attr in ("item", "tolist") and \
                    _contains_traced(node.func.value, aliases):
                f(node, "tracer-concretize",
                  f".{node.func.attr}() on a traced value {where} "
                  "forces a device sync inside the trace")
            elif rq is not None and rq.startswith("numpy.") and \
                    any(_contains_traced(a, aliases)
                        for a in list(node.args)
                        + [kw.value for kw in node.keywords]):
                f(node, "tracer-numpy",
                  f"{rq.replace('numpy', 'np')}() applied to a traced "
                  f"value {where}: numpy concretizes tracers "
                  "(use the jnp equivalent)")
        elif isinstance(node, (ast.If, ast.While, ast.IfExp)):
            if _contains_traced(node.test, aliases):
                kind = {"If": "if", "While": "while",
                        "IfExp": "conditional expression"}[
                    type(node).__name__]
                f(node, "tracer-branch",
                  f"Python `{kind}` on a traced value {where}: "
                  "branches must be static at trace time "
                  "(use jnp.where / lax.cond)")
        elif isinstance(node, ast.Assert):
            if _contains_traced(node.test, aliases):
                f(node, "tracer-branch",
                  f"assert on a traced value {where} concretizes at "
                  "trace time")
        elif isinstance(node, ast.comprehension):
            for cond in node.ifs:
                if _contains_traced(cond, aliases):
                    f(cond, "tracer-branch",
                      f"comprehension filter on a traced value {where} "
                      "concretizes at trace time")


def _check_static_args(statics: list[tuple],
                       findings: list[Finding]) -> None:
    for u, static_names, call in statics:
        args = u.node.args
        params = [a.arg for a in args.posonlyargs + args.args
                  + args.kwonlyargs]
        pos = args.posonlyargs + args.args
        defaults: dict[str, ast.AST] = dict(zip(
            [a.arg for a in pos[len(pos) - len(args.defaults):]],
            args.defaults))
        defaults.update({a.arg: d for a, d in
                         zip(args.kwonlyargs, args.kw_defaults)
                         if d is not None})
        for name in static_names:
            if name not in params:
                findings.append(Finding(
                    "tracer-static-arg", u.mod.relpath,
                    (call or u.node).lineno,
                    (call or u.node).col_offset,
                    f"static_argnames names '{name}' which is not a "
                    f"parameter of `{u.name}`"))
                continue
            d = defaults.get(name)
            if d is not None and isinstance(d, _MUTABLE_LITERALS):
                findings.append(Finding(
                    "tracer-static-arg", u.mod.relpath, d.lineno,
                    d.col_offset,
                    f"static argument '{name}' of `{u.name}` has an "
                    "unhashable mutable default: jit static args must "
                    "hash (this raises at call time)"))
        # mutable defaults on TRACED params of a jit root force
        # cache-key churn when callers rebuild the default themselves
        for name, d in defaults.items():
            if name in static_names or d is None:
                continue
            if isinstance(d, _MUTABLE_LITERALS):
                findings.append(Finding(
                    "tracer-static-arg", u.mod.relpath, d.lineno,
                    d.col_offset,
                    f"mutable default for parameter '{name}' of "
                    f"jit-wrapped `{u.name}`: shared mutable state "
                    "inside a traced function is a retrace/aliasing "
                    "hazard"))


@rule("tracer-concretize")
def tracer_concretize(project: Project) -> list[Finding]:
    return _run_family(project, {"tracer-concretize"})


@rule("tracer-branch")
def tracer_branch(project: Project) -> list[Finding]:
    return _run_family(project, {"tracer-branch"})


@rule("tracer-numpy")
def tracer_numpy(project: Project) -> list[Finding]:
    return _run_family(project, {"tracer-numpy"})


@rule("tracer-static-arg")
def tracer_static_arg(project: Project) -> list[Finding]:
    return _run_family(project, {"tracer-static-arg"})


# [weakref to project, findings]: lets the four tracer rules share one
# reachability analysis within a run_lint call WITHOUT pinning the
# parsed package (full ASTs, tens of MB) after the run finishes
_family_cache: list = []


def _run_family(project: Project, keep: set[str]) -> list[Finding]:
    """All four tracer rules share one reachability analysis; compute
    once per project and filter."""
    import weakref
    if _family_cache and _family_cache[0]() is project:
        cached = _family_cache[1]
    else:
        # one CallGraph per (project, scopes) — module alias tables and
        # function units are cached on the modules themselves, so the
        # tracekey rule riding the same graph machinery pays nothing
        # extra for the shared directories
        graph = call_graph(project, TRACE_SCOPES)
        roots, statics = graph.find_roots()
        reach = graph.reachable(roots)
        cached = []
        for key in sorted(reach):
            u = graph.units.get(key)
            if u is not None:
                _check_unit(u, cached,
                            graph.alias_cache[u.mod.relpath])
        _check_static_args(statics, cached)
        _family_cache[:] = [weakref.ref(project), cached]
    return [f for f in cached if f.rule in keep]
