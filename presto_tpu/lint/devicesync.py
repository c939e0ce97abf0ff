"""Device-boundary discipline: no hidden host<->device syncs.

A host-blocking device read in the middle of the execute path —
``.item()`` on a device scalar, ``np.asarray`` over a jit output,
``jax.device_get``, ``.block_until_ready()`` — serializes the
dispatch pipeline: every occurrence costs a device round-trip and
stalls the host until the device drains.
One stray ``.item()`` in a stage walk turns an async pipeline into a
lock-step crawl, and it benches fine on CPU where the transfer is a
memcpy (Tailwind's transfer/compute discipline is THE practical
accelerator-query bottleneck). The engine therefore has ONE designated
boundary — ``exec/hostsync.py`` (``fetch``/``fetch_int``/``wait``,
each batched and counted) — and this rule proves, whole-tree, that
every sync on the execute path goes through it.

The rule rides the shared ``lint/tracer.py`` ``CallGraph`` from the
execute-path roots (``exec/executor.prepare_plan``/``run_plan``,
``parallel/executor.execute_plan_distributed``, the serve/result
paths in ``server/``, ``parallel/coordinator``, ``parallel/worker``)
and asks, for every reachable call site: is this a host-blocking sync,
and is the value a DEVICE value? Value provenance reuses the tracekey
least-fixpoint argument-taint over the call graph: device-ness seeds
at ``jax.numpy``/``jax.lax`` producers, ``jax.jit``/``shard_map``
wrappers and AOT ``.compile()`` results (calls on a tainted callable
yield device values), ``jax.device_put`` and ``Engine.device_array``;
it propagates through tuple unpacking, subscripts, arithmetic,
comprehensions, helper parameters, and return values. Attribute reads
(``x.shape``, ``r.nbytes``) deliberately kill taint — shape/metadata
math is host-side and free.

Findings:

- ``jax.device_get``/``jax.block_until_ready``/``.block_until_ready()``
  outside the boundary: ALWAYS flagged (these exist only to sync);
- ``np.asarray``/``np.array``/``np.ascontiguousarray`` of a device
  value (the implicit ``__array__`` round-trip);
- ``.item()``/``.tolist()`` on a device value;
- ``int()``/``float()``/``bool()`` of a device value (implicit
  concretization — the tuple-of-ok-flags ladder bug class: one
  round-trip per flag instead of one per program).

Deliberate boundary reads are declared in
``exec/hostsync.DEVICE_SYNC_EXEMPT`` (id -> justification, id form
``<relpath>:<dotted.unit>:<kind>``) with staleness enforcement: an
entry matching no finding is itself a finding.
"""

from __future__ import annotations

import ast

from presto_tpu.lint.core import (Finding, Project, literal_str_dict,
                                  qual_name, rule)
from presto_tpu.lint.tracekey import _params, _taint_targets
from presto_tpu.lint.tracer import (CallGraph, _FnUnit,
                                    _is_traced_producer, _resolve,
                                    call_graph)

RULE = "device-sync"

# everything the execute path can reach: the trace scopes plus the
# serve/dispatch layers that demux results, and the engine facade
SCOPES = (
    "presto_tpu/ops/",
    "presto_tpu/exec/",
    "presto_tpu/expr/",
    "presto_tpu/parallel/",
    "presto_tpu/server/",
    "presto_tpu/obs/",
    "presto_tpu/templates/",
    "presto_tpu/engine.py",
)

# the designated boundary: syncs INSIDE it are the point
BOUNDARY_PATH = "presto_tpu/exec/hostsync.py"

# execute-path roots: whole serve/dispatch modules (every handler
# demuxes results) plus the named executor entry points
_ROOT_MODULES = (
    "presto_tpu/server/server.py",
    "presto_tpu/server/results.py",
    "presto_tpu/parallel/coordinator.py",
    "presto_tpu/parallel/worker.py",
)
_ROOT_UNITS = (
    ("presto_tpu/exec/executor.py", "prepare_plan"),
    ("presto_tpu/exec/executor.py", "execute_plan"),
    ("presto_tpu/exec/executor.py", "run_plan"),
    ("presto_tpu/exec/executor.py", "run_plan_device"),
    ("presto_tpu/parallel/executor.py", "execute_plan_distributed"),
    ("presto_tpu/exec/streaming.py", "try_execute_streamed"),
    ("presto_tpu/exec/spill.py", "try_execute_spilled"),
    ("presto_tpu/exec/spill.py", "try_execute_grouped"),
    ("presto_tpu/exec/profile.py", "explain_analyze"),
    ("presto_tpu/exec/profile.py", "explain_analyze_distributed"),
)

# numpy coercions that call __array__ on a device value (one implicit
# device->host transfer each)
_NP_COERCE = {"numpy.asarray", "numpy.array", "numpy.ascontiguousarray"}

# builtins that concretize a device scalar
_CONCRETIZE = {"int", "float", "bool"}


def _roots(graph: CallGraph) -> set[tuple]:
    roots: set[tuple] = set()
    for key, u in graph.units.items():
        if u.mod.relpath in _ROOT_MODULES:
            roots.add(key)
    for relpath, name in _ROOT_UNITS:
        for u in graph.named(relpath, name):
            roots.add(u.key)
    return roots


class _DeviceTaint:
    """Least-fixpoint device-value provenance over the call graph (the
    tracekey session-taint machinery applied to array values)."""

    def __init__(self, graph: CallGraph):
        self.graph = graph
        self.param_taint: dict[tuple, set[str]] = {}
        self.returns_device: set[tuple] = set()
        self._stmts: dict[tuple, list[ast.AST]] = {}
        self._propagate()

    def stmts(self, u: _FnUnit) -> list[ast.AST]:
        out = self._stmts.get(u.key)
        if out is None:
            out = self._stmts[u.key] = list(u.own_statements())
        return out

    # -- expression provenance ---------------------------------------

    def is_device(self, node: ast.AST, env: set[str],
                  u: _FnUnit) -> bool:
        if isinstance(node, ast.Name):
            return node.id in env
        if isinstance(node, (ast.Subscript, ast.Starred,
                             ast.NamedExpr, ast.Await)):
            return self.is_device(node.value, env, u)
        if isinstance(node, (ast.Tuple, ast.List, ast.Set)):
            return any(self.is_device(e, env, u) for e in node.elts)
        if isinstance(node, ast.IfExp):
            return (self.is_device(node.body, env, u)
                    or self.is_device(node.orelse, env, u))
        if isinstance(node, ast.BinOp):
            return (self.is_device(node.left, env, u)
                    or self.is_device(node.right, env, u))
        if isinstance(node, ast.UnaryOp):
            return self.is_device(node.operand, env, u)
        if isinstance(node, ast.BoolOp):
            return any(self.is_device(v, env, u) for v in node.values)
        if isinstance(node, (ast.ListComp, ast.SetComp,
                             ast.GeneratorExp)):
            return self.is_device(node.elt, env, u)
        if isinstance(node, ast.Call):
            return self._call_is_device(node, env, u)
        # Attribute (x.shape, r.nbytes), Constant, Compare, JoinedStr:
        # host-side metadata — taint deliberately stops here
        return False

    def _call_is_device(self, call: ast.Call, env: set[str],
                        u: _FnUnit) -> bool:
        aliases = self.graph.alias_cache[u.mod.relpath]
        fn = call.func
        q = _resolve(qual_name(fn), aliases)
        if q is not None:
            if q in _NP_COERCE or q == "jax.device_get":
                return False  # the sync itself yields a HOST value
            if q.startswith("re."):
                return False  # compiled regexes are not executables
            if _is_traced_producer(q) or q in (
                    "jax.device_put", "jax.jit") or \
                    q.endswith("shard_map"):
                return True
        if isinstance(fn, ast.Name):
            # a tainted callable (an AOT-compiled executable) returns
            # device outputs
            if fn.id in env:
                return True
            if fn.id in _CONCRETIZE or fn.id == "len":
                return False
        if isinstance(fn, ast.Attribute):
            if fn.attr in ("compile", "device_array"):
                # jax.jit(...).lower(...).compile() executables and
                # Engine.device_array pins — the two cross-module
                # device producers name resolution cannot follow
                return True
            if self.is_device(fn.value, env, u):
                # a method of a device value (x.astype, live.sum,
                # jit(fn).lower) stays on device — except the syncs
                return fn.attr not in ("item", "tolist")
        for callee in self.graph.resolve_call(u, call):
            if callee.key in self.returns_device:
                return True
        return False

    # -- per-unit name environment ------------------------------------

    def _flood(self, t: ast.AST, env: set[str]) -> bool:
        if isinstance(t, (ast.Tuple, ast.List)):
            grew = False
            for e in t.elts:
                grew |= self._flood(e, env)
            return grew
        if isinstance(t, ast.Starred):
            return self._flood(t.value, env)
        while isinstance(t, (ast.Subscript, ast.Attribute)):
            t = t.value  # storing device data taints the container
        if isinstance(t, ast.Name) and t.id not in env:
            env.add(t.id)
            return True
        return False

    def _assign(self, t: ast.AST, v: ast.AST, env: set[str],
                u: _FnUnit) -> bool:
        if isinstance(t, (ast.Tuple, ast.List)) and \
                isinstance(v, (ast.Tuple, ast.List)) and \
                len(t.elts) == len(v.elts) and not any(
                    isinstance(e, ast.Starred) for e in t.elts):
            grew = False
            for te, ve in zip(t.elts, v.elts):
                grew |= self._assign(te, ve, env, u)
            return grew
        if not self.is_device(v, env, u):
            return False
        return self._flood(t, env)

    def env(self, u: _FnUnit) -> set[str]:
        env = set(self.param_taint.get(u.key, ()))
        changed = True
        while changed:
            changed = False
            for stmt in self.stmts(u):
                if isinstance(stmt, ast.Assign):
                    for t in stmt.targets:
                        changed |= self._assign(t, stmt.value, env, u)
                elif isinstance(stmt, (ast.AnnAssign, ast.AugAssign)):
                    if stmt.value is not None:
                        changed |= self._assign(stmt.target,
                                                stmt.value, env, u)
                elif isinstance(stmt, ast.NamedExpr):
                    changed |= self._assign(stmt.target, stmt.value,
                                            env, u)
                elif isinstance(stmt, ast.For):
                    # iterating a device array yields device elements
                    if self.is_device(stmt.iter, env, u):
                        changed |= self._flood(stmt.target, env)
        return env

    # -- interprocedural fixpoint -------------------------------------

    def _propagate(self) -> None:
        units = list(self.graph.units.values())
        changed = True
        while changed:
            changed = False
            for u in units:
                if u.mod.relpath == BOUNDARY_PATH:
                    continue  # fetch/wait return HOST values
                env = self.env(u)
                for stmt in self.stmts(u):
                    if isinstance(stmt, ast.Return) and \
                            stmt.value is not None and \
                            u.key not in self.returns_device and \
                            self.is_device(stmt.value, env, u):
                        self.returns_device.add(u.key)
                        changed = True
                    if not isinstance(stmt, ast.Call):
                        continue
                    args = [(i, a) for i, a in enumerate(stmt.args)
                            if self.is_device(a, env, u)]
                    kwargs = [kw for kw in stmt.keywords
                              if kw.arg is not None
                              and self.is_device(kw.value, env, u)]
                    if not args and not kwargs:
                        continue
                    for callee, shift in _taint_targets(
                            self.graph, u, stmt):
                        cp = _params(callee)
                        tset = self.param_taint.setdefault(
                            callee.key, set())
                        for i, _a in args:
                            j = i + shift
                            if j < len(cp) and cp[j] not in tset:
                                tset.add(cp[j])
                                changed = True
                        for kw in kwargs:
                            if kw.arg in cp and kw.arg not in tset:
                                tset.add(kw.arg)
                                changed = True


class _Sync:
    """One host-blocking sync call site."""

    __slots__ = ("kind", "unit", "line", "col", "what")

    def __init__(self, kind: str, unit: _FnUnit, line: int, col: int,
                 what: str):
        self.kind = kind
        self.unit = unit
        self.line = line
        self.col = col
        self.what = what

    @property
    def exempt_id(self) -> str:
        return (f"{self.unit.mod.relpath}:"
                f"{'.'.join(self.unit.path)}:{self.kind}")


def _collect_syncs(graph: CallGraph, taint: _DeviceTaint,
                   reachable: set[tuple]) -> list[_Sync]:
    syncs: list[_Sync] = []
    for key in sorted(reachable):
        u = graph.units.get(key)
        if u is None or u.mod.relpath == BOUNDARY_PATH:
            continue
        aliases = graph.alias_cache[u.mod.relpath]
        env = taint.env(u)
        for stmt in taint.stmts(u):
            if not isinstance(stmt, ast.Call):
                continue
            fn = stmt.func
            q = _resolve(qual_name(fn), aliases)
            if q == "jax.device_get":
                syncs.append(_Sync("device_get", u, stmt.lineno,
                                   stmt.col_offset, "`jax.device_get`"))
                continue
            if q == "jax.block_until_ready" or (
                    isinstance(fn, ast.Attribute)
                    and fn.attr == "block_until_ready"):
                syncs.append(_Sync(
                    "block_until_ready", u, stmt.lineno,
                    stmt.col_offset, "`block_until_ready`"))
                continue
            if q in _NP_COERCE and stmt.args and \
                    taint.is_device(stmt.args[0], env, u):
                syncs.append(_Sync(
                    "asarray", u, stmt.lineno, stmt.col_offset,
                    f"`{q.replace('numpy.', 'np.')}` over a device "
                    "value (implicit `__array__` transfer)"))
                continue
            if isinstance(fn, ast.Attribute) and \
                    fn.attr in ("item", "tolist") and \
                    taint.is_device(fn.value, env, u):
                syncs.append(_Sync(
                    fn.attr, u, stmt.lineno, stmt.col_offset,
                    f"`.{fn.attr}()` on a device value"))
                continue
            if isinstance(fn, ast.Name) and fn.id in _CONCRETIZE and \
                    len(stmt.args) == 1 and \
                    taint.is_device(stmt.args[0], env, u):
                syncs.append(_Sync(
                    fn.id, u, stmt.lineno, stmt.col_offset,
                    f"`{fn.id}()` of a device value (implicit "
                    "concretization)"))
    return syncs


@rule(RULE)
def device_sync(project: Project) -> list[Finding]:
    graph = call_graph(project, SCOPES)
    if not graph.mods:
        return []
    findings: list[Finding] = []

    exempt: dict[str, tuple[str, int]] = {}
    boundary_mod = project.by_relpath.get(BOUNDARY_PATH)
    if boundary_mod is not None:
        exempt = literal_str_dict(boundary_mod, "DEVICE_SYNC_EXEMPT")

    roots = _roots(graph)
    if not roots:
        return []
    taint = _DeviceTaint(graph)
    reachable = graph.reachable(roots)
    syncs = _collect_syncs(graph, taint, reachable)

    used_exemptions: set[str] = set()

    def exempted(eid: str) -> bool:
        if eid in exempt:
            used_exemptions.add(eid)
            return True
        return False

    for s in syncs:
        if exempted(s.exempt_id):
            continue
        where = f"execute-path `{'.'.join(s.unit.path)}`"
        findings.append(Finding(
            RULE, s.unit.mod.relpath, s.line, s.col,
            f"hidden host sync: {where} calls {s.what} outside the "
            "exec/hostsync boundary — every occurrence blocks the "
            "host for a device round-trip and "
            "serializes the dispatch pipeline; batch it through "
            "hostsync.fetch / fetch_int / wait (counted in "
            "presto_tpu_device_syncs_total) or exempt "
            f"'{s.exempt_id}' in DEVICE_SYNC_EXEMPT with a "
            "justification"))

    # exemption hygiene: the registry must not rot
    for eid, (reason, line) in sorted(exempt.items()):
        if eid not in used_exemptions:
            findings.append(Finding(
                RULE, BOUNDARY_PATH, line, 0,
                f"stale-exemption: DEVICE_SYNC_EXEMPT entry {eid!r} "
                "matched no finding this run — the sync it excused "
                "was fixed, moved, or routed through the boundary; "
                "delete the stale exemption (it would silently waive "
                "the next real sync under that id)"))
        elif not reason:
            findings.append(Finding(
                RULE, BOUNDARY_PATH, line, 0,
                f"DEVICE_SYNC_EXEMPT entry {eid!r} needs a non-empty "
                "justification string"))
    return findings
