"""Parameterizability analysis: which literals of an optimized plan can
hoist into runtime arguments without changing the traced program.

The specialize-vs-generalize line ("Fine-Tuning Data Structures for
Analytical Query Processing", PAPERS.md 2112.13099) is drawn per
literal occurrence:

* **Hoistable** — comparison/arithmetic operands whose value flows
  straight into jnp ops: the traced program is identical for every
  value, so the literal becomes an ``ir.Parameter`` leaf fed as a
  device scalar at execute time. Numeric/date/timestamp/decimal
  literals under :data:`HOISTABLE_CALL_FNS`, plus VARCHAR literals in
  eq/neq comparisons (hoisted as a dictionary code resolved at bind
  time, templates/runtime.py).

* **Dictionary masks** — ``col LIKE <literal>`` (with or without
  ``ESCAPE``; ``NOT LIKE`` is a ``not`` above it) where ``col`` is a
  scanned, dictionary-coded column: the pattern becomes a parameter
  whose value at execute time is its boolean mask over ``col``'s
  dictionary, computed on the host at bind time and indexed by the
  codes on the device (templates/runtime.py). TPC-H Q9's 92 colors
  share one program.

* **Structural** — everything else stays baked: literals the compiler
  reads host-side at trace time (regexp patterns, a LIKE over a
  computed string or with a non-literal pattern, substring bounds,
  date_trunc units — any scalar that reads ``e.args`` instead of
  compiled values; drift-guarded by tests/test_templates.py),
  LIMIT/TopN counts (plan-node ints, hashed by the plan fingerprint),
  IN-list values (the list shapes the trace), CASE/CAST/lambda
  internals, NULL literals (validity shape), and decimal *types*
  (precision/scale live in dtypes, which are structural by
  construction).

The rewrite runs on the final optimized plan (after cost-based
decisions — capacity hints and join order were chosen from the original
literals and stay in the template as structural annotations), walking
only the expression positions the trace-time ExprCompiler actually
compiles: Filter predicates, Project assignments, and Join filters.
Parameter indices are allocated in deterministic walk order, so the
same SQL shape always yields the same (template fingerprint, parameter
vector) pairing.
"""

from __future__ import annotations

import dataclasses

from presto_tpu import types as T
from presto_tpu.expr import ir
from presto_tpu.plan import nodes as N

# Scalar fns whose compiled (traced) argument values fully determine
# the result — a literal argument of these hoists. Everything else is
# structural. tests/test_templates.py drift-guards this set against
# expr/compile.py: a whitelisted fn must never read ``e.args`` (the IR)
# at trace time.
HOISTABLE_CALL_FNS = frozenset({
    "eq", "neq", "lt", "lte", "gt", "gte", "between",
    "add", "subtract", "multiply", "divide", "modulus", "negate",
})

# VARCHAR literals only hoist under these fns: the engine's string
# substrate is dictionary codes, and only equality against a column
# resolves a code through _align_strings (ordering comparisons
# host-evaluate predicates over the dictionary — structural).
STRING_HOISTABLE_FNS = frozenset({"eq", "neq"})

# value dtypes whose physical encoding is value-shape-free
_HOISTABLE_VALUE_TYPES = (
    T.BigintType, T.IntegerType, T.DoubleType, T.DateType,
    T.TimestampType, T.TimeType, T.DecimalType,
)


@dataclasses.dataclass(frozen=True)
class LikePattern:
    """The value of a hoisted LIKE pattern: bound at execute time to
    the pattern's boolean mask over the dictionary of ``column`` (a
    TableScan symbol), so the mask is an argument of the program and
    not a constant in it."""

    column: str
    pattern: str
    escape: str | None = None


@dataclasses.dataclass(frozen=True)
class ParamSpec:
    """One hoisted literal: its declared type and this query's value
    (the value rides OUTSIDE the template fingerprint)."""

    dtype: T.DataType
    value: object


@dataclasses.dataclass
class Template:
    """A parameterized plan + this query's ordered parameter vector."""

    plan: N.PlanNode
    params: list[ParamSpec]

    def fingerprint(self) -> str:
        from presto_tpu.plan.fingerprint import plan_fingerprint
        return plan_fingerprint(self.plan)

    def example_args(self, scan_inputs=()) -> list:
        """Physical placeholder args for tracing (VARCHAR codes bind
        for real only after the trace records their dictionaries; a
        LIKE pattern's mask takes its length from the scanned column's
        dictionary in ``scan_inputs``)."""
        from presto_tpu.templates.runtime import example_values
        return example_values(self.params, scan_inputs)

    def bind(self, bindings: dict | None) -> list:
        """Physical args for one execution, string codes resolved
        through the trace-recorded ``bindings`` (program-cache meta)."""
        from presto_tpu.templates.runtime import bind_values
        return bind_values(self.params, bindings)


def _hoistable(lit: ir.Literal, call: ir.Call) -> bool:
    if lit.value is None:
        return False  # typed NULL: validity shape is structural
    if isinstance(lit.dtype, T.VarcharType):
        if call.fn not in STRING_HOISTABLE_FNS:
            return False
        # a code parameter needs a real column side to bind against
        return any(not isinstance(a, (ir.Literal, ir.Parameter))
                   for a in call.args)
    if not isinstance(lit.dtype, _HOISTABLE_VALUE_TYPES):
        return False
    return call.fn in HOISTABLE_CALL_FNS


def _scan_symbols(node: N.PlanNode, out: set[str]) -> set[str]:
    if isinstance(node, N.TableScan):
        out.update(node.assignments)
    for s in node.sources():
        _scan_symbols(s, out)
    return out


def _varchar_literal(e: ir.Expr) -> bool:
    return (isinstance(e, ir.Literal) and e.value is not None
            and isinstance(e.dtype, T.VarcharType))


class _Rewriter:
    def __init__(self, scan_symbols: set[str]):
        self.params: list[ParamSpec] = []
        self.scan_symbols = scan_symbols

    def _like(self, e: ir.Call) -> ir.Expr | None:
        """``like(col, pattern[, escape])`` with the pattern hoisted,
        or None where it stays structural: ``col`` has to be a scanned
        column (its dictionary is then known before the trace, which
        the mask's shape needs) and pattern and escape literals."""
        col = e.args[0]
        if not (isinstance(col, ir.ColumnRef)
                and isinstance(col.dtype, T.VarcharType)
                and col.name in self.scan_symbols
                and all(_varchar_literal(a) for a in e.args[1:])):
            return None
        escape = e.args[2].value if len(e.args) > 2 else None
        self.params.append(ParamSpec(T.VARCHAR, LikePattern(
            col.name, e.args[1].value, escape)))
        return ir.Call(e.dtype, "like", (col, ir.Parameter(
            T.VARCHAR, len(self.params) - 1)))

    # -- expressions --------------------------------------------------------

    def expr(self, e: ir.Expr, call: ir.Call | None = None) -> ir.Expr:
        """Rewrite one expression; ``call`` is the immediate enclosing
        Call when it admits hoisting, else None."""
        if isinstance(e, ir.Literal):
            if call is not None and _hoistable(e, call):
                self.params.append(ParamSpec(e.dtype, e.value))
                return ir.Parameter(e.dtype, len(self.params) - 1)
            return e
        if isinstance(e, ir.Call):
            hoisted = self._like(e) if e.fn == "like" else None
            if hoisted is not None:
                return hoisted
            ctx = e if e.fn in HOISTABLE_CALL_FNS else None
            args = tuple(self.expr(a, ctx) for a in e.args)
            if args == e.args:
                return e
            return ir.Call(e.dtype, e.fn, args)
        if isinstance(e, ir.Cast):
            arg = self.expr(e.arg)
            return e if arg is e.arg else ir.Cast(e.dtype, arg)
        if isinstance(e, ir.CaseWhen):
            conds = tuple(self.expr(c) for c in e.conditions)
            results = tuple(self.expr(r) for r in e.results)
            default = (None if e.default is None
                       else self.expr(e.default))
            if (conds == e.conditions and results == e.results
                    and default is e.default):
                return e
            return ir.CaseWhen(e.dtype, conds, results, default)
        if isinstance(e, ir.InList):
            arg = self.expr(e.arg)  # values stay baked (shape the trace)
            return e if arg is e.arg else ir.InList(e.dtype, arg,
                                                    e.values)
        if isinstance(e, ir.IsNull):
            arg = self.expr(e.arg)
            return e if arg is e.arg else ir.IsNull(e.dtype, arg,
                                                    e.negated)
        # Lambda bodies (and any future Expr kind) stay untouched:
        # higher-order kernels re-enter compilation host-side
        return e

    # -- plan ---------------------------------------------------------------

    def node(self, node: N.PlanNode) -> N.PlanNode:
        updates: dict = {}
        if isinstance(node, N.Filter):
            pred = self.expr(node.predicate)
            if pred is not node.predicate:
                updates["predicate"] = pred
        elif isinstance(node, N.Project):
            assigns = {s: self.expr(e)
                       for s, e in node.assignments.items()}
            if any(assigns[s] is not node.assignments[s]
                   for s in assigns):
                updates["assignments"] = assigns
        elif isinstance(node, N.Join) and node.filter is not None:
            filt = self.expr(node.filter)
            if filt is not node.filter:
                updates["filter"] = filt
        for f in dataclasses.fields(node):
            v = getattr(node, f.name)
            if isinstance(v, N.PlanNode):
                nv = self.node(v)
                if nv is not v:
                    updates[f.name] = nv
            elif isinstance(v, list) and v and isinstance(v[0],
                                                          N.PlanNode):
                nl = [self.node(x) for x in v]
                if any(a is not b for a, b in zip(nl, v)):
                    updates[f.name] = nl
        return dataclasses.replace(node, **updates) if updates else node


def _has_match_recognize(node: N.PlanNode) -> bool:
    if isinstance(node, N.MatchRecognize):
        return True
    return any(_has_match_recognize(s) for s in node.sources())


def parameterize(plan: N.PlanNode) -> Template | None:
    """Hoist every hoistable literal of ``plan`` into an ordered
    parameter vector. Returns None when nothing hoists (the plan keys
    the program cache as-is) or when the plan contains host-evaluated
    regions (MATCH_RECOGNIZE defines run outside the trace)."""
    if _has_match_recognize(plan):
        return None
    rw = _Rewriter(_scan_symbols(plan, set()))
    tplan = rw.node(plan)
    if not rw.params:
        return None
    return Template(tplan, rw.params)
