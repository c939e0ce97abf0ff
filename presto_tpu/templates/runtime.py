"""Trace/execute-time machinery for parameterized plan templates.

A parameterized plan (templates/analysis.py) carries ``ir.Parameter``
leaves instead of hoistable literals. At trace time the expression
compiler resolves each Parameter against the :class:`TraceParams`
context installed around the interpreter walk — the parameter's traced
value is a DEVICE argument of the jitted program, so a literal-variant
replay reuses the compiled executable with a different scalar instead
of recompiling (the Trino prepared-statement execution model,
StatementClientV1, applied at the XLA artifact layer).

VARCHAR parameters are special: the engine's string substrate is
dictionary codes, so the traced value is an int32 code *in the
dictionary of the column the parameter is compared against*. That
dictionary is only discovered mid-trace (expr/compile._align_strings),
so the compare path records a (parameter index -> dictionary) binding
here; :func:`bind_values` resolves the actual string through the
recorded dictionary at execute time (code -1 = absent = matches no
row, exactly the baked-literal semantics). The bindings ride in the
program-cache ``meta`` so disk-tier hits in a fresh process can still
bind.

A LIKE pattern over a scanned column (analysis.LikePattern) binds the
same way: its traced value is a boolean mask over the column's
dictionary, ``expr/compile._like`` records that dictionary, and
:func:`bind_values` runs the pattern over it on the host (the
``dict-mask`` span) for every execution; the codes index the mask on
the device.

State is strictly per-trace and confined to the tracing thread
(``threading.local``): parallel segment compilation traces concurrent
programs, each under its own installed context.
"""

from __future__ import annotations

import contextlib
import threading

import numpy as np

from presto_tpu import types as T
from presto_tpu.obs.trace import TRACER
from presto_tpu.templates.analysis import LikePattern

_TLS = threading.local()


class TemplateError(RuntimeError):
    """A parameterized plan was traced without a params context, or a
    parameter was used in a context the analysis should have rejected
    — always an engine bug, never a user error."""


class ParamDictionary:
    """Stand-in dictionary of a hoisted VARCHAR literal during trace.

    The compare path (expr/compile._align_strings) calls :meth:`bind`
    with the dictionary of the other side, recording where the
    parameter's runtime code must be resolved. Any other dictionary
    operation on a parameter is a bug: the analysis only hoists VARCHAR
    literals into eq/neq comparisons, and LIKE patterns (whose traced
    value is a mask over the dictionary bound here)."""

    __slots__ = ("index", "_params")

    def __init__(self, index: int, params: "TraceParams"):
        self.index = index
        self._params = params

    def bind(self, dictionary) -> None:
        self._params.record_binding(self.index, dictionary)

    def __getattr__(self, name):  # astype/__len__/searchsorted/...
        raise TemplateError(
            "VARCHAR template parameter used outside an eq/neq "
            "comparison or a LIKE (templates/analysis.py must not "
            "hoist here)")


class TraceParams:
    """One trace's parameter values + recorded string bindings."""

    def __init__(self, values: list):
        self.values = list(values)
        # parameter index -> host dictionary array the traced code
        # indexes into (recorded by ParamDictionary.bind)
        self.bindings: dict[int, object] = {}

    def traced(self, index: int):
        """The traced device value of parameter ``index``."""
        return self.values[index]

    def record_binding(self, index: int, dictionary) -> None:
        prev = self.bindings.get(index)
        if prev is not None and prev is not dictionary:
            # one Parameter node occupies exactly one tree position, so
            # two distinct dictionaries can only mean expression-level
            # aliasing the analysis failed to split
            raise TemplateError(
                f"template parameter {index} compared against two "
                f"different dictionaries")
        self.bindings[index] = dictionary


@contextlib.contextmanager
def active(params: TraceParams):
    """Install ``params`` for the duration of one interpreter trace."""
    prev = getattr(_TLS, "params", None)
    _TLS.params = params
    try:
        yield params
    finally:
        _TLS.params = prev


def current_params() -> TraceParams:
    params = getattr(_TLS, "params", None)
    if params is None:
        raise TemplateError(
            "parameterized plan traced without a TraceParams context")
    return params


def _long_limbs(value: int) -> np.ndarray:
    from presto_tpu.expr.compile import _lit128_np
    return _lit128_np(int(value))


def mask_length(dictionary) -> int:
    """Entries of a LIKE mask over ``dictionary``: the next power of
    two, not the dictionary's own length. The codes never reach the
    padding, and the program's shape then does not follow the exact
    count of distinct strings (1,999,647 of TPC-H SF10's 2,000,000
    ``p_name`` under one seed, another count under the next), as the
    scans' pow2 row buckets keep it from following the row count."""
    from presto_tpu.ops.hash import next_pow2
    return next_pow2(max(len(dictionary), 1))


def _dict_mask(value, dictionary) -> np.ndarray:
    """A LIKE pattern over every entry of the dictionary it was traced
    against: host work per execution, one regular-expression match an
    entry (2,000,000 for TPC-H SF10's ``p_name``)."""
    from presto_tpu.expr.compile import like_mask
    if dictionary is None:
        raise TemplateError(
            f"LIKE parameter over {value.column!r}: the trace recorded "
            f"no dictionary to bind it against")
    with TRACER.span("dict-mask", entries=len(dictionary)) as span:
        mask = np.zeros(mask_length(dictionary), np.bool_)
        mask[:len(dictionary)] = like_mask(
            dictionary, value.pattern, value.escape)
        if span is not None:
            span.attrs["matched"] = int(mask.sum())
    return mask


def physical_value(dtype, value, dictionary=None) -> np.ndarray:
    """Host physical encoding of one parameter value, matching what
    expr/compile._c_literal would bake for the same literal."""
    if isinstance(value, LikePattern):
        return _dict_mask(value, dictionary)
    if isinstance(dtype, T.VarcharType):
        if dictionary is None or value is None:
            return np.int32(-1)  # matches no code
        from presto_tpu.expr.compile import _lit_code
        return np.int32(_lit_code(dictionary, str(value)))
    if isinstance(dtype, T.DecimalType) and dtype.is_long:
        return _long_limbs(value)
    return np.asarray(value, dtype=dtype.physical_dtype)


def bind_values(specs, bindings: dict | None) -> list:
    """Physical argument vector for one execution: ``specs`` is the
    template's ordered parameter list (templates/analysis.ParamSpec),
    ``bindings`` the recorded string dictionaries (from trace meta;
    None/missing entries bind to code -1)."""
    bindings = bindings or {}
    return [physical_value(s.dtype, s.value, bindings.get(i))
            for i, s in enumerate(specs)]


def example_values(specs, scan_inputs) -> list:
    """Placeholder argument vector with the shapes and dtypes a bind
    will have, for lowering: string codes -1, and for a LIKE pattern an
    all-false mask of :func:`mask_length` of the scanned column's
    dictionary (``exec/executor.ScanInput.dictionaries``)."""
    dicts = {sym: d for scan in scan_inputs
             for sym, d in scan.dictionaries.items() if d is not None}
    out = []
    for s in specs:
        if not isinstance(s.value, LikePattern):
            out.append(physical_value(s.dtype, s.value))
        elif s.value.column in dicts:
            out.append(np.zeros(mask_length(dicts[s.value.column]),
                                np.bool_))
        else:
            raise TemplateError(
                f"LIKE parameter over {s.value.column!r}: no scan "
                f"input carries its dictionary")
    return out
