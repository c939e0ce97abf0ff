"""Expression IR -> JAX lowering.

Runs at jit-trace time: the compiler walks the IR and emits jnp ops over
the input columns, so the whole operator pipeline fuses into one XLA
computation. Analog of sql/gen/ExpressionCompiler.java +
PageFunctionCompiler.java:101 in the reference (which emits JVM bytecode
per (expression, types) and caches it — here jax's jit cache plays that
role).

Value model (`Val`): (dtype, data, valid, dictionary)
- data: jnp array [N] or scalar; physical per types.py
- valid: bool array or None (None = all valid); Kleene 3-valued logic for
  AND/OR, null-propagation elsewhere
- dictionary: host-side sorted numpy str array, present for VARCHAR values.
  String ops are *dictionary transforms*: LIKE evaluates the pattern over
  the (small) dictionary on host and gathers a boolean LUT by code;
  substring/lower/... rewrite the dictionary and remap codes. This is the
  TPU-native generalisation of the reference's DictionaryAwarePageProjection
  (operator/project/DictionaryAwarePageProjection.java).
"""

from __future__ import annotations

import dataclasses
import re
import threading
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from presto_tpu import types as T
from presto_tpu.expr import ir


@dataclasses.dataclass
class Val:
    """One columnar value during trace.

    Scalar columns: data [n]. ARRAY columns are FIXED-CAPACITY padded
    2D device values — data [n, cap] element values (codes for string
    elements), ``lengths`` [n] element counts, ``elem_valid`` [n, cap]
    per-element non-NULL mask (None = no NULL elements); positions past
    the length are dead padding. MAP columns additionally carry their
    key array in ``map_keys``. The 2D layout keeps every array
    operation (constructors, subscripts, lambdas, unnest) inside the
    traced XLA program — the TPU-native answer to the reference's
    variable-width ArrayBlock (spi/block/ArrayBlock.java)."""

    dtype: T.DataType
    data: object
    valid: object | None = None
    dictionary: np.ndarray | None = None
    lengths: object | None = None
    elem_valid: object | None = None
    map_keys: "Val | None" = None

    @property
    def is_string(self) -> bool:
        return isinstance(self.dtype, T.VarcharType)

    @property
    def is_array(self) -> bool:
        return isinstance(self.dtype, T.ArrayType)

    def elem_mask(self):
        """[n, cap] mask of live (present, non-NULL) elements."""
        cap = self.data.shape[1]
        m = jnp.arange(cap)[None, :] < self.lengths[:, None]
        if self.elem_valid is not None:
            m = m & self.elem_valid
        return m


def is_long_dec(t) -> bool:
    """LONG decimal (precision 19..38): int128 as [n, 2] int64 limbs
    (reference spi/type/Decimals.java:45 long decimals; limb kernels in
    ops/int128.py)."""
    return isinstance(t, T.DecimalType) and t.is_long


def _lit128_np(value: int) -> np.ndarray:
    """Python int -> [2] int64 limb constant (low word bit pattern,
    signed high word)."""
    m = value & ((1 << 128) - 1)
    lov, hiv = m & ((1 << 64) - 1), (m >> 64) & ((1 << 64) - 1)
    tos = lambda x: x - (1 << 64) if x >= (1 << 63) else x  # noqa: E731
    return np.asarray([tos(lov), tos(hiv)], np.int64)


def as128(v: Val, scale: int):
    """A decimal/integer Val's data as int128 limbs at ``scale``
    (rescaling up only — callers align to the wider scale)."""
    from presto_tpu.ops import int128 as I
    if is_long_dec(v.dtype):
        d = v.data
        ds = v.dtype.scale
    elif isinstance(v.dtype, T.DecimalType):
        d = I.from_i64(v.data.astype(jnp.int64))
        ds = v.dtype.scale
    else:
        d = I.from_i64(v.data.astype(jnp.int64))
        ds = 0
    if scale > ds:
        d = I.rescale_up(d, scale - ds)
    return d


def where_data(cond, x, y, long: bool = False):
    """jnp.where that broadcasts a scalar/[n] condition over [n, 2]
    limb data. ``long`` marks LONG-decimal branches explicitly: two
    scalar limb values are [2]-shaped, indistinguishable from a 2-row
    column by shape alone."""
    if long or max(getattr(x, "ndim", 1), getattr(y, "ndim", 1)) == 2:
        if long:
            if getattr(x, "ndim", 1) == 1:
                x = x[None, :]
            if getattr(y, "ndim", 1) == 1:
                y = y[None, :]
        cond = jnp.asarray(cond)
        if cond.ndim == 0:
            cond = cond[None, None]
        elif cond.ndim == 1:
            cond = cond[:, None]
    return jnp.where(cond, x, y)


def and_valid(*vs):
    """AND of validity masks, None = all-valid."""
    masks = [v for v in vs if v is not None]
    if not masks:
        return None
    out = masks[0]
    for m in masks[1:]:
        out = out & m
    return out


def _bool(data, valid=None) -> Val:
    return Val(T.BOOLEAN, data, valid)


# column bindings of the innermost _c_call in flight (lambda
# captures). Per-THREAD: parallel segment compilation traces
# concurrent programs on pool threads, and a process-global stack
# would interleave their push/pop and bind another trace's columns
# into a lambda body (caught by the tracekey lint: a mutable module
# global read at trace time is also a cache-key soundness hazard)
_COMPILER_TLS = threading.local()


def _compiler_columns() -> list[dict]:
    stack = getattr(_COMPILER_TLS, "stack", None)
    if stack is None:
        stack = _COMPILER_TLS.stack = []
    return stack


# --- dictionary helpers (host side, trace time) ----------------------------


def _lit_code(dictionary: np.ndarray, s: str) -> int:
    """Code of string literal in a sorted dictionary, or -1 if absent."""
    i = int(np.searchsorted(dictionary, s))
    if i < len(dictionary) and dictionary[i] == s:
        return i
    return -1


def _dict_transform(v: Val, fn: Callable[[np.ndarray], np.ndarray]) -> Val:
    """Apply a host-side string->string function over the dictionary and
    remap codes to the new sorted dictionary."""
    new_strings = fn(v.dictionary.astype("U")).astype(object)
    new_dict, inverse = np.unique(new_strings.astype("U"), return_inverse=True)
    remap = jnp.asarray(inverse.astype(np.int32))
    return Val(T.VARCHAR, remap[v.data], v.valid, new_dict.astype(object))


def _dict_predicate(v: Val, pred: Callable[[np.ndarray], np.ndarray]) -> Val:
    """Host-evaluate a string predicate over the dictionary, gather by code."""
    lut = jnp.asarray(pred(v.dictionary.astype("U")).astype(np.bool_))
    return _bool(lut[v.data], v.valid)


def _like_regex(pattern: str, escape: str | None = None) -> re.Pattern:
    out = []
    i = 0
    while i < len(pattern):
        ch = pattern[i]
        if escape and ch == escape and i + 1 < len(pattern):
            out.append(re.escape(pattern[i + 1]))
            i += 2
            continue
        if ch == "%":
            out.append(".*")
        elif ch == "_":
            out.append(".")
        else:
            out.append(re.escape(ch))
        i += 1
    return re.compile("".join(out), re.DOTALL)


def like_mask(dictionary, pattern: str,
              escape: str | None = None) -> np.ndarray:
    """LIKE over every entry of a dictionary, on the host: the boolean
    table the codes index on the device. One match an entry."""
    match = _like_regex(pattern, escape).fullmatch
    entries = np.asarray(dictionary).tolist()
    return np.fromiter((match(str(s)) is not None for s in entries),
                       np.bool_, len(entries))


def _align_strings(a: Val, b: Val) -> tuple[object, object]:
    """Return comparable code arrays for two string Vals.

    - same dictionary object: codes compare directly;
    - template parameter vs column: the parameter's traced value IS a
      code in the column's dictionary (resolved at bind time against
      the dictionary recorded here; -1 = absent = matches nothing);
    - literal vs column: resolve through the column's dictionary;
    - different dictionaries: translate a's codes into b's code space via a
      host-computed mapping (-1 where a's string is absent from b's dict).
    Only valid for equality comparisons unless dictionaries are identical.
    """
    from presto_tpu.templates.runtime import ParamDictionary
    if isinstance(a.dictionary, ParamDictionary):
        a.dictionary.bind(b.dictionary)
        return a.data, b.data
    if isinstance(b.dictionary, ParamDictionary):
        b.dictionary.bind(a.dictionary)
        return a.data, b.data
    if a.dictionary is b.dictionary:
        return a.data, b.data
    # map a's dict entries into b's code space
    idx = np.searchsorted(b.dictionary, a.dictionary.astype("U"))
    idx = np.clip(idx, 0, max(len(b.dictionary) - 1, 0))
    ok = (b.dictionary.astype("U")[idx] == a.dictionary.astype("U")) if len(
        b.dictionary) else np.zeros(len(a.dictionary), bool)
    mapping = np.where(ok, idx, -1).astype(np.int32)
    return jnp.asarray(mapping)[a.data], b.data


# --- the compiler ----------------------------------------------------------


class ExprCompiler:
    """Compiles IR against a set of named input columns (Vals)."""

    def __init__(self, columns: dict[str, Val]):
        self.columns = columns

    def compile(self, expr: ir.Expr) -> Val:
        method = getattr(self, "_c_" + type(expr).__name__.lower())
        return method(expr)

    # -- leaves

    def _c_columnref(self, e: ir.ColumnRef) -> Val:
        return self.columns[e.name]

    def _c_literal(self, e: ir.Literal) -> Val:
        if e.value is None:
            zero = np.zeros((2,) if is_long_dec(e.dtype) else (),
                            dtype=e.dtype.physical_dtype)
            dictionary = (np.array([""], dtype=object)
                          if isinstance(e.dtype, T.VarcharType) else None)
            return Val(e.dtype, jnp.asarray(zero), jnp.asarray(False),
                       dictionary)
        if isinstance(e.dtype, T.VarcharType):
            return Val(e.dtype, jnp.asarray(np.int32(0)), None,
                       np.array([e.value], dtype=object))
        if is_long_dec(e.dtype):
            return Val(e.dtype, jnp.asarray(_lit128_np(int(e.value))))
        return Val(e.dtype, jnp.asarray(
            np.asarray(e.value, dtype=e.dtype.physical_dtype)))

    # -- structured forms

    def _c_cast(self, e: ir.Cast) -> Val:
        v = self.compile(e.arg)
        return cast_val(v, e.dtype)

    def _c_isnull(self, e: ir.IsNull) -> Val:
        v = self.compile(e.arg)
        isnull = jnp.asarray(False) if v.valid is None else ~v.valid
        return _bool(~isnull if e.negated else isnull)

    def _c_inlist(self, e: ir.InList) -> Val:
        v = self.compile(e.arg)
        if v.is_string:
            values = {lit.value for lit in e.values}
            return _dict_predicate(v, lambda d: np.isin(d, list(values)))
        acc = None
        for lit in e.values:
            lv = self.compile(lit)
            hit = v.data == cast_val(lv, v.dtype).data
            acc = hit if acc is None else (acc | hit)
        return _bool(acc, v.valid)

    def _c_casewhen(self, e: ir.CaseWhen) -> Val:
        default = (self.compile(e.default) if e.default is not None
                   else self.compile(ir.Literal(e.dtype, None)))
        result = cast_val(default, e.dtype)
        # evaluate WHENs in reverse so earlier conditions win
        for cond, res in list(zip(e.conditions, e.results))[::-1]:
            c = self.compile(cond)
            r = cast_val(self.compile(res), e.dtype)
            take = c.data if c.valid is None else (c.data & c.valid)
            if r.is_string or result.is_string:
                r, result = _merge_dicts(r, result)
            data = where_data(take, r.data, result.data,
                              long=is_long_dec(e.dtype))
            rv = jnp.ones_like(take) if r.valid is None else r.valid
            dv = jnp.ones_like(take) if result.valid is None else result.valid
            valid = jnp.where(take, rv, dv)
            result = Val(e.dtype, data, valid, result.dictionary)
        return result

    def _c_call(self, e: ir.Call) -> Val:
        args = [self.compile(a) for a in e.args]
        fn = SCALARS.get(e.fn)
        if fn is None:
            raise NotImplementedError(f"scalar function {e.fn}")
        # higher-order kernels re-enter compilation for lambda bodies
        # and need this call's column bindings (outer captures)
        stack = _compiler_columns()
        stack.append(self.columns)
        try:
            return fn(e, args)
        finally:
            stack.pop()

    def _c_lambda(self, e: "ir.Lambda") -> Val:
        # lambdas are not values: higher-order kernels read them from
        # e.args and bind the params themselves
        return Val(e.dtype, None)

    def _c_parameter(self, e: "ir.Parameter") -> Val:
        # hoisted literal (templates/): the value is a traced device
        # scalar from the active params context, so literal variants
        # of one plan template share a compiled program. VARCHAR
        # parameters are dictionary codes; the marker dictionary makes
        # _align_strings record which dictionary to resolve against.
        from presto_tpu.templates.runtime import (ParamDictionary,
                                                  current_params)
        tp = current_params()
        data = tp.traced(e.index)
        if isinstance(e.dtype, T.VarcharType):
            return Val(e.dtype, data, None, ParamDictionary(e.index, tp))
        return Val(e.dtype, data)


def _merge_dicts(a: Val, b: Val) -> tuple[Val, Val]:
    """Bring two string Vals onto one shared sorted dictionary."""
    if a.dictionary is b.dictionary:
        return a, b
    union = np.unique(np.concatenate(
        [a.dictionary.astype("U"), b.dictionary.astype("U")]))
    ra = jnp.asarray(np.searchsorted(union, a.dictionary.astype("U"))
                     .astype(np.int32))
    rb = jnp.asarray(np.searchsorted(union, b.dictionary.astype("U"))
                     .astype(np.int32))
    u = union.astype(object)
    return (Val(a.dtype, ra[a.data], a.valid, u),
            Val(b.dtype, rb[b.data], b.valid, u))


# --- casts -----------------------------------------------------------------


def _parse_numeric_dictionary(v: Val, to: T.DataType) -> Val:
    """varchar -> numeric cast: parse each DICTIONARY entry host-side
    into a LUT, rows gather by code; malformed strings become NULL
    (try_cast) / the row's validity carries the failure."""
    k = len(v.dictionary)
    ok = np.zeros(k, bool)
    if isinstance(to, T.DoubleType):
        vals = np.zeros(k, np.float64)
        for i, s in enumerate(v.dictionary):
            try:
                vals[i] = float(str(s).strip())
                ok[i] = True
            except ValueError:
                pass
    elif isinstance(to, T.DecimalType):
        from decimal import Decimal, InvalidOperation
        vals = np.zeros((k, 2) if to.is_long else k, np.int64)
        for i, s in enumerate(v.dictionary):
            try:
                raw = int(Decimal(str(s).strip())
                          .scaleb(to.scale).to_integral_value())
                vals[i] = _lit128_np(raw) if to.is_long else raw
                ok[i] = True
            except (InvalidOperation, ValueError, OverflowError):
                pass
    else:
        vals = np.zeros(k, to.physical_dtype)
        for i, s in enumerate(v.dictionary):
            t = str(s).strip()
            try:
                vals[i] = int(t)
                ok[i] = True
            except ValueError:
                try:  # integral-valued decimals cast too ('5.0')
                    f = float(t)
                    if f == int(f):
                        vals[i] = int(f)
                        ok[i] = True
                except (ValueError, OverflowError):
                    pass
    codes = jnp.clip(v.data, 0, max(k - 1, 0))
    data = (jnp.asarray(vals)[codes] if k
            else jnp.zeros_like(v.data, dtype=vals.dtype))
    okrow = (jnp.asarray(ok)[codes] if k
             else jnp.zeros_like(v.data, dtype=bool))
    return Val(to, data, and_valid(v.valid, okrow))


def _rescale128(d, from_scale: int, to_scale: int):
    """int128 limbs rescaled between decimal scales (HALF_UP down)."""
    from presto_tpu.ops import int128 as I
    if to_scale >= from_scale:
        return I.rescale_up(d, to_scale - from_scale)
    k = from_scale - to_scale
    f = I.from_i64(jnp.int64(10 ** min(k, 18)))
    if k > 18:
        f = I.rescale_up(f, k - 18)
    return I.div_round_half_up(d, jnp.broadcast_to(f, d.shape))


def _cast_long_decimal(v: Val, to: T.DecimalType) -> Val:
    """Casts where the source or target is a LONG decimal."""
    from presto_tpu.ops import int128 as I
    if isinstance(v.dtype, T.UnknownType):  # typed NULL
        shape = ((v.data.shape[0], 2)
                 if getattr(v.data, "ndim", 0) >= 1 else (2,))
        return Val(to, jnp.zeros(shape, jnp.int64),
                   jnp.zeros(shape[:-1], bool) if len(shape) > 1
                   else jnp.asarray(False))
    if isinstance(v.dtype, T.DecimalType):
        src_scale = v.dtype.scale
        d = v.data if is_long_dec(v.dtype) \
            else I.from_i64(v.data.astype(jnp.int64))
    elif isinstance(v.dtype, (T.BigintType, T.IntegerType)):
        src_scale = 0
        d = I.from_i64(v.data.astype(jnp.int64))
    elif isinstance(v.dtype, T.DoubleType):
        x = v.data * (10.0 ** to.scale)
        hi = jnp.floor(x / jnp.float64(2.0 ** 64))
        lo = x - hi * jnp.float64(2.0 ** 64)
        d = I.pack(lo.astype(jnp.uint64), hi.astype(jnp.int64))
        src_scale = to.scale
    else:
        raise NotImplementedError(
            f"cast {v.dtype} -> {to}")
    d = _rescale128(d, src_scale, to.scale)
    if not to.is_long:
        return Val(to, I.to_i64(d), v.valid)
    return Val(to, d, v.valid)


def cast_val(v: Val, to: T.DataType) -> Val:
    if v.dtype == to:
        return v
    if v.is_string and isinstance(
            to, (T.BigintType, T.IntegerType, T.DoubleType,
                 T.DecimalType)) and v.dictionary is not None:
        return _parse_numeric_dictionary(v, to)
    d = v.data
    if isinstance(to, T.DoubleType):
        if is_long_dec(v.dtype):
            from presto_tpu.ops import int128 as I
            return Val(to, I.to_f64(d) / v.dtype.unscale_factor,
                       v.valid)
        if isinstance(v.dtype, T.DecimalType):
            return Val(to, d.astype(jnp.float64) / v.dtype.unscale_factor,
                       v.valid)
        return Val(to, d.astype(jnp.float64), v.valid)
    if isinstance(to, T.DecimalType):
        if to.is_long or is_long_dec(v.dtype):
            return _cast_long_decimal(v, to)
        if isinstance(v.dtype, T.DecimalType):
            ds, ts = v.dtype.scale, to.scale
            if ts >= ds:
                return Val(to, d * (10 ** (ts - ds)), v.valid)
            f = 10 ** (ds - ts)
            # round half up (reference DecimalType rescale semantics)
            return Val(to, _div_round(d, f), v.valid)
        if isinstance(v.dtype, (T.BigintType, T.IntegerType)):
            return Val(to, d.astype(jnp.int64) * to.unscale_factor, v.valid)
        if isinstance(v.dtype, T.DoubleType):
            return Val(to, jnp.round(d * to.unscale_factor).astype(jnp.int64),
                       v.valid)
    if isinstance(to, T.BigintType):
        if is_long_dec(v.dtype):
            from presto_tpu.ops import int128 as I
            scaled = _rescale128(d, v.dtype.scale, 0)
            return Val(to, I.to_i64(scaled), v.valid)
        if isinstance(v.dtype, T.DecimalType):
            return Val(to, _div_round(d, v.dtype.unscale_factor), v.valid)
        return Val(to, d.astype(jnp.int64), v.valid)
    if isinstance(to, T.IntegerType):
        return Val(to, d.astype(jnp.int32), v.valid)
    if isinstance(to, T.TimestampType):
        if isinstance(v.dtype, T.DateType):
            return Val(to, v.data.astype(jnp.int64) * T.US_PER_DAY,
                       v.valid)
        if v.is_string:
            return _parse_datetime_dictionary(v, to)
    if isinstance(to, T.DateType) and isinstance(v.dtype,
                                                 T.TimestampType):
        return Val(to, jnp.floor_divide(v.data, T.US_PER_DAY)
                   .astype(jnp.int32), v.valid)
    if isinstance(to, T.DateType) and v.is_string:
        # per-dictionary-entry ISO date parse (one parse per unique
        # string, rows gather by code); malformed strings become NULL
        # (reference operator/scalar/DateTimeFunctions castToDate)
        epoch = np.datetime64("1970-01-01")
        days = np.empty(len(v.dictionary), dtype=np.int32)
        ok = np.zeros(len(v.dictionary), dtype=bool)
        for i, s in enumerate(v.dictionary):
            try:
                d64 = np.datetime64(str(s).strip()[:10])
                # '' / 'NaT' parse to NaT without raising; NaT - epoch
                # is INT64_MIN which overflows the int32 store
                if not np.isnat(d64):
                    days[i] = int((d64 - epoch).astype(int))
                    ok[i] = True
                else:
                    days[i] = 0
            except (ValueError, OverflowError):
                days[i] = 0
        data = jnp.asarray(days)[jnp.clip(d, 0, max(len(days) - 1, 0))] \
            if len(days) else jnp.zeros_like(d, dtype=jnp.int32)
        okrow = (jnp.asarray(ok)[jnp.clip(d, 0, max(len(ok) - 1, 0))]
                 if len(ok) else jnp.zeros_like(d, dtype=bool))
        return Val(to, data, and_valid(v.valid, okrow))
    if isinstance(to, T.UnknownType) or isinstance(v.dtype, T.UnknownType):
        return Val(to, jnp.zeros_like(d, dtype=to.physical_dtype), v.valid)
    raise NotImplementedError(f"cast {v.dtype} -> {to}")


def _div_round(x, f: int):
    """Integer division rounding half away from zero."""
    half = f // 2
    return jnp.where(x >= 0, (x + half) // f, -((-x + half) // f))


def _parse_datetime_dictionary(v: Val, to: T.DataType) -> Val:
    """Per-dictionary-entry timestamp parse (cast varchar -> timestamp);
    malformed strings become NULL."""
    epoch = np.datetime64("1970-01-01", "us")
    us = np.zeros(len(v.dictionary), dtype=np.int64)
    ok = np.zeros(len(v.dictionary), dtype=bool)
    for i, s in enumerate(v.dictionary):
        try:
            d64 = np.datetime64(str(s).strip().replace(" ", "T"), "us")
            if not np.isnat(d64):
                us[i] = int((d64 - epoch).astype(np.int64))
                ok[i] = True
        except (ValueError, OverflowError):
            pass
    d = v.data
    data = (jnp.asarray(us)[jnp.clip(d, 0, max(len(us) - 1, 0))]
            if len(us) else jnp.zeros_like(d, dtype=jnp.int64))
    okrow = (jnp.asarray(ok)[jnp.clip(d, 0, max(len(ok) - 1, 0))]
             if len(ok) else jnp.zeros_like(d, dtype=bool))
    return Val(to, data, and_valid(v.valid, okrow))


# --- scalar function registry ---------------------------------------------

SCALARS: dict[str, Callable] = {}


def scalar(name: str):
    def deco(fn):
        SCALARS[name] = fn
        return fn
    return deco


def _decimal_align(a: Val, b: Val) -> tuple[Val, Val, int]:
    sa = a.dtype.scale if isinstance(a.dtype, T.DecimalType) else 0
    sb = b.dtype.scale if isinstance(b.dtype, T.DecimalType) else 0
    s = max(sa, sb)
    da = a.data * (10 ** (s - sa))
    db = b.data * (10 ** (s - sb))
    return (Val(a.dtype, da, a.valid), Val(b.dtype, db, b.valid), s)


def _arith(e: ir.Call, args: list[Val], op) -> Val:
    from presto_tpu.ops import int128 as I
    a, b = args
    valid = and_valid(a.valid, b.valid)
    if isinstance(e.dtype, T.DoubleType):
        a, b = cast_val(a, T.DOUBLE), cast_val(b, T.DOUBLE)
        return Val(e.dtype, op(a.data, b.data), valid)
    if isinstance(e.dtype, T.DecimalType):
        long_any = (e.dtype.is_long or is_long_dec(a.dtype)
                    or is_long_dec(b.dtype))
        if e.fn in ("add", "subtract"):
            if long_any:
                s = e.dtype.scale
                x, y = as128(a, s), as128(b, s)
                d = I.add(x, y) if e.fn == "add" else I.sub(x, y)
                if not e.dtype.is_long:
                    d = I.to_i64(d)
                return Val(e.dtype, d, valid)
            a2, b2, _ = _decimal_align(a, b)
            return Val(e.dtype, op(a2.data, b2.data), valid)
        if e.fn == "multiply":
            if long_any:
                if not (is_long_dec(a.dtype) or is_long_dec(b.dtype)):
                    # short x short -> exact int128 product
                    d = I.mul_i64(a.data.astype(jnp.int64),
                                  b.data.astype(jnp.int64))
                else:
                    sa = (a.dtype.scale if isinstance(
                        a.dtype, T.DecimalType) else 0)
                    sb = (b.dtype.scale if isinstance(
                        b.dtype, T.DecimalType) else 0)
                    d = I.mul(as128(a, sa), as128(b, sb))
                if not e.dtype.is_long:
                    d = I.to_i64(d)
                return Val(e.dtype, d, valid)
            return Val(e.dtype, a.data * b.data, valid)
    return Val(e.dtype, op(a.data, b.data), valid)


@scalar("add")
def _add(e, args):
    if isinstance(e.dtype, T.DateType):  # date + interval(days)
        a, b = args
        return Val(e.dtype, (a.data + b.data).astype(jnp.int32),
                   and_valid(a.valid, b.valid))
    return _arith(e, args, lambda x, y: x + y)


@scalar("subtract")
def _sub(e, args):
    if isinstance(e.dtype, T.DateType):
        a, b = args
        return Val(e.dtype, (a.data - b.data).astype(jnp.int32),
                   and_valid(a.valid, b.valid))
    return _arith(e, args, lambda x, y: x - y)


@scalar("multiply")
def _mul(e, args):
    return _arith(e, args, lambda x, y: x * y)


@scalar("divide")
def _div(e, args):
    a, b = args
    valid = and_valid(a.valid, b.valid)
    if isinstance(e.dtype, T.DoubleType):
        af, bf = cast_val(a, T.DOUBLE), cast_val(b, T.DOUBLE)
        # division by zero is an error in SQL; mask it as null to keep the
        # kernel total, matching masked-row semantics
        safe = jnp.where(bf.data == 0.0, 1.0, bf.data)
        return Val(e.dtype, af.data / safe,
                   and_valid(valid, bf.data != 0.0))
    if isinstance(e.dtype, T.DecimalType):
        # decimal / decimal at result scale s: (a * 10^(s + sb - sa)) / b,
        # rounded half up (reference DecimalOperators.divideShortShortShort)
        sa = a.dtype.scale if isinstance(a.dtype, T.DecimalType) else 0
        sb = b.dtype.scale if isinstance(b.dtype, T.DecimalType) else 0
        s = e.dtype.scale
        if (e.dtype.is_long or is_long_dec(a.dtype)
                or is_long_dec(b.dtype)
                or s + sb - sa + (a.dtype.precision if isinstance(
                    a.dtype, T.DecimalType) else 19) > 18):
            from presto_tpu.ops import int128 as I
            num = I.rescale_up(as128(a, sa), s + sb - sa)
            den = as128(b, sb)
            bz = I.eq(den, jnp.zeros_like(den))
            q = I.div_round_half_up(num, den)
            if not e.dtype.is_long:
                q = I.to_i64(q)
            return Val(e.dtype, q, and_valid(valid, ~bz))
        num = a.data * (10 ** (s + sb - sa))
        den = jnp.where(b.data == 0, 1, b.data)
        q = jnp.where(
            (num >= 0) == (den >= 0),
            (jnp.abs(num) + jnp.abs(den) // 2) // jnp.abs(den),
            -((jnp.abs(num) + jnp.abs(den) // 2) // jnp.abs(den)))
        return Val(e.dtype, q, and_valid(valid, b.data != 0))
    # SQL integer division truncates toward zero (floor differs on
    # negatives)
    safe = jnp.where(b.data == 0, 1, b.data)
    q = jnp.abs(a.data) // jnp.abs(safe)
    q = jnp.where((a.data >= 0) == (safe >= 0), q, -q)
    return Val(e.dtype, q, and_valid(valid, b.data != 0))


@scalar("modulus")
def _mod(e, args):
    a, b = args
    if isinstance(e.dtype, T.DoubleType):
        a, b = cast_val(a, T.DOUBLE), cast_val(b, T.DOUBLE)
    elif (is_long_dec(a.dtype) or is_long_dec(b.dtype)
          or is_long_dec(e.dtype)):
        # LONG decimal remainder via int128 (the int64 align/fmod
        # below would broadcast over the [n,2] limb arrays and decode
        # garbage — ADVICE r5 medium). Scales align up to the result
        # scale s = max(sa, sb); the remainder of the aligned values
        # is already at scale s (= e.dtype.scale by the planner's %
        # derivation).
        from presto_tpu.ops import int128 as I
        sa = a.dtype.scale if isinstance(a.dtype, T.DecimalType) else 0
        sb = b.dtype.scale if isinstance(b.dtype, T.DecimalType) else 0
        s = max(sa, sb)
        pa = (a.dtype.precision
              if isinstance(a.dtype, T.DecimalType) else 19)
        pb = (b.dtype.precision
              if isinstance(b.dtype, T.DecimalType) else 19)
        need = max(pa + s - sa, pb + s - sb)
        if need > 38:
            # the planner rejects `%` with this shape at plan time;
            # this guards the mod() function route to the same seam —
            # aligning past 38 digits wraps int128 into a silently
            # wrong remainder
            raise NotImplementedError(
                f"decimal remainder aligning {a.dtype} and {b.dtype} "
                f"needs {need} digits, exceeding the maximum decimal "
                f"precision 38")
        x, y = as128(a, s), as128(b, s)
        bz = I.eq(y, jnp.zeros_like(y))
        r = I.rem_trunc(x, y)
        out = r if is_long_dec(e.dtype) else I.to_i64(r)
        return Val(e.dtype, out, and_valid(a.valid, b.valid, ~bz))
    elif isinstance(a.dtype, T.DecimalType) or \
            isinstance(b.dtype, T.DecimalType):
        # align scales: (a*f) mod (b*f) = f*(a mod b), so the scaled-
        # int result is already at the common scale of e.dtype
        a, b, _ = _decimal_align(a, b)
    safe = jnp.where(b.data == 0, jnp.ones_like(b.data), b.data)
    # fmod truncates toward zero (result takes the dividend's sign) —
    # SQL/reference mod semantics; % would floor-mod
    out = jnp.fmod(a.data, safe)
    nz = b.data != 0
    if getattr(nz, "ndim", 1) == 0 and getattr(out, "ndim", 0) > 0:
        nz = jnp.broadcast_to(nz, out.shape)  # literal divisor
    return Val(e.dtype, out, and_valid(a.valid, b.valid, nz))


@scalar("negate")
def _neg(e, args):
    (a,) = args
    if is_long_dec(e.dtype):
        from presto_tpu.ops import int128 as I
        return Val(e.dtype, I.neg(a.data), a.valid)
    return Val(e.dtype, -a.data, a.valid)


def _compare(e: ir.Call, args: list[Val], op, eq_only_op) -> Val:
    a, b = args
    valid = and_valid(a.valid, b.valid)
    if a.is_string or b.is_string:
        if e.fn in ("eq", "neq"):
            da, db = _align_strings(a, b)
            return _bool(eq_only_op(da, db), valid)
        # ordering: same dictionary -> codes are collation-ordered; against a
        # literal -> host-evaluate the predicate over the dictionary
        if a.dictionary is b.dictionary:
            return _bool(op(a.data, b.data), valid)
        if len(b.dictionary) == 1:
            s = str(b.dictionary[0])
            out = _dict_predicate(a, lambda d: op(d, np.asarray(s)))
            return _bool(out.data, valid)
        if len(a.dictionary) == 1:
            s = str(a.dictionary[0])
            out = _dict_predicate(b, lambda d: op(np.asarray(s), d))
            return _bool(out.data, valid)
        raise NotImplementedError(
            "ordering comparison between differently-encoded strings")
    da, db = a.data, b.data
    if isinstance(a.dtype, T.DecimalType) or isinstance(b.dtype, T.DecimalType):
        if isinstance(a.dtype, T.DoubleType) or isinstance(b.dtype, T.DoubleType):
            da = cast_val(a, T.DOUBLE).data
            db = cast_val(b, T.DOUBLE).data
        elif is_long_dec(a.dtype) or is_long_dec(b.dtype):
            from presto_tpu.ops import int128 as I
            sc = max(a.dtype.scale if isinstance(a.dtype, T.DecimalType)
                     else 0,
                     b.dtype.scale if isinstance(b.dtype, T.DecimalType)
                     else 0)
            x, y = as128(a, sc), as128(b, sc)
            res = {"eq": I.eq(x, y), "neq": ~I.eq(x, y),
                   "lt": I.lt(x, y), "lte": I.le(x, y),
                   "gt": I.lt(y, x), "gte": I.le(y, x)}[e.fn]
            return _bool(res, valid)
        else:
            a2, b2, _ = _decimal_align(a, b)
            da, db = a2.data, b2.data
    elif isinstance(a.dtype, T.DoubleType) != isinstance(b.dtype, T.DoubleType):
        da = cast_val(a, T.DOUBLE).data
        db = cast_val(b, T.DOUBLE).data
    elif {type(a.dtype), type(b.dtype)} == {T.DateType, T.TimestampType}:
        # align epoch-days against epoch-micros (DATE widens)
        da = cast_val(a, T.TIMESTAMP).data
        db = cast_val(b, T.TIMESTAMP).data
    return _bool(op(da, db), valid)


@scalar("eq")
def _eq(e, args):
    return _compare(e, args, lambda x, y: x == y, lambda x, y: x == y)


@scalar("neq")
def _neq(e, args):
    return _compare(e, args, lambda x, y: x != y, lambda x, y: x != y)


@scalar("lt")
def _lt(e, args):
    return _compare(e, args, lambda x, y: x < y, None)


@scalar("lte")
def _lte(e, args):
    return _compare(e, args, lambda x, y: x <= y, None)


@scalar("gt")
def _gt(e, args):
    return _compare(e, args, lambda x, y: x > y, None)


@scalar("gte")
def _gte(e, args):
    return _compare(e, args, lambda x, y: x >= y, None)


@scalar("and")
def _and(e, args):
    # Kleene: FALSE dominates NULL
    data, valid = None, None
    for v in args:
        d = v.data
        vl = v.valid
        if data is None:
            data, valid = d, vl
            continue
        new_data = data & d
        if valid is None and vl is None:
            new_valid = None
        else:
            av = jnp.ones_like(data) if valid is None else valid
            bv = jnp.ones_like(d) if vl is None else vl
            known_false = (av & ~data) | (bv & ~d)
            new_valid = (av & bv) | known_false
        data, valid = new_data, new_valid
    return _bool(data, valid)


@scalar("or")
def _or(e, args):
    data, valid = None, None
    for v in args:
        d = v.data
        vl = v.valid
        if data is None:
            data, valid = d, vl
            continue
        new_data = data | d
        if valid is None and vl is None:
            new_valid = None
        else:
            av = jnp.ones_like(data) if valid is None else valid
            bv = jnp.ones_like(d) if vl is None else vl
            known_true = (av & data) | (bv & d)
            new_valid = (av & bv) | known_true
        data, valid = new_data, new_valid
    return _bool(data, valid)


@scalar("not")
def _not(e, args):
    (a,) = args
    return _bool(~a.data, a.valid)


@scalar("like")
def _like(e, args):
    from presto_tpu.templates.runtime import (ParamDictionary,
                                              TemplateError, mask_length)
    col, pat = args[0], args[1]
    if isinstance(pat.dictionary, ParamDictionary):
        # hoisted pattern (templates/analysis.LikePattern): the traced
        # value is the pattern's mask over col's dictionary, bound on
        # the host for every execution against the dictionary recorded
        # here, so every pattern shares this program
        pat.dictionary.bind(col.dictionary)
        if pat.data.shape[-1] != mask_length(col.dictionary):
            raise TemplateError(
                f"LIKE mask of {pat.data.shape[-1]} entries over a "
                f"dictionary of {len(col.dictionary)}")
        return _bool(pat.data[col.data], col.valid)
    # a plan that was not templated (plan_templates off, EXPLAIN
    # ANALYZE) bakes every literal, this one too
    escape = str(args[2].dictionary[0]) if len(args) > 2 else None
    mask = like_mask(col.dictionary, str(pat.dictionary[0]), escape)
    return _bool(jnp.asarray(mask)[col.data], col.valid)


@scalar("regexp_like")
def _regexp_like(e, args):
    col, pat = args[0], args[1]
    if not isinstance(e.args[1], ir.Literal):
        raise NotImplementedError("regexp_like with non-literal pattern")
    rx = re.compile(str(pat.dictionary[0]))
    return _dict_predicate(
        col, lambda d: np.array([rx.search(s) is not None for s in d]))


@scalar("regexp_replace")
def _regexp_replace(e, args):
    col = args[0]
    if not all(isinstance(a, ir.Literal) for a in e.args[1:]):
        raise NotImplementedError(
            "regexp_replace with non-literal pattern")
    rx = re.compile(str(args[1].dictionary[0]))
    repl = str(args[2].dictionary[0]) if len(args) > 2 else ""
    # SQL replacement groups use $1; python re uses \1
    repl_py = re.sub(r"\$(\d+)", r"\\\1", repl)
    return _dict_transform(
        col, lambda d: np.array([rx.sub(repl_py, s) for s in d], object))


@scalar("regexp_extract")
def _regexp_extract(e, args):
    col = args[0]
    if not all(isinstance(a, ir.Literal) for a in e.args[1:]):
        raise NotImplementedError(
            "regexp_extract with non-literal pattern")
    rx = re.compile(str(args[1].dictionary[0]))
    group = int(e.args[2].value) if len(e.args) > 2 else 0

    def f(d):
        out = []
        for s in d:
            m = rx.search(s)
            out.append("" if m is None else (m.group(group) or ""))
        return np.array(out, object)

    # NULL result for non-matching rows (reference regexp_extract
    # returns NULL when the pattern does not match)
    matched = _dict_predicate(
        col, lambda d: np.array([rx.search(s) is not None for s in d]))
    v = _dict_transform(col, f)
    valid = (matched.data if v.valid is None
             else (v.valid & matched.data))
    return Val(v.dtype, v.data, valid, v.dictionary)


def _string_contains(e, args):
    col = args[0]
    if not isinstance(e.args[1], ir.Literal):
        raise NotImplementedError("contains with non-literal needle")
    needle = str(args[1].dictionary[0])
    return _dict_predicate(
        col, lambda d: np.array([needle in s for s in d]))


@scalar("lpad")
def _lpad(e, args):
    col = args[0]
    if not all(isinstance(a, ir.Literal) for a in e.args[1:]):
        raise NotImplementedError("lpad with non-literal arguments")
    n = int(e.args[1].value)
    fill = str(args[2].dictionary[0]) if len(args) > 2 else " "
    return _dict_transform(col, lambda d: np.array(
        [s.rjust(n, fill)[:n] for s in d], object))


@scalar("rpad")
def _rpad(e, args):
    col = args[0]
    if not all(isinstance(a, ir.Literal) for a in e.args[1:]):
        raise NotImplementedError("rpad with non-literal arguments")
    n = int(e.args[1].value)
    fill = str(args[2].dictionary[0]) if len(args) > 2 else " "
    return _dict_transform(col, lambda d: np.array(
        [s.ljust(n, fill)[:n] for s in d], object))


@scalar("split_part")
def _split_part(e, args):
    col = args[0]
    if not all(isinstance(a, ir.Literal) for a in e.args[1:]):
        raise NotImplementedError("split_part with non-literal arguments")
    sep = str(args[1].dictionary[0])
    idx = int(e.args[2].value)  # 1-based

    def f(d):
        out = []
        for s in d:
            parts = s.split(sep)
            out.append(parts[idx - 1] if 0 < idx <= len(parts) else "")
        return np.array(out, object)

    return _dict_transform(col, f)


@scalar("between")
def _between(e, args):
    v, lo, hi = args
    ge = _compare(ir.Call(T.BOOLEAN, "gte", ()), [v, lo],
                  lambda x, y: x >= y, None)
    le = _compare(ir.Call(T.BOOLEAN, "lte", ()), [v, hi],
                  lambda x, y: x <= y, None)
    return _and(e, [ge, le])


# -- date/time ---------------------------------------------------------------


def _civil_from_days(days):
    """Hinnant's civil_from_days, vectorised: epoch days -> (y, m, d)."""
    z = days.astype(jnp.int64) + 719468
    era = jnp.floor_divide(z, 146097)
    doe = z - era * 146097
    yoe = (doe - doe // 1460 + doe // 36524 - doe // 146096) // 365
    y = yoe + era * 400
    doy = doe - (365 * yoe + yoe // 4 - yoe // 100)
    mp = (5 * doy + 2) // 153
    d = doy - (153 * mp + 2) // 5 + 1
    m = mp + jnp.where(mp < 10, 3, -9)
    y = y + (m <= 2)
    return y, m, d


def _days_of(v: Val):
    """Epoch days of a DATE or TIMESTAMP Val (floor for pre-epoch)."""
    if isinstance(v.dtype, T.TimestampType):
        return jnp.floor_divide(v.data, T.US_PER_DAY)
    return v.data


def _us_of(v: Val):
    """Epoch micros of a DATE or TIMESTAMP Val."""
    if isinstance(v.dtype, T.DateType):
        return v.data.astype(jnp.int64) * T.US_PER_DAY
    return v.data


def _tod_us(v: Val):
    """Micros since midnight of a TIME/DATE/TIMESTAMP Val."""
    if isinstance(v.dtype, T.TimeType):
        return v.data
    return _us_of(v) - _days_of(v) * T.US_PER_DAY


def _days_from_civil(y, m, d):
    """Inverse of _civil_from_days (Hinnant's days_from_civil)."""
    y = y - (m <= 2)
    era = jnp.floor_divide(y, 400)
    yoe = y - era * 400
    mp = m + jnp.where(m > 2, -3, 9)
    doy = (153 * mp + 2) // 5 + d - 1
    doe = yoe * 365 + yoe // 4 - yoe // 100 + doy
    return era * 146097 + doe - 719468


@scalar("add_months")
def _add_months(e, args):
    """date + N months [+ D days] with day-of-month clamping (reference
    DateTimeFunctions.addFieldValueDate semantics)."""
    a, months = args[0], args[1]
    days = args[2] if len(args) > 2 else None
    y, m, d = _civil_from_days(a.data)
    total = (y * 12 + (m - 1)) + months.data
    ny = jnp.floor_divide(total, 12)
    nm = total - ny * 12 + 1
    # clamp day to target month length
    month_days = jnp.asarray(
        [31, 28, 31, 30, 31, 30, 31, 31, 30, 31, 30, 31])[nm - 1]
    leap = ((ny % 4 == 0) & (ny % 100 != 0)) | (ny % 400 == 0)
    month_days = jnp.where((nm == 2) & leap, 29, month_days)
    nd = jnp.minimum(d, month_days)
    out = _days_from_civil(ny, nm, nd)
    if days is not None:
        out = out + days.data
    return Val(e.dtype, out.astype(jnp.int32), a.valid)


@scalar("year")
def _year(e, args):
    (a,) = args
    y, _, _ = _civil_from_days(_days_of(a))
    return Val(e.dtype, y, a.valid)


@scalar("month")
def _month(e, args):
    (a,) = args
    _, m, _ = _civil_from_days(_days_of(a))
    return Val(e.dtype, m, a.valid)


@scalar("day")
def _day(e, args):
    (a,) = args
    _, _, d = _civil_from_days(_days_of(a))
    return Val(e.dtype, d, a.valid)


@scalar("hour")
def _hour(e, args):
    (a,) = args
    us = _tod_us(a)
    return Val(e.dtype, us // T.US_PER_HOUR, a.valid)


@scalar("minute")
def _minute(e, args):
    (a,) = args
    us = _tod_us(a)
    return Val(e.dtype, (us // T.US_PER_MINUTE) % 60, a.valid)


@scalar("second")
def _second(e, args):
    (a,) = args
    us = _tod_us(a)
    return Val(e.dtype, (us // T.US_PER_SECOND) % 60, a.valid)


@scalar("millisecond")
def _millisecond(e, args):
    (a,) = args
    us = _tod_us(a)
    return Val(e.dtype, (us // 1000) % 1000, a.valid)


def _trunc_days(unit: str, days):
    """Truncate epoch days to the start of a civil unit (day stays)."""
    y, m, _d = _civil_from_days(days)
    one = jnp.ones_like(y)
    if unit == "year":
        return _days_from_civil(y, one, one)
    if unit == "quarter":
        return _days_from_civil(y, ((m - 1) // 3) * 3 + 1, one)
    if unit == "month":
        return _days_from_civil(y, m, one)
    if unit == "week":  # ISO week starts Monday; epoch day 0 = Thursday
        d = days.astype(jnp.int64)
        return d - ((d + 3) % 7)
    raise NotImplementedError(f"date_trunc unit {unit}")


@scalar("date_trunc")
def _date_trunc(e, args):
    unit = str(e.args[0].value).lower()
    v = args[1]
    if isinstance(v.dtype, T.DateType):
        if unit == "day":
            return v
        out = _trunc_days(unit, v.data)
        return Val(e.dtype, out.astype(jnp.int32), v.valid)
    us_per = {"second": T.US_PER_SECOND, "minute": T.US_PER_MINUTE,
              "hour": T.US_PER_HOUR, "day": T.US_PER_DAY}.get(unit)
    if us_per is not None:
        out = jnp.floor_divide(v.data, us_per) * us_per
        return Val(e.dtype, out, v.valid)
    out = _trunc_days(unit, _days_of(v)) * T.US_PER_DAY
    return Val(e.dtype, out, v.valid)


def _add_months_days(days, months):
    """days + months with day-of-month clamping (shared by add_months,
    ts_add_months, date_add)."""
    y, m, d = _civil_from_days(days)
    total = (y * 12 + (m - 1)) + months
    ny = jnp.floor_divide(total, 12)
    nm = total - ny * 12 + 1
    month_days = jnp.asarray(
        [31, 28, 31, 30, 31, 30, 31, 31, 30, 31, 30, 31])[nm - 1]
    leap = ((ny % 4 == 0) & (ny % 100 != 0)) | (ny % 400 == 0)
    month_days = jnp.where((nm == 2) & leap, 29, month_days)
    return _days_from_civil(ny, nm, jnp.minimum(d, month_days))


@scalar("ts_add_months")
def _ts_add_months(e, args):
    a, months = args
    days = _days_of(a)
    tod = a.data - days * T.US_PER_DAY
    out = _add_months_days(days, months.data) * T.US_PER_DAY + tod
    return Val(e.dtype, out, and_valid(a.valid, months.valid))


@scalar("date_add")
def _date_add(e, args):
    unit = str(e.args[0].value).lower()
    if unit.endswith("s"):
        unit = unit[:-1]
    n, v = args[1], args[2]
    valid = and_valid(n.valid, v.valid)
    months = {"year": 12, "quarter": 3, "month": 1}.get(unit)
    if isinstance(v.dtype, T.DateType):
        if months is not None:
            out = _add_months_days(v.data, n.data * months)
            return Val(e.dtype, out.astype(jnp.int32), valid)
        per_day = {"day": 1, "week": 7}.get(unit)
        if per_day is None:
            raise NotImplementedError(
                f"date_add({unit}) on a date value")
        return Val(e.dtype, (v.data + n.data * per_day)
                   .astype(jnp.int32), valid)
    if months is not None:
        days = _days_of(v)
        tod = v.data - days * T.US_PER_DAY
        out = _add_months_days(days, n.data * months) \
            * T.US_PER_DAY + tod
        return Val(e.dtype, out, valid)
    us_per = {"second": T.US_PER_SECOND, "minute": T.US_PER_MINUTE,
              "hour": T.US_PER_HOUR, "day": T.US_PER_DAY,
              "week": 7 * T.US_PER_DAY,
              "millisecond": 1000}.get(unit)
    if us_per is None:
        raise NotImplementedError(f"date_add unit {unit}")
    return Val(e.dtype, v.data + n.data * us_per, valid)


@scalar("date_diff")
def _date_diff(e, args):
    unit = str(e.args[0].value).lower()
    if unit.endswith("s"):
        unit = unit[:-1]
    a, b = args[1], args[2]
    valid = and_valid(a.valid, b.valid)
    if unit in ("year", "quarter", "month"):
        # calendar-component difference (reference DateTimeFunctions
        # diffDate via epoch-month arithmetic)
        ya, ma, _ = _civil_from_days(_days_of(a))
        yb, mb, _ = _civil_from_days(_days_of(b))
        months = (yb * 12 + mb) - (ya * 12 + ma)
        div = {"year": 12, "quarter": 3, "month": 1}[unit]
        return Val(e.dtype, (months // div).astype(jnp.int64), valid)
    if unit in ("day", "week") and isinstance(a.dtype, T.DateType) \
            and isinstance(b.dtype, T.DateType):
        d = (b.data - a.data).astype(jnp.int64)
        if unit == "week":  # truncate toward zero, like the us branch
            d = jnp.where(d >= 0, d // 7, -((-d) // 7))
        return Val(e.dtype, d, valid)
    us_per = {"second": T.US_PER_SECOND, "minute": T.US_PER_MINUTE,
              "hour": T.US_PER_HOUR, "day": T.US_PER_DAY,
              "week": 7 * T.US_PER_DAY,
              "millisecond": 1000}.get(unit)
    if us_per is None:
        raise NotImplementedError(f"date_diff unit {unit}")
    diff = _us_of(b) - _us_of(a)
    # truncate toward zero (reference diffTimestamp semantics)
    out = jnp.where(diff >= 0, diff // us_per, -((-diff) // us_per))
    return Val(e.dtype, out, valid)


@scalar("from_unixtime")
def _from_unixtime(e, args):
    (a,) = args
    sec = a.data.astype(jnp.float64) / (
        a.dtype.unscale_factor if isinstance(a.dtype, T.DecimalType)
        else 1)
    return Val(e.dtype, jnp.round(sec * T.US_PER_SECOND)
               .astype(jnp.int64), a.valid)


@scalar("to_unixtime")
def _to_unixtime(e, args):
    (a,) = args
    return Val(e.dtype, _us_of(a).astype(jnp.float64) / T.US_PER_SECOND,
               a.valid)


# MySQL-style date_format specifiers with day granularity (time-of-day
# specifiers need per-row strings, which have no dictionary encoding)
_MYSQL_STRFTIME = {
    "%Y": "%Y", "%y": "%y", "%m": "%m", "%c": "%-m", "%d": "%d",
    "%e": "%-d", "%j": "%j", "%M": "%B", "%b": "%b", "%W": "%A",
    "%a": "%a",
}
_DATE_FORMAT_LO = -40179  # 1860-01-01
_DATE_FORMAT_HI = 80468   # 2190-04-25
_DATE_FORMAT_CACHE: dict[str, np.ndarray] = {}


@scalar("date_format")
def _date_format(e, args):
    import datetime
    import re

    if not isinstance(e.args[1], ir.Literal):
        raise NotImplementedError("date_format with non-literal format")
    fmt = str(e.args[1].value)
    v = args[0]
    if re.search(r"%[HhiSsfprT]", fmt):
        raise NotImplementedError(
            "date_format with time-of-day specifiers")
    lut = _DATE_FORMAT_CACHE.get(fmt)
    if lut is None:
        pyfmt = re.sub(
            "%.", lambda m: _MYSQL_STRFTIME.get(m.group(0), m.group(0)),
            fmt)
        base = datetime.date(1970, 1, 1).toordinal()
        lut = np.array(
            [datetime.date.fromordinal(base + d).strftime(pyfmt)
             for d in range(_DATE_FORMAT_LO, _DATE_FORMAT_HI)], object)
        if len(_DATE_FORMAT_CACHE) > 16:
            _DATE_FORMAT_CACHE.clear()
        _DATE_FORMAT_CACHE[fmt] = lut
    days = _days_of(v)
    code = (days - _DATE_FORMAT_LO).astype(jnp.int32)
    in_range = (code >= 0) & (code < len(lut))
    return Val(T.VARCHAR, jnp.clip(code, 0, len(lut) - 1),
               and_valid(v.valid, in_range), lut)


# -- strings -----------------------------------------------------------------


@scalar("substring")
def _substring(e, args):
    col = args[0]
    # start/length must be literals: read them from the IR, not traced
    # values (string ops run host-side over the dictionary)
    if not all(isinstance(a, ir.Literal) for a in e.args[1:]):
        raise NotImplementedError("substring with non-literal start/length")
    s0 = int(e.args[1].value)  # SQL 1-based
    ln = int(e.args[2].value) if len(e.args) > 2 else None

    def f(d):
        if ln is None:
            return np.array([s[s0 - 1:] for s in d], object)
        return np.array([s[s0 - 1:s0 - 1 + ln] for s in d], object)

    return _dict_transform(col, f)


@scalar("lower")
def _lower(e, args):
    return _dict_transform(args[0], lambda d: np.char.lower(d).astype(object))


@scalar("upper")
def _upper(e, args):
    return _dict_transform(args[0], lambda d: np.char.upper(d).astype(object))


@scalar("length")
def _length(e, args):
    (col,) = args
    lut = jnp.asarray(np.char.str_len(col.dictionary.astype("U"))
                      .astype(np.int64))
    return Val(e.dtype, lut[col.data], col.valid)


_CONCAT_PRODUCT_MAX = 1 << 16


@scalar("concat")
def _concat(e, args):
    a, b = args
    if a.is_array and b.is_array:
        return _array_concat_fn(e, args)
    if len(a.dictionary) == 1:  # literal + column
        s = str(a.dictionary[0])
        return _dict_transform(b, lambda d: np.array([s + x for x in d], object))
    if len(b.dictionary) == 1:
        s = str(b.dictionary[0])
        return _dict_transform(a, lambda d: np.array([x + s for x in d], object))
    # two real columns: product dictionary, code = ca * |db| + cb. The
    # dictionary must be static (host-side), so it enumerates all pairs;
    # bounded to keep degenerate high-cardinality concats from exploding
    # (the reference's per-row VarcharConcat has no such table at all —
    # dictionary encoding is this engine's string substrate).
    na, nb = len(a.dictionary), len(b.dictionary)
    if na * nb > _CONCAT_PRODUCT_MAX:
        raise NotImplementedError(
            f"concat of string columns with {na}x{nb} dictionary product "
            f"(> {_CONCAT_PRODUCT_MAX})")
    d = np.array([str(x) + str(y)
                  for x in a.dictionary for y in b.dictionary], object)
    codes = a.data.astype(jnp.int32) * nb + b.data.astype(jnp.int32)
    return Val(e.dtype, codes, and_valid(a.valid, b.valid), d)


@scalar("trim")
def _trim(e, args):
    return _dict_transform(
        args[0], lambda d: np.array([str(s).strip() for s in d], object))


@scalar("ltrim")
def _ltrim(e, args):
    return _dict_transform(
        args[0], lambda d: np.array([str(s).lstrip() for s in d], object))


@scalar("rtrim")
def _rtrim(e, args):
    return _dict_transform(
        args[0], lambda d: np.array([str(s).rstrip() for s in d], object))


@scalar("reverse")
def _reverse(e, args):
    return _dict_transform(
        args[0], lambda d: np.array([str(s)[::-1] for s in d], object))


@scalar("replace")
def _replace(e, args):
    col = args[0]
    if not all(isinstance(a, ir.Literal) for a in e.args[1:]):
        raise NotImplementedError("replace with non-literal patterns")
    pat = str(e.args[1].value)
    rep = str(e.args[2].value) if len(e.args) > 2 else ""
    return _dict_transform(
        col, lambda d: np.array([str(s).replace(pat, rep) for s in d],
                                object))


@scalar("starts_with")
def _starts_with(e, args):
    col = args[0]
    if not isinstance(e.args[1], ir.Literal):
        raise NotImplementedError("starts_with with non-literal prefix")
    prefix = str(e.args[1].value)
    return _dict_predicate(
        col, lambda d: np.array([str(s).startswith(prefix) for s in d]))


@scalar("strpos")
def _strpos(e, args):
    col = args[0]
    if not isinstance(e.args[1], ir.Literal):
        raise NotImplementedError("strpos with non-literal needle")
    needle = str(e.args[1].value)
    lut = jnp.asarray(np.array(
        [str(s).find(needle) + 1 for s in col.dictionary], np.int64))
    return Val(e.dtype, lut[col.data], col.valid)


@scalar("coalesce")
def _coalesce(e, args):
    if not any(a.is_string for a in args) \
            and not isinstance(e.dtype, T.VarcharType):
        # physical alignment to the result type (e.g. a DATE branch
        # under a TIMESTAMP result must not merge days with micros)
        args = [cast_val(a, e.dtype) for a in args]
    out = args[-1]
    for v in args[:-1][::-1]:
        if v.valid is not None:
            take = v.valid
        elif is_long_dec(e.dtype) and getattr(v.data, "ndim", 1) == 1:
            take = jnp.asarray(True)  # scalar limb pair [2]
        else:
            take = jnp.ones(v.data.shape[:1] or (), dtype=bool)
        if v.is_string or out.is_string:
            v, out = _merge_dicts(v, out)
        data = where_data(take, v.data, out.data,
                          long=is_long_dec(e.dtype))
        ov = (jnp.ones_like(take) if out.valid is None else out.valid)
        valid = jnp.where(take, True, ov)
        out = Val(e.dtype, data, valid, out.dictionary)
    return out


@scalar("row_index")
def _row_index(e, args):
    """Synthetic per-row identifier (planner-internal; backs the
    residual-EXISTS decorrelation when the outer relation has no
    unique key). Under a mesh axis the shard index lands in the high
    bits so ids are GLOBALLY unique across shards."""
    import jax as _jax
    (a,) = args
    n = a.data.shape[0]
    idx = jnp.arange(n, dtype=jnp.int64)
    try:
        shard = _jax.lax.axis_index("d").astype(jnp.int64)
        idx = idx + (shard << jnp.int64(40))
    except NameError:
        pass
    return Val(e.dtype, idx, None)


@scalar("abs")
def _abs(e, args):
    (a,) = args
    if is_long_dec(e.dtype):
        from presto_tpu.ops import int128 as I
        return Val(e.dtype, I.abs_(a.data), a.valid)
    return Val(e.dtype, jnp.abs(a.data), a.valid)


def _as_f64(v: Val):
    return cast_val(v, T.DOUBLE).data


def _mathfn(name, op, arity=1):
    """DOUBLE-valued math function (reference MathFunctions.java)."""
    @scalar(name)
    def _f(e, args, _op=op, _n=arity):
        if _n == 1:
            (a,) = args
            return Val(e.dtype, _op(_as_f64(a)), a.valid)
        a, b = args
        return Val(e.dtype, _op(_as_f64(a), _as_f64(b)),
                   and_valid(a.valid, b.valid))
    return _f


_mathfn("sqrt", jnp.sqrt)
_mathfn("cbrt", jnp.cbrt)
_mathfn("exp", jnp.exp)
_mathfn("ln", jnp.log)
_mathfn("log10", jnp.log10)
_mathfn("log2", jnp.log2)
_mathfn("floor", jnp.floor)
_mathfn("ceiling", jnp.ceil)
_mathfn("ceil", jnp.ceil)
_mathfn("truncate", jnp.trunc)
_mathfn("power", jnp.power, arity=2)
_mathfn("pow", jnp.power, arity=2)


@scalar("sign")
def _sign(e, args):
    (a,) = args
    return Val(e.dtype, jnp.sign(a.data).astype(a.data.dtype), a.valid)


@scalar("mod")
def _mod_alias(e, args):
    return _mod(e, args)


@scalar("greatest")
@scalar("least")
def _greatest_least(e, args):
    # NULL if any argument is NULL (reference semantics)
    op = jnp.maximum if e.fn == "greatest" else jnp.minimum
    if any(a.is_string for a in args):
        # merged dictionary is sorted, so codes are collation-ordered
        out = args[0]
        valid = out.valid
        for v in args[1:]:
            v, out = _merge_dicts(v, out)
            valid = and_valid(valid, v.valid)
            out = Val(e.dtype, op(out.data, v.data), None,
                      out.dictionary)
        return Val(e.dtype, out.data, valid, out.dictionary)
    out = cast_val(args[0], e.dtype)
    valid = out.valid
    for v in args[1:]:
        v = cast_val(v, e.dtype)
        if is_long_dec(e.dtype):
            from presto_tpu.ops import int128 as I
            sel = I.lt(out.data, v.data)
            pick_v = sel if e.fn == "greatest" else ~sel
            d = where_data(pick_v, v.data, out.data, long=True)
            out = Val(e.dtype, d, None)
        else:
            out = Val(e.dtype, op(out.data, v.data), None)
        valid = and_valid(valid, v.valid)
    return Val(e.dtype, out.data, valid)


@scalar("nullif")
def _nullif(e, args):
    a, b = args
    eqv = _compare(ir.Call(T.BOOLEAN, "eq", e.args), args,
                   lambda x, y: x == y, lambda x, y: x == y)
    both = eqv.data if eqv.valid is None else (eqv.data & eqv.valid)
    valid = (jnp.ones_like(both) if a.valid is None else a.valid) & ~both
    return Val(e.dtype, a.data, valid, a.dictionary)


@scalar("quarter")
def _quarter(e, args):
    (a,) = args
    _, m, _ = _civil_from_days(_days_of(a))
    return Val(e.dtype, (m - 1) // 3 + 1, a.valid)


@scalar("day_of_week")
def _day_of_week(e, args):
    # ISO: Monday=1..Sunday=7; epoch 1970-01-01 was a Thursday
    (a,) = args
    dow = (_days_of(a).astype(jnp.int64) + 3) % 7 + 1
    return Val(e.dtype, dow, a.valid)


@scalar("day_of_year")
def _day_of_year(e, args):
    (a,) = args
    days = _days_of(a)
    y, _, _ = _civil_from_days(days)
    jan1 = _days_from_civil(y, jnp.ones_like(y), jnp.ones_like(y))
    return Val(e.dtype, days.astype(jnp.int64) - jan1 + 1, a.valid)


@scalar("week")
def _week(e, args):
    # ISO week number of the year (reference week_of_year)
    (a,) = args
    d = _days_of(a).astype(jnp.int64)
    # Thursday of this row's ISO week determines the ISO year
    thursday = d - ((d + 3) % 7) + 3
    y, _, _ = _civil_from_days(thursday)
    jan1 = _days_from_civil(y, jnp.ones_like(y), jnp.ones_like(y))
    return Val(e.dtype, (thursday - jan1) // 7 + 1, a.valid)


@scalar("round")
def _round(e, args):
    a = args[0]
    digits = 0
    if len(e.args) > 1:
        if not isinstance(e.args[1], ir.Literal):
            raise NotImplementedError("round with non-literal digits")
        digits = int(e.args[1].value)
    if isinstance(a.dtype, T.DecimalType):
        drop = a.dtype.scale - digits
        if drop <= 0:
            return Val(e.dtype, a.data, a.valid)
        keep_scale = (isinstance(e.dtype, T.DecimalType)
                      and e.dtype.scale == a.dtype.scale)
        if is_long_dec(a.dtype) or is_long_dec(e.dtype):
            # LONG decimals are [n,2] int128 limb arrays: the int64
            # _div_round below would divide the limbs elementwise and
            # return garbage (ADVICE r5 high) — round through int128
            from presto_tpu.ops import int128 as I
            d = (a.data if is_long_dec(a.dtype)
                 else I.from_i64(a.data.astype(jnp.int64)))
            if drop > 38:
                # 10^drop exceeds int128 (wraps into a garbage
                # divisor), but |x| < 10^38 <= 0.5 * 10^drop, so every
                # value half-up rounds to exactly zero
                q = jnp.zeros_like(d)
                if is_long_dec(e.dtype):
                    return Val(e.dtype, q, a.valid)
                return Val(e.dtype, I.to_i64(q), a.valid)
            f = I.from_i64(jnp.int64(10 ** min(drop, 18)))
            if drop > 18:
                f = I.rescale_up(f, drop - 18)
            q = I.div_round_half_up(d, jnp.broadcast_to(f, d.shape))
            if keep_scale:
                q = I.rescale_up(q, drop)
            elif digits < 0:
                # result scale is 0 but the rounding unit is 10^-digits:
                # round(123.45, -1) -> 12 tens -> 120
                q = I.rescale_up(q, -digits)
            if is_long_dec(e.dtype):
                return Val(e.dtype, q, a.valid)
            return Val(e.dtype, I.to_i64(q), a.valid)
        if drop > 18:
            # SHORT decimals hold |x| < 10^18 <= 0.5 * 10^drop: zero,
            # and 10^drop would not fit the int64 divisor anyway
            return Val(e.dtype, jnp.zeros_like(a.data), a.valid)
        # negative digits round to multiples of 10^-digits at scale 0:
        # the quotient counts units of 10^-digits, scale it back up
        mult = ((10 ** drop) if keep_scale
                else (10 ** -digits) if digits < 0 else 1)
        return Val(e.dtype, _div_round(a.data, 10 ** drop) * mult,
                   a.valid)
    f = 10.0 ** digits
    return Val(e.dtype, jnp.round(a.data * f) / f, a.valid)


# --- JSON functions (dictionary transforms over host-side parsing) ---------
# The reference implements these as per-row operators over a JSON slice
# type (operator/scalar/JsonFunctions.java, JsonExtract.java); here JSON
# values are dictionary-encoded strings, so each unique document parses
# exactly ONCE on host at trace time and rows gather the result by code
# — a strictly better fit for columnar repeated-document data.


def _json_path_steps(path: str) -> list:
    """Parse a JSONPath subset: $, .key, [index] (strict or lax head)."""
    if path.startswith("lax ") or path.startswith("strict "):
        path = path.split(" ", 1)[1]
    if not path.startswith("$"):
        raise NotImplementedError(f"unsupported JSON path {path!r}")
    steps: list = []
    i = 1
    while i < len(path):
        if path[i] == ".":
            j = i + 1
            while j < len(path) and path[j] not in ".[":
                j += 1
            steps.append(path[i + 1:j])
            i = j
        elif path[i] == "[":
            j = path.index("]", i)
            body = path[i + 1:j].strip()
            if body.startswith('"') or body.startswith("'"):
                steps.append(body[1:-1])
            else:
                steps.append(int(body))
            i = j + 1
        else:
            raise NotImplementedError(f"unsupported JSON path {path!r}")
    return steps


def _json_eval(doc: str, steps: list):
    """Returns (value, found)."""
    import json
    try:
        v = json.loads(doc)
    except (ValueError, TypeError):
        return None, False
    for s in steps:
        if isinstance(s, int):
            if not isinstance(v, list) or not -len(v) <= s < len(v):
                return None, False
            v = v[s]
        else:
            if not isinstance(v, dict) or s not in v:
                return None, False
            v = v[s]
    return v, True


def _json_lut(col: Val, e, per_doc) -> Val:
    """Gather a per-dictionary-entry (value, found) transform by code;
    rows whose document yields found=False become NULL."""
    import json
    strings = []
    found = np.zeros(len(col.dictionary), dtype=bool)
    for k, doc in enumerate(col.dictionary):
        v, ok = per_doc(str(doc))
        found[k] = ok
        strings.append(v if ok else None)
    lut_valid = jnp.asarray(found)
    row_valid = and_valid(col.valid, lut_valid[col.data])
    if isinstance(e.dtype, T.VarcharType):
        uniq = sorted({s for s in strings if s is not None})
        new_dict = np.asarray(uniq, dtype=object)
        remap = np.asarray(
            [0 if s is None else int(np.searchsorted(uniq, s))
             for s in strings], dtype=np.int32)
        codes = jnp.asarray(remap)[col.data]
        return Val(T.VARCHAR, codes, row_valid, new_dict)
    vals = np.asarray([0 if s is None else s for s in strings],
                      dtype=np.int64)
    return Val(e.dtype, jnp.asarray(vals)[col.data], row_valid)


def _literal_path(e, idx: int = 1) -> list:
    if not isinstance(e.args[idx], ir.Literal):
        raise NotImplementedError("JSON path must be a literal")
    return _json_path_steps(str(e.args[idx].value))


@scalar("json_extract_scalar")
def _json_extract_scalar(e, args):
    steps = _literal_path(e)

    def per_doc(doc):
        v, ok = _json_eval(doc, steps)
        if not ok or isinstance(v, (dict, list)) or v is None:
            return None, False
        if isinstance(v, bool):
            return ("true" if v else "false"), True
        if isinstance(v, float) and v.is_integer():
            return str(int(v)), True
        return str(v), True

    return _json_lut(args[0], e, per_doc)


@scalar("json_extract")
def _json_extract(e, args):
    import json
    steps = _literal_path(e)

    def per_doc(doc):
        v, ok = _json_eval(doc, steps)
        if not ok:
            return None, False
        return json.dumps(v, separators=(",", ":"), sort_keys=True), True

    return _json_lut(args[0], e, per_doc)


@scalar("json_array_length")
def _json_array_length(e, args):
    import json

    def per_doc(doc):
        try:
            v = json.loads(doc)
        except (ValueError, TypeError):
            return None, False
        if not isinstance(v, list):
            return None, False
        return len(v), True

    return _json_lut(args[0], e, per_doc)


@scalar("json_size")
def _json_size(e, args):
    steps = _literal_path(e)

    def per_doc(doc):
        v, ok = _json_eval(doc, steps)
        if not ok:
            return None, False
        return (len(v) if isinstance(v, (dict, list)) else 0), True

    return _json_lut(args[0], e, per_doc)


@scalar("json_parse")
@scalar("json_format")
def _json_identity(e, args):
    # JSON values are dictionary-encoded strings end to end; parse and
    # format are type adapters with no physical change
    a = args[0]
    return Val(T.VARCHAR, a.data, a.valid, a.dictionary)


# --- arrays / maps (fixed-capacity 2D device layout; see Val) ---------------


def _elem_string(t: T.DataType) -> bool:
    return isinstance(t, T.VarcharType)


def _broadcast_cols_2d(columns: dict[str, Val], cap: int) -> dict:
    """Outer scalar columns as [n, 1] views so lambda bodies broadcast
    against [n, cap] element values."""
    out = {}
    for sym, v in columns.items():
        if v.is_array or getattr(v.data, "ndim", 1) != 1:
            out[sym] = v
            continue
        out[sym] = Val(v.dtype, v.data[:, None],
                       None if v.valid is None else v.valid[:, None],
                       v.dictionary)
    return out


def _bind_lambda(lam: ir.Lambda, arrays: list[Val],
                 columns: dict[str, Val] | None = None) -> Val:
    """Compile a lambda body with each param bound to its array's
    [n, cap] element values (outer columns broadcast to [n, 1]);
    returns the body's [n, cap] Val."""
    if columns is None:
        stack = _compiler_columns()
        columns = stack[-1] if stack else {}
    cap = arrays[0].data.shape[1]
    cols = _broadcast_cols_2d(columns, cap)
    for p, arr in zip(lam.params, arrays):
        ev = arr.elem_mask()
        cols[p] = Val(arr.dtype.element, arr.data,
                      ev if arr.elem_valid is not None else None,
                      arr.dictionary)
    return ExprCompiler(cols).compile(lam.body)


@scalar("array_ctor")
def _array_ctor(e, args):
    """ARRAY[e1, ..., ek]: stack k scalar columns into [n, k]."""
    if not args:
        return Val(e.dtype, jnp.zeros((1, 1), jnp.int64), None, None,
                   jnp.zeros((1,), jnp.int32), None)
    et = e.dtype.element
    if _elem_string(et):
        base = args[0]
        unified = [base]
        for v in args[1:]:
            v, base = _merge_dicts(v, base)
            unified.append(v)
        # re-unify earlier args against the final dictionary
        args = [_merge_dicts(v, base)[0] for v in unified]
        dictionary = args[0].dictionary
    else:
        dictionary = None
    n = None
    for v in args:
        if getattr(v.data, "ndim", 0) == 1:
            n = v.data.shape[0]
            break
    if n is None:
        n = 1
    datas = []
    valids = []
    for v in args:
        d = v.data
        if getattr(d, "ndim", 0) == 0:
            d = jnp.broadcast_to(d, (n,))
        datas.append(d)
        va = v.valid
        if va is None:
            va = jnp.ones((n,), bool)
        elif getattr(va, "ndim", 0) == 0:
            va = jnp.broadcast_to(va, (n,))
        valids.append(va)
    data = jnp.stack(datas, axis=1)
    elem_valid = jnp.stack(valids, axis=1)
    lengths = jnp.full((n,), len(args), jnp.int32)
    return Val(e.dtype, data, None, dictionary, lengths, elem_valid)


@scalar("element_at")
@scalar("subscript")
def _element_at(e, args):
    v, idx = args
    if isinstance(v.dtype, T.MapType):
        # map lookup: position of the matching key
        keys = v.map_keys
        if _elem_string(keys.dtype.element) and idx.is_string:
            kd, _ = _align_strings(
                Val(T.VARCHAR, keys.data, None, keys.dictionary), idx)
            want = idx.data
            hit = (kd == (want[:, None] if getattr(
                want, "ndim", 0) == 1 else want)) & keys.elem_mask()
        else:
            want = idx.data
            hit = (keys.data == (want[:, None] if getattr(
                want, "ndim", 0) == 1 else want)) & keys.elem_mask()
        pos = jnp.argmax(hit, axis=1)
        found = jnp.any(hit, axis=1)
        data = jnp.take_along_axis(v.data, pos[:, None], axis=1)[:, 0]
        ev = (jnp.take_along_axis(v.elem_valid, pos[:, None],
                                  axis=1)[:, 0]
              if v.elem_valid is not None else True)
        valid = and_valid(v.valid, found & ev)
        return Val(e.dtype, data, valid, v.dictionary)
    # SQL arrays are 1-based; out-of-range -> NULL
    cap = v.data.shape[1]
    i0 = idx.data - 1
    if getattr(i0, "ndim", 0) == 0:
        i0 = jnp.broadcast_to(i0, (v.data.shape[0],))
    in_range = (i0 >= 0) & (i0 < v.lengths.astype(i0.dtype))
    pos = jnp.clip(i0, 0, cap - 1).astype(jnp.int32)
    data = jnp.take_along_axis(v.data, pos[:, None], axis=1)[:, 0]
    ev = (jnp.take_along_axis(v.elem_valid, pos[:, None], axis=1)[:, 0]
          if v.elem_valid is not None else True)
    valid = and_valid(v.valid, and_valid(idx.valid, in_range & ev))
    return Val(e.dtype, data, valid, v.dictionary)


@scalar("cardinality")
def _cardinality(e, args):
    (v,) = args
    return Val(e.dtype, v.lengths.astype(jnp.int64), v.valid)


@scalar("contains")
def _contains_dispatch(e, args):
    v, x = args
    if not v.is_array:  # string contains (substring test) kept as-is
        return _string_contains(e, args)
    if _elem_string(v.dtype.element) and x.is_string:
        vd, _ = _align_strings(
            Val(T.VARCHAR, v.data, None, v.dictionary), x)
        want = x.data
    else:
        vd, want = v.data, x.data
    if getattr(want, "ndim", 0) <= 1:
        want = want[..., None] if getattr(want, "ndim", 0) else want
    hit = (vd == want) & v.elem_mask()
    return Val(e.dtype, jnp.any(hit, axis=1),
               and_valid(v.valid, x.valid))


@scalar("transform")
def _transform(e, args):
    v = args[0]
    lam = e.args[1]
    body = _bind_lambda(lam, [v])
    data = body.data
    if getattr(data, "ndim", 0) != 2:
        data = jnp.broadcast_to(data, v.data.shape)
    # an outer-column capture widens a literal array's single row to
    # the table's row count: companion arrays follow the body shape
    n_out = data.shape[0]
    lengths = v.lengths
    if lengths.shape[0] != n_out:
        lengths = jnp.broadcast_to(lengths, (n_out,))
    valid = v.valid
    if valid is not None and valid.shape[0] != n_out:
        valid = jnp.broadcast_to(valid, (n_out,))
    ev = body.valid
    if ev is not None and ev.shape != data.shape:
        ev = jnp.broadcast_to(ev, data.shape)
    return Val(e.dtype, data, valid, body.dictionary, lengths, ev)


@scalar("filter")
def _filter_array(e, args):
    v = args[0]
    lam = e.args[1]
    body = _bind_lambda(lam, [v])
    keep = body.data
    if body.valid is not None:
        keep = keep & body.valid
    # PRESENT positions only (a NULL element the lambda accepts stays:
    # Trino filter(array[1,null], x -> x IS NULL) keeps the NULL)
    cap = v.data.shape[1]
    present = jnp.arange(cap)[None, :] < v.lengths[:, None]
    keep = keep & present
    key = (~keep).astype(jnp.int32)
    pos = jnp.broadcast_to(jnp.arange(cap, dtype=jnp.int32),
                           v.data.shape)
    operands = [key, pos, v.data]
    has_ev = v.elem_valid is not None
    if has_ev:
        operands.append(v.elem_valid)
    out = jax.lax.sort(tuple(operands), num_keys=2, is_stable=True,
                       dimension=1)
    data = out[2]
    elem_valid = out[3] if has_ev else None
    lengths = jnp.sum(keep, axis=1).astype(jnp.int32)
    return Val(e.dtype, data, v.valid, v.dictionary, lengths,
               elem_valid)


@scalar("reduce")
def _reduce_array(e, args):
    v, init = args[0], args[1]
    lam = e.args[2]  # (acc, x) -> expr
    out_lam = e.args[3] if len(e.args) > 3 else None
    n, cap = v.data.shape
    acc_t = init.dtype
    acc_data = init.data
    if getattr(acc_data, "ndim", 0) == 0:
        acc_data = jnp.broadcast_to(acc_data, (n,))
    acc = Val(acc_t, acc_data, init.valid)
    mask = v.elem_mask()
    for j in range(cap):
        elem = Val(v.dtype.element, v.data[:, j], None, v.dictionary)
        stack = _compiler_columns()
        cols = dict(stack[-1]) if stack else {}
        cols[lam.params[0]] = acc
        cols[lam.params[1]] = elem
        stepped = ExprCompiler(cols).compile(lam.body)
        take = mask[:, j]
        sd = stepped.data
        if getattr(sd, "ndim", 0) == 0:
            sd = jnp.broadcast_to(sd, (n,))
        new_data = jnp.where(take, sd, acc.data)
        if acc.valid is None and stepped.valid is None:
            new_valid = None
        else:
            av = acc.valid if acc.valid is not None \
                else jnp.ones((n,), bool)
            sv = stepped.valid if stepped.valid is not None \
                else jnp.ones((n,), bool)
            new_valid = jnp.where(take, sv, av)
        acc = Val(acc_t, new_data, new_valid)
    if out_lam is not None:
        stack = _compiler_columns()
        cols = dict(stack[-1]) if stack else {}
        cols[out_lam.params[0]] = acc
        acc = ExprCompiler(cols).compile(out_lam.body)
    return Val(e.dtype, acc.data, and_valid(v.valid, acc.valid))


def _match_reduce(e, args, op):
    v = args[0]
    lam = e.args[1]
    body = _bind_lambda(lam, [v])
    hit = body.data
    if body.valid is not None:
        hit = hit & body.valid
    m = v.elem_mask()
    if op == "any":
        out = jnp.any(hit & m, axis=1)
    else:
        out = jnp.all(jnp.where(m, hit, True), axis=1)
    return Val(e.dtype, out, v.valid)


@scalar("any_match")
def _any_match(e, args):
    return _match_reduce(e, args, "any")


@scalar("all_match")
def _all_match(e, args):
    return _match_reduce(e, args, "all")


@scalar("none_match")
def _none_match(e, args):
    r = _match_reduce(e, args, "any")
    return Val(e.dtype, ~r.data, r.valid)


@scalar("array_position")
def _array_position(e, args):
    v, x = args
    if _elem_string(v.dtype.element) and x.is_string:
        vd, _ = _align_strings(
            Val(T.VARCHAR, v.data, None, v.dictionary), x)
        want = x.data
    else:
        vd, want = v.data, x.data
    if getattr(want, "ndim", 0) == 1:
        want = want[:, None]
    hit = (vd == want) & v.elem_mask()
    pos = jnp.argmax(hit, axis=1) + 1
    found = jnp.any(hit, axis=1)
    return Val(e.dtype, jnp.where(found, pos, 0).astype(jnp.int64),
               and_valid(v.valid, x.valid))


@scalar("array_max")
@scalar("array_min")
def _array_minmax(e, args):
    (v,) = args
    is_max = e.fn == "array_max"
    m = v.elem_mask()
    if jnp.issubdtype(v.data.dtype, jnp.integer):
        ident = (jnp.iinfo(v.data.dtype).min if is_max
                 else jnp.iinfo(v.data.dtype).max)
    else:
        ident = -jnp.inf if is_max else jnp.inf
    masked = jnp.where(m, v.data, ident)
    out = masked.max(axis=1) if is_max else masked.min(axis=1)
    nonempty = jnp.any(m, axis=1)
    return Val(e.dtype, out, and_valid(v.valid, nonempty),
               v.dictionary)


@scalar("array_sum")
def _array_sum(e, args):
    (v,) = args
    m = v.elem_mask()
    out = jnp.sum(jnp.where(m, v.data, 0), axis=1)
    return Val(e.dtype, out, v.valid)


@scalar("array_concat_fn")
def _array_concat_fn(e, args):
    a, b = args
    if _elem_string(e.dtype.element):
        av = Val(T.VARCHAR, a.data, None, a.dictionary)
        bv = Val(T.VARCHAR, b.data, None, b.dictionary)
        av, bv = _merge_dicts(av, bv)
        a = dataclasses.replace(a, data=av.data,
                                dictionary=av.dictionary)
        b = dataclasses.replace(b, data=bv.data,
                                dictionary=bv.dictionary)
    n, ca = a.data.shape
    cb = b.data.shape[1]
    # concatenate then compact b's elements to follow a's lengths
    data = jnp.concatenate([a.data, b.data], axis=1)
    am, bm = a.elem_mask(), b.elem_mask()
    keep = jnp.concatenate([am, bm], axis=1)
    pos = jnp.broadcast_to(jnp.arange(ca + cb, dtype=jnp.int32),
                           data.shape)
    out = jax.lax.sort(((~keep).astype(jnp.int32), pos, data),
                       num_keys=2, is_stable=True, dimension=1)
    lengths = (jnp.sum(am, axis=1) + jnp.sum(bm, axis=1)) \
        .astype(jnp.int32)
    return Val(e.dtype, out[2], and_valid(a.valid, b.valid),
               a.dictionary, lengths, None)


@scalar("array_distinct")
def _array_distinct(e, args):
    (v,) = args
    m = v.elem_mask()
    n, cap = v.data.shape
    # sort elements (dead padding last), mark the first of each equal
    # run, compact the marks. Output order is value-sorted, NOT
    # first-occurrence order (Trino preserves occurrence order;
    # documented divergence).
    big = jnp.where(m, v.data, jnp.asarray(
        jnp.iinfo(v.data.dtype).max if jnp.issubdtype(
            v.data.dtype, jnp.integer) else jnp.inf, v.data.dtype))
    sdata = jnp.sort(big, axis=1)
    first = jnp.concatenate(
        [jnp.ones((n, 1), bool), sdata[:, 1:] != sdata[:, :-1]], axis=1)
    cnt = jnp.sum(m, axis=1)
    slive = (jnp.arange(cap)[None, :] < cnt[:, None])
    keep = first & slive
    pos = jnp.broadcast_to(jnp.arange(cap, dtype=jnp.int32),
                           sdata.shape)
    out = jax.lax.sort(((~keep).astype(jnp.int32), pos, sdata),
                       num_keys=2, is_stable=True, dimension=1)
    lengths = jnp.sum(keep, axis=1).astype(jnp.int32)
    return Val(e.dtype, out[2], v.valid, v.dictionary, lengths, None)


@scalar("array_sort_fn")
def _array_sort_fn(e, args):
    (v,) = args
    m = v.elem_mask()
    big = jnp.where(m, v.data, jnp.asarray(
        jnp.iinfo(v.data.dtype).max if jnp.issubdtype(
            v.data.dtype, jnp.integer) else jnp.inf, v.data.dtype))
    sdata = jnp.sort(big, axis=1)
    return Val(e.dtype, sdata, v.valid, v.dictionary,
               jnp.sum(m, axis=1).astype(jnp.int32), None)


@scalar("sequence")
def _sequence(e, args):
    lo, hi = e.args[0], e.args[1]
    if not (isinstance(lo, ir.Literal) and isinstance(hi, ir.Literal)):
        raise NotImplementedError(
            "sequence() requires literal bounds (static array "
            "capacity)")
    step = int(e.args[2].value) if len(e.args) > 2 else 1
    vals = np.arange(int(lo.value), int(hi.value) + (1 if step > 0
                                                     else -1), step,
                     dtype=np.int64)
    n = 1
    for v in args:
        if getattr(v.data, "ndim", 0) == 1:
            n = v.data.shape[0]
            break
    data = jnp.broadcast_to(jnp.asarray(vals)[None, :],
                            (n, len(vals)))
    lengths = jnp.full((n,), len(vals), jnp.int32)
    return Val(e.dtype, data, None, None, lengths, None)


@scalar("split")
def _split(e, args):
    """split(string, delim): per-dictionary-entry split into a padded
    2D LUT, rows gather by code (dictionary transform generalized to
    array outputs)."""
    v, delim = args[0], args[1]
    if not isinstance(e.args[1], ir.Literal):
        raise NotImplementedError("split() delimiter must be a literal")
    d = str(e.args[1].value)
    parts = [str(s).split(d) for s in v.dictionary]
    cap = max((len(p) for p in parts), default=1)
    vocab = sorted({x for p in parts for x in p})
    code_of = {x: i for i, x in enumerate(vocab)}
    lut = np.zeros((len(parts), cap), np.int32)
    lens = np.zeros(len(parts), np.int32)
    for i, p in enumerate(parts):
        lens[i] = len(p)
        for j, x in enumerate(p):
            lut[i, j] = code_of[x]
    codes = v.data
    if getattr(codes, "ndim", 0) == 0:
        codes = codes[None]
    codes = jnp.clip(codes, 0, max(len(parts) - 1, 0))
    data = jnp.asarray(lut)[codes]
    lengths = jnp.asarray(lens)[codes]
    return Val(e.dtype, data, v.valid,
               np.array(vocab, dtype=object), lengths, None)


@scalar("map_ctor")
def _map_ctor(e, args):
    karr, varr = args
    return Val(e.dtype, varr.data, and_valid(karr.valid, varr.valid),
               varr.dictionary, varr.lengths, varr.elem_valid,
               map_keys=karr)


@scalar("map_keys")
def _map_keys(e, args):
    (v,) = args
    k = v.map_keys
    return Val(e.dtype, k.data, v.valid, k.dictionary, k.lengths,
               k.elem_valid)


@scalar("map_values")
def _map_values(e, args):
    (v,) = args
    return Val(e.dtype, v.data, v.valid, v.dictionary, v.lengths,
               v.elem_valid)


# --- math tail / bitwise / url / binary-string functions --------------------
# (reference operator/scalar/MathFunctions.java, BitwiseFunctions.java,
# UrlFunctions.java, StringFunctions.java, VarbinaryFunctions.java)

_mathfn("sin", jnp.sin)
_mathfn("cos", jnp.cos)
_mathfn("tan", jnp.tan)
_mathfn("asin", jnp.arcsin)
_mathfn("acos", jnp.arccos)
_mathfn("atan", jnp.arctan)
_mathfn("atan2", jnp.arctan2, arity=2)
_mathfn("sinh", jnp.sinh)
_mathfn("cosh", jnp.cosh)
_mathfn("tanh", jnp.tanh)
_mathfn("degrees", jnp.degrees)
_mathfn("radians", jnp.radians)
_mathfn("exp2", jnp.exp2)


@scalar("log")
def _log_base(e, args):
    # log(base, x) — the reference's two-argument log
    b, x = args
    return Val(e.dtype, jnp.log(_as_f64(x)) / jnp.log(_as_f64(b)),
               and_valid(b.valid, x.valid))


@scalar("is_nan")
def _is_nan(e, args):
    (a,) = args
    return Val(e.dtype, jnp.isnan(_as_f64(a)), a.valid)


@scalar("is_finite")
def _is_finite(e, args):
    (a,) = args
    return Val(e.dtype, jnp.isfinite(_as_f64(a)), a.valid)


@scalar("is_infinite")
def _is_infinite(e, args):
    (a,) = args
    return Val(e.dtype, jnp.isinf(_as_f64(a)), a.valid)


def _bitfn(name, op, arity=2):
    @scalar(name)
    def _f(e, args, _op=op, _n=arity):
        if _n == 1:
            (a,) = args
            return Val(e.dtype, _op(a.data.astype(jnp.int64)), a.valid)
        a, b = args
        return Val(e.dtype, _op(a.data.astype(jnp.int64),
                                b.data.astype(jnp.int64)),
                   and_valid(a.valid, b.valid))
    return _f


_bitfn("bitwise_and", jnp.bitwise_and)
_bitfn("bitwise_or", jnp.bitwise_or)
_bitfn("bitwise_xor", jnp.bitwise_xor)
_bitfn("bitwise_not", jnp.bitwise_not, arity=1)
_bitfn("bitwise_left_shift", jnp.left_shift)
_bitfn("bitwise_right_shift",
       lambda a, b: (a.astype(jnp.uint64) >> b.astype(jnp.uint64))
       .astype(jnp.int64))


@scalar("bit_count")
def _bit_count(e, args):
    a = args[0]
    bits = int(e.args[1].value) if len(e.args) > 1 else 64
    v = a.data.astype(jnp.int64)
    if bits < 64:  # interpret as a ``bits``-wide two's complement value
        v = v & jnp.int64((1 << bits) - 1)
    cnt = jax.lax.population_count(v.view(jnp.uint64))
    return Val(e.dtype, cnt.astype(jnp.int64), a.valid)


@scalar("width_bucket")
def _width_bucket(e, args):
    x, lo, hi, nb = (cast_val(a, T.DOUBLE) for a in args)
    n = nb.data.astype(jnp.int64)
    span = hi.data - lo.data
    frac = (x.data - lo.data) / jnp.where(span == 0, 1.0, span)
    b = jnp.floor(frac * n).astype(jnp.int64) + 1
    b = jnp.where(x.data < lo.data, 0, b)
    b = jnp.where(x.data >= hi.data, n + 1, b)
    return Val(e.dtype, b, and_valid(*[a.valid for a in args]))


@scalar("codepoint")
def _codepoint(e, args):
    (col,) = args
    lut = jnp.asarray(np.array(
        [ord(str(s)[0]) if len(str(s)) else 0
         for s in col.dictionary], np.int64))
    return Val(e.dtype, lut[col.data], col.valid)


@scalar("chr")
def _chr(e, args):
    (a,) = args
    if not isinstance(e.args[0], ir.Literal):
        raise NotImplementedError("chr() requires a literal")
    return Val(T.VARCHAR, jnp.asarray(np.int32(0)), a.valid,
               np.array([chr(int(e.args[0].value))], object))


@scalar("translate")
def _translate(e, args):
    col = args[0]
    if not all(isinstance(a, ir.Literal) for a in e.args[1:]):
        raise NotImplementedError("translate with non-literal maps")
    src, dst = str(e.args[1].value), str(e.args[2].value)
    table = {ord(c): (dst[i] if i < len(dst) else None)
             for i, c in enumerate(src)}
    return _dict_transform(
        col, lambda d: np.array([str(s).translate(table) for s in d],
                                object))


@scalar("levenshtein_distance")
def _levenshtein(e, args):
    a, b = args
    if len(e.args) < 2 or not isinstance(e.args[1], ir.Literal):
        raise NotImplementedError(
            "levenshtein_distance needs a literal second argument")
    want = str(e.args[1].value)

    def dist(s: str) -> int:
        prev = list(range(len(want) + 1))
        for i, ca in enumerate(s, 1):
            cur = [i]
            for j, cb in enumerate(want, 1):
                cur.append(min(prev[j] + 1, cur[j - 1] + 1,
                               prev[j - 1] + (ca != cb)))
            prev = cur
        return prev[-1]

    lut = jnp.asarray(np.array([dist(str(s)) for s in a.dictionary],
                               np.int64))
    return Val(e.dtype, lut[a.data], and_valid(a.valid, b.valid))


@scalar("hamming_distance")
def _hamming(e, args):
    a, b = args
    if len(e.args) < 2 or not isinstance(e.args[1], ir.Literal):
        raise NotImplementedError(
            "hamming_distance needs a literal second argument")
    want = str(e.args[1].value)

    def dist(s: str) -> int:
        s = str(s)
        if len(s) != len(want):
            return -1
        return sum(x != y for x, y in zip(s, want))

    lut = jnp.asarray(np.array([dist(s) for s in a.dictionary],
                               np.int64))
    ok = lut >= 0
    return Val(e.dtype, lut[a.data],
               and_valid(a.valid, ok[a.data]))


def _urlfn(name, extract):
    @scalar(name)
    def _f(e, args, _x=extract):
        return _dict_transform(
            args[0], lambda d: np.array([_x(str(s)) for s in d],
                                        object))
    return _f


def _url_parts(s: str):
    from urllib.parse import urlparse
    try:
        return urlparse(s)
    except ValueError:
        return urlparse("")


_urlfn("url_extract_protocol", lambda s: _url_parts(s).scheme)
_urlfn("url_extract_host", lambda s: _url_parts(s).hostname or "")
_urlfn("url_extract_path", lambda s: _url_parts(s).path)
_urlfn("url_extract_query", lambda s: _url_parts(s).query)
_urlfn("url_extract_fragment", lambda s: _url_parts(s).fragment)


@scalar("url_extract_port")
def _url_port(e, args):
    (col,) = args
    ports = np.array(
        [(_url_parts(str(s)).port or -1) for s in col.dictionary],
        np.int64)
    lut = jnp.asarray(ports)
    has = lut >= 0
    return Val(e.dtype, jnp.clip(lut[col.data], 0, None),
               and_valid(col.valid, has[col.data]))


@scalar("url_extract_parameter")
def _url_param(e, args):
    if not isinstance(e.args[1], ir.Literal):
        raise NotImplementedError(
            "url_extract_parameter needs a literal name")
    name = str(e.args[1].value)

    def get(s: str):
        from urllib.parse import parse_qs
        vals = parse_qs(_url_parts(s).query,
                        keep_blank_values=True).get(name)
        return vals[0] if vals else ""

    col = args[0]
    out = _dict_transform(
        col, lambda d: np.array([get(str(s)) for s in d], object))
    has = np.array(
        [name in parse_qs_keys(str(s)) for s in col.dictionary])
    hasr = jnp.asarray(has)[jnp.clip(col.data, 0,
                                     max(len(has) - 1, 0))]
    return Val(T.VARCHAR, out.data, and_valid(col.valid, hasr),
               out.dictionary)


def parse_qs_keys(s: str):
    from urllib.parse import parse_qs
    return parse_qs(_url_parts(s).query, keep_blank_values=True).keys()


_urlfn("url_encode",
       lambda s: __import__("urllib.parse", fromlist=["quote_plus"])
       .quote_plus(s))
_urlfn("url_decode",
       lambda s: __import__("urllib.parse", fromlist=["unquote_plus"])
       .unquote_plus(s))
_urlfn("to_hex", lambda s: s.encode().hex().upper())
_urlfn("from_hex", lambda s: bytes.fromhex(s).decode("utf-8",
                                                     "replace"))
_urlfn("md5",
       lambda s: __import__("hashlib").md5(s.encode()).hexdigest())
_urlfn("sha256",
       lambda s: __import__("hashlib").sha256(s.encode()).hexdigest())
_urlfn("to_base64",
       lambda s: __import__("base64").b64encode(s.encode()).decode())
_urlfn("from_base64",
       lambda s: __import__("base64").b64decode(s.encode())
       .decode("utf-8", "replace"))
