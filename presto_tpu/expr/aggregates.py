"""Aggregate function registry.

Analog of the reference's accumulator framework
(operator/aggregation/AccumulatorCompiler.java + ~90 @AggregationFunction
implementations). Each aggregate defines how to fold masked rows into
per-slot state via segment reductions, how to merge partial states
(the partial->final split used across exchanges, reference
PushPartialAggregationThroughExchange), and how to produce the final value.

State columns are plain device arrays, so partial aggregation states flow
through exchanges like any other column — exactly how the reference ships
serialized accumulator state in Pages.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from presto_tpu import types as T
from presto_tpu.expr import ir
from presto_tpu.ops import segred


@dataclasses.dataclass(frozen=True)
class AggCall:
    """A planned aggregate: function name, argument expression (None for
    count(*)), distinct flag, output type."""

    fn: str
    arg: ir.Expr | None
    dtype: T.DataType
    distinct: bool = False
    # boolean column restricting which rows this call folds (reference
    # Aggregation.mask, fed by MarkDistinct for DISTINCT aggregates)
    mask: str | None = None
    # second argument for two-argument aggregates (min_by/max_by's
    # comparison key, corr/covar/regr's x)
    arg2: ir.Expr | None = None
    # literal parameter (approx_percentile's percentile)
    param: float | None = None
    # varlen aggregates (array_agg/map_agg/listagg): separator literal
    # and intra-group ordering column (host-finalized, exec/varlen.py)
    sep: str | None = None
    order_sym: str | None = None
    order_desc: bool = False

    def __str__(self) -> str:
        inner = "*" if self.arg is None else str(self.arg)
        if self.arg2 is not None:
            inner += f", {self.arg2}"
        if self.param is not None:
            inner += f", {self.param:g}"
        d = "distinct " if self.distinct else ""
        m = f" mask {self.mask}" if self.mask else ""
        return f"{self.fn}({d}{inner}){m}"


# sample/population variance family — all DOUBLE-valued (reference
# operator/aggregation/VarianceAggregation + DoubleSumAggregation kin)
VAR_FNS = frozenset({"variance", "var_samp", "var_pop",
                     "stddev", "stddev_samp", "stddev_pop"})
BOOL_FNS = frozenset({"bool_and", "bool_or", "every"})
# central-moments family: skewness/kurtosis carry (count, sum, m2, m3,
# m4) states (reference CentralMomentsAggregation /
# AggregationUtils.mergeCentralMomentsState)
MOMENT_FNS = frozenset({"skewness", "kurtosis"})
# bivariate co-moment family (reference CentralMomentsAggregation /
# CorrelationAggregation / CovarianceAggregation / RegressionAggregation):
# SQL shape fn(y, x), all DOUBLE-valued, rows with a NULL in either
# argument excluded
COVAR_FNS = frozenset({"corr", "covar_samp", "covar_pop",
                       "regr_slope", "regr_intercept"})
BY_FNS = frozenset({"min_by", "max_by"})
# variable-length-output aggregates: computed host-side at finalization
# (exec/varlen.py) because their results cannot live in fixed-width HBM
# arrays (reference operator/aggregation/ArrayAggregationFunction,
# MapAggAggregationFunction, ListaggAggregationFunction)
VARLEN_FNS = frozenset({"array_agg", "map_agg", "listagg"})

# HyperLogLog register count for approx_distinct: p=11 -> 2048 buckets,
# standard error 1.04/sqrt(2048) ~= 2.3% — the reference's default
# maxStandardError (ApproximateCountDistinctAggregation DEFAULT_STANDARD
# _ERROR 0.023). Registers live in a single [capacity, HLL_M] uint8
# state array: one flattened segment_max folds every row's rank.
HLL_M = 2048
# min-hash reservoir cells for approx_percentile: each group keeps, per
# cell, the row whose 64-bit hash is smallest among rows landing there —
# a mergeable uniform sample of ~K rows per group (TPU-first stand-in
# for the reference's qdigest state; error ~ 1/sqrt(K))
PCT_K = 1024


def output_type(fn: str, arg_type: T.DataType | None) -> T.DataType:
    if fn in ("count", "count_star", "count_if"):
        return T.BIGINT
    if fn in VAR_FNS or fn in MOMENT_FNS or fn == "geometric_mean":
        return T.DOUBLE
    if fn in BOOL_FNS:
        return T.BOOLEAN
    if fn == "sum":
        if isinstance(arg_type, T.DecimalType):
            # LONG input sums exactly in int128 limbs -> decimal(38, s)
            # (reference DecimalSumAggregation); short inputs keep the
            # int64 state (documented headroom: |sum| < 2^63)
            return T.DecimalType(38 if arg_type.is_long else 18,
                                 arg_type.scale)
        if isinstance(arg_type, T.DoubleType):
            return T.DOUBLE
        return T.BIGINT
    if fn == "avg":
        if isinstance(arg_type, T.DecimalType):
            # decimal in -> decimal out at the same scale, HALF_UP
            # (reference AverageAggregations decimal path); this repo's
            # tpch catalog serves decimal columns, so parity demands the
            # decimal behavior, not the DOUBLE the reference shows on
            # its own all-DOUBLE tpch catalog
            return T.DecimalType(38 if arg_type.is_long else 18,
                                 arg_type.scale)
        return T.DOUBLE
    if fn in ("min", "max", "arbitrary"):
        return arg_type
    if fn in ("approx_distinct", "checksum"):
        return T.BIGINT
    if fn in COVAR_FNS:
        return T.DOUBLE
    if fn in BY_FNS or fn == "approx_percentile":
        return arg_type
    if fn == "array_agg":
        return T.ArrayType(arg_type if arg_type is not None else T.UNKNOWN)
    if fn == "listagg":
        return T.VARCHAR
    raise NotImplementedError(f"aggregate {fn}")


def state_type(call: "AggCall", field: str) -> T.DataType:
    """Type of one partial-state column (the wire schema of partial
    aggregation states shipped through exchanges)."""
    if field == "count":
        return T.BIGINT
    if field in ("a", "b", "hi"):
        return T.BIGINT  # int128 limb sums (long-decimal sum/avg)
    if field in ("vlo", "vhi"):
        return T.BIGINT  # int128 extremum limbs (long-decimal min/max)
    if field == "sum":
        if call.fn == "checksum":
            return T.BIGINT  # wrapping uint64 hash sum, bitcast
        if call.fn == "avg":
            at = call.arg.dtype if call.arg is not None else T.BIGINT
            if isinstance(at, T.DecimalType):
                return T.DecimalType(18, at.scale)
            if isinstance(at, T.DoubleType):
                return T.DOUBLE
            return T.BIGINT
        return call.dtype
    if field == "val":
        if call.fn in BOOL_FNS:
            return T.INTEGER  # bool folded as 0/1 through min/max
        if call.fn in BY_FNS:  # extremum of the comparison key (arg2)
            return call.arg2.dtype
        return call.arg.dtype if call.arg is not None else call.dtype
    if field in ("m2", "m3", "m4", "sumlog", "sumx", "sumy", "cxy",
                 "m2x", "m2y", "rval"):
        return T.DOUBLE
    if field in ("regs", "rhash"):
        return T.BIGINT  # nominal: arrays carry their real dtype
    if field == "xval":
        return call.arg.dtype
    if field == "xok":
        return T.BOOLEAN
    raise NotImplementedError(field)


# state column suffixes per function (partial aggregation schema)
def state_fields(fn) -> list[str]:
    """``fn`` is a function name or an AggCall (needed to distinguish
    the long-decimal sum/avg limb states from the int64 state)."""
    if not isinstance(fn, str):
        call = fn
        if long_sum_call(call):
            return ["a", "b", "hi", "count"]
        if long_minmax_call(call):
            return ["vlo", "vhi", "count"]
        fn = call.fn
    if fn in ("count", "count_star", "count_if"):
        return ["count"]
    if fn == "sum":
        return ["sum", "count"]  # count tracks non-null presence for SQL sum
    if fn == "avg":
        return ["sum", "count"]
    if fn in ("min", "max", "arbitrary") or fn in BOOL_FNS:
        return ["val", "count"]
    if fn in VAR_FNS:
        return ["count", "sum", "m2"]
    if fn in MOMENT_FNS:
        return ["count", "sum", "m2", "m3", "m4"]
    if fn == "geometric_mean":
        return ["count", "sumlog"]
    if fn == "approx_distinct":
        return ["regs"]
    if fn == "checksum":
        return ["sum"]
    if fn in COVAR_FNS:
        return ["count", "sumx", "sumy", "cxy", "m2x", "m2y"]
    if fn in BY_FNS:
        return ["val", "xval", "xok", "count"]
    if fn == "approx_percentile":
        return ["rhash", "rval"]
    raise NotImplementedError(fn)


def _value_hash(data):
    """Per-row 64-bit hash of a value column (any numeric dtype).
    Distinct values map to distinct pre-mix words, so the only failure
    mode is a 64-bit hash collision.

    Floats use the double-float decomposition hi=f32(x), lo=f32(x-hi)
    (unique for doubles within f32 exponent range) because this TPU
    toolchain's X64 rewriter has no f64<->u64 bitcast; doubles beyond
    f32 range collapse to the inf fingerprint."""
    from presto_tpu.ops.hash import _splitmix64
    if jnp.issubdtype(data.dtype, jnp.floating):
        x = data.astype(jnp.float64)
        x = jnp.where(x == 0, 0.0, x)  # -0.0 and 0.0 are SQL-equal
        hi = x.astype(jnp.float32)
        lo = (x - hi.astype(jnp.float64)).astype(jnp.float32)
        hb = jax.lax.bitcast_convert_type(hi, jnp.uint32)
        lb = jax.lax.bitcast_convert_type(lo, jnp.uint32)
        bits = (hb.astype(jnp.uint64)
                | (lb.astype(jnp.uint64) << jnp.uint64(32)))
    elif data.dtype == jnp.bool_:
        bits = data.astype(jnp.uint64)
    else:
        bits = data.astype(jnp.int64).astype(jnp.uint64)
    return _splitmix64(bits)


def is_long_decimal(t) -> bool:
    return isinstance(t, T.DecimalType) and t.is_long


def long_minmax_call(call) -> bool:
    """min/max/arbitrary over a LONG decimal argument: the state is the
    extremum's two int64 limbs (vlo/vhi)."""
    return (call.fn in ("min", "max", "arbitrary")
            and call.arg is not None
            and is_long_decimal(call.arg.dtype))


def long_sum_call(call) -> bool:
    """True for sum/avg over a LONG decimal argument: the state is the
    exact int128 limb decomposition (fields a/b/hi/count) instead of an
    int64 running sum (reference DecimalSumAggregation's
    Int128State)."""
    return (call.fn in ("sum", "avg") and call.arg is not None
            and is_long_decimal(call.arg.dtype))


_I64_MIN = -(1 << 63)
_I64_MAX = (1 << 63) - 1


def _lo_sortable(lo64):
    """Low limb's bit pattern -> order-preserving SIGNED int64 (flip
    the top bit: unsigned u64 order == signed order of the flip)."""
    return (lo64.astype(jnp.uint64)
            ^ jnp.uint64(1 << 63)).astype(jnp.int64)


def _lo_unsortable(s64):
    return (s64.astype(jnp.uint64)
            ^ jnp.uint64(1 << 63)).astype(jnp.int64)


def _limb32(lo64):
    """Non-negative int64 halves of a low limb's bit pattern: each sums
    exactly in int64 for up to 2^31 rows (values < 2^32, sums < 2^63).
    The high 64-bit limb sums separately, wrapping mod 2^64 — the
    recombination in finalize is exact mod 2^128."""
    u = lo64.astype(jnp.uint64)
    a = (u & jnp.uint64(0xFFFFFFFF)).astype(jnp.int64)
    b = (u >> jnp.uint64(32)).astype(jnp.int64)
    return a, b


def _recombine128(a, b, hi64):
    """Per-slot limb sums -> int128 [n, 2] (see _limb32)."""
    from presto_tpu.ops import int128 as I
    ua = a.astype(jnp.uint64)
    ub = b.astype(jnp.uint64)
    lo = ua + (ub << jnp.uint64(32))
    carry = (lo < ua).astype(jnp.uint64)
    hi = hi64.astype(jnp.uint64) + (ub >> jnp.uint64(32)) + carry
    return I.pack(lo, hi)


def _normalize_limbs(states: dict) -> dict:
    """Carry-normalize LONG-decimal a/b partial sums back into the
    32-bit limb domain (hi absorbs the carries, wrapping mod 2^64 —
    the recombination is exact mod 2^128).

    A partial state's ``a``/``b`` accumulate one 32-bit half per row,
    so after N rows each holds up to N * (2^32 - 1): safe in int64 for
    N < 2^31 rows, but the PARTIAL->FINAL merge re-SUMS those already-
    large per-worker sums, so without normalization the merged total
    wraps int64 once the rows covered by the merged states pass 2^31
    (~2 x 10^9 — real at SF1000; ADVICE round 5). Normalized states
    re-enter the per-row domain (a', b' < 2^32), making the merge sum
    safe for up to 2^31 *states* instead of rows."""
    packed = _recombine128(states["a"], states["b"], states["hi"])
    u = packed[..., 0].astype(jnp.uint64)
    return {
        **states,
        "a": (u & jnp.uint64(0xFFFFFFFF)).astype(jnp.int64),
        "b": (u >> jnp.uint64(32)).astype(jnp.int64),
        "hi": packed[..., 1],
    }


def prepare_arg(fn: str, data, arg_type: T.DataType | None):
    """Pre-convert the argument for aggregates that fold in a derived
    domain: variance family / geometric_mean / covariances unscale
    decimals to float64; sketches hash the value."""
    if fn in ("approx_distinct", "checksum"):
        return _value_hash(data)
    if fn == "approx_percentile":
        return data.astype(jnp.float64)  # scaled domain; recast at end
    if fn in COVAR_FNS:
        x = data.astype(jnp.float64)
        if isinstance(arg_type, T.DecimalType):
            x = x / arg_type.unscale_factor
        return x
    if (fn not in VAR_FNS and fn not in MOMENT_FNS
            and fn != "geometric_mean"):
        return data
    x = data.astype(jnp.float64)
    if isinstance(arg_type, T.DecimalType):
        x = x / arg_type.unscale_factor
    if fn == "geometric_mean":
        return jnp.log(x)
    return x


def prepare_arg2(fn: str, data, arg2_type: T.DataType | None):
    """Pre-convert the second argument (covariance family x; min_by /
    max_by comparison key stays in its natural dtype)."""
    if fn in COVAR_FNS:
        x = data.astype(jnp.float64)
        if isinstance(arg2_type, T.DecimalType):
            x = x / arg2_type.unscale_factor
        return x
    return data


# NumPy scalar: a module-level jnp scalar would initialise the backend
# at import (see ops/hash._EMPTY)
_U64_MAX = np.uint64(0xFFFFFFFFFFFFFFFF)


def _bitlen(x):
    """Bit length of a uint64 array (0 for 0) via unrolled binary CLZ —
    no data-dependent control flow, maps to 6 shift/compare rounds."""
    n = jnp.zeros(x.shape, jnp.int32)
    for s in (32, 16, 8, 4, 2, 1):
        big = x >= (jnp.uint64(1) << jnp.uint64(s))
        n = n + jnp.where(big, s, 0)
        x = jnp.where(big, x >> jnp.uint64(s), x)
    return n + (x > 0).astype(jnp.int32)


def _winner_scatter(values, valid, winner, slots, capacity: int):
    """Scatter ``values`` of winner rows to their slots (arbitrary
    winner on ties — SQL allows any row attaining the extremum)."""
    dest = jnp.where(winner, slots, capacity)
    data = jnp.zeros((capacity,), dtype=values.dtype)
    data = data.at[dest].set(values, mode="drop")
    ok = jnp.zeros((capacity,), dtype=bool)
    ok = ok.at[dest].set(valid if valid is not None
                         else jnp.ones(winner.shape, bool), mode="drop")
    return data, ok


def fold(fn: str, data, weight, slots, capacity: int, *,
         data2=None, data_valid=None, param=None):
    """Fold rows into per-slot states. ``weight`` is bool live&valid.
    Returns dict state-field -> array[capacity] (sketch states are
    [capacity, width])."""
    w = weight
    if fn == "approx_distinct":
        # data pre-hashed to uint64 (prepare_arg). Low 11 bits pick the
        # register, the remaining 53 bits' leading-zero rank feeds a
        # single flattened segment_max over [capacity * HLL_M]
        if capacity * HLL_M > (1 << 30):
            raise ValueError(
                "approx_distinct group capacity too large for HLL "
                f"registers ({capacity} slots x {HLL_M})")
        bucket = (data & jnp.uint64(HLL_M - 1)).astype(jnp.int64)
        rank = 54 - _bitlen(data >> jnp.uint64(11))
        seg = slots.astype(jnp.int64) * HLL_M + bucket
        regs = segred.segment_max(
            jnp.where(w, rank, 0), seg, num_segments=capacity * HLL_M)
        return {"regs": regs.reshape(capacity, HLL_M).astype(jnp.uint8)}
    if fn == "checksum":
        # order/partition-invariant wrapping int64 sum of row hashes
        # (reference ChecksumAggregationFunction's XOR equivalent); NULL
        # rows were remapped to a fixed constant by the caller.
        # u64 state reassembles to a wrapped int64 at finalize (no
        # 64-bit bitcast on this TPU toolchain)
        return {"sum": segred.segment_sum(
            jnp.where(w, data, jnp.uint64(0)), slots,
            num_segments=capacity)}
    if fn in COVAR_FNS:
        # two-pass centered co-moments (same cancellation argument as
        # the variance family): y=data, x=data2, both float64
        z = jnp.zeros((), jnp.float64)
        c = segred.segment_sum(w.astype(jnp.int64), slots,
                                num_segments=capacity)
        sy = segred.segment_sum(jnp.where(w, data, z), slots,
                                 num_segments=capacity)
        sx = segred.segment_sum(jnp.where(w, data2, z), slots,
                                 num_segments=capacity)
        cf = jnp.maximum(c, 1).astype(jnp.float64)
        dy = data - (sy / cf)[slots]
        dx = data2 - (sx / cf)[slots]
        seg = lambda v: segred.segment_sum(  # noqa: E731
            jnp.where(w, v, z), slots, num_segments=capacity)
        return {"count": c, "sumx": sx, "sumy": sy, "cxy": seg(dx * dy),
                "m2x": seg(dx * dx), "m2y": seg(dy * dy)}
    if fn in BY_FNS:
        # x=data (kept raw), comparison key y=data2: extremum of y per
        # slot, then the winning row's x scatters into xval/xok
        # (reference MinMaxByNAggregation n=1 semantics: NULL y rows
        # ignored, x may be NULL)
        if fn == "max_by":
            sentinel = _min_sentinel(data2.dtype)
            best = segred.segment_max(jnp.where(w, data2, sentinel),
                                       slots, num_segments=capacity)
        else:
            sentinel = _max_sentinel(data2.dtype)
            best = segred.segment_min(jnp.where(w, data2, sentinel),
                                       slots, num_segments=capacity)
        winner = w & (data2 == best[slots])
        xval, xok = _winner_scatter(data, data_valid, winner, slots,
                                    capacity)
        c = segred.segment_sum(w.astype(jnp.int64), slots,
                                num_segments=capacity)
        return {"val": best, "xval": xval, "xok": xok, "count": c}
    if fn == "approx_percentile":
        # min-hash reservoir: each (slot, cell) keeps the row with the
        # smallest decorrelated row hash — a mergeable uniform sample
        if capacity * PCT_K > (1 << 30):
            raise ValueError(
                "approx_percentile group capacity too large for the "
                f"reservoir ({capacity} slots x {PCT_K})")
        from presto_tpu.ops.hash import _splitmix64
        idx = jnp.arange(data.shape[0], dtype=jnp.uint64)
        h = _splitmix64(_value_hash(data)
                        ^ (idx * jnp.uint64(0xBF58476D1CE4E5B9)))
        cell = (h % jnp.uint64(PCT_K)).astype(jnp.int64)
        seg = slots.astype(jnp.int64) * PCT_K + cell
        minh = segred.segment_min(
            jnp.where(w, h, _U64_MAX), seg,
            num_segments=capacity * PCT_K)
        winner = w & (h == minh[seg])
        dest = jnp.where(winner, seg, capacity * PCT_K)
        rval = jnp.zeros((capacity * PCT_K,), jnp.float64)
        rval = rval.at[dest].set(data, mode="drop")
        return {"rhash": minh.reshape(capacity, PCT_K),
                "rval": rval.reshape(capacity, PCT_K)}
    if fn in ("count", "count_star"):
        return {"count": segred.segment_sum(
            w.astype(jnp.int64), slots, num_segments=capacity)}
    if fn in ("sum", "avg"):
        c = segred.segment_sum(
            w.astype(jnp.int64), slots, num_segments=capacity)
        if data2 is not None:
            # LONG decimal: data/data2 are the int128 value's low/high
            # int64 limbs (see _limb32); three exact int64 segment sums
            z = jnp.zeros((), jnp.int64)
            a, b = _limb32(jnp.where(w, data, z))
            return {"a": segred.segment_sum(a, slots,
                                            num_segments=capacity),
                    "b": segred.segment_sum(b, slots,
                                            num_segments=capacity),
                    "hi": segred.segment_sum(jnp.where(w, data2, z),
                                             slots,
                                             num_segments=capacity),
                    "count": c}
        if jnp.issubdtype(data.dtype, jnp.integer):
            data = data.astype(jnp.int64)  # int32 args must not wrap
        zero = jnp.zeros((), dtype=data.dtype)
        s = segred.segment_sum(
            jnp.where(w, data, zero), slots, num_segments=capacity)
        return {"sum": s, "count": c}
    if fn in ("min", "max", "arbitrary"):
        c = segred.segment_sum(w.astype(jnp.int64), slots,
                                num_segments=capacity)
        if data2 is not None:
            # LONG decimal extremum, two passes: signed high-limb
            # extremum, then the low limb (order-preserving signed
            # view) among high-limb winners
            maxi = fn in ("max", "arbitrary")
            ext = segred.segment_max if maxi else segred.segment_min
            hs = jnp.where(w, data2, _I64_MIN if maxi else _I64_MAX)
            bh = ext(hs, slots, num_segments=capacity)
            winner = w & (data2 == bh[slots])
            ls = jnp.where(winner, _lo_sortable(data),
                           _I64_MIN if maxi else _I64_MAX)
            bl = ext(ls, slots, num_segments=capacity)
            return {"vlo": _lo_unsortable(bl), "vhi": bh, "count": c}
        if fn == "max" or fn == "arbitrary":
            sentinel = _min_sentinel(data.dtype)
            v = segred.segment_max(jnp.where(w, data, sentinel), slots,
                                    num_segments=capacity)
        else:
            sentinel = _max_sentinel(data.dtype)
            v = segred.segment_min(jnp.where(w, data, sentinel), slots,
                                    num_segments=capacity)
        return {"val": v, "count": c}
    if fn == "count_if":
        return {"count": segred.segment_sum(
            (w & data.astype(bool)).astype(jnp.int64), slots,
            num_segments=capacity)}
    if fn in BOOL_FNS:
        b = data.astype(jnp.int32)
        c = segred.segment_sum(w.astype(jnp.int64), slots,
                                num_segments=capacity)
        if fn == "bool_or":
            v = segred.segment_max(jnp.where(w, b, 0), slots,
                                    num_segments=capacity)
        else:
            v = segred.segment_min(jnp.where(w, b, 1), slots,
                                    num_segments=capacity)
        return {"val": v, "count": c}
    if fn in VAR_FNS:
        # data pre-converted to float64 by prepare_arg. Two-pass M2
        # (centered second moment) per slot — the sumsq - mean^2 form
        # cancels catastrophically for mean >> spread; the reference's
        # accumulators carry M2 for the same reason (Welford merging)
        z = jnp.zeros((), jnp.float64)
        c = segred.segment_sum(w.astype(jnp.int64), slots,
                                num_segments=capacity)
        s = segred.segment_sum(jnp.where(w, data, z), slots,
                                num_segments=capacity)
        mean = s / jnp.maximum(c, 1).astype(jnp.float64)
        d = data - mean[slots]
        m2 = segred.segment_sum(jnp.where(w, d * d, z), slots,
                                 num_segments=capacity)
        return {"count": c, "sum": s, "m2": m2}
    if fn in MOMENT_FNS:
        # exact two-pass central moments about the group mean
        z = jnp.zeros((), jnp.float64)
        c = segred.segment_sum(w.astype(jnp.int64), slots,
                                num_segments=capacity)
        s = segred.segment_sum(jnp.where(w, data, z), slots,
                                num_segments=capacity)
        mean = s / jnp.maximum(c, 1).astype(jnp.float64)
        d = data - mean[slots]
        seg = lambda v: segred.segment_sum(  # noqa: E731
            jnp.where(w, v, z), slots, num_segments=capacity)
        return {"count": c, "sum": s, "m2": seg(d * d),
                "m3": seg(d * d * d), "m4": seg(d * d * d * d)}
    if fn == "geometric_mean":
        z = jnp.zeros((), jnp.float64)
        return {
            "count": segred.segment_sum(w.astype(jnp.int64), slots,
                                         num_segments=capacity),
            "sumlog": segred.segment_sum(jnp.where(w, data, z), slots,
                                          num_segments=capacity),
        }
    raise NotImplementedError(fn)


# aggregates foldable by segmented scans over hash-sorted rows (all but
# the 2D-register sketches, which keep the segment-op path)
SCAN_FNS = (frozenset({"count", "count_star", "count_if", "sum", "avg",
                       "min", "max", "arbitrary", "geometric_mean",
                       "checksum"})
            | VAR_FNS | MOMENT_FNS | BOOL_FNS | COVAR_FNS | BY_FNS)


def scan_fold(fn: str, data, weight, sg, *, data2=None, data_valid=None,
              param=None):
    """Sorted-order fold: like ``fold`` but inputs are in hash-sorted
    row order (``sg`` = ops.hash.SortedGroups) and the returned state
    arrays are per-row running values, meaningful at each run's last
    row. No scatters — see ops/segscan.py."""
    from presto_tpu.ops import segscan as S
    w = weight
    z64 = jnp.zeros((), jnp.float64)
    if fn in ("count", "count_star"):
        return {"count": S.seg_sum(w.astype(jnp.int64), sg)}
    if fn == "count_if":
        return {"count": S.seg_sum(
            (w & data.astype(bool)).astype(jnp.int64), sg)}
    if fn in ("sum", "avg"):
        c = S.seg_sum(w.astype(jnp.int64), sg)
        if data2 is not None:
            # LONG decimal limbs (see fold)
            z = jnp.zeros((), jnp.int64)
            a, b = _limb32(jnp.where(w, data, z))
            return {"a": S.seg_sum(a, sg), "b": S.seg_sum(b, sg),
                    "hi": S.seg_sum(jnp.where(w, data2, z), sg),
                    "count": c}
        if jnp.issubdtype(data.dtype, jnp.integer):
            data = data.astype(jnp.int64)
        s = S.seg_sum(jnp.where(w, data, jnp.zeros((), data.dtype)), sg)
        return {"sum": s, "count": c}
    if fn in ("min", "max", "arbitrary"):
        c = S.seg_sum(w.astype(jnp.int64), sg)
        if data2 is not None:
            maxi = fn != "min"
            ext = S.seg_max if maxi else S.seg_min
            hs = jnp.where(w, data2, _I64_MIN if maxi else _I64_MAX)
            bh = ext(hs, sg)
            tot_bh = S.broadcast_last(bh, sg)
            winner = w & (data2 == tot_bh)
            ls = jnp.where(winner, _lo_sortable(data),
                           _I64_MIN if maxi else _I64_MAX)
            bl = ext(ls, sg)
            return {"vlo": _lo_unsortable(bl), "vhi": bh, "count": c}
        if fn == "min":
            v = S.seg_min(jnp.where(w, data, _max_sentinel(data.dtype)),
                          sg)
        else:
            v = S.seg_max(jnp.where(w, data, _min_sentinel(data.dtype)),
                          sg)
        return {"val": v, "count": c}
    if fn in BOOL_FNS:
        b = data.astype(jnp.int32)
        c = S.seg_sum(w.astype(jnp.int64), sg)
        if fn == "bool_or":
            v = S.seg_max(jnp.where(w, b, 0), sg)
        else:
            v = S.seg_min(jnp.where(w, b, 1), sg)
        return {"val": v, "count": c}
    if fn in VAR_FNS:
        c = S.seg_sum(w.astype(jnp.int64), sg)
        s = S.seg_sum(jnp.where(w, data, z64), sg)
        tot_c = S.broadcast_last(c, sg)
        tot_s = S.broadcast_last(s, sg)
        mean = tot_s / jnp.maximum(tot_c, 1).astype(jnp.float64)
        d = data - mean
        m2 = S.seg_sum(jnp.where(w, d * d, z64), sg)
        return {"count": c, "sum": s, "m2": m2}
    if fn in MOMENT_FNS:
        c = S.seg_sum(w.astype(jnp.int64), sg)
        s = S.seg_sum(jnp.where(w, data, z64), sg)
        tot_c = S.broadcast_last(c, sg)
        tot_s = S.broadcast_last(s, sg)
        mean = tot_s / jnp.maximum(tot_c, 1).astype(jnp.float64)
        d = data - mean
        return {"count": c, "sum": s,
                "m2": S.seg_sum(jnp.where(w, d * d, z64), sg),
                "m3": S.seg_sum(jnp.where(w, d * d * d, z64), sg),
                "m4": S.seg_sum(jnp.where(w, d * d * d * d, z64), sg)}
    if fn == "geometric_mean":
        return {"count": S.seg_sum(w.astype(jnp.int64), sg),
                "sumlog": S.seg_sum(jnp.where(w, data, z64), sg)}
    if fn == "checksum":
        return {"sum": S.seg_sum(jnp.where(w, data, jnp.uint64(0)), sg)}
    if fn in COVAR_FNS:
        c = S.seg_sum(w.astype(jnp.int64), sg)
        sy = S.seg_sum(jnp.where(w, data, z64), sg)
        sx = S.seg_sum(jnp.where(w, data2, z64), sg)
        cf = jnp.maximum(S.broadcast_last(c, sg), 1).astype(jnp.float64)
        dy = data - S.broadcast_last(sy, sg) / cf
        dx = data2 - S.broadcast_last(sx, sg) / cf
        return {"count": c, "sumx": sx, "sumy": sy,
                "cxy": S.seg_sum(jnp.where(w, dx * dy, z64), sg),
                "m2x": S.seg_sum(jnp.where(w, dx * dx, z64), sg),
                "m2y": S.seg_sum(jnp.where(w, dy * dy, z64), sg)}
    if fn in BY_FNS:
        maximize = fn == "max_by"
        sentinel = (_min_sentinel(data2.dtype) if maximize
                    else _max_sentinel(data2.dtype))
        y = jnp.where(w, data2, sentinel)
        xok = (data_valid if data_valid is not None
               else jnp.ones(w.shape, bool)) & w
        best, (xval, xok) = S.seg_argbest(y, (data, xok), sg, maximize)
        return {"val": best, "xval": xval, "xok": xok,
                "count": S.seg_sum(w.astype(jnp.int64), sg)}
    raise NotImplementedError(fn)


def scan_merge(fn: str, states: dict, live, sg):
    """Sorted-order merge of partial states (states already gathered to
    sorted order); per-row running values, meaningful at run-last rows."""
    from presto_tpu.ops import segscan as S
    w = live
    z64 = jnp.zeros((), jnp.float64)
    if fn in ("count", "count_star", "count_if"):
        return {"count": S.seg_sum(jnp.where(w, states["count"], 0), sg)}
    if fn in ("sum", "avg"):
        if "a" in states:  # LONG decimal limb states
            states = _normalize_limbs(states)
            return {f: S.seg_sum(jnp.where(w, states[f], 0), sg)
                    for f in ("a", "b", "hi", "count")}
        zero = jnp.zeros((), states["sum"].dtype)
        return {"sum": S.seg_sum(jnp.where(w, states["sum"], zero), sg),
                "count": S.seg_sum(jnp.where(w, states["count"], 0), sg)}
    if fn in ("min", "max", "arbitrary") and "vlo" in states:
        from presto_tpu.ops import segscan as SS
        maxi = fn in ("max", "arbitrary")
        ext = SS.seg_max if maxi else SS.seg_min
        present = w & (states["count"] > 0)
        hs = jnp.where(present, states["vhi"],
                       _I64_MIN if maxi else _I64_MAX)
        bh = ext(hs, sg)
        winner = present & (states["vhi"] == SS.broadcast_last(bh, sg))
        ls = jnp.where(winner, _lo_sortable(states["vlo"]),
                       _I64_MIN if maxi else _I64_MAX)
        bl = ext(ls, sg)
        return {"vlo": _lo_unsortable(bl), "vhi": bh,
                "count": SS.seg_sum(jnp.where(w, states["count"], 0),
                                    sg)}
    if fn in ("min", "max", "arbitrary") or fn in BOOL_FNS:
        val = states["val"]
        if fn in ("max", "arbitrary", "bool_or"):
            v = S.seg_max(jnp.where(w, val, _min_sentinel(val.dtype)), sg)
        else:
            v = S.seg_min(jnp.where(w, val, _max_sentinel(val.dtype)), sg)
        return {"val": v, "count": S.seg_sum(
            jnp.where(w, states["count"], 0), sg)}
    if fn == "checksum":
        return {"sum": S.seg_sum(
            jnp.where(w, states["sum"], jnp.uint64(0)), sg)}
    if fn in VAR_FNS:
        n_i = jnp.where(w, states["count"], 0)
        s_i = jnp.where(w, states["sum"], z64)
        n = S.seg_sum(n_i, sg)
        s = S.seg_sum(s_i, sg)
        mean_tot = (S.broadcast_last(s, sg)
                    / jnp.maximum(S.broadcast_last(n, sg), 1
                                  ).astype(jnp.float64))
        mean_i = s_i / jnp.maximum(n_i, 1).astype(jnp.float64)
        dev = mean_i - mean_tot
        m2 = S.seg_sum(jnp.where(w, states["m2"]
                                 + n_i.astype(jnp.float64) * dev * dev,
                                 z64), sg)
        return {"count": n, "sum": s, "m2": m2}
    if fn in MOMENT_FNS:
        # shifted-moment identities (binomial expansion about the total
        # mean; the odd terms vanish because sum(x - mean_i) = 0):
        #   M3 += 3*d*M2_i + n_i*d^3;  M4 += 4*d*M3_i + 6*d^2*M2_i
        #   + n_i*d^4 — the k-way generalization of the reference's
        #   pairwise mergeCentralMomentsState
        n_i = jnp.where(w, states["count"], 0)
        s_i = jnp.where(w, states["sum"], z64)
        n = S.seg_sum(n_i, sg)
        s = S.seg_sum(s_i, sg)
        mean_tot = (S.broadcast_last(s, sg)
                    / jnp.maximum(S.broadcast_last(n, sg), 1
                                  ).astype(jnp.float64))
        mean_i = s_i / jnp.maximum(n_i, 1).astype(jnp.float64)
        d = mean_i - mean_tot
        nf = n_i.astype(jnp.float64)
        m2_i = jnp.where(w, states["m2"], z64)
        m3_i = jnp.where(w, states["m3"], z64)
        m4_i = jnp.where(w, states["m4"], z64)
        return {"count": n, "sum": s,
                "m2": S.seg_sum(m2_i + nf * d * d, sg),
                "m3": S.seg_sum(m3_i + 3 * d * m2_i + nf * d**3, sg),
                "m4": S.seg_sum(m4_i + 4 * d * m3_i + 6 * d * d * m2_i
                                + nf * d**4, sg)}
    if fn == "geometric_mean":
        return {"count": S.seg_sum(jnp.where(w, states["count"], 0), sg),
                "sumlog": S.seg_sum(
                    jnp.where(w, states["sumlog"], z64), sg)}
    if fn in COVAR_FNS:
        n_i = jnp.where(w, states["count"], 0)
        sx_i = jnp.where(w, states["sumx"], z64)
        sy_i = jnp.where(w, states["sumy"], z64)
        n = S.seg_sum(n_i, sg)
        sx = S.seg_sum(sx_i, sg)
        sy = S.seg_sum(sy_i, sg)
        nf = jnp.maximum(S.broadcast_last(n, sg), 1).astype(jnp.float64)
        nf_i = jnp.maximum(n_i, 1).astype(jnp.float64)
        dx = sx_i / nf_i - S.broadcast_last(sx, sg) / nf
        dy = sy_i / nf_i - S.broadcast_last(sy, sg) / nf
        nw = n_i.astype(jnp.float64)
        return {"count": n, "sumx": sx, "sumy": sy,
                "cxy": S.seg_sum(
                    jnp.where(w, states["cxy"] + nw * dx * dy, z64), sg),
                "m2x": S.seg_sum(
                    jnp.where(w, states["m2x"] + nw * dx * dx, z64), sg),
                "m2y": S.seg_sum(
                    jnp.where(w, states["m2y"] + nw * dy * dy, z64), sg)}
    if fn in BY_FNS:
        maximize = fn == "max_by"
        val = states["val"]
        present = w & (states["count"] > 0)
        sentinel = (_min_sentinel(val.dtype) if maximize
                    else _max_sentinel(val.dtype))
        y = jnp.where(present, val, sentinel)
        best, (xval, xok) = S.seg_argbest(
            y, (states["xval"], states["xok"] & present), sg, maximize)
        return {"val": best, "xval": xval, "xok": xok,
                "count": S.seg_sum(jnp.where(w, states["count"], 0), sg)}
    raise NotImplementedError(fn)


def merge(fn: str, states: dict, slots, capacity: int, live):
    """Merge partial states (rows of state columns) into a final state
    table — used on the final side of an exchange."""
    w = live
    if fn == "approx_distinct":
        # register-wise max across partials: segment_max broadcasts over
        # the trailing register axis
        regs = states["regs"]
        return {"regs": segred.segment_max(
            jnp.where(w[:, None], regs, jnp.uint8(0)), slots,
            num_segments=capacity)}
    if fn == "checksum":
        return {"sum": segred.segment_sum(
            jnp.where(w, states["sum"], jnp.uint64(0)), slots,
            num_segments=capacity)}
    if fn in COVAR_FNS:
        # bivariate Chan et al. combination: co-moments shift by the
        # product of the per-partial mean deviations
        z = jnp.zeros((), jnp.float64)
        n_i = jnp.where(w, states["count"], 0)
        sx_i = jnp.where(w, states["sumx"], z)
        sy_i = jnp.where(w, states["sumy"], z)
        n = segred.segment_sum(n_i, slots, num_segments=capacity)
        sx = segred.segment_sum(sx_i, slots, num_segments=capacity)
        sy = segred.segment_sum(sy_i, slots, num_segments=capacity)
        nf_i = jnp.maximum(n_i, 1).astype(jnp.float64)
        nf = jnp.maximum(n, 1).astype(jnp.float64)
        dx = sx_i / nf_i - (sx / nf)[slots]
        dy = sy_i / nf_i - (sy / nf)[slots]
        nw = n_i.astype(jnp.float64)
        seg = lambda v: segred.segment_sum(  # noqa: E731
            jnp.where(w, v, z), slots, num_segments=capacity)
        return {"count": n, "sumx": sx, "sumy": sy,
                "cxy": seg(states["cxy"] + nw * dx * dy),
                "m2x": seg(states["m2x"] + nw * dx * dx),
                "m2y": seg(states["m2y"] + nw * dy * dy)}
    if fn in BY_FNS:
        present = w & (states["count"] > 0)
        if fn == "max_by":
            sentinel = _min_sentinel(states["val"].dtype)
            best = segred.segment_max(
                jnp.where(present, states["val"], sentinel), slots,
                num_segments=capacity)
        else:
            sentinel = _max_sentinel(states["val"].dtype)
            best = segred.segment_min(
                jnp.where(present, states["val"], sentinel), slots,
                num_segments=capacity)
        winner = present & (states["val"] == best[slots])
        xval, xok = _winner_scatter(states["xval"], states["xok"],
                                    winner, slots, capacity)
        c = segred.segment_sum(jnp.where(w, states["count"], 0), slots,
                                num_segments=capacity)
        return {"val": best, "xval": xval, "xok": xok, "count": c}
    if fn == "approx_percentile":
        # same min-hash winner rule, per (slot, cell), across partials
        rhash, rval = states["rhash"], states["rval"]
        n, k = rhash.shape
        seg2 = (slots.astype(jnp.int64)[:, None] * k
                + jnp.arange(k, dtype=jnp.int64)[None, :])
        flat_seg = seg2.reshape(-1)
        minh = segred.segment_min(
            jnp.where(w[:, None], rhash, _U64_MAX).reshape(-1),
            flat_seg, num_segments=capacity * k)
        winner = w[:, None] & (rhash == minh[seg2])
        dest = jnp.where(winner, seg2, capacity * k).reshape(-1)
        out_val = jnp.zeros((capacity * k,), jnp.float64)
        out_val = out_val.at[dest].set(rval.reshape(-1), mode="drop")
        return {"rhash": minh.reshape(capacity, k),
                "rval": out_val.reshape(capacity, k)}
    if fn in ("count", "count_star"):
        return {"count": segred.segment_sum(
            jnp.where(w, states["count"], 0), slots, num_segments=capacity)}
    if fn in ("sum", "avg"):
        if "a" in states:  # LONG decimal limb states
            states = _normalize_limbs(states)
            return {f: segred.segment_sum(
                jnp.where(w, states[f], 0), slots,
                num_segments=capacity)
                for f in ("a", "b", "hi", "count")}
        zero = jnp.zeros((), dtype=states["sum"].dtype)
        return {
            "sum": segred.segment_sum(
                jnp.where(w, states["sum"], zero), slots,
                num_segments=capacity),
            "count": segred.segment_sum(
                jnp.where(w, states["count"], 0), slots,
                num_segments=capacity),
        }
    if fn in ("min", "max", "arbitrary") and "vlo" in states:
        maxi = fn in ("max", "arbitrary")
        ext = segred.segment_max if maxi else segred.segment_min
        present = w & (states["count"] > 0)
        hs = jnp.where(present, states["vhi"],
                       _I64_MIN if maxi else _I64_MAX)
        bh = ext(hs, slots, num_segments=capacity)
        winner = present & (states["vhi"] == bh[slots])
        ls = jnp.where(winner, _lo_sortable(states["vlo"]),
                       _I64_MIN if maxi else _I64_MAX)
        bl = ext(ls, slots, num_segments=capacity)
        return {"vlo": _lo_unsortable(bl), "vhi": bh,
                "count": segred.segment_sum(
                    jnp.where(w, states["count"], 0), slots,
                    num_segments=capacity)}
    if fn in ("min", "max", "arbitrary") or fn in BOOL_FNS:
        seg_max = fn in ("max", "arbitrary", "bool_or")
        if seg_max:
            sentinel = _min_sentinel(states["val"].dtype)
            v = segred.segment_max(
                jnp.where(w, states["val"], sentinel), slots,
                num_segments=capacity)
        else:
            sentinel = _max_sentinel(states["val"].dtype)
            v = segred.segment_min(
                jnp.where(w, states["val"], sentinel), slots,
                num_segments=capacity)
        return {"val": v, "count": segred.segment_sum(
            jnp.where(w, states["count"], 0), slots, num_segments=capacity)}
    if fn == "count_if":
        return {"count": segred.segment_sum(
            jnp.where(w, states["count"], 0), slots, num_segments=capacity)}
    if fn in VAR_FNS:
        # parallel M2 combination (Chan et al.): M2_tot = sum(M2_i) +
        # sum(n_i * (mean_i - mean_tot)^2), all segment reductions
        z = jnp.zeros((), jnp.float64)
        n_i = jnp.where(w, states["count"], 0)
        s_i = jnp.where(w, states["sum"], z)
        n = segred.segment_sum(n_i, slots, num_segments=capacity)
        s = segred.segment_sum(s_i, slots, num_segments=capacity)
        mean_tot = s / jnp.maximum(n, 1).astype(jnp.float64)
        mean_i = s_i / jnp.maximum(n_i, 1).astype(jnp.float64)
        dev = mean_i - mean_tot[slots]
        m2 = segred.segment_sum(
            jnp.where(w, states["m2"], z)
            + n_i.astype(jnp.float64) * dev * dev,
            slots, num_segments=capacity)
        return {"count": n, "sum": s, "m2": m2}
    if fn in MOMENT_FNS:
        z = jnp.zeros((), jnp.float64)
        n_i = jnp.where(w, states["count"], 0)
        s_i = jnp.where(w, states["sum"], z)
        n = segred.segment_sum(n_i, slots, num_segments=capacity)
        s = segred.segment_sum(s_i, slots, num_segments=capacity)
        mean_tot = s / jnp.maximum(n, 1).astype(jnp.float64)
        mean_i = s_i / jnp.maximum(n_i, 1).astype(jnp.float64)
        d = mean_i - mean_tot[slots]
        nf = n_i.astype(jnp.float64)
        m2_i = jnp.where(w, states["m2"], z)
        m3_i = jnp.where(w, states["m3"], z)
        m4_i = jnp.where(w, states["m4"], z)
        seg = lambda v: segred.segment_sum(  # noqa: E731
            v, slots, num_segments=capacity)
        return {"count": n, "sum": s,
                "m2": seg(m2_i + nf * d * d),
                "m3": seg(m3_i + 3 * d * m2_i + nf * d**3),
                "m4": seg(m4_i + 4 * d * m3_i + 6 * d * d * m2_i
                          + nf * d**4)}
    if fn == "geometric_mean":
        z = jnp.zeros((), jnp.float64)
        return {
            "count": segred.segment_sum(
                jnp.where(w, states["count"], 0), slots,
                num_segments=capacity),
            "sumlog": segred.segment_sum(
                jnp.where(w, states["sumlog"], z), slots,
                num_segments=capacity),
        }
    raise NotImplementedError(fn)


def finalize(fn: str, states: dict, out_type: T.DataType,
             arg_type: T.DataType | None, param: float | None = None):
    """States -> (data, valid) final columns."""
    if fn in ("count", "count_star"):
        return states["count"], None
    if fn == "approx_distinct":
        # standard HyperLogLog estimator with the linear-counting
        # small-range correction (Flajolet et al.; reference
        # ApproximateCountDistinctAggregation via airlift HLL)
        regs = states["regs"].astype(jnp.float64)
        m = float(HLL_M)
        z = jnp.sum(jnp.exp2(-regs), axis=1)
        v = jnp.sum(states["regs"] == 0, axis=1).astype(jnp.float64)
        alpha = 0.7213 / (1.0 + 1.079 / m)
        e = alpha * m * m / z
        lin = m * jnp.log(m / jnp.maximum(v, 1.0))
        e = jnp.where((e <= 2.5 * m) & (v > 0), lin, e)
        return jnp.round(e).astype(jnp.int64), None
    if fn == "checksum":
        # u64 -> two's-complement int64 via 32-bit halves (wrapping
        # multiply-add; no 64-bit bitcast on this toolchain)
        s = states["sum"]
        lo = (s & jnp.uint64(0xFFFFFFFF)).astype(jnp.int64)
        hi = (s >> jnp.uint64(32)).astype(jnp.int64)
        return hi * jnp.int64(1 << 32) + lo, None
    if fn in COVAR_FNS:
        c = states["count"]
        cf = jnp.maximum(c, 1).astype(jnp.float64)
        cxy, m2x, m2y = states["cxy"], states["m2x"], states["m2y"]
        if fn == "covar_pop":
            return cxy / cf, c > 0
        if fn == "covar_samp":
            return cxy / jnp.maximum(cf - 1.0, 1.0), c > 1
        if fn == "corr":
            denom = jnp.sqrt(m2x * m2y)
            ok = (c > 1) & (m2x > 0) & (m2y > 0)
            return cxy / jnp.where(ok, denom, 1.0), ok
        slope = cxy / jnp.where(m2x > 0, m2x, 1.0)
        ok = (c > 0) & (m2x > 0)
        if fn == "regr_slope":
            return slope, ok
        meany = states["sumy"] / cf
        meanx = states["sumx"] / cf
        return meany - slope * meanx, ok  # regr_intercept
    if fn in BY_FNS:
        return states["xval"], (states["count"] > 0) & states["xok"]
    if fn == "approx_percentile":
        rhash, rval = states["rhash"], states["rval"]
        occupied = rhash != _U64_MAX
        cnt = jnp.sum(occupied, axis=1)
        vals = jnp.where(occupied, rval, jnp.inf)
        svals = jnp.sort(vals, axis=1)
        p = 0.5 if param is None else float(param)
        idx = jnp.clip(jnp.round(p * (cnt - 1)).astype(jnp.int32), 0,
                       rhash.shape[1] - 1)
        out = jnp.take_along_axis(svals, idx[:, None], axis=1)[:, 0]
        out = jnp.where(cnt > 0, out, 0.0)
        if isinstance(out_type, (T.DecimalType, T.BigintType,
                                 T.IntegerType, T.DateType)):
            out = jnp.round(out).astype(jnp.int64)
        return out, cnt > 0
    if fn in ("min", "max", "arbitrary") and "vlo" in states:
        from presto_tpu.ops import int128 as I
        return (I.pack(states["vlo"], states["vhi"]),
                states["count"] > 0)
    if fn == "sum" and "a" in states:
        return (_recombine128(states["a"], states["b"], states["hi"]),
                states["count"] > 0)
    if fn == "avg" and "a" in states:
        from presto_tpu.ops import int128 as I
        total = _recombine128(states["a"], states["b"], states["hi"])
        c = states["count"]
        q = I.div_round_half_up(total,
                                I.from_i64(jnp.maximum(c, 1)))
        return q, c > 0
    if fn == "sum":
        return states["sum"], states["count"] > 0
    if fn == "avg":
        s, c = states["sum"], states["count"]
        safe = jnp.maximum(c, 1)
        if isinstance(out_type, T.DecimalType):
            # HALF_UP integer division in the scaled domain:
            # sign(s) * ((2|s| + c) // 2c)
            q = jnp.sign(s) * ((2 * jnp.abs(s) + safe) // (2 * safe))
            return q, c > 0
        sf = s.astype(jnp.float64)
        if isinstance(arg_type, T.DecimalType):
            # decimal arg with a declared DOUBLE output (hand-built
            # plans; output_type-planned calls take the branch above)
            sf = sf / arg_type.unscale_factor
        return sf / safe.astype(jnp.float64), c > 0
    if fn in ("min", "max", "arbitrary"):
        return states["val"], states["count"] > 0
    if fn == "count_if":
        return states["count"], None
    if fn in BOOL_FNS:
        return states["val"] > 0, states["count"] > 0
    if fn in VAR_FNS:
        c = states["count"]
        safe = jnp.maximum(c, 1).astype(jnp.float64)
        m2 = states["m2"]
        if fn.endswith("_pop"):
            var = m2 / safe
            ok = c > 0
        else:
            # sample variance: M2/(n-1), undefined for n < 2
            var = m2 / jnp.maximum(safe - 1.0, 1.0)
            ok = c > 1
        if fn.startswith("stddev"):
            return jnp.sqrt(var), ok
        return var, ok
    if fn in MOMENT_FNS:
        # reference CentralMomentsAggregation.java:55-87 exactly
        c = states["count"]
        nf = c.astype(jnp.float64)
        m2 = states["m2"]
        if fn == "skewness":
            denom = jnp.maximum(m2, 1e-300) ** 1.5
            return jnp.sqrt(nf) * states["m3"] / denom, c > 2
        m4 = states["m4"]
        d23 = jnp.maximum((nf - 2) * (nf - 3), 1.0)
        val = ((nf - 1) * nf * (nf + 1)) / d23 * m4 \
            / jnp.maximum(m2 * m2, 1e-300) \
            - 3 * ((nf - 1) * (nf - 1)) / d23
        return val, c > 3
    if fn == "geometric_mean":
        c = states["count"]
        safe = jnp.maximum(c, 1).astype(jnp.float64)
        return jnp.exp(states["sumlog"] / safe), c > 0
    raise NotImplementedError(fn)


def _min_sentinel(dtype):
    if jnp.issubdtype(dtype, jnp.floating):
        return jnp.asarray(-jnp.inf, dtype)
    return jnp.asarray(jnp.iinfo(dtype).min, dtype)


def _max_sentinel(dtype):
    if jnp.issubdtype(dtype, jnp.floating):
        return jnp.asarray(jnp.inf, dtype)
    return jnp.asarray(jnp.iinfo(dtype).max, dtype)
