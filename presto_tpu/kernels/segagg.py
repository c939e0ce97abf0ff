"""Segmented aggregation as Pallas tile-accumulate kernels.

The XLA aggregation path pays for 64-bit scatters twice over: plain
``jax.ops.segment_sum`` costs ~500 ms per 6M-row call on v5e (emulated
64-bit scatter-add), and the MXU workaround (ops/segred.py) pays 8
one-hot matmuls per 256-row block. These kernels accumulate
per-segment partials on-chip while each HBM tile is resident, one
pass, no scatter unit and no one-hot FLOPs. Totals live as two uint32
planes with explicit carry (kernels/u64.add64) — exact mod 2^64, i.e.
bit-identical to the int64 scatter-add contract including wraparound.

The body is a per-row read-modify-write on the scalar core, so every
ref it touches lives in SMEM: Mosaic has no scalar store to VMEM
("Cannot store scalars to VMEM"). That compiles on the v5e; how it
grades against the XLA bodies is in kernels/__init__.AUTO_PALLAS.

Eligibility is integer-only on purpose: integer sums are
order-independent mod 2^64 and min/max are order-independent always,
so a sequential tile walk cannot diverge from the scatter's
unspecified accumulation order. Float SUMs would reassociate — those
stay on the XLA path on every backend (the same line ops/segred.py
already draws for its MXU path).

The XLA fallbacks (:func:`segment_sum_xla` & co) ARE ops/segred.py —
registered here so the ``kernel_backend`` dispatch table (and the
``kernel-parity`` lint rule) see one catalog of kernel/fallback
pairs.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from presto_tpu.kernels import u64

# 1-D 32-bit SMEM blocks must match XLA's T(1024) layout of the operand
TILE = 1024
# accumulator planes ([k] uint32 x 2) must stay SMEM-resident. The v5e
# has 1.00 MB of SMEM: libtpu 0.0.34 compiles these kernels for it at
# 1 << 16 segments (2 x 256 KB) and refuses 1 << 17 ("Ran out of memory
# in memory space smem. Used 1.02M of 1.00M"; AOT against a v5e
# topology). On the chip all three ran at the gate's 65,536 segments
# over 1,048,576 rows and equalled their XLA twins (PR 21, one-off
# probe, PERF.md section 5).
PALLAS_MAX_SEGMENTS = 1 << 16

# lint/kernels.py kernel-parity rule: *_pallas functions outside the
# dispatch table must justify themselves
KERNEL_DISPATCH_EXEMPT = {
    "_cmp_pallas": "shared body of segment_max_pallas/"
                   "segment_min_pallas, both registered",
}


def _eligible(data, num_segments: int) -> bool:
    if getattr(data, "ndim", 1) != 1 or data.shape[0] == 0:
        return False
    if num_segments > PALLAS_MAX_SEGMENTS:
        return False
    return (jnp.issubdtype(data.dtype, jnp.integer)
            or data.dtype == jnp.bool_)


def sum_eligible(data, num_segments: int) -> bool:
    return _eligible(data, num_segments)


def cmp_eligible(data, num_segments: int) -> bool:
    # bool has no min/max fold in the engine; integers only
    return _eligible(data, num_segments) and data.dtype != jnp.bool_


def _interpret_mode() -> bool:
    from presto_tpu import kernels as K
    return K.interpret_mode()


def _accumulate(kernel, planes, k: int):
    """Run ``kernel(vh, vl, sid, acc_hi, acc_lo)`` over the row tiles
    of ``planes`` (three [n] 32-bit arrays, n a multiple of TILE) with
    the two [k] accumulator planes resident across the sequential
    grid. Index maps and loop bounds are explicit int32: under
    ``jax_enable_x64`` a Python int traces as i64, which Mosaic does
    not legalise."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    ntiles = planes[0].shape[0] // TILE
    return pl.pallas_call(
        kernel,
        grid=(ntiles,),
        in_specs=[pl.BlockSpec((TILE,), lambda t: (t,),
                               memory_space=pltpu.SMEM)] * 3,
        out_specs=[pl.BlockSpec((k,), lambda t: (t * 0,),
                                memory_space=pltpu.SMEM)] * 2,
        out_shape=[jax.ShapeDtypeStruct((k,), jnp.uint32)] * 2,
        interpret=_interpret_mode(),
    )(*planes)


def _fill(refs, values, k: int) -> None:
    """Scalar-loop fill of [k] SMEM planes (SMEM takes no vector
    store)."""
    def one(i, _):
        for ref, v in zip(refs, values):
            ref[i] = jnp.uint32(v)
        return 0

    jax.lax.fori_loop(jnp.int32(0), jnp.int32(k), one, 0)


def segment_sum_pallas(data, segment_ids, num_segments: int, **_kw):
    """Per-segment wrapping 64-bit sum of an integer column (bool
    counts as int64, matching jax.ops/segred). Out-of-range segment
    ids drop, matching the scatter contract."""
    from jax.experimental import pallas as pl

    from presto_tpu import kernels as K
    K.note("pallas:agg_sum")
    out_dtype = jnp.int64 if data.dtype == jnp.bool_ else data.dtype
    k = int(num_segments)
    u = data.astype(jnp.uint64)  # sign-extends: two's complement sum
    v_hi, v_lo = u64.split(u)
    v_hi = u64.pad_rows(v_hi, TILE, 0)
    v_lo = u64.pad_rows(v_lo, TILE, 0)
    sid = u64.pad_rows(segment_ids.astype(jnp.int32), TILE, -1)

    def kernel(vh_ref, vl_ref, sid_ref, ah_ref, al_ref):
        t = pl.program_id(0)

        @pl.when(t == 0)
        def _init():
            _fill((ah_ref, al_ref), (0, 0), k)

        def row(i, _):
            s = sid_ref[i]

            @pl.when((s >= 0) & (s < k))
            def _acc():
                hi, lo = u64.add64(ah_ref[s], al_ref[s],
                                   vh_ref[i], vl_ref[i])
                ah_ref[s] = hi
                al_ref[s] = lo

            return 0

        jax.lax.fori_loop(jnp.int32(0), jnp.int32(TILE), row, 0)

    ah, al = _accumulate(kernel, (v_hi, v_lo, sid), k)
    return u64.join(ah, al).astype(out_dtype)


def _cmp_pallas(data, segment_ids, num_segments: int, is_max: bool):
    """Per-segment integer min/max via lexicographic limb compare
    (high limb sign-flipped so unsigned order == signed order). Empty
    segments hold the dtype identity, matching jax.ops.segment_max's
    dtype-min fill (and segment_min's dtype-max)."""
    from jax.experimental import pallas as pl
    k = int(num_segments)
    info = jnp.iinfo(data.dtype)
    ident = int(info.min if is_max else info.max)
    signed = jnp.issubdtype(data.dtype, jnp.signedinteger)
    if signed:
        u = data.astype(jnp.int64).astype(jnp.uint64)
        id_bits = ident & 0xFFFFFFFFFFFFFFFF  # two's complement
    else:
        u = data.astype(jnp.uint64)
        id_bits = ident
    v_hi, v_lo = u64.split(u)
    # bias flips the sign bit so unsigned limb order == value order
    # (python ints: captured jnp scalars are rejected by pallas)
    sign = 0x80000000 if signed else 0
    v_hi = u64.pad_rows(v_hi, TILE, 0)
    v_lo = u64.pad_rows(v_lo, TILE, 0)
    sid = u64.pad_rows(segment_ids.astype(jnp.int32), TILE, -1)
    id_hi = id_bits >> 32
    id_lo = id_bits & 0xFFFFFFFF

    def kernel(vh_ref, vl_ref, sid_ref, ah_ref, al_ref):
        t = pl.program_id(0)

        @pl.when(t == 0)
        def _init():
            _fill((ah_ref, al_ref), (id_hi, id_lo), k)

        def row(i, _):
            s = sid_ref[i]

            @pl.when((s >= 0) & (s < k))
            def _acc():
                vh = vh_ref[i]
                vl = vl_ref[i]
                ch = ah_ref[s]
                cl = al_ref[s]
                vb, cb = vh ^ sign, ch ^ sign  # biased signed compare
                if is_max:
                    better = (vb > cb) | ((vb == cb) & (vl > cl))
                else:
                    better = (vb < cb) | ((vb == cb) & (vl < cl))

                @pl.when(better)
                def _take():
                    ah_ref[s] = vh
                    al_ref[s] = vl

            return 0

        jax.lax.fori_loop(jnp.int32(0), jnp.int32(TILE), row, 0)

    ah, al = _accumulate(kernel, (v_hi, v_lo, sid), k)
    packed = u64.join(ah, al)
    if signed:
        packed = packed.astype(jnp.int64)
    return packed.astype(data.dtype)


def segment_max_pallas(data, segment_ids, num_segments: int, **_kw):
    from presto_tpu import kernels as K
    K.note("pallas:agg_max")
    return _cmp_pallas(data, segment_ids, num_segments, True)


def segment_min_pallas(data, segment_ids, num_segments: int, **_kw):
    from presto_tpu import kernels as K
    K.note("pallas:agg_min")
    return _cmp_pallas(data, segment_ids, num_segments, False)


# -- XLA fallbacks: the existing segred paths, re-exported so the
#    kernel registry maps every Pallas kernel to its fallback ---------


def segment_sum_xla(data, segment_ids, num_segments: int, **kwargs):
    from presto_tpu.ops import segred
    return segred.xla_segment_sum(data, segment_ids, num_segments,
                                  **kwargs)


def segment_max_xla(data, segment_ids, num_segments: int, **kwargs):
    from presto_tpu.ops import segred
    return segred.xla_segment_max(data, segment_ids, num_segments,
                                  **kwargs)


def segment_min_xla(data, segment_ids, num_segments: int, **kwargs):
    from presto_tpu.ops import segred
    return segred.xla_segment_min(data, segment_ids, num_segments,
                                  **kwargs)
