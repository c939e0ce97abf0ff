"""Fused filter+compact: write surviving rows densely in one pass.

Between-operator compaction on the XLA path is a sort or an
``nonzero``+gather — full-width random-access passes over every
column just to drop dead rows (exec/operators.compact_dtable; a
60M-row ``jnp.nonzero`` alone measured 5.4 s on v5e). This kernel
streams the input tiles once: a running survivor count lives in a
VMEM accumulator, each live row appends at the next dense output
position, and every column of the row is copied while the tile is
resident — the predicate's mask goes in, compacted columns come out,
and downstream operators stop paying for padded width + ``__live__``
masks.

Semantics match the XLA fallback (:func:`filter_compact_xla`, the
pre-kernel ``compact_dtable`` gather) exactly where results can
observe them: live rows land at the same dense positions in the same
stable order; positions past the live count are DEAD either way (the
returned mask kills them) and only differ in which garbage they hold
(the gather replicates the last row, the kernel leaves zeros).

The sequential TPU grid is what makes the running count race-free —
same property the hash-build kernel leans on. Appends past
``capacity`` drop; the caller computes the overflow flag from the
live count (identical on both backends) and feeds the capacity retry
ladder.

Status on the chip (v5e, PR 21): the per-row ``dst[pos] = src[i]`` is
a scalar store to a VMEM ref, which Pallas refuses ("Cannot store
scalars to VMEM"); ``auto`` does not select this kernel
(kernels/__init__.AUTO_PALLAS). It runs interpreted on the CPU only.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from presto_tpu.kernels import u64

TILE = 256
# eligibility gate: every OUTPUT column block is [capacity, ...] with
# a constant index map, i.e. all compacted columns stay VMEM-resident
# together; past this byte bound the kernel declines and the XLA
# gather runs (a 60M-row compaction is exactly the case that must
# degrade, not fail Mosaic allocation)
PALLAS_MAX_OUT_BYTES = 8 << 20


def _interpret_mode() -> bool:
    from presto_tpu import kernels as K
    return K.interpret_mode()


def _out_bytes(arrays: dict, capacity: int) -> int:
    total = 0
    for a in arrays.values():
        row = int(a.dtype.itemsize)
        for dim in a.shape[1:]:
            row *= int(dim)
        total += capacity * row
    return total


def _split64(a):
    """Bitcast a 64-bit column into uint32 planes for the kernel body
    (Mosaic has no 64-bit ALU — see kernels/u64.py; row copies are
    dtype-blind, so a [n] int64/float64 column rides as [n, 2] uint32
    and a [n, m] one as [n, 2m]). Returns (kernel array, restore spec
    or None for pass-through dtypes)."""
    if a.dtype.itemsize != 8:
        return a, None
    v = a.view(jnp.uint32)
    if a.ndim == 1:
        v = v.reshape(a.shape[0], 2)
    return v, (a.dtype, a.ndim)


def _join64(a, spec, capacity: int):
    """Inverse of :func:`_split64` at the compacted width."""
    if spec is None:
        return a
    dtype, ndim = spec
    out = a.view(dtype)
    if ndim == 1:
        return out.reshape(capacity)
    return out


def filter_compact_pallas(live, arrays: dict, capacity: int) -> dict:
    """Compact ``arrays`` (1-D or 2-D, [n, ...]) to ``capacity`` rows,
    keeping rows where ``live`` in stable order. Returns the
    compacted arrays keyed as given (pad rows zeroed, dead).
    Output sets past the VMEM bound fall back to the XLA gather."""
    from jax.experimental import pallas as pl

    from presto_tpu import kernels as K
    cap = int(capacity)
    if _out_bytes(arrays, cap) > PALLAS_MAX_OUT_BYTES:
        return filter_compact_xla(live, arrays, capacity)
    K.note("pallas:compact")
    names = list(arrays)
    specs64 = {}
    arrays = dict(arrays)
    for k in names:
        arrays[k], specs64[k] = _split64(arrays[k])
    ins = [u64.pad_rows(live, TILE, False)] + [
        u64.pad_rows(arrays[k], TILE, 0) for k in names]

    def kernel(*refs):
        live_ref = refs[0]
        in_refs = refs[1:1 + len(names)]
        out_refs = refs[1 + len(names):-1]
        cnt_ref = refs[-1]
        t = pl.program_id(0)

        @pl.when(t == 0)
        def _init():
            cnt_ref[...] = jnp.zeros((1,), jnp.int32)
            for o in out_refs:
                o[...] = jnp.zeros(o.shape, o.dtype)

        def row(i, _):
            pos = cnt_ref[0]

            @pl.when(live_ref[i] & (pos < cap))
            def _emit():
                for src, dst in zip(in_refs, out_refs):
                    if len(dst.shape) == 1:
                        dst[pos] = src[i]
                    else:
                        dst[pos, :] = src[i, :]
                cnt_ref[0] = pos + 1

            return 0

        jax.lax.fori_loop(0, TILE, row, 0)

    ntiles = ins[0].shape[0] // TILE
    in_specs = [pl.BlockSpec((TILE,), lambda t: (t,))]
    out_specs = []
    out_shape = []
    for k in names:
        a = arrays[k]
        if a.ndim == 1:
            in_specs.append(pl.BlockSpec((TILE,), lambda t: (t,)))
            out_specs.append(pl.BlockSpec((cap,), lambda t: (0,)))
            out_shape.append(jax.ShapeDtypeStruct((cap,), a.dtype))
        else:
            m = a.shape[1]
            in_specs.append(
                pl.BlockSpec((TILE, m), lambda t: (t, 0)))
            out_specs.append(
                pl.BlockSpec((cap, m), lambda t: (0, 0)))
            out_shape.append(
                jax.ShapeDtypeStruct((cap, m), a.dtype))
    out_specs.append(pl.BlockSpec((1,), lambda t: (0,)))
    out_shape.append(jax.ShapeDtypeStruct((1,), jnp.int32))
    outs = pl.pallas_call(
        kernel,
        grid=(ntiles,),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=_interpret_mode(),
    )(*ins)
    return {k: _join64(o, specs64[k], cap)
            for k, o in zip(names, outs[:-1])}


def filter_compact_xla(live, arrays: dict, capacity: int) -> dict:
    """XLA fallback: the nonzero+gather compaction the kernel
    replaces (pad rows replicate the last row — dead either way)."""
    from presto_tpu import kernels as K
    K.note("xla:compact")
    n = live.shape[0]
    idx = jnp.nonzero(live, size=int(capacity), fill_value=n - 1)[0]
    return {k: v[idx] for k, v in arrays.items()}
