"""Hash-join build + probe as Pallas open-addressing kernels.

The XLA lookup path (ops/hash.py) pays a sort/gather cascade per join:
one combined (build+probe) sort, two scans, two un-sort permutations —
each a full random-access HBM pass. These kernels replace it with the
classic in-kernel hash table the reference engine uses
(operator/join/PagesHash.java): a BUILD pass inserts every live build
row into an open-addressing table (linear probing, table resident in
VMEM across the sequential TPU grid), and a PROBE pass looks each
probe row up with a data-dependent probe chain — O(rows) work instead
of O(rows log rows) sort passes, no permutation traffic.

Layout is specialized per query: the planner-chosen ``capacity``
(build NDV estimate, grown by the executor's overflow-retry ladder)
sizes the table, and hashes live as two uint32 planes (kernels/u64.py
— Mosaic has no 64-bit ALU) with key width folded in by the XLA-side
``combine_hashes`` before the kernel ever sees a row.

Semantics are byte-identical to the XLA fallback (:func:`lookup_join_xla`
— the exact code this replaces): ``found`` = live probe row whose
64-bit combined hash matches a live build row, representative on
duplicate build keys = the LARGEST build row index (the sorted path's
last-run-row choice; the build kernel accumulates ``max`` per slot),
value verification against residual 64-bit collisions stays with the
caller (exec/operators._verify_keys) on both backends.

Probe chains are bounded at ``max_probes``: a chain that long means
the capacity estimate was badly wrong, and the kernel reports it
LOUDLY through the ``ok`` flag so the executor's capacity retry
ladder rebuilds at a larger size (counted as
``presto_tpu_hash_probe_overflow_total``; the ladder's exhaustion
raises ops/hash.HashChainOverflow) — never a silent wrong answer.

On the CPU platform the kernels run under ``interpret=True`` so the
CPU test tier executes the real kernel bodies (the ``kernel_backend``
session property's ``pallas`` setting forces exactly that). On the
TPU these bodies do not lower — the per-row table claims are scalar
stores to VMEM refs, which Pallas refuses ("Cannot store scalars to
VMEM", v5e, PR 21) — so ``auto`` never selects them and forcing
``pallas`` there raises that error (kernels/__init__.AUTO_PALLAS).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from presto_tpu.kernels import u64
from presto_tpu.ops import hash as H

TILE = 256
MAX_PROBES = 256
# auto-eligibility bound: three table planes (hi, lo, row) must stay
# VMEM-resident across the grid; 1<<20 slots * 12 B = 12 MB ~ one core
PALLAS_MAX_TABLE = 1 << 20


def _interpret_mode() -> bool:
    from presto_tpu import kernels as K
    return K.interpret_mode()


def build_table(row_hash, live, capacity: int,
                max_probes: int = MAX_PROBES):
    """Insert live rows into an open-addressing table. Returns
    (table_hi, table_lo uint32 [capacity], table_row int32 [capacity]
    (-1 = empty; duplicates keep the max row index), ok bool [1]).

    The grid over row tiles is SEQUENTIAL on TPU, so read-modify-write
    claims need no atomics; the table planes are outputs with a
    constant index map, i.e. VMEM-resident accumulators written back
    once at the end.
    """
    from jax.experimental import pallas as pl
    cap = max(int(capacity), 8)
    if cap & (cap - 1):
        cap = H.next_pow2(cap)
    mask = cap - 1
    hi, lo = u64.split(row_hash)
    hi = u64.pad_rows(hi, TILE, 0)
    lo = u64.pad_rows(lo, TILE, 0)
    livep = u64.pad_rows(live, TILE, False)

    def kernel(hi_ref, lo_ref, live_ref, thi_ref, tlo_ref, trow_ref,
               ok_ref):
        t = pl.program_id(0)

        @pl.when(t == 0)
        def _init():
            thi_ref[...] = jnp.full((cap,), u64.EMPTY32, jnp.uint32)
            tlo_ref[...] = jnp.full((cap,), u64.EMPTY32, jnp.uint32)
            trow_ref[...] = jnp.full((cap,), -1, jnp.int32)
            ok_ref[...] = jnp.ones((1,), jnp.bool_)

        base = t * TILE

        def row(i, _):
            h_hi = hi_ref[i]
            h_lo = lo_ref[i]
            slot0 = (u64.slot32(h_hi, h_lo)
                     & jnp.uint32(mask)).astype(jnp.int32)

            def cond(c):
                _slot, j, done = c
                return jnp.logical_not(done) & (j < max_probes)

            def step(c):
                slot, j, _done = c
                t_hi = thi_ref[slot]
                t_lo = tlo_ref[slot]
                empty = (t_hi == u64.EMPTY32) & (t_lo == u64.EMPTY32)
                claim = empty | ((t_hi == h_hi) & (t_lo == h_lo))

                @pl.when(claim)
                def _claim():
                    thi_ref[slot] = h_hi
                    tlo_ref[slot] = h_lo
                    trow_ref[slot] = jnp.maximum(trow_ref[slot],
                                                 base + i)

                nxt = jnp.where(claim, slot,
                                (slot + 1) & jnp.int32(mask))
                return nxt, j + jnp.int32(1), claim

            _slot, _j, done = jax.lax.while_loop(
                cond, step,
                (slot0, jnp.int32(0), jnp.logical_not(live_ref[i])))

            @pl.when(jnp.logical_not(done))
            def _overflow():
                ok_ref[0] = False

            return 0

        jax.lax.fori_loop(0, TILE, row, 0)

    ntiles = hi.shape[0] // TILE
    thi, tlo, trow, ok = pl.pallas_call(
        kernel,
        grid=(ntiles,),
        in_specs=[pl.BlockSpec((TILE,), lambda t: (t,))] * 3,
        out_specs=[pl.BlockSpec((cap,), lambda t: (0,)),
                   pl.BlockSpec((cap,), lambda t: (0,)),
                   pl.BlockSpec((cap,), lambda t: (0,)),
                   pl.BlockSpec((1,), lambda t: (0,))],
        out_shape=[jax.ShapeDtypeStruct((cap,), jnp.uint32),
                   jax.ShapeDtypeStruct((cap,), jnp.uint32),
                   jax.ShapeDtypeStruct((cap,), jnp.int32),
                   jax.ShapeDtypeStruct((1,), jnp.bool_)],
        interpret=_interpret_mode(),
    )(hi, lo, livep)
    return thi, tlo, trow, ok


def probe_table(thi, tlo, trow, probe_hash, probe_live,
                max_probes: int = MAX_PROBES):
    """Look each live probe row up in a built table. Returns
    (build_row int32 [n] (-1 = no match), found bool [n], ok bool [1]
    — False when a chain hit ``max_probes`` undecided)."""
    from jax.experimental import pallas as pl
    cap = thi.shape[0]
    mask = cap - 1
    n = probe_hash.shape[0]
    hi, lo = u64.split(probe_hash)
    hi = u64.pad_rows(hi, TILE, 0)
    lo = u64.pad_rows(lo, TILE, 0)
    livep = u64.pad_rows(probe_live, TILE, False)

    # per-row probe outcome states (python ints: captured jnp scalars
    # are rejected by pallas as closure constants)
    walk, hit, miss = 0, 1, 2

    def kernel(hi_ref, lo_ref, live_ref, thi_ref, tlo_ref, trow_ref,
               brow_ref, found_ref, ok_ref):
        t = pl.program_id(0)

        @pl.when(t == 0)
        def _init():
            ok_ref[...] = jnp.ones((1,), jnp.bool_)

        def row(i, _):
            h_hi = hi_ref[i]
            h_lo = lo_ref[i]
            slot0 = (u64.slot32(h_hi, h_lo)
                     & jnp.uint32(mask)).astype(jnp.int32)

            def cond(c):
                _slot, j, state = c
                return (state == walk) & (j < max_probes)

            def step(c):
                slot, j, _state = c
                t_hi = thi_ref[slot]
                t_lo = tlo_ref[slot]
                empty = (t_hi == u64.EMPTY32) & (t_lo == u64.EMPTY32)
                match = (t_hi == h_hi) & (t_lo == h_lo)
                state = jnp.where(match, jnp.int32(hit),
                                  jnp.where(empty, jnp.int32(miss),
                                            jnp.int32(walk)))
                nxt = jnp.where(state == walk,
                                (slot + 1) & jnp.int32(mask), slot)
                return nxt, j + jnp.int32(1), state

            slot, _j, state = jax.lax.while_loop(
                cond, step,
                (slot0, jnp.int32(0),
                 jnp.where(live_ref[i], jnp.int32(walk),
                           jnp.int32(miss))))
            got = state == hit
            brow_ref[i] = jnp.where(got, trow_ref[slot], -1)
            found_ref[i] = got

            @pl.when(state == walk)
            def _undecided():
                ok_ref[0] = False

            return 0

        jax.lax.fori_loop(0, TILE, row, 0)

    ntiles = hi.shape[0] // TILE
    brow, found, ok = pl.pallas_call(
        kernel,
        grid=(ntiles,),
        in_specs=[pl.BlockSpec((TILE,), lambda t: (t,)),
                  pl.BlockSpec((TILE,), lambda t: (t,)),
                  pl.BlockSpec((TILE,), lambda t: (t,)),
                  pl.BlockSpec((cap,), lambda t: (0,)),
                  pl.BlockSpec((cap,), lambda t: (0,)),
                  pl.BlockSpec((cap,), lambda t: (0,))],
        out_specs=[pl.BlockSpec((TILE,), lambda t: (t,)),
                   pl.BlockSpec((TILE,), lambda t: (t,)),
                   pl.BlockSpec((1,), lambda t: (0,))],
        out_shape=[jax.ShapeDtypeStruct((hi.shape[0],), jnp.int32),
                   jax.ShapeDtypeStruct((hi.shape[0],), jnp.bool_),
                   jax.ShapeDtypeStruct((1,), jnp.bool_)],
        interpret=_interpret_mode(),
    )(hi, lo, livep, thi, tlo, trow)
    return brow[:n], found[:n], ok


def table_fits_vmem(capacity: int) -> bool:
    """Eligibility gate: the table planes must stay VMEM-resident
    across the sequential grid. Past the bound the kernel DECLINES
    and the numerically identical XLA lookup runs instead — a
    too-large build must degrade to the sort path, not fail Mosaic
    allocation (the capacity retry ladder would only grow it)."""
    return H.next_pow2(max(int(capacity), 8)) <= PALLAS_MAX_TABLE


def lookup_join_pallas(build_hash, build_live, probe_hash, probe_live,
                       capacity: int, max_probes: int = MAX_PROBES):
    """Pallas FK->PK join lookup: (build_row int32 [n_probe]
    (-1 = none), found bool [n_probe], ok bool scalar). Tables past
    the VMEM bound fall back to the XLA lookup (see
    table_fits_vmem)."""
    from presto_tpu import kernels as K
    if not table_fits_vmem(capacity):
        return lookup_join_xla(build_hash, build_live, probe_hash,
                               probe_live, capacity, max_probes)
    K.note("pallas:join_lookup")
    thi, tlo, trow, b_ok = build_table(build_hash, build_live,
                                       capacity, max_probes)
    brow, found, p_ok = probe_table(thi, tlo, trow, probe_hash,
                                    probe_live, max_probes)
    return brow, found, b_ok[0] & p_ok[0]


def lookup_join_xla(build_hash, build_live, probe_hash, probe_live,
                    capacity: int, max_probes: int = MAX_PROBES):
    """XLA fallback: the sorted-merge lookup this package's kernel
    replaces (sort_build_side + probe_runs + last-run representative —
    verbatim the pre-kernel apply_join/apply_semijoin body, so the
    two backends are byte-identical by construction)."""
    from presto_tpu import kernels as K
    K.note("xla:join_lookup")
    nb = build_hash.shape[0]
    _bsh, bsidx = H.sort_build_side(build_hash, build_live)
    lo, count, found = H.probe_runs(build_hash, build_live,
                                    probe_hash, probe_live)
    build_row = jnp.where(
        found, bsidx[jnp.clip(lo + count - 1, 0, nb - 1)], -1)
    return build_row, found, jnp.asarray(True)
