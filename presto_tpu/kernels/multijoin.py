"""The MultiJoin sorted-probe walk as ONE Pallas kernel.

The fused star-schema chain (plan/nodes.MultiJoin, PR 10) already
collapsed Q5/Q9's join cascade into a sequential probe walk — but on
the XLA path each of its k steps still pays the full sort/merge
lookup over the spine's static width, so a 5-dimension chain makes
~10 full-width HBM sort passes. This kernel walks the WHOLE chain
while a spine tile is resident in VMEM: per row it combines the
step's key hashes (gathering hashes of earlier builds' matched rows
straight out of the walk state), probes that step's open-addressing
table (built once per build by kernels/hashjoin.build_table), and
carries the accumulated live mask — k probes, one pass over the
spine, zero sorts.

Semantics against the XLA walk (exec/operators.apply_multi_join):
identical per live row. Step hashes are the same per-column
hash + ``combine_hashes`` chain (re-derived in 32-bit limbs,
kernels/u64.py), dead rows gather build row 0 exactly like the XLA
path's ``clip(where(found, row, -1))``, and 64-bit-collision value
verification is applied to the kernel's gather outputs with the same
skip-strings rule as ``_verify_keys``. Rows differ only in the
garbage their DEAD lanes carry — invisible to results.

``try_fused`` returns None when the chain isn't kernel-shaped (a
2-D LONG-decimal key, a key symbol that isn't a plain spine/build
column): the caller then runs the XLA walk — dispatch-level parity
is total either way.

Status on the chip (v5e, PR 21): refused with hashjoin.build_table's
"Cannot store scalars to VMEM"; ``auto`` does not select this kernel
(kernels/__init__.AUTO_PALLAS). It runs interpreted on the CPU only.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from presto_tpu.kernels import hashjoin as HJ
from presto_tpu.kernels import u64
from presto_tpu.ops import hash as H

TILE = 256
_SPINE = -1


def _interpret_mode() -> bool:
    from presto_tpu import kernels as K
    return K.interpret_mode()


def _col_hash(v):
    """Per-column 64-bit key of a 1-D Val (ops/hash contract)."""
    if v.is_string:
        return H.hash_string_column(v.data, v.dictionary, v.valid)
    return H.hash_int_column(v.data, v.valid)


def _combined_hash(cols, keys):
    return H.combine_hashes([_col_hash(cols[k]) for k in keys])


def try_fused(spine_cols: dict, spine_live, width: int,
              builds: list, criteria: list, growth: int = 1,
              max_probes: int = HJ.MAX_PROBES):
    """Run the fused probe walk. ``builds`` is a list of
    (cols dict, live mask, nrows) per build, ``criteria`` the
    per-step [(probe_sym, build_sym)] lists. Returns
    (gathers list of int32 [width], live bool [width], ok bool
    scalar) or None when the chain is not kernel-shaped."""
    from jax.experimental import pallas as pl

    # -- resolve every probe key to its source relation --------------
    sources: dict[str, int] = {s: _SPINE for s in spine_cols}
    steps: list[dict] = []
    for si, ((bcols, blive, bn), crit) in enumerate(
            zip(builds, criteria)):
        keys = []
        for lk, rk in crit:
            src = sources.get(lk)
            v = spine_cols[lk] if src == _SPINE else \
                builds[src][0].get(lk) if src is not None else None
            bv = bcols.get(rk)
            if (src is None or v is None or bv is None
                    or getattr(v.data, "ndim", 1) != 1
                    or getattr(bv.data, "ndim", 1) != 1):
                return None
            keys.append((lk, rk, src, v))
        steps.append({"keys": keys, "build": (bcols, blive, bn)})
        for sym in bcols:
            sources[sym] = si

    # -- build one open-addressing table per step --------------------
    # every step's table (and its build-side hash planes) must be
    # VMEM-resident during the walk: a chain with one oversized build
    # declines whole, and the caller runs the XLA walk instead
    k = len(steps)
    for st in steps:
        bn = st["build"][2]
        if not HJ.table_fits_vmem(
                H.next_pow2(2 * max(bn, 1)) * max(int(growth), 1)):
            return None
    for st in steps:
        bcols, blive, bn = st["build"]
        rkeys = [rk for _lk, rk, _s, _v in st["keys"]]
        bl = blive
        for rk in rkeys:
            bv = bcols[rk]
            if bv.valid is not None:
                bl = bl & bv.valid
        cap = H.next_pow2(2 * max(bn, 1)) * max(int(growth), 1)
        rh = _combined_hash(bcols, rkeys)
        thi, tlo, trow, b_ok = HJ.build_table(rh, bl, cap, max_probes)
        st["table"] = (thi, tlo, trow)
        st["cap"] = thi.shape[0]
        st["build_ok"] = b_ok

    # -- flatten kernel inputs ---------------------------------------
    flat = [u64.pad_rows(spine_live, TILE, False)]
    specs = [pl.BlockSpec((TILE,), lambda t: (t,))]

    def add(arr, spine_side: bool) -> int:
        if spine_side:
            arr = u64.pad_rows(arr, TILE, 0)
            specs.append(pl.BlockSpec((TILE,), lambda t: (t,)))
        else:
            size = arr.shape[0]
            specs.append(pl.BlockSpec((size,), lambda t: (0,)))
        flat.append(arr)
        return len(flat) - 1

    kspec = []  # per step: table positions + key/valid positions
    for st in steps:
        thi, tlo, trow = st["table"]
        tpos = (add(thi, False), add(tlo, False), add(trow, False))
        kpos = []
        vpos = []
        for _lk, _rk, src, v in st["keys"]:
            hhi, hlo = u64.split(_col_hash(v))
            kpos.append((src, add(hhi, src == _SPINE),
                         add(hlo, src == _SPINE)))
            if v.valid is not None:
                vpos.append((src, add(v.valid, src == _SPINE)))
        kspec.append({"tpos": tpos, "mask": st["cap"] - 1,
                      "kpos": kpos, "vpos": vpos})

    # probe outcome states (python ints: captured jnp scalars are
    # rejected by pallas as closure constants)
    walk, hit, miss = 0, 1, 2

    def kernel(*refs):
        live_ref = refs[0]
        g_refs = refs[len(flat):len(flat) + k]
        alive_ref = refs[len(flat) + k]
        ok_ref = refs[len(flat) + k + 1]
        t = pl.program_id(0)

        @pl.when(t == 0)
        def _init():
            ok_ref[...] = jnp.ones((1,), jnp.bool_)

        def row(i, _):
            alive = live_ref[i]
            g = [jnp.int32(0)] * k
            for si, st in enumerate(kspec):
                kv = alive
                for src, vp in st["vpos"]:
                    vref = refs[vp]
                    kv = kv & (vref[i] if src == _SPINE
                               else vref[g[src]])
                hh = hl = None
                for src, hp, lp in st["kpos"]:
                    idx = i if src == _SPINE else g[src]
                    kh = refs[hp][idx]
                    kl = refs[lp][idx]
                    if hh is None:
                        hh, hl = kh, kl
                    else:
                        hh, hl = u64.combine_step(hh, hl, kh, kl)
                hh, hl = u64.remap_empty(hh, hl)
                thi_ref = refs[st["tpos"][0]]
                tlo_ref = refs[st["tpos"][1]]
                trow_ref = refs[st["tpos"][2]]
                mask = st["mask"]
                slot0 = (u64.slot32(hh, hl)
                         & jnp.uint32(mask)).astype(jnp.int32)

                def cond(c):
                    _slot, j, state = c
                    return (state == walk) & (j < max_probes)

                def step(c, thi_ref=thi_ref, tlo_ref=tlo_ref,
                         hh=hh, hl=hl, mask=mask):
                    slot, j, _state = c
                    t_hi = thi_ref[slot]
                    t_lo = tlo_ref[slot]
                    empty = ((t_hi == u64.EMPTY32)
                             & (t_lo == u64.EMPTY32))
                    match = (t_hi == hh) & (t_lo == hl)
                    state = jnp.where(match, jnp.int32(hit),
                                      jnp.where(empty, jnp.int32(miss),
                                                jnp.int32(walk)))
                    nxt = jnp.where(state == walk,
                                    (slot + 1) & jnp.int32(mask),
                                    slot)
                    return nxt, j + jnp.int32(1), state

                # dead rows (and zero-hash pad rows) skip the chain
                # entirely: their found is False regardless, and a
                # long cluster walked by a row whose result cannot
                # matter must not flip the overflow flag
                slot, _j, state = jax.lax.while_loop(
                    cond, step,
                    (slot0, jnp.int32(0),
                     jnp.where(kv, jnp.int32(walk), jnp.int32(miss))))
                found = kv & (state == hit)
                rowi = jnp.where(found, trow_ref[slot], 0)
                g_refs[si][i] = rowi
                g[si] = rowi
                alive = found

                @pl.when(state == walk)
                def _undecided():
                    ok_ref[0] = False

            alive_ref[i] = alive
            return 0

        jax.lax.fori_loop(0, TILE, row, 0)

    padded = flat[0].shape[0]
    ntiles = padded // TILE
    out_specs = ([pl.BlockSpec((TILE,), lambda t: (t,))] * (k + 1)
                 + [pl.BlockSpec((1,), lambda t: (0,))])
    out_shape = ([jax.ShapeDtypeStruct((padded,), jnp.int32)] * k
                 + [jax.ShapeDtypeStruct((padded,), jnp.bool_),
                    jax.ShapeDtypeStruct((1,), jnp.bool_)])
    outs = pl.pallas_call(
        kernel,
        grid=(ntiles,),
        in_specs=specs,
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=_interpret_mode(),
    )(*flat)
    gathers = [o[:width] for o in outs[:k]]
    alive = outs[k][:width]
    ok = outs[k + 1][0]
    for st in steps:
        ok = ok & st["build_ok"][0]

    # -- 64-bit-collision value verification (XLA, gathers only) -----
    live = alive
    for si, st in enumerate(steps):
        bcols = st["build"][0]
        gather = gathers[si]
        for lk, rk, src, v in st["keys"]:
            bv = bcols[rk]
            if v.is_string or bv.is_string:
                continue  # content-hashed dictionaries, as _verify_keys
            ld = v.data if src == _SPINE else v.data[gathers[src]]
            live = live & (ld == bv.data[gather])
    from presto_tpu import kernels as K
    K.note("pallas:multijoin")
    return gathers, live, ok


def try_fused_xla(*_args, **_kw):
    """The dispatch-table fallback of the fused walk: returns None —
    "not fused" — so the caller runs its inline XLA walk
    (exec/operators.apply_multi_join's sequential sorted-probe body,
    which is the numerical reference the kernel is held to). The walk
    is an operator body, not a separable array->array function, so
    the fallback lives as this sentinel rather than a copy."""
    return None
