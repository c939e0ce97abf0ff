"""TPC-H Q1 (clause 2.4.1) in plain NumPy; parameter DELTA.

``chip_smoke.ref_q1`` sums the kept rows of each group per call, about
15 s over SF10's 60M rows. Here the exact sums are taken once per
(group, ship day) and a DELTA is a prefix over the days, so a window's
many Q1 statements cost one pass; tests hold the two equal.
"""

import numpy as np

from reference.common import avg_half_up, days, dec

LIMB = 24  # bits: 60M rows x 2**24 = 2**50, inside float64's 2**53


def exact_sums(cell: np.ndarray, x: np.ndarray, ncells: int) -> np.ndarray:
    """Exact per-cell sums of a non-negative int64 array, as Python
    ints: bincount adds float64 weights, so each 24-bit limb is summed
    apart, where every partial sum is an integer below 2**53."""
    out = np.zeros(ncells, dtype=object)
    shift = 0
    while True:
        limb = (x >> shift) & ((1 << LIMB) - 1)
        part = np.bincount(cell, weights=limb.astype(np.float64),
                           minlength=ncells)
        out += part.astype(np.int64).astype(object) * (1 << shift)
        shift += LIMB
        if not (x >> shift).any():
            return out


def per_day(data):
    memo = data.memo.get("q01_per_day")
    if memo is None:
        ship = data.col("lineitem", "l_shipdate").astype(np.int64)
        ngrp = (len(data.dictionary("lineitem", "l_returnflag"))
                * len(data.dictionary("lineitem", "l_linestatus")))
        gid = (data.col("lineitem", "l_returnflag").astype(np.int64)
               * len(data.dictionary("lineitem", "l_linestatus"))
               + data.col("lineitem", "l_linestatus"))
        d0 = int(ship.min())
        ndays = int(ship.max()) - d0 + 1
        cell = (ship - d0) * ngrp + gid
        qty = data.col("lineitem", "l_quantity")
        price = data.col("lineitem", "l_extendedprice")
        disc = data.col("lineitem", "l_discount")
        disc_price = price * (100 - disc)
        charge = disc_price * (100 + data.col("lineitem", "l_tax"))
        n = ndays * ngrp
        sums = [exact_sums(cell, x, n).reshape(ndays, ngrp)
                for x in (qty, price, disc_price, charge, disc)]
        sums.append(np.bincount(cell, minlength=n).astype(object)
                    .reshape(ndays, ngrp))
        memo = data.memo["q01_per_day"] = (d0, sums)
    return memo


def answer(data, params, state=None):
    cutoff = days("1998-12-01") - int(params["DELTA"])
    d0, sums = per_day(data)
    upto = max(0, min(cutoff - d0 + 1, sums[0].shape[0]))
    qty, base, disc_price, charge, disc, count = (
        s[:upto].sum(axis=0) for s in sums)
    rf_d = data.dictionary("lineitem", "l_returnflag")
    ls_d = data.dictionary("lineitem", "l_linestatus")
    rows = []
    for g in range(len(count)):  # code order == collation order
        n = int(count[g])
        if not n:
            continue
        rows.append([
            str(rf_d[g // len(ls_d)]), str(ls_d[g % len(ls_d)]),
            dec(int(qty[g]), 2), dec(int(base[g]), 2),
            dec(int(disc_price[g]), 4), dec(int(charge[g]), 6),
            dec(avg_half_up(int(qty[g]), n), 2),
            dec(avg_half_up(int(base[g]), n), 2),
            dec(avg_half_up(int(disc[g]), n), 2), n])
    return rows
