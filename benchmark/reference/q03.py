"""TPC-H Q3 (clause 2.4.3) in plain NumPy; parameters SEGMENT and DATE.

``chip_smoke.ref_q3`` with its two ``np.isin`` calls replaced by boolean
tables over the (dense, positive) keys, which is what keeps the check of
a handful of SF10 statements to seconds; tests hold the two equal.
"""

import numpy as np

from reference.common import date_str, days, dec


def answer(data, params, state=None):
    day = days(params["DATE"])
    seg = data.col("customer", "c_mktsegment")
    seg_d = data.dictionary("customer", "c_mktsegment")
    code = int(np.flatnonzero(seg_d == params["SEGMENT"])[0])
    ckey = data.col("customer", "c_custkey")
    okey = data.col("orders", "o_orderkey")
    ocust = data.col("orders", "o_custkey")
    odate = data.col("orders", "o_orderdate")
    oprio = data.col("orders", "o_shippriority")
    in_seg = np.zeros(int(max(ckey.max(), ocust.max())) + 1, dtype=bool)
    in_seg[ckey[seg == code]] = True
    okeep = (odate < day) & in_seg[ocust]
    lkey = data.col("lineitem", "l_orderkey")
    order_ok = np.zeros(int(max(okey.max(), lkey.max())) + 1, dtype=bool)
    order_ok[okey[okeep]] = True
    lkeep = (data.col("lineitem", "l_shipdate") > day) & order_ok[lkey]
    keys = lkey[lkeep]
    rev = (data.col("lineitem", "l_extendedprice")[lkeep]
           * (100 - data.col("lineitem", "l_discount")[lkeep]))
    order = np.argsort(keys, kind="stable")
    keys, rev = keys[order], rev[order]
    starts = np.flatnonzero(np.r_[True, keys[1:] != keys[:-1]])
    # an order has at most 7 lines: group sums stay far inside int64
    sums = np.add.reduceat(rev, starts) if len(starts) else rev[:0]
    gkeys = keys[starts]
    by_key = np.argsort(okey, kind="stable")
    oidx = by_key[np.searchsorted(okey[by_key], gkeys)]
    top = np.lexsort((odate[oidx], -sums))[:10]
    return [[int(gkeys[i]), dec(int(sums[i]), 4),
             date_str(odate[oidx[i]]), int(oprio[oidx[i]])]
            for i in top]
