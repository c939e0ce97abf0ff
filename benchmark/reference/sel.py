"""``sel``: orders by priority over ``memory.default.bench_orders``.

The table holds the orders before BASE_BEFORE (the mix's set-up) plus
one calendar day of orders for every ``ins`` in ``state`` (the
parameters of the writes this answer has to show).
"""

import numpy as np

from reference.common import days, dec

BASE_BEFORE = "1993-01-01"  # traffic/dash.json's set-up fills up to here


def per_day(data):
    """(first day, counts[day, priority], totals[day, priority]): exact
    int64; a day's total price is far inside int64."""
    memo = data.memo.get("orders_per_day")
    if memo is None:
        odate = data.col("orders", "o_orderdate").astype(np.int64)
        prio = data.col("orders", "o_orderpriority").astype(np.int64)
        total = data.col("orders", "o_totalprice").astype(np.int64)
        nprio = len(data.dictionary("orders", "o_orderpriority"))
        d0 = int(odate.min())
        ndays = int(odate.max()) - d0 + 1
        cell = (odate - d0) * nprio + prio
        counts = np.bincount(cell, minlength=ndays * nprio)
        totals = np.zeros(ndays * nprio, dtype=np.int64)
        np.add.at(totals, cell, total)
        memo = data.memo["orders_per_day"] = (
            d0, counts.reshape(ndays, nprio), totals.reshape(ndays, nprio))
    return memo


def answer(data, params, state=None):
    d0, counts, totals = per_day(data)
    base = days(BASE_BEFORE) - d0
    extra = sorted({days(w["DAY"]) - d0 for w in (state or [])})
    n = counts[:base].sum(axis=0) + counts[extra].sum(axis=0)
    t = totals[:base].sum(axis=0) + totals[extra].sum(axis=0)
    prio_d = data.dictionary("orders", "o_orderpriority")
    return [[str(prio_d[k]), int(n[k]), dec(int(t[k]), 2)]
            for k in range(len(prio_d)) if n[k]]
