"""TPC-H Q10 (clause 2.4.10) in plain NumPy; parameter DATE.

Orders of the three months from DATE, their lines with
``l_returnflag = 'R'``, joined through direct-address arrays over the
dense keys (``o_orderkey``, ``c_custkey``, ``n_nationkey``); revenue is
``l_extendedprice * (100 - l_discount)`` in int64 at scale 4, summed
per customer (a customer has a few tens of such lines: the sums stay far
inside int64); the 20 customers of the largest revenue. The
specification orders by revenue alone, so customers of one revenue
among the 20, or the 20th and the 21st, leave the answer open: such a
run is compared as a set (``reference/ties.py``: any order inside the
run, and any of its customers in the places it fills, is the answer).
Nothing here comes from the engine.
"""

import datetime

import numpy as np

from reference import ties
from reference.common import days, dec

LIMIT = 20


def _plus_months(date: str, months: int) -> str:
    d = datetime.date.fromisoformat(date)
    years, month = divmod(d.month - 1 + months, 12)
    return str(d.replace(year=d.year + years, month=month + 1))


def answer(data, params, state=None):
    lo = days(params["DATE"])
    hi = days(_plus_months(params["DATE"], 3))
    okey = data.col("orders", "o_orderkey")
    odate = data.col("orders", "o_orderdate")
    okeep = (odate >= lo) & (odate < hi)
    lkey = data.col("lineitem", "l_orderkey")
    # customer of the order, 0 (no customer has that key) where the
    # order is out of the three months
    order_cust = np.zeros(int(max(okey.max(), lkey.max())) + 1,
                          dtype=np.int64)
    order_cust[okey[okeep]] = data.col("orders", "o_custkey")[okeep]
    flags = data.dictionary("lineitem", "l_returnflag")
    r_code = int(np.flatnonzero(flags == "R")[0])
    keep = data.col("lineitem", "l_returnflag") == r_code
    keep[keep] = order_cust[lkey[keep]] > 0
    cust = order_cust[lkey[keep]]
    rev = (data.col("lineitem", "l_extendedprice")[keep]
           * (100 - data.col("lineitem", "l_discount")[keep]))
    if not len(cust):
        return []
    by_cust = np.argsort(cust, kind="stable")
    cust, rev = cust[by_cust], rev[by_cust]
    starts = np.flatnonzero(np.r_[True, cust[1:] != cust[:-1]])
    sums = np.add.reduceat(rev, starts)
    gcust = cust[starts]
    order = np.argsort(-sums, kind="stable")
    ckey = data.col("customer", "c_custkey")
    row_of = np.full(int(ckey.max()) + 1, -1, dtype=np.int64)
    row_of[ckey] = np.arange(len(ckey))
    nkey = data.col("nation", "n_nationkey")
    nation_row = np.full(int(nkey.max()) + 1, -1, dtype=np.int64)
    nation_row[nkey] = np.arange(len(nkey))

    def text(table, column, at):
        return str(data.dictionary(table, column)[
            data.col(table, column)[at]])

    def row(k):
        i = order[k]
        c = int(row_of[gcust[i]])
        n = int(nation_row[data.col("customer", "c_nationkey")[c]])
        return [
            int(gcust[i]), text("customer", "c_name", c),
            dec(int(sums[i]), 4),
            dec(int(data.col("customer", "c_acctbal")[c]), 2),
            text("nation", "n_name", n), text("customer", "c_address", c),
            text("customer", "c_phone", c),
            text("customer", "c_comment", c)]

    return ties.first(sums[order].tolist(), LIMIT, row)
