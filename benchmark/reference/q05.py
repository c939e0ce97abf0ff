"""TPC-H Q5 (clause 2.4.5) in plain NumPy; parameters REGION and DATE.

Every join is a direct-address array over a dense, positive key
(``c_custkey``, ``o_orderkey``, ``s_suppkey``, ``n_nationkey``);
revenue is ``l_extendedprice * (100 - l_discount)`` in int64 at scale 4,
summed exactly per nation. Nothing here comes from the engine or from
``bench.py``.
"""

import datetime

import numpy as np

from reference.common import days, dec, isum


def _by_key(keys, values, fill=-1):
    table = np.full(int(keys.max()) + 1, fill, dtype=np.int64)
    table[keys] = values
    return table


def answer(data, params, state=None):
    lo = datetime.date.fromisoformat(params["DATE"])
    day_lo, day_hi = days(str(lo)), days(str(lo.replace(year=lo.year + 1)))
    r_names = data.dictionary("region", "r_name")
    r_code = int(np.flatnonzero(r_names == params["REGION"])[0])
    region = data.col("region", "r_regionkey")[
        data.col("region", "r_name") == r_code]
    nkey = data.col("nation", "n_nationkey")
    in_region = np.zeros(int(nkey.max()) + 1, dtype=bool)
    in_region[nkey[np.isin(data.col("nation", "n_regionkey"), region)]] = True
    cust_nation = _by_key(data.col("customer", "c_custkey"),
                          data.col("customer", "c_nationkey"))
    supp_nation = _by_key(data.col("supplier", "s_suppkey"),
                          data.col("supplier", "s_nationkey"))
    odate = data.col("orders", "o_orderdate")
    okeep = (odate >= day_lo) & (odate < day_hi)
    lkey = data.col("lineitem", "l_orderkey")
    # nation of the order's customer, -1 where the order is out of range
    order_nation = np.full(
        int(max(data.col("orders", "o_orderkey").max(), lkey.max())) + 1,
        -1, dtype=np.int64)
    order_nation[data.col("orders", "o_orderkey")[okeep]] = cust_nation[
        data.col("orders", "o_custkey")[okeep]]
    onat = order_nation[lkey]
    snat = supp_nation[data.col("lineitem", "l_suppkey")]
    keep = (onat >= 0) & (onat == snat)
    keep[keep] = in_region[snat[keep]]
    nat = snat[keep]
    rev = (data.col("lineitem", "l_extendedprice")[keep]
           * (100 - data.col("lineitem", "l_discount")[keep]))
    n_names = data.dictionary("nation", "n_name")
    name_of = _by_key(nkey, data.col("nation", "n_name"))
    groups = [(isum(rev[nat == k]), str(n_names[name_of[k]]))
              for k in np.unique(nat)]
    groups.sort(key=lambda g: -g[0])
    return [[name, dec(total, 4)] for total, name in groups]
