"""TPC-H Q9 (clause 2.4.9) in plain NumPy; parameter COLOR.

``p_name like '%COLOR%'`` is ``np.char.find`` over the dictionary of
``p_name``; ``part``, ``supplier`` and ``orders`` are joined through
direct-address arrays over their dense keys, ``partsupp`` through a
sorted composite key (part x supplier is too wide for a table); the
amount is ``l_extendedprice * (100 - l_discount) - ps_supplycost *
l_quantity`` in int64 at scale 4; ``o_year`` comes from the day
number. Nothing here comes from the engine or from ``bench.py``.
"""

import numpy as np

from reference.common import dec, isum


def _by_key(keys, values, size=None):
    table = np.full(int(keys.max()) + 1 if size is None else size, -1,
                    dtype=np.int64)
    table[keys] = values
    return table


def answer(data, params, state=None):
    names = data.dictionary("part", "p_name").astype("U")
    has_color = np.char.find(names, params["COLOR"]) >= 0
    lpart = data.col("lineitem", "l_partkey")
    pkey = data.col("part", "p_partkey")
    part_ok = np.zeros(int(max(pkey.max(), lpart.max())) + 1, dtype=bool)
    part_ok[pkey] = has_color[data.col("part", "p_name")]
    rows = np.flatnonzero(part_ok[lpart])
    lpart = lpart[rows]
    lsupp = data.col("lineitem", "l_suppkey")[rows]
    # partsupp by (ps_partkey, ps_suppkey): one sorted int64 key
    stride = int(max(data.col("partsupp", "ps_suppkey").max(),
                     lsupp.max() if len(lsupp) else 0)) + 1
    pskey = (data.col("partsupp", "ps_partkey") * stride
             + data.col("partsupp", "ps_suppkey"))
    order = np.argsort(pskey, kind="stable")
    pskey = pskey[order]
    want = lpart * stride + lsupp
    at = np.minimum(np.searchsorted(pskey, want), len(pskey) - 1)
    found = pskey[at] == want
    rows, lsupp, at = rows[found], lsupp[found], at[found]
    cost = data.col("partsupp", "ps_supplycost")[order][at]
    supp_nation = _by_key(data.col("supplier", "s_suppkey"),
                          data.col("supplier", "s_nationkey"))
    nat = supp_nation[lsupp]
    lorder = data.col("lineitem", "l_orderkey")[rows]
    okey = data.col("orders", "o_orderkey")
    odate = data.col("orders", "o_orderdate")
    year_of = _by_key(
        okey, odate.astype("M8[D]").astype("M8[Y]").astype(np.int64) + 1970,
        size=int(max(okey.max(), lorder.max() if len(lorder) else 0)) + 1)
    year = year_of[lorder]
    keep = (nat >= 0) & (year >= 0)
    nat, year, rows = nat[keep], year[keep], rows[keep]
    amount = (data.col("lineitem", "l_extendedprice")[rows]
              * (100 - data.col("lineitem", "l_discount")[rows])
              - cost[keep] * data.col("lineitem", "l_quantity")[rows])
    nkey = data.col("nation", "n_nationkey")
    n_names = data.dictionary("nation", "n_name")
    name_of = _by_key(nkey, data.col("nation", "n_name"))
    group = nat * 10000 + year
    out = [(str(n_names[name_of[g // 10000]]), int(g % 10000),
            isum(amount[group == g])) for g in np.unique(group)]
    out.sort(key=lambda r: (r[0], -r[1]))
    return [[name, y, dec(total, 4)] for name, y, total in out]
