"""TPC-H Q18 (clause 2.4.18) in plain NumPy; parameter QUANTITY.

As the text is written: ``sum(l_quantity)`` per ``l_orderkey`` over the
whole of ``lineitem``, the orders whose sum is above QUANTITY, each
with its customer, ordered by ``o_totalprice`` descending and then
``o_orderdate``, the first 100. The sums do not depend on the
parameter and are kept in ``data.memo``. ``np.bincount`` adds in
float64, which is exact here: every addend and every partial sum is a
whole number far below 2**53 (an order's quantities at scale 2 sum to a
few ten thousand), and the result is held to that. Orders of one
price and date among the rows returned, or the last returned and the
first cut, leave the answer open: such a run is compared as a set
(``reference/ties.py``). Nothing here comes from the engine.
"""

import numpy as np

from reference import ties
from reference.common import date_str, dec

LIMIT = 100


def _sums(data):
    if "q18_sums" not in data.memo:
        lkey = data.col("lineitem", "l_orderkey")
        qty = data.col("lineitem", "l_quantity")
        sums = np.bincount(lkey, weights=qty.astype(np.float64))
        whole = sums.astype(np.int64)
        if not (whole == sums).all() or int(whole.sum()) != int(qty.sum()):
            raise ArithmeticError("the quantities did not add exactly")
        data.memo["q18_sums"] = whole
    return data.memo["q18_sums"]


def answer(data, params, state=None):
    bound = int(params["QUANTITY"]) * 100  # l_quantity is at scale 2
    sums = _sums(data)
    okey = data.col("orders", "o_orderkey")
    in_range = okey < len(sums)
    rows = np.flatnonzero(in_range)[sums[okey[in_range]] > bound]
    price = data.col("orders", "o_totalprice")[rows]
    date = data.col("orders", "o_orderdate")[rows]
    order = np.lexsort((date, -price))
    ckey = data.col("customer", "c_custkey")
    row_of = np.full(int(ckey.max()) + 1, -1, dtype=np.int64)
    row_of[ckey] = np.arange(len(ckey))
    names = data.dictionary("customer", "c_name")
    name_code = data.col("customer", "c_name")
    def row(k):
        i = order[k]
        o = int(rows[i])
        cust = int(data.col("orders", "o_custkey")[o])
        return [
            str(names[name_code[row_of[cust]]]), cust, int(okey[o]),
            date_str(date[i]), dec(int(price[i]), 2),
            dec(int(sums[okey[o]]), 2)]

    return ties.first(list(zip(price[order].tolist(),
                               date[order].tolist())), LIMIT, row)
