"""TPC-DS q95 (query template 95) in plain NumPy; parameters YEAR,
MONTH and STATE.

``ws_wh`` holds an order when two of its lines have warehouses that
differ, both non-NULL (the text's self-join on ``ws_order_number`` with
``<>``): an order with two distinct non-NULL warehouse keys. The first
IN keeps web_sales lines of such orders, the second those whose order
also has a return. The 61 days are ``d_date`` between the first of
MONTH and 60 days after it, both ends in. Sums are exact integers at
scale 2; ``count(distinct ...)`` counts order numbers. The orders of
``ws_wh`` depend on no parameter and are kept in ``data.memo``.
"""

import datetime

import numpy as np

from reference.common import dec, days
from reference.ds72 import lookup, valid


def _multi_warehouse(data):
    """Sorted order numbers with two distinct non-NULL warehouses."""
    memo = data.memo.get("ds95.ws_wh")
    if memo is None:
        order = data.col("web_sales", "ws_order_number")
        wh = data.col("web_sales", "ws_warehouse_sk")
        ok = (valid(data, "web_sales", "ws_warehouse_sk")
              & valid(data, "web_sales", "ws_order_number"))
        top = int(wh.max()) + 1
        pairs = np.unique(order[ok] * top + wh[ok])
        orders, n = np.unique(pairs // top, return_counts=True)
        memo = data.memo["ds95.ws_wh"] = orders[n >= 2]
    return memo


def pairs(data) -> int:
    """How many rows ``ws_wh``'s self-join makes: the ordered pairs of
    lines of one order whose warehouses differ, both non-NULL. The
    answer cannot show it (``ws_wh`` only feeds two INs); a test holds
    the ``segment`` span's ``expanded`` to it."""
    order = data.col("web_sales", "ws_order_number")
    wh = data.col("web_sales", "ws_warehouse_sk")
    ok = (valid(data, "web_sales", "ws_warehouse_sk")
          & valid(data, "web_sales", "ws_order_number"))
    order, wh = order[ok].astype(np.int64), wh[ok].astype(np.int64)
    lines = np.unique(order, return_counts=True)[1].astype(np.int64)
    same = np.unique(order * (int(wh.max()) + 1) + wh,
                     return_counts=True)[1].astype(np.int64)
    return int((lines ** 2).sum() - (same ** 2).sum())


def _sales(data):
    """Each web sales line's row in date_dim, customer_address and
    web_site, and whether it found one (computed once)."""
    memo = data.memo.get("ds95.sales")
    if memo is None:
        ws = lambda c: data.col("web_sales", c)  # noqa: E731
        ok = lambda c: valid(data, "web_sales", c)  # noqa: E731
        memo = data.memo["ds95.sales"] = {
            "date": lookup(data.col("date_dim", "d_date_sk"),
                           ws("ws_ship_date_sk"), ok("ws_ship_date_sk")),
            "addr": lookup(data.col("customer_address", "ca_address_sk"),
                           ws("ws_ship_addr_sk"), ok("ws_ship_addr_sk")),
            "site": lookup(data.col("web_site", "web_site_sk"),
                           ws("ws_web_site_sk"), ok("ws_web_site_sk"))}
    return memo


def _code(data, table, name, value):
    d = data.dictionary(table, name)
    hit = np.flatnonzero(d == value)
    return int(hit[0]) if len(hit) else -1


def answer(data, params, state=None):
    ws = lambda c: data.col("web_sales", c)  # noqa: E731
    ws_ok = lambda c: valid(data, "web_sales", c)  # noqa: E731
    first = datetime.date(int(params["YEAR"]), int(params["MONTH"]), 1)
    lo = days(str(first))
    hi = lo + 60
    dims = _sales(data)
    rd, ok = dims["date"]
    d = data.col("date_dim", "d_date")[rd]
    ok = ok & (d >= lo) & (d <= hi)
    ra, fa = dims["addr"]
    ok &= fa & (data.col("customer_address", "ca_state")[ra]
                == _code(data, "customer_address", "ca_state",
                         params["STATE"]))
    rs, fs = dims["site"]
    ok &= fs & (data.col("web_site", "web_company_name")[rs]
                == _code(data, "web_site", "web_company_name", "pri"))
    keep = np.flatnonzero(ok & ws_ok("ws_order_number"))
    order = ws("ws_order_number")
    wh_orders = _multi_warehouse(data)
    returned = data.col("web_returns", "wr_order_number")[
        valid(data, "web_returns", "wr_order_number")]
    keep = keep[np.isin(order[keep], wh_orders)
                & np.isin(order[keep], np.intersect1d(returned, wh_orders))]
    if not len(keep):
        return [[0, None, None]]

    def total(c):
        v = ws_ok(c)[keep]
        if not v.any():
            return None
        return dec(int(ws(c)[keep][v].sum(dtype=np.int64)), 2)

    return [[int(len(np.unique(order[keep]))),
             total("ws_ext_ship_cost"), total("ws_net_profit")]]
