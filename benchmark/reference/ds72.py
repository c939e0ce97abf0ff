"""TPC-DS q72 (query template 72) in plain NumPy; parameters YEAR, BP
(``hd_buy_potential``) and MS (``cd_marital_status``).

Joins on what the text says: catalog_sales to inventory on the item,
with the week equality and ``inv_quantity_on_hand < cs_quantity`` as
the text's WHERE has them; the eight dimensions by their keys, a NULL
foreign key matching nothing; promotion and catalog_returns LEFT OUTER,
each match multiplying a row and a missed one keeping it once with NULL
columns. What no statement's parameters change is kept in
``data.memo``: inventory sorted by (item, week), the weeks of the
snapshot days. Counts are exact integers.
"""

import numpy as np


def valid(data, table, name):
    """The column's valid bits (True = not NULL)."""
    v = data._conn.table(table).columns[name].valid
    return np.ones(len(data.col(table, name)), bool) if v is None \
        else np.asarray(v)


def lookup(keys, probe, probe_ok=None):
    """(row of ``keys`` equal to each ``probe`` value, found): a unique
    key column searched by value; through an array over the keys' range
    where it is dense (the surrogate keys), else by binary search."""
    lo, hi = int(keys.min()), int(keys.max())
    if hi - lo < 4 * len(keys) + 1024:
        table = np.full(hi - lo + 1, -1, np.int64)
        table[keys - lo] = np.arange(len(keys))
        inside = (probe >= lo) & (probe <= hi)
        row = np.where(inside, table[np.clip(probe - lo, 0, hi - lo)], -1)
        found = row >= 0
        row = np.maximum(row, 0)
    else:
        order = np.argsort(keys, kind="stable")
        pos = np.clip(np.searchsorted(keys[order], probe), 0,
                      len(keys) - 1)
        row = order[pos]
        found = keys[row] == probe
    if probe_ok is not None:
        found &= probe_ok
    return row, found


def matches(keys, probe, probe_ok):
    """How many rows of ``keys`` (not unique) equal each ``probe``."""
    s = np.sort(keys)
    n = (np.searchsorted(s, probe, side="right")
         - np.searchsorted(s, probe, side="left"))
    return np.where(probe_ok, n, 0)


def _inventory(data):
    """Inventory sorted by (item, week): keys, warehouse keys, quantity
    on hand, and the week of the key's parts (computed once, a block of
    rows at a time, so the 133 million rows of SF10 cost little more
    than their sorted copy)."""
    memo = data.memo.get("ds72.inventory")
    if memo is None:
        d_sk = data.col("date_dim", "d_date_sk")
        week = data.col("date_dim", "d_week_seq")
        wmax = int(week.max()) + 1
        inv = lambda c: data.col("inventory", c)  # noqa: E731
        n = len(inv("inv_item_sk"))
        key = np.empty(n, np.int64)
        step = 1 << 24
        for lo in range(0, n, step):
            sl = slice(lo, min(lo + step, n))
            row, found = lookup(d_sk, inv("inv_date_sk")[sl],
                                valid(data, "inventory", "inv_date_sk")[sl])
            ok = found & valid(data, "inventory", "inv_item_sk")[sl]
            key[sl] = np.where(ok, inv("inv_item_sk")[sl] * wmax
                               + week[row], -1)
        # the order inside a run of one key is of no account; a stable
        # sort merges the runs a weekly snapshot is written in
        order = np.argsort(key, kind="stable")
        key = key[order]
        wh, qoh = inv("inv_warehouse_sk"), inv("inv_quantity_on_hand")
        small = lambda a: a.astype(np.int32) if a.max() < 2 ** 31 \
            and a.min() >= -2 ** 31 else a  # noqa: E731
        memo = data.memo["ds72.inventory"] = {
            "key": key, "wmax": wmax,
            "wh": small(wh)[order],
            "wh_ok": valid(data, "inventory", "inv_warehouse_sk")[order],
            "qoh": small(qoh)[order],
            "qoh_ok": valid(data, "inventory",
                            "inv_quantity_on_hand")[order]}
    return memo


def _code(data, table, name, value):
    d = data.dictionary(table, name)
    hit = np.flatnonzero(d == value)
    return int(hit[0]) if len(hit) else -1


def _sales(data):
    """Each sales line's row in the dimensions it names, and whether it
    found one (computed once)."""
    memo = data.memo.get("ds72.sales")
    if memo is None:
        cs = lambda c: data.col("catalog_sales", c)  # noqa: E731
        ok = lambda c: valid(data, "catalog_sales", c)  # noqa: E731
        d_sk = data.col("date_dim", "d_date_sk")
        memo = data.memo["ds72.sales"] = {
            "d1": lookup(d_sk, cs("cs_sold_date_sk"), ok("cs_sold_date_sk")),
            "d3": lookup(d_sk, cs("cs_ship_date_sk"), ok("cs_ship_date_sk")),
            "hd": lookup(data.col("household_demographics", "hd_demo_sk"),
                         cs("cs_bill_hdemo_sk"), ok("cs_bill_hdemo_sk")),
            "cd": lookup(data.col("customer_demographics", "cd_demo_sk"),
                         cs("cs_bill_cdemo_sk"), ok("cs_bill_cdemo_sk")),
            "item": lookup(data.col("item", "i_item_sk"), cs("cs_item_sk"),
                           ok("cs_item_sk"))}
    return memo


def answer(data, params, state=None):
    cs = lambda c: data.col("catalog_sales", c)  # noqa: E731
    cs_ok = lambda c: valid(data, "catalog_sales", c)  # noqa: E731
    dims = _sales(data)
    d_date = data.col("date_dim", "d_date").astype(np.int64)
    # d1: the sale's day, in YEAR
    r1, ok = dims["d1"]
    ok = ok & (data.col("date_dim", "d_year")[r1] == int(params["YEAR"]))
    # d3: the ship day, more than 5 days after the sale
    r3, f3 = dims["d3"]
    ok &= f3 & (d_date[r3] > d_date[r1] + 5)
    rh, fh = dims["hd"]
    ok &= fh & (data.col("household_demographics", "hd_buy_potential")[rh]
                == _code(data, "household_demographics",
                         "hd_buy_potential", params["BP"]))
    rc, fc = dims["cd"]
    ok &= fc & (data.col("customer_demographics", "cd_marital_status")[rc]
                == _code(data, "customer_demographics",
                         "cd_marital_status", params["MS"]))
    ri, fi = dims["item"]
    ok &= fi
    sales = np.flatnonzero(ok)
    week = data.col("date_dim", "d_week_seq")[r1[sales]]

    # inventory on the item, in the sale's week, holding less than sold
    inv = _inventory(data)
    key = cs("cs_item_sk")[sales] * inv["wmax"] + week
    lo = np.searchsorted(inv["key"], key, side="left")
    hi = np.searchsorted(inv["key"], key, side="right")
    n = hi - lo
    sale = np.repeat(np.arange(len(sales)), n)
    pos = np.repeat(lo - np.cumsum(n) + n, n) + np.arange(n.sum())
    keep = (inv["qoh_ok"][pos] & cs_ok("cs_quantity")[sales[sale]]
            & (inv["qoh"][pos] < cs("cs_quantity")[sales[sale]]))
    sale, pos = sale[keep], pos[keep]
    rw, fw = lookup(data.col("warehouse", "w_warehouse_sk"),
                    inv["wh"][pos], inv["wh_ok"][pos])
    sale, rw = sale[fw], rw[fw]
    line = sales[sale]

    # LEFT OUTER promotion and catalog_returns: matches multiply a row
    n_p = matches(data.col("promotion", "p_promo_sk"), cs("cs_promo_sk")[line],
                  cs_ok("cs_promo_sk")[line])
    top = int(max(cs("cs_order_number").max(),
                  data.col("catalog_returns", "cr_order_number").max())) + 1
    cr_key = lambda i, o: i * top + o  # noqa: E731
    cr_ok = (valid(data, "catalog_returns", "cr_item_sk")
             & valid(data, "catalog_returns", "cr_order_number"))
    n_c = matches(cr_key(data.col("catalog_returns", "cr_item_sk"),
                         data.col("catalog_returns", "cr_order_number")
                         )[cr_ok],
                  cr_key(cs("cs_item_sk")[line], cs("cs_order_number")[line]),
                  cs_ok("cs_item_sk")[line] & cs_ok("cs_order_number")[line])
    rows = np.maximum(n_p, 1) * np.maximum(n_c, 1)
    no_promo = np.where(n_p == 0, np.maximum(n_c, 1), 0)
    promo = n_p * np.maximum(n_c, 1)

    # group by (i_item_desc, w_warehouse_name, d1.d_week_seq)
    desc = data.col("item", "i_item_desc")[ri[line]]
    wname = data.col("warehouse", "w_warehouse_name")[rw]
    wk = week[sale]
    groups: dict = {}
    for k, a, b, c in zip(zip(desc.tolist(), wname.tolist(), wk.tolist()),
                          no_promo.tolist(), promo.tolist(), rows.tolist()):
        g = groups.setdefault(k, [0, 0, 0])
        g[0] += a
        g[1] += b
        g[2] += c
    d_desc = data.dictionary("item", "i_item_desc")
    d_wname = data.dictionary("warehouse", "w_warehouse_name")
    out = [[str(d_desc[k[0]]), str(d_wname[k[1]]), int(k[2]), *v]
           for k, v in groups.items()]
    out.sort(key=lambda r: (-r[5], r[0], r[1], r[2]))
    return out[:100]
