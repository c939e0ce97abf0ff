"""The cell ``tpch_sf10_custorders.groupby``: the manifest takes its
configuration, mix, classes and entries; the cell rehearsed on the CPU
at a toy scale factor in a temporary copy gives a line with every
per-layer metric that needs no device; the two references agree with
a brute-force loop over 1,000 orders; and rows that tie on the ORDER BY
keys are compared as a set."""

import json

import numpy as np
import pytest
import traffic
import verify
from conftest import ROOT
from test_rehearsal import copy_of_the_benchmark, rehearse

CELL = "tpch_sf10_custorders.groupby"
CONFIG = "tpch_sf10_custorders"
# what a CPU rehearsal cannot read: the device's trace and its memory
NEEDS_DEVICE = {
    "compile.traced_compile_share", "device.busy_ms_per_query",
    "device.peak_bytes", "q10_roofline", "q18_roofline", "q10_join_ms",
    "q18_join_ms", "q10_aggregate_ms", "q18_aggregate_ms", "q10_topn_ms",
    "q18_topn_ms", "q10_top_build_ms", "q18_semijoin_ms"}
NEW = NEEDS_DEVICE - {
    "compile.traced_compile_share", "device.busy_ms_per_query",
    "device.peak_bytes"} | {
    "class.q10_ms", "class.q18_ms", "capacity.window_retries",
    "aggregate.groups_per_query"}


@pytest.fixture(scope="module")
def manifest():
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def test_the_manifest_takes_the_cell(manifest):
    (cell,) = [w for w in manifest["workloads"] if w["name"] == CELL]
    assert cell == {**cell, "config": CONFIG, "traffic": "groupby",
                    "chips": 1}
    (entry,) = [c for c in manifest["configs"] if c["name"] == CONFIG]
    config = traffic.load_config(CONFIG)
    assert entry["reduced"] == config["reduced"] == [
        "scale_factor", "query_set"]
    assert config["scale_factor"] == 10 and config["mesh"] is None
    assert config["tables"] == ["customer", "lineitem", "nation", "orders"]
    assert config["compile_cache_in_window"] == "off"
    assert config["verify"] == {"sample_per_class": None}
    alltables = traffic.load_config("tpch_sf10_alltables")
    assert config["guarantees"] == alltables["guarantees"]
    # the new entries, each for this cell alone; none that was there
    # gained or lost a cell
    added = {m["name"] for m in manifest["per_layer"]
             if m.get("workloads") == [CELL]}
    assert added == NEW
    assert not [m["name"] for m in manifest["per_layer"]
                if CELL in m.get("workloads", []) and m["name"] not in NEW]


def test_the_mix_and_the_domains_are_the_issues(manifest):
    mix = traffic.load_mix("groupby")
    assert mix["loop"] == "closed" and mix["clients"] == 1
    assert mix["session"] == {"result_cache": "false"}
    assert [c["name"] for c in mix["classes"]] == ["q10", "q18"]
    assert mix["draw"] == {"kind": "uniform"}
    assert mix["min_per_class"] == 2 and mix["statement_timeout_s"] == 150
    assert mix["trace"]["start_s"] == 0.0
    assert mix["trace"]["seconds"] == 50.0 and mix["trace_why"]
    q10, q18 = traffic.load_class("q10"), traffic.load_class("q18")
    # the prewarm: each class's own text at one fixed point of its
    # domain, so that a process's first statements do not move with the
    # seed (the parent of PR 32 keeps the hand-over widths they leave)
    assert mix["setup"] == [
        traffic.statement(q10, {"DATE": "1993-10-01"}),
        traffic.statement(q18, {"QUANTITY": "312"})] and mix["setup_why"]
    dates = [a["DATE"] for a in q10["axes"][0]]
    assert len(dates) == 24 and dates[0] == "1993-02-01"
    assert dates[-1] == "1995-01-01" and all(d.endswith("-01") for d in dates)
    assert [a["QUANTITY"] for a in q18["axes"][0]] == [
        "312", "313", "314", "315"]
    read = {t for c in (q10, q18) for t in c["reads"]}
    assert read == set(traffic.load_config(CONFIG)["tables"])
    # Q18 reads lineitem's two columns twice and lists them once
    assert q18["reads"]["lineitem"] == ["l_orderkey", "l_quantity"]
    assert manifest["run_seconds"] == 51


@pytest.fixture(scope="module")
def toy(tmp_path_factory):
    """A copy in which the cell has a twin at SF 0.02."""
    root = tmp_path_factory.mktemp("groupby")
    manifest = copy_of_the_benchmark(root)
    (entry,) = [c for c in manifest["configs"] if c["name"] == CONFIG]
    body = json.loads((ROOT / entry["file"]).read_text())
    body["scale_factor"] = 0.02
    (root / "benchmark" / "configs" / "toy_custorders.json").write_text(
        json.dumps(body))
    manifest["configs"].append({
        **entry, "name": "toy_custorders",
        "file": "benchmark/configs/toy_custorders.json"})
    (cell,) = [w for w in manifest["workloads"] if w["name"] == CELL]
    manifest["workloads"].append({
        **cell, "name": "toy_custorders.groupby", "config": "toy_custorders"})
    for m in manifest["per_layer"]:
        if CELL in m.get("workloads", []):
            m["workloads"].append("toy_custorders.groupby")
    (root / "BENCHMARK.json").write_text(json.dumps(manifest))
    return root


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_of_the_cell(toy, trace):
    out = rehearse(toy, "toy_custorders.groupby", trace)
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] >= 4  # two whole rounds of Q10, Q18
    manifest = json.loads((toy / "BENCHMARK.json").read_text())
    kind = "per_layer" if trace else "end_to_end"
    declared = {m["name"] for m in manifest[kind]
                if "toy_custorders.groupby" in m.get(
                    "workloads", ["toy_custorders.groupby"])}
    if not trace:
        assert set(out["metrics"]) == declared == {
            "setup_s", "geomean_ms", "qph"}
        return
    assert declared - set(out["metrics"]) == NEEDS_DEVICE
    values = {k: v["value"] for k, v in out["metrics"].items()}
    assert values["compile.window_compiles"] == 0
    assert values["capacity.window_retries"] == 0
    # SF 0.02 has 30,000 orders, each with a line: the IN's aggregate
    assert values["aggregate.groups_per_query"] == 30000
    assert values["class.q10_ms"] > 0 and values["class.q18_ms"] > 0


# -- the references ---------------------------------------------------------------

class Columns:
    """A hand-made ``refdata.Columns``: table -> column -> values, text
    as (codes, dictionary)."""

    def __init__(self, tables):
        self.tables = tables
        self.memo = {}

    def col(self, table, name):
        v = self.tables[table][name]
        return np.asarray(v[0] if isinstance(v, tuple) else v)

    def dictionary(self, table, name):
        return np.asarray(self.tables[table][name][1], dtype=object)


def _text(values):
    d = sorted(set(values))
    return np.array([d.index(v) for v in values], dtype=np.int32), d


def _star(prices, orders_per_customer=1):
    """``len(prices)`` orders of one line each, one customer an order
    (or ``orders_per_customer``), every line of quantity 40 and
    returned; the line's price is the order's."""
    n = len(prices)
    keys = np.arange(1, n + 1, dtype=np.int64)
    cust = (keys - 1) // orders_per_customer + 1
    ckeys = np.unique(cust)
    return Columns({
        "orders": {"o_orderkey": keys, "o_custkey": cust,
                   "o_orderdate": np.full(n, 8700, dtype=np.int32),
                   "o_totalprice": np.asarray(prices, dtype=np.int64)},
        "lineitem": {"l_orderkey": keys,
                     "l_quantity": np.full(n, 4000, dtype=np.int64),
                     "l_extendedprice": np.asarray(prices, dtype=np.int64),
                     "l_discount": np.zeros(n, dtype=np.int64),
                     "l_returnflag": _text(["R"] * n)},
        "customer": {"c_custkey": ckeys,
                     "c_name": _text([f"C{k:03d}" for k in ckeys]),
                     "c_acctbal": ckeys * 100,
                     "c_nationkey": np.zeros(len(ckeys), dtype=np.int64),
                     "c_address": _text(["a"] * len(ckeys)),
                     "c_phone": _text(["p"] * len(ckeys)),
                     "c_comment": _text(["c"] * len(ckeys))},
        "nation": {"n_nationkey": np.array([0]),
                   "n_name": _text(["PERU"])}})


@pytest.mark.parametrize("cls_name,limit,params,key", [
    ("q10", 20, {"DATE": "1993-10-01"}, 0),
    ("q18", 100, {"QUANTITY": "39"}, 2)])
def test_rows_that_tie_on_the_order_by_keys_compare_as_a_set(
        cls_name, limit, params, key):
    """8700 days is 1993-10-27; 150 orders of distinct prices answer as
    a plain list. With a tie inside the answer either order of the two
    rows is the answer and no other row is; with a tie across the cut
    either of the two rows may fill the last place."""
    answer = verify.load_reference(cls_name)
    prices = [1000000 - 100 * i for i in range(150)]
    plain = answer(_star(prices), params)
    assert type(plain) is list and len(plain) == limit
    assert plain[0][key] == 1  # the dearest order, or its customer
    tied = list(prices)
    tied[4] = tied[3]
    want = answer(_star(tied), params)
    got = [list(r) for r in want]
    assert got == want
    got[3], got[4] = got[4], got[3]
    assert got == want and want == got
    got[3], got[5] = got[5], got[3]  # a row from outside the run
    assert got != want
    got = [list(r) for r in want]
    got[4] = list(got[3])  # the same row twice
    assert got != want
    cut = list(prices)
    cut[limit] = cut[limit - 1]
    want = answer(_star(cut), params)
    last = [list(r) for r in want]
    other = [list(r) for r in want]
    other[-1][key] = limit + 1
    if cls_name == "q10":
        other[-1][1] = f"C{limit + 1:03d}"
        other[-1][3] = f"{limit + 1}.00"
    else:
        other[-1][0], other[-1][1] = f"C{limit + 1:03d}", limit + 1
    assert last == want and other == want
    assert last[:-1] != want  # a row short
    # a tie further down leaves the answer a plain list
    below = list(prices)
    below[limit + 2] = below[limit + 1]
    assert answer(_star(below), params) == plain
    assert type(answer(_star(below), params)) is list


def _thousand_orders(seed):
    """1,000 orders of 1 to 7 lines over 150 customers and 5 nations,
    dates over 1993 and 1994, flags R, A and N."""
    rng = np.random.default_rng(seed)
    n = 1000
    okey = rng.permutation(np.arange(1, n + 1)).astype(np.int64)
    lines = rng.integers(1, 8, n)
    lkey = np.repeat(okey, lines)
    m = len(lkey)
    ckey = np.arange(1, 151, dtype=np.int64)
    return Columns({
        "orders": {"o_orderkey": okey,
                   "o_custkey": rng.integers(1, 151, n).astype(np.int64),
                   "o_orderdate": rng.integers(8401, 9131, n)
                   .astype(np.int32),
                   "o_totalprice": rng.integers(1, 10 ** 7, n)
                   .astype(np.int64)},
        "lineitem": {"l_orderkey": lkey,
                     "l_quantity": rng.integers(1, 51, m) * 100,
                     "l_extendedprice": rng.integers(100, 10 ** 7, m)
                     .astype(np.int64),
                     "l_discount": rng.integers(0, 11, m).astype(np.int64),
                     "l_returnflag": _text(
                         [str(f) for f in rng.choice(list("RAN"), m)])},
        "customer": {"c_custkey": ckey,
                     "c_name": _text([f"Customer#{k:09d}" for k in ckey]),
                     "c_acctbal": rng.integers(-99999, 999999, 150)
                     .astype(np.int64),
                     "c_nationkey": rng.integers(0, 5, 150)
                     .astype(np.int64),
                     "c_address": _text([f"addr {k % 40}" for k in ckey]),
                     "c_phone": _text([f"1{k % 9}-{k:04d}" for k in ckey]),
                     "c_comment": _text([f"note {k % 7}" for k in ckey])},
        "nation": {"n_nationkey": np.arange(5),
                   "n_name": _text(["PERU", "CHINA", "KENYA", "IRAN",
                                    "FRANCE"])}})


def _dec(v, scale):
    q, r = divmod(abs(v), 10 ** scale)
    return f"{'-' if v < 0 else ''}{q}.{r:0{scale}d}"


def _loop_q10(data, lo, hi):
    """Q10 as its text reads, row by row, in Python integers."""
    t = data.tables

    def txt(table, col, i):
        codes, d = t[table][col]
        return d[codes[i]]

    cust_row = {int(k): i for i, k in enumerate(t["customer"]["c_custkey"])}
    order_row = {int(k): i for i, k in enumerate(t["orders"]["o_orderkey"])}
    revenue = {}
    for i, ok in enumerate(t["lineitem"]["l_orderkey"]):
        o = order_row[int(ok)]
        if not lo <= t["orders"]["o_orderdate"][o] < hi \
                or txt("lineitem", "l_returnflag", i) != "R":
            continue
        c = int(t["orders"]["o_custkey"][o])
        revenue[c] = revenue.get(c, 0) + int(
            t["lineitem"]["l_extendedprice"][i]) * (
                100 - int(t["lineitem"]["l_discount"][i]))
    out = []
    for c, rev in sorted(revenue.items(), key=lambda kv: -kv[1])[:20]:
        r = cust_row[c]
        out.append([c, txt("customer", "c_name", r), _dec(rev, 4),
                    _dec(int(t["customer"]["c_acctbal"][r]), 2),
                    txt("nation", "n_name",
                        int(t["customer"]["c_nationkey"][r])),
                    txt("customer", "c_address", r),
                    txt("customer", "c_phone", r),
                    txt("customer", "c_comment", r)])
    return out


def _loop_q18(data, quantity):
    t = data.tables
    total = {}
    for ok, q in zip(t["lineitem"]["l_orderkey"],
                     t["lineitem"]["l_quantity"]):
        total[int(ok)] = total.get(int(ok), 0) + int(q)
    cust_row = {int(k): i for i, k in enumerate(t["customer"]["c_custkey"])}
    names, d = t["customer"]["c_name"]
    rows = []
    for i, ok in enumerate(t["orders"]["o_orderkey"]):
        if total.get(int(ok), 0) > quantity * 100:
            rows.append((-int(t["orders"]["o_totalprice"][i]),
                         int(t["orders"]["o_orderdate"][i]), i))
    out = []
    for price, date, i in sorted(rows)[:100]:
        c = int(t["orders"]["o_custkey"][i])
        s = total[int(t["orders"]["o_orderkey"][i])]
        out.append([d[names[cust_row[c]]], c,
                    int(t["orders"]["o_orderkey"][i]),
                    str(np.datetime64("1970-01-01") + date),
                    _dec(-price, 2), _dec(s, 2)])
    return out


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_the_references_agree_with_a_loop_over_1000_orders(seed):
    data = _thousand_orders(seed)
    q10, q18 = verify.load_reference("q10"), verify.load_reference("q18")
    for date, lo, hi in (("1993-02-01", 8432, 8521),
                         ("1993-11-01", 8705, 8797),
                         ("1994-10-01", 9039, 9131)):
        want = _loop_q10(data, lo, hi)
        assert 0 < len(want) <= 20
        assert q10(data, {"DATE": date}) == want
    for quantity in (150, 200, 250, 400):
        want = _loop_q18(data, quantity)
        assert q18(data, {"QUANTITY": str(quantity)}) == want
    assert len(_loop_q18(data, 150)) == 100 > len(_loop_q18(data, 250)) > 0
    assert _loop_q18(data, 400) == []
