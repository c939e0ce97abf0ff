"""TPC-DS q72 and q95 as the benchmark's ``tpcds_sf10`` deployment runs
them: the spec-shaped generator, the plans, the many-to-many joins'
spans and counter, and the answers of the served path against the
benchmark's plain NumPy references, at SF 0.02 (where both classes'
answers are non-empty) on two seeds, as planned there and with the
plans cut into segments as SF10's row counts cut them."""

from __future__ import annotations

import os
import sys
from pathlib import Path

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REFERENCE = Path(REPO) / "benchmark" / "reference"
sys.path.insert(0, os.path.join(REPO, "benchmark"))

import refdata  # noqa: E402
import traffic  # noqa: E402
import verify  # noqa: E402

from presto_tpu import Engine  # noqa: E402
from presto_tpu.client import Client  # noqa: E402
from presto_tpu.connectors.tpcds import (CD_ROWS, DATE_SK0, HD_ROWS,  # noqa: E402
                                         INV_WEEKS, TpcdsConnector,
                                         TpcdsGenerator)
from presto_tpu.exec import executor as EX  # noqa: E402
from presto_tpu.expr import ir  # noqa: E402
from presto_tpu.obs.metrics import REGISTRY  # noqa: E402
from presto_tpu.obs.trace import TRACER  # noqa: E402
from presto_tpu.plan import nodes as N  # noqa: E402
from presto_tpu.server.server import CoordinatorServer  # noqa: E402

SCALE = 0.02
SEEDS = (20030527, 2147483659)
TABLES = ["catalog_returns", "catalog_sales", "customer_address",
          "customer_demographics", "date_dim", "household_demographics",
          "inventory", "item", "promotion", "warehouse", "web_returns",
          "web_sales", "web_site"]
# three domain points a class, spread over its axes
POINTS = {"ds72": (0, 77, 149), "ds95": (2, 73, 158)}


def _col(conn, table, name):
    return np.asarray(conn.table(table).columns[name].data)


def _valid(conn, table, name):
    v = conn.table(table).columns[name].valid
    return None if v is None else np.asarray(v)


@pytest.fixture(scope="module", params=SEEDS)
def conn(request):
    return TpcdsConnector(scale=SCALE, seed=request.param, tables=TABLES)


@pytest.fixture(scope="module")
def engine():
    e = Engine()
    e.register_catalog("tpcds", TpcdsConnector(scale=SCALE, tables=TABLES))
    return e


def _plan(engine, cls_name, point=0):
    cls = traffic.load_class(cls_name)
    plan, _ = engine.plan_sql(
        traffic.statement(cls, traffic.params_at(cls, point, 0)))
    return plan


def _base(sym: str) -> str:
    return sym.rsplit("_", 1)[0]


def _tables(node) -> set:
    return {n.table for n in N.preorder(node) if isinstance(n, N.TableScan)}


# -- the generator --------------------------------------------------------

def test_sf10_row_counts_are_the_specifications_without_generating():
    gen = TpcdsGenerator(10)
    assert {t: gen.rows(t) for t in TABLES} == {
        "catalog_sales": 14_401_261, "catalog_returns": 1_439_749,
        "web_sales": 7_197_566, "web_returns": 719_217,
        "inventory": 133_110_000, "item": 102_000,
        "customer_address": 250_000, "customer_demographics": 1_920_800,
        "household_demographics": 7_200, "date_dim": 73_049,
        "warehouse": 10, "promotion": 500, "web_site": 42}
    assert gen._pending == {}


def test_demographics_are_the_cross_products_of_their_domains(conn):
    hd = ["hd_income_band_sk", "hd_buy_potential", "hd_dep_count",
          "hd_vehicle_count"]
    rows = np.stack([_col(conn, "household_demographics", c) for c in hd])
    assert rows.shape[1] == HD_ROWS == 20 * 6 * 10 * 6
    assert len(np.unique(rows, axis=1).T) == HD_ROWS
    cd = ["cd_gender", "cd_marital_status", "cd_education_status",
          "cd_purchase_estimate", "cd_credit_rating", "cd_dep_count",
          "cd_dep_employed_count", "cd_dep_college_count"]
    rows = np.stack([_col(conn, "customer_demographics", c) for c in cd])
    # below SF1 the first 39,200 rows: the cross product of the first six
    assert rows.shape[1] == CD_ROWS // 49
    assert len(np.unique(rows, axis=1).T) == rows.shape[1]
    assert [len(np.unique(r)) for r in rows] == [2, 5, 7, 20, 4, 7, 1, 1]


def test_date_dim_is_keyed_by_julian_day(conn):
    sk = _col(conn, "date_dim", "d_date_sk")
    days = _col(conn, "date_dim", "d_date")
    assert sk[0] == DATE_SK0 == 2_415_022 and len(sk) == 73_049
    assert (np.diff(sk) == 1).all() and (sk - days == sk[0] - days[0]).all()
    assert str(np.datetime64("1970-01-01") + int(days[0])) == "1900-01-02"


def test_inventory_is_a_weekly_snapshot_of_every_item_and_warehouse(conn):
    item = _col(conn, "inventory", "inv_item_sk")
    wh = _col(conn, "inventory", "inv_warehouse_sk")
    day = _col(conn, "inventory", "inv_date_sk")
    n_item = conn.row_count_estimate("item")
    n_wh = conn.row_count_estimate("warehouse")
    assert len(item) == INV_WEEKS * (n_item // 2) * n_wh
    key = (day * (n_item + 1) + item) * (n_wh + 1) + wh
    assert len(np.unique(key)) == len(key)
    assert len(np.unique(day)) == INV_WEEKS
    assert set(np.unique(np.diff(np.unique(day)))) == {7}
    assert len(np.unique(item)) == n_item // 2
    assert set(np.unique(wh)) == set(range(1, n_wh + 1))


def test_fact_keys_carry_nulls_and_returns_are_sales_lines(conn):
    for table, column in (("catalog_sales", "cs_sold_date_sk"),
                          ("catalog_sales", "cs_promo_sk"),
                          ("web_sales", "ws_warehouse_sk"),
                          ("catalog_returns", "cr_returned_date_sk")):
        share = 1 - _valid(conn, table, column).mean()
        assert 0.02 < share < 0.06, (column, share)
    assert _valid(conn, "catalog_sales", "cs_item_sk") is None
    for sales, returns, p, r in (
            ("catalog_sales", "catalog_returns", "cs", "cr"),
            ("web_sales", "web_returns", "ws", "wr")):
        top = int(_col(conn, sales, f"{p}_order_number").max()) + 1
        lines = (_col(conn, sales, f"{p}_item_sk") * top
                 + _col(conn, sales, f"{p}_order_number"))
        back = (_col(conn, returns, f"{r}_item_sk") * top
                + _col(conn, returns, f"{r}_order_number"))
        assert len(np.unique(lines)) == len(lines)
        assert np.isin(back, lines).all()
        assert len(np.unique(back)) == len(back) == \
            conn.row_count_estimate(returns)
        orders = np.unique(_col(conn, sales, f"{p}_order_number"),
                           return_counts=True)[1]
        assert orders.max() > 1


def test_a_deployment_holds_only_its_tables():
    conn = TpcdsConnector(scale=SCALE, tables=["date_dim", "item"])
    assert conn.table_names() == ["date_dim", "item"]
    with pytest.raises(ValueError):
        TpcdsConnector(scale=SCALE, tables=["lineitem"])


# -- the plans --------------------------------------------------------------

def test_q72_joins_inventory_on_item_and_week_with_the_residual(engine):
    plan = _plan(engine, "ds72")
    expanding = [n for n in N.preorder(plan) if isinstance(n, N.Join)
                 and not n.build_unique]
    assert len(expanding) == 1
    join = expanding[0]
    keys = {frozenset(map(_base, pair)) for pair in join.criteria}
    assert keys == {frozenset({"inv_item_sk", "cs_item_sk"}),
                    frozenset({"d_week_seq"})}
    refs = {_base(s) for s in ir.referenced_columns([join.filter])}
    assert refs == {"inv_quantity_on_hand", "cs_quantity"}
    # no join anywhere keyed on the item alone
    for n in N.preorder(plan):
        if isinstance(n, N.Join):
            assert {frozenset(map(_base, c)) for c in n.criteria} != \
                {frozenset({"inv_item_sk", "cs_item_sk"})}


def test_q72_single_table_predicates_sit_below_the_left_joins(engine):
    plan = _plan(engine, "ds72")
    lefts = [n for n in N.preorder(plan) if isinstance(n, N.Join)
             and n.join_type == N.JoinType.LEFT]
    assert len(lefts) == 2
    placed = {}
    for n in N.preorder(lefts[-1].left):
        if isinstance(n, N.Filter) and isinstance(n.source, N.TableScan):
            for ref in ir.referenced_columns([n.predicate]):
                placed[_base(ref)] = n.source.table
    assert placed["hd_buy_potential"] == "household_demographics"
    assert placed["d_year"] == "date_dim"
    assert placed["cd_marital_status"] == "customer_demographics"
    above = [n for n in N.preorder(plan) if isinstance(n, N.Filter)
             and any(_base(r) in ("hd_buy_potential", "d_year",
                                  "cd_marital_status")
                     for r in ir.referenced_columns([n.predicate]))]
    assert all(isinstance(f.source, N.TableScan) for f in above)


def test_q72_dimension_legs_are_direct_address(engine):
    plan = _plan(engine, "ds72")
    direct, lookup = set(), set()
    for n in N.preorder(plan):
        if isinstance(n, N.MultiJoin):
            for i, b in enumerate(n.builds):
                (direct if n.leg_dense_key(i) else lookup).update(_tables(b))
    assert {"date_dim", "item", "household_demographics",
            "customer_demographics"} <= direct
    assert not lookup
    # date_dim's range starts at its first Julian day, not at 1
    hints = [n.leg_dense_key(i) for n in N.preorder(plan)
             if isinstance(n, N.MultiJoin)
             for i, b in enumerate(n.builds) if _tables(b) == {"date_dim"}]
    assert hints and all(h[1] == DATE_SK0 for h in hints)


def test_q95_returns_in_is_a_semi_join_and_only_the_self_join_expands(
        engine):
    plan = _plan(engine, "ds95")
    expanding = [n for n in N.preorder(plan) if isinstance(n, N.Join)
                 and not n.build_unique]
    assert expanding and all(_tables(j) == {"web_sales"}
                             for j in expanding)
    assert all(j.filter is not None for j in expanding)
    semis = [n for n in N.preorder(plan) if isinstance(n, N.SemiJoin)
             and _tables(n.source) == {"web_returns"}]
    assert len(semis) == 1
    # the self-join's output is sized for orders of up to 16 lines
    rows = engine.catalogs["tpcds"].row_count_estimate("web_sales")
    assert all(j.output_capacity >= 12 * rows for j in expanding)


# -- the answers --------------------------------------------------------------

@pytest.fixture(scope="module", params=["planned", "segmented"])
def served(request, conn):
    """``ask(sql) -> (rows, the statement's spans)`` through a
    CoordinatorServer over ``conn``; ``segmented`` cuts the plans as
    SF10's row counts do."""
    mp = pytest.MonkeyPatch()
    if request.param == "segmented":
        mp.setattr(EX, "AGG_SPLIT_MIN_ROWS", 1)
    engine = Engine()
    engine.register_catalog("tpcds", conn)
    server = CoordinatorServer(engine).start()
    client = Client(server.uri)

    def ask(sql):
        with TRACER._lock:
            before = set(TRACER._traces)
        _cols, rows = client.execute(sql)
        with TRACER._lock:
            spans = [s for qid, trace in TRACER._traces.items()
                     if qid not in before for s in trace]
        return [list(r) for r in rows], spans

    try:
        yield request.param, ask
    finally:
        server.stop()
        mp.undo()


def _expanded_total() -> float:
    for line in REGISTRY.render().splitlines():
        if line.startswith("presto_tpu_join_expanded_rows_total"):
            return float(line.rsplit(" ", 1)[1])
    return 0.0


@pytest.mark.parametrize("cls_name", ["ds72", "ds95"])
def test_served_answers_equal_the_reference(served, conn, cls_name):
    shape, ask = served
    cls = traffic.load_class(cls_name)
    answer = verify.load_reference(cls_name)
    data = refdata.Columns(conn)
    # the answer cannot show how many pairs q95's self-join made: the
    # rows it counts are held to the reference's count of them
    pairs = verify.load_attr(REFERENCE / "ds95.py", "pairs")(data)
    for point in POINTS[cls_name]:
        params = traffic.params_at(cls, point, 0)
        want = answer(data, params)
        # an empty answer would prove nothing
        assert (want if cls_name == "ds72" else want[0][0]), params
        before = _expanded_total()
        got, spans = ask(traffic.statement(cls, params))
        assert got == want, params
        assert _expanded_total() > before
        segments = [s for s in spans if s.name == "segment"]
        expansions = [s.attrs for s in segments if "expanded" in s.attrs]
        assert bool(expansions) == (shape == "segmented")
        for attrs in expansions:
            assert 0 < attrs["expanded"] <= attrs["expand_capacity"]
            assert attrs["expand_probe_rows"] > 0
        if cls_name == "ds95":
            # one self-join a segment, once for each IN
            assert [a["expanded"] for a in expansions] == \
                [pairs] * len(expansions)
            # each side's order number and warehouse ride one word
            assert all(a["expand_words"] == 2
                       and a["expand_pack"] == "packed"
                       for a in expansions)
            assert len(expansions) == (2 if shape == "segmented" else 0)


def test_no_expansion_overflows_its_capacity(conn):
    """Both classes at every point of a sample of their domains answer
    without growing a capacity: the planner's estimate sizes each
    expansion's output for what it makes."""
    engine = Engine()
    engine.register_catalog("tpcds", conn)
    retries = REGISTRY.counter("presto_tpu_capacity_overflow_retries_total")
    before = retries.value(operator="out")
    for cls_name in ("ds72", "ds95"):
        cls = traffic.load_class(cls_name)
        for point in POINTS[cls_name]:
            engine.execute(traffic.statement(
                cls, traffic.params_at(cls, point, 0)))
    assert retries.value(operator="out") == before


def test_the_lowered_expansion_runs_under_its_own_scope(engine):
    """``Join#<n>/expand`` names the expansion's operations in the
    lowered program, so a device trace reads its time apart
    (``benchmark/layers/CLASS_expand_ms.py``)."""
    import re

    import jax
    plan = _plan(engine, "ds95")
    pos = EX.preorder_index(plan)
    joins = {pos[id(n)] for n in N.preorder(plan)
             if isinstance(n, N.Join) and not n.build_unique}
    scans = EX.collect_scans(plan, engine)
    traced_fn, flat, meta = EX.make_traced(scans, plan, {}, engine.session)
    text = jax.jit(traced_fn).lower(*flat).as_text(debug_info=True)
    assert set(map(int, re.findall(r"Join#(\d+)/expand", text))) == joins
    assert {p for p, _n, _c in meta["expand_nodes"]} == joins
