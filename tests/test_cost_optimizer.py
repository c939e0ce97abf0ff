"""Cost-based optimizer subsystem (presto_tpu/cost/): plan-wide stats
propagation, the mesh-aware cost model's single distribution decision,
and DP join reordering — the engine's io.trino.cost analog
(cost/StatsCalculator.java, CostCalculatorUsingExchanges.java,
iterative/rule/ReorderJoins.java)."""

from __future__ import annotations

import numpy as np
import pytest

from presto_tpu import Engine
from presto_tpu import types as T
from presto_tpu.connectors.memory import MemoryConnector
from presto_tpu.cost.model import (CostCalculator,
                                   decide_join_distribution)
from presto_tpu.cost.stats import StatsCalculator
from presto_tpu.plan import nodes as N

from tpch_queries import QUERIES


def make_engine(tpch_tiny, **props) -> Engine:
    e = Engine()
    e.register_catalog("tpch", tpch_tiny)
    for k, v in props.items():
        e.session.set(k, v)
    return e


def _joins(plan):
    out = []

    def visit(n):
        if isinstance(n, N.Join):
            out.append(n)
        for s in n.sources():
            visit(s)

    visit(plan)
    return out


def _multijoins(plan):
    out = []

    def visit(n):
        if isinstance(n, N.MultiJoin):
            out.append(n)
        for s in n.sources():
            visit(s)

    visit(plan)
    return out


# -- oracle: reordering must not change results -----------------------------


@pytest.mark.parametrize("qname", ["q05", "q09"])
def test_reordered_results_identical_to_none(tpch_tiny, qname):
    """The DP-reordered plan and the un-reordered plan must produce
    byte-identical results (both queries aggregate exact decimals and
    carry a total ORDER BY, so even accumulation order cannot differ)."""
    base = make_engine(
        tpch_tiny,
        optimizer_join_reordering_strategy="NONE").execute(
        QUERIES[qname])
    auto = make_engine(
        tpch_tiny,
        optimizer_join_reordering_strategy="AUTOMATIC").execute(
        QUERIES[qname])
    assert base == auto


def test_strategy_none_keeps_planner_annotations(tpch_tiny):
    """NONE must leave the plan exactly as planned — no pow2-bucketed
    build_rows rewrites, no explicit distributions."""
    eng = make_engine(tpch_tiny,
                      optimizer_join_reordering_strategy="NONE")
    plan, _ = eng.plan_sql(QUERIES["q05"])
    assert all(j.distribution == "automatic" for j in _joins(plan))


def test_automatic_writes_distribution_and_bucketed_rows(tpch_tiny):
    """AUTOMATIC writes the cost model's decisions into the join
    nodes: explicit distribution and power-of-two build_rows (coarse
    estimates keep the compiled-program cache hitting). Under the
    default multiway_join the Q5 star chain fuses into ONE MultiJoin
    carrying the same per-build annotations."""
    eng = make_engine(tpch_tiny)
    plan, _ = eng.plan_sql(QUERIES["q05"])
    mjs = _multijoins(plan)
    assert mjs and not _joins(plan)
    for mj in mjs:
        assert len(mj.builds) >= 3
        assert len(mj.distributions) == len(mj.builds)
        for d, rows in zip(mj.distributions, mj.build_rows):
            assert d in ("broadcast", "partitioned", "hybrid")
            assert rows is not None
            assert rows & (rows - 1) == 0  # pow2-bucketed

    # with fusion off the cascade keeps the binary annotations
    eng2 = make_engine(tpch_tiny, multiway_join=False)
    plan2, _ = eng2.plan_sql(QUERIES["q05"])
    joins = _joins(plan2)
    assert joins
    for j in joins:
        assert j.distribution in ("broadcast", "partitioned", "hybrid")
        assert j.build_rows is not None
        assert j.build_rows & (j.build_rows - 1) == 0  # pow2-bucketed


def test_eliminate_cross_joins_keeps_shape_refreshes_estimates(
        tpch_tiny):
    eng_none = make_engine(tpch_tiny,
                           optimizer_join_reordering_strategy="NONE")
    eng_ecj = make_engine(
        tpch_tiny,
        optimizer_join_reordering_strategy="ELIMINATE_CROSS_JOINS")
    plan_none, _ = eng_none.plan_sql(QUERIES["q05"])
    plan_ecj, _ = eng_ecj.plan_sql(QUERIES["q05"])

    def shape(plan):
        return [tuple(sorted(j.criteria)) for j in _joins(plan)]

    assert shape(plan_none) == shape(plan_ecj)
    assert all(j.distribution in ("broadcast", "partitioned")
               for j in _joins(plan_ecj))


# -- DP ordering ------------------------------------------------------------


def _chain_engine(n_big, n_mid, n_small) -> Engine:
    eng = Engine()
    mem = MemoryConnector()
    for name, prefix, n in (("big", "b", n_big), ("mid", "m", n_mid),
                            ("small", "s", n_small)):
        mem.create_table(
            name, {f"{prefix}_id": T.BIGINT, f"{prefix}_x": T.BIGINT},
            {f"{prefix}_id": np.arange(n),
             f"{prefix}_x": np.arange(n) % max(n // 2, 1)},
            {f"{prefix}_id": None, f"{prefix}_x": None})
    eng.register_catalog("mem", mem)
    eng.session.catalog = "mem"
    return eng


def test_dp_smallest_build_side_innermost():
    """With a fact table joining two dims, the DP must attach the
    smaller estimated build side first (innermost), mirroring the
    reference ReorderJoins' cost preference for early reduction."""
    eng = _chain_engine(100_000, 1_000, 10)
    plan, _ = eng.plan_sql(
        "select count(*) from big, mid, small "
        "where b_id = m_id and b_x = s_id")
    joins = _joins(plan)
    assert len(joins) == 2
    # joins[] is top-down: the LAST entry is the innermost join
    inner_build_rows = joins[-1].build_rows
    outer_build_rows = joins[0].build_rows
    assert inner_build_rows <= outer_build_rows
    inner_syms = set(joins[-1].right.output_types())
    assert any(s.startswith("s_") for s in inner_syms), inner_syms


def test_probe_side_is_larger_relation():
    """Two-way join priced for an 8-shard mesh: the DP must keep the
    big side as probe (left) whichever order stats imply (the
    test_cost.py flipped-stats property, re-checked through the cost
    pass) — replicating it to seven peers is what the network term
    refuses. (The memory connector proves no key unique, so the join
    expands; on one chip the co-sort is symmetric and the expansion
    binary-searches the probe side, so there the small side probes.)"""
    eng = _chain_engine(50_000, 100, 10)
    plan, _ = eng.plan_sql(
        "select count(*) from mid, big where b_id = m_id", nshards=8)
    j = _joins(plan)[0]
    assert any(s.startswith("b_") for s in j.left.output_types())


# -- stats bounded error ----------------------------------------------------


def test_scan_and_filter_estimates_bounded(tpch_tiny):
    """Estimates on TPC-H scans/filters must stay within a small
    constant factor of actuals at SF0.01."""
    eng = make_engine(tpch_tiny)
    calc = StatsCalculator(eng)

    plan, _ = eng.plan_sql("select l_orderkey from lineitem")
    scan = plan
    while not isinstance(scan, N.TableScan):
        scan = scan.sources()[0]
    actual = tpch_tiny.table("lineitem").nrows
    est = calc.stats(scan).row_count
    assert 0.5 <= est / actual <= 2.0

    plan, _ = eng.plan_sql(
        "select l_orderkey from lineitem "
        "where l_shipdate <= date '1995-09-02'")
    filt = plan
    while not isinstance(filt, N.Filter):
        filt = filt.sources()[0]
    rows = make_engine(tpch_tiny).execute(
        "select count(*) from lineitem "
        "where l_shipdate <= date '1995-09-02'")[0][0]
    est = StatsCalculator(eng).stats(filt).row_count
    assert 0.25 <= est / rows <= 4.0


def test_join_estimate_bounded(tpch_tiny):
    """FK->PK join estimate (orders x lineitem) within 4x of actual."""
    eng = make_engine(tpch_tiny)
    plan, _ = eng.plan_sql(
        "select count(*) from lineitem, orders "
        "where l_orderkey = o_orderkey")
    join = _joins(plan)[0]
    actual = eng.execute(
        "select count(*) from lineitem, orders "
        "where l_orderkey = o_orderkey")[0][0]
    est = StatsCalculator(eng).stats(join).row_count
    assert 0.25 <= est / actual <= 4.0


# -- cost model -------------------------------------------------------------


def test_distribution_decision_precedence():
    assert decide_join_distribution("partitioned", "broadcast",
                                    1, 100) == "partitioned"
    assert decide_join_distribution(None, "broadcast",
                                    10**9, 100) == "broadcast"
    assert decide_join_distribution(None, "automatic",
                                    101, 100) == "partitioned"
    assert decide_join_distribution(None, "automatic",
                                    100, 100) == "broadcast"
    # unknown build size broadcasts (historical fragmenter+executor
    # behavior, now one shared rule)
    assert decide_join_distribution(None, "automatic",
                                    None, 100) == "broadcast"


def test_network_cost_models_mesh_collectives(tpch_tiny):
    """Broadcast prices the build all_gather (scales with mesh size);
    partitioned prices the two-sided all_to_all (bounded by total
    bytes); the crossover favors partitioning large builds."""
    eng = make_engine(tpch_tiny)
    calc = StatsCalculator(eng)
    cc8 = CostCalculator(nshards=8)
    plan, _ = eng.plan_sql(
        "select count(*) from lineitem, orders "
        "where l_orderkey = o_orderkey")
    join = _joins(plan)[0]
    probe = calc.stats(join.left)
    build = calc.stats(join.right)
    bcast = cc8.join_cost(probe, build, 1.0,
                          join.right.output_types(),
                          join.left.output_types(), "broadcast")
    part = cc8.join_cost(probe, build, 1.0,
                         join.right.output_types(),
                         join.left.output_types(), "partitioned")
    build_bytes = build.output_bytes(join.right.output_types())
    probe_bytes = probe.output_bytes(join.left.output_types())
    assert bcast.network == pytest.approx(build_bytes * 7)
    assert part.network == pytest.approx(
        (probe_bytes + build_bytes) * 7 / 8)
    # a broadcast build table is replicated per device; partitioned
    # holds 1/n of it
    assert bcast.memory == pytest.approx(build_bytes)
    assert part.memory == pytest.approx(build_bytes / 8)


# -- EXPLAIN surfacing ------------------------------------------------------


def test_explain_shows_estimates(tpch_tiny):
    out = make_engine(tpch_tiny).explain(QUERIES["q05"])
    assert "Estimates: {rows:" in out
    assert "network:" in out
    # every Join line is followed by an estimate detail line
    lines = out.splitlines()
    for i, line in enumerate(lines):
        if "Join[" in line:
            assert "Estimates:" in lines[i + 1], line


def test_explain_analyze_shows_est_vs_actual(tpch_tiny):
    rows = make_engine(tpch_tiny).execute(
        "explain analyze select count(*) from lineitem, orders "
        "where l_orderkey = o_orderkey")
    text = rows[0][0]
    assert "(est " in text and "rows: " in text
