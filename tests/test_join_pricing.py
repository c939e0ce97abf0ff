"""The join enumerator prices the devices a statement runs on and the
physical join the executor runs (PR 26): Q3's order at the benchmark's
scales with and without a mesh, ``join_cost``'s three physical kinds,
the shard count a preplanned hand-over carries, and the ``plan`` span
and counter that say which joins were planned.

Planning reads estimates only, so the SF1 and SF10 plans generate no
data; one execution at SF 0.5 (the smallest scale at which an 8-shard
price picks the expanding order) is held to the benchmark's reference.
"""

from __future__ import annotations

import time
from pathlib import Path

import jax
import numpy as np
import pytest
from jax.sharding import Mesh

from presto_tpu import Engine
from presto_tpu.connectors.tpch import TpchConnector
from presto_tpu.cost.model import CostCalculator, join_kind
from presto_tpu.cost.stats import PlanNodeStatsEstimate
from presto_tpu.obs.metrics import REGISTRY
from presto_tpu.obs.trace import TRACER
from presto_tpu.plan import nodes as N
from presto_tpu.plan.optimizer import joins_by_kind
from presto_tpu import types as T

REPO = Path(__file__).resolve().parent.parent
Q3_PARAMS = {"SEGMENT": "BUILDING", "DATE": "1995-03-15"}
Q3 = (REPO / "benchmark" / "queries" / "q03.sql").read_text().format(
    **Q3_PARAMS)


def _engine(scale: float) -> Engine:
    e = Engine()
    e.register_catalog("tpch", TpchConnector(scale=scale))
    return e


def _nodes(plan, cls):
    out = []

    def visit(n):
        if isinstance(n, cls):
            out.append(n)
        for s in n.sources():
            visit(s)

    visit(plan)
    return out


def _spine_table(join: N.Join) -> str:
    """The table at the bottom of a join's probe (left) spine."""
    node = join
    while not isinstance(node, N.TableScan):
        node = node.sources()[0]
    return node.table


# -- Q3's order ---------------------------------------------------------------

@pytest.mark.parametrize("scale,span", [(1, 1_500_000), (10, 15_000_000)])
def test_q3_without_a_mesh_probes_with_lineitem(scale, span):
    """On one chip nothing crosses a link: lineitem stays on the probe
    spine, both joins are direct-address probes of a dense unique key,
    and the dependent order columns re-join after grouping."""
    plan, _ = _engine(scale).plan_sql(Q3)
    assert joins_by_kind(plan) == {"dense": 3, "lookup": 0,
                                   "expanding": 0}
    inner = [j for j in _nodes(plan, N.Join)
             if j.join_type == N.JoinType.INNER]
    assert len(inner) == 2
    assert all(j.build_unique and j.dense_key is not None for j in inner)
    assert all(_spine_table(j) == "lineitem" for j in inner)
    by_key = {j.criteria[0][1].rsplit("_", 1)[0]: j for j in inner}
    assert by_key["o_orderkey"].dense_key == (0, 1, span)
    # late materialisation: the aggregate groups on l_orderkey alone
    # and o_orderdate / o_shippriority come back by a LEFT re-join
    (agg,) = _nodes(plan, N.Aggregate)
    assert [k.rsplit("_", 1)[0] for k in agg.group_keys] == ["l_orderkey"]
    (rejoin,) = [j for j in _nodes(plan, N.Join)
                 if j.join_type == N.JoinType.LEFT]
    assert rejoin.left is agg and rejoin.build_unique
    assert _spine_table(rejoin.right) == "orders"


def test_q3_priced_for_eight_shards_at_sf1_keeps_the_mesh_order():
    """Priced for an 8-shard mesh the broadcast of filtered orders to
    seven peers outweighs the co-partitioned expanding join, as before
    this PR: orders x customer probes, lineitem is the build side."""
    plan, _ = _engine(1).plan_sql(Q3, nshards=8)
    assert joins_by_kind(plan) == {"dense": 1, "lookup": 0,
                                   "expanding": 1}
    (top,) = [j for j in _nodes(plan, N.Join) if not j.build_unique]
    assert _spine_table(top) == "orders"
    assert _spine_table(top.right) == "lineitem"
    assert top.distribution == "partitioned"
    (agg,) = _nodes(plan, N.Aggregate)
    assert len(agg.group_keys) == 3


def test_q3_at_sf10_the_expansion_outweighs_eight_shards_of_network():
    """What the expanding join's own price (a co-sort of 84M rows and
    an expansion) changes for a mesh: at SF10 it costs more than the
    broadcast it avoided, so the 8-shard plan probes with lineitem
    too; at 8 shards and SF1 (above) it does not."""
    plan, _ = _engine(10).plan_sql(Q3, nshards=8)
    assert joins_by_kind(plan)["expanding"] == 0


def test_one_device_mesh_and_no_mesh_plan_alike():
    devices = jax.devices()
    assert len(devices) >= 8, "conftest forces 8 virtual CPU devices"
    e = _engine(1)
    explain = "explain " + Q3
    alone = e.execute(explain)
    one = e.execute(explain, mesh=Mesh(np.array(devices[:1]), ("d",)))
    eight = e.execute(explain, mesh=Mesh(np.array(devices[:8]), ("d",)))
    assert one == alone
    assert "network: 0B" in alone[0][0]
    assert eight != alone and "expanding" in eight[0][0]
    assert "expanding" not in alone[0][0]


def test_q3_at_sf_half_equals_the_benchmark_reference(monkeypatch):
    """The order this PR gives executes to the reference's ten rows at
    the smallest scale at which the 8-shard price picked the other."""
    monkeypatch.syspath_prepend(str(REPO / "benchmark"))
    import refdata
    import verify
    conn = TpchConnector(scale=0.5)
    e = Engine()
    e.register_catalog("tpch", conn)
    old, _ = e.plan_sql(Q3, nshards=8)
    assert joins_by_kind(old)["expanding"] == 1
    new, _ = e.plan_sql(Q3)
    assert joins_by_kind(new)["expanding"] == 0
    got = [[int(k), str(rev), str(day), int(prio)]
           for k, rev, day, prio in e.execute(Q3)]
    want = verify.load_reference("q03")(refdata.Columns(conn), Q3_PARAMS)
    assert len(want) == 10 and got == want


# -- the price of one join ----------------------------------------------------

def test_join_cost_charges_the_physical_join():
    """A unique build (direct-address or lookup) is linear; an
    expanding join is a co-sort plus a binary search per output slot;
    on one shard neither distribution pays any network."""
    types = {"k": T.BIGINT}
    probe = PlanNodeStatsEstimate(1 << 20, {}, True)
    build = PlanNodeStatsEstimate(1 << 16, {}, True)
    out = float(1 << 20)
    one = CostCalculator(1)
    unique = one.join_cost(probe, build, out, types, types, "broadcast")
    assert unique.cpu == (1 << 20) + 2.0 * (1 << 16) + out
    both = float((1 << 20) + (1 << 16))
    expanding = one.join_cost(probe, build, out, types, types,
                              "partitioned", build_unique=False)
    assert expanding.cpu == pytest.approx(
        both * np.log2(both) + out * 20.0)
    assert expanding.cpu > 10 * unique.cpu
    assert unique.network == expanding.network == 0.0
    eight = CostCalculator(8)
    assert eight.join_cost(probe, build, out, types, types,
                           "broadcast").network == 7.0 * 8 * (1 << 16)
    assert eight.join_cost(probe, build, out, types, types,
                           "partitioned").network == 8 * both * 7 / 8


def test_join_kind_follows_the_executors_dispatch():
    scan = N.TableScan("c", "t", {"k": "k"}, {"k": T.BIGINT})
    crit = [("k", "k")]
    dense = N.Join(scan, scan, N.JoinType.INNER, crit, None, True,
                   dense_key=(0, 1, 100))
    assert join_kind(dense) == "dense"
    assert join_kind(N.Join(scan, scan, N.JoinType.LEFT, crit, None,
                            True)) == "lookup"
    assert join_kind(N.Join(scan, scan, N.JoinType.INNER, crit, None,
                            False)) == "expanding"
    # FULL owns the unmatched-build tail pass: always the expanding path
    assert join_kind(N.Join(scan, scan, N.JoinType.FULL, crit, None,
                            True, dense_key=(0, 1, 100))) == "expanding"


# -- what a plan was priced for -----------------------------------------------

def test_preplanned_plan_is_taken_only_at_its_shard_count(tpch_tiny):
    e = Engine()
    e.register_catalog("tpch", tpch_tiny)
    sql = "select count(*) from nation"
    plan, _ = e.plan_sql(sql)
    e.offer_preplanned(sql, plan)
    assert e.take_preplanned(sql, 8) is None
    assert e.take_preplanned(sql) is None  # one-shot: consumed above
    e.offer_preplanned(sql, plan, 8)
    assert e.take_preplanned(sql, 8) is plan


def test_plan_span_and_counter_say_what_was_planned(tpch_tiny):
    e = Engine()
    e.register_catalog("tpch", tpch_tiny)
    planned = REGISTRY.counter("presto_tpu_joins_planned_total")
    before = {k: planned.value(kind=k)
              for k in ("dense", "lookup", "expanding")}
    tid = f"t{time.monotonic_ns()}"
    with TRACER.trace(tid, "query"):
        plan, _ = e.plan_sql(Q3)
        e.plan_sql("select count(*) from lineitem", nshards=4)
    q3, scan = [s for s in TRACER.spans(tid) if s.name == "plan"]
    assert q3.attrs["nshards"] == 1
    assert q3.attrs["joins"] == "dense:3,lookup:0,expanding:0"
    assert scan.attrs["nshards"] == 4
    assert scan.attrs["joins"] == "dense:0,lookup:0,expanding:0"
    after = {k: planned.value(kind=k) for k in before}
    assert after == {**before, "dense": before["dense"] + 3}
