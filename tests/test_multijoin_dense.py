"""A MultiJoin leg whose build has a dense unique integer key is planned
as a direct-address probe (PR 30): which of Q5's and Q9's legs get the
hint at the benchmark's scales, what the ``plan`` span and the counter
then say, and that everything which rebuilds or serialises the node
keeps the hints. Planning reads estimates only, so SF1 and SF10 plan
here without data; one tiny execution over a mesh holds a co-partitioned
direct leg to the local answer.
"""

from __future__ import annotations

import dataclasses
import json
import time
from pathlib import Path

import pytest

from presto_tpu import Engine
from presto_tpu.connectors.tpch import TpchConnector
from presto_tpu.obs.metrics import REGISTRY
from presto_tpu.obs.trace import TRACER
from presto_tpu.plan import nodes as N
from presto_tpu.plan.fingerprint import plan_fingerprint
from presto_tpu.plan.optimizer import (collapse_multiway, joins_by_kind,
                                       unfuse_multijoin)
from presto_tpu.plan.printer import format_plan
from presto_tpu.plan.serde import fragment_from_dict, fragment_to_dict

REPO = Path(__file__).resolve().parent.parent
PARAMS = {"q03": {"SEGMENT": "BUILDING", "DATE": "1995-03-15"},
          "q05": {"REGION": "ASIA", "DATE": "1994-01-01"},
          "q09": {"COLOR": "green"}}
SQL = {name: (REPO / "benchmark" / "queries" / f"{name}.sql")
       .read_text().format(**params) for name, params in PARAMS.items()}
KINDS = {"q03": "dense:3,lookup:0,expanding:0",
         "q05": "dense:5,lookup:0,expanding:0",
         "q09": "dense:4,lookup:1,expanding:0"}

# per leg in plan order: (build table, the criterion's build key that
# the hint names or None, lo, hi as a multiple of the scale factor or
# a constant)
LEGS = {
    "q05": [("orders", "o_orderkey", 1, 1_500_000),
            ("supplier", "s_suppkey", 1, 10_000),
            ("nation", "n_nationkey", 0, 24),
            ("region", "r_regionkey", 0, 4),
            ("customer", "c_custkey", 1, 150_000)],
    "q09": [("part", "p_partkey", 1, 200_000),
            ("supplier", "s_suppkey", 1, 10_000),
            ("nation", "n_nationkey", 0, 24),
            ("partsupp", None, None, None),
            ("orders", "o_orderkey", 1, 1_500_000)],
}


def _engine(scale: float) -> Engine:
    e = Engine()
    e.register_catalog("tpch", TpchConnector(scale=scale))
    return e


@pytest.fixture(scope="module", params=[1, 10])
def planned(request):
    """(scale, {class: optimized plan}) without any data."""
    e = _engine(request.param)
    return request.param, e, {n: e.plan_sql(SQL[n])[0] for n in SQL}


def _multijoin(plan) -> N.MultiJoin:
    found = []

    def visit(n):
        if isinstance(n, N.MultiJoin):
            found.append(n)
        for s in n.sources():
            visit(s)

    visit(plan)
    (mj,) = found
    return mj


def _table(node: N.PlanNode) -> str:
    while not isinstance(node, N.TableScan):
        node = node.sources()[0]
    return node.table


def _base(sym: str) -> str:
    return sym.rsplit("_", 1)[0]


# -- which legs go direct -----------------------------------------------------

@pytest.mark.parametrize("name", ["q03", "q05", "q09"])
def test_joins_by_kind_counts_a_leg_by_its_hint(planned, name):
    _scale, _e, plans = planned
    got = joins_by_kind(plans[name])
    assert ",".join(f"{k}:{n}" for k, n in got.items()) == KINDS[name]
    if name == "q03":  # two collapsible joins: under MIN_MULTIWAY_CHAIN
        assert "MultiJoin" not in format_plan(plans[name])


@pytest.mark.parametrize("name", ["q05", "q09"])
def test_the_leg_table(planned, name):
    scale, _e, plans = planned
    mj = _multijoin(plans[name])
    assert _table(mj.spine) == "lineitem"
    assert len(mj.dense_keys) == len(mj.builds) == 5
    for i, (table, key, lo, hi) in enumerate(LEGS[name]):
        assert _table(mj.builds[i]) == table
        hint = mj.leg_dense_key(i)
        if key is None:
            assert hint is None, table
            continue
        ci, got_lo, got_hi = hint
        assert _base(mj.criteria[i][ci][1]) == key
        # nation's and region's keys do not grow with the scale
        want_hi = hi if hi < 100 else hi * scale
        assert (got_lo, got_hi) == (lo, want_hi), table


def test_customer_is_direct_on_custkey_and_nationkey_is_verified(planned):
    _scale, _e, plans = planned
    mj = _multijoin(plans["q05"])
    crit, (ci, _lo, _hi) = mj.criteria[4], mj.leg_dense_key(4)
    assert len(crit) == 2
    assert _base(crit[ci][1]) == "c_custkey"
    (rest,) = [c for i, c in enumerate(crit) if i != ci]
    assert (_base(rest[0]), _base(rest[1])) == ("s_nationkey",
                                                "c_nationkey")


def test_partsupp_has_no_hint_neither_key_is_unique_alone(planned):
    _scale, _e, plans = planned
    mj = _multijoin(plans["q09"])
    assert {_base(rk) for _lk, rk in mj.criteria[3]} == {
        "ps_suppkey", "ps_partkey"}
    assert mj.leg_dense_key(3) is None


def test_explain_says_which_legs_are_direct(planned):
    _scale, _e, plans = planned
    line = next(ln for ln in format_plan(plans["q09"]).splitlines()
                if "MultiJoin[" in ln)
    assert line.count("direct ") == 4 and line.count("lookup]") == 1
    assert "direct o_orderkey" in line and "direct p_partkey" in line
    q5 = format_plan(plans["q05"])
    assert q5.count("direct ") == 5 and "direct c_custkey" in q5


# -- the span and the counter -------------------------------------------------

def test_plan_span_and_counter_read_the_same(planned):
    _scale, e, _plans = planned
    counter = REGISTRY.counter("presto_tpu_joins_planned_total")
    kinds = ("dense", "lookup", "expanding")
    before = {k: counter.value(kind=k) for k in kinds}
    tid = f"t{time.monotonic_ns()}"
    with TRACER.trace(tid, "query"):
        for name in ("q05", "q09", "q03"):
            e.plan_sql(SQL[name])
    spans = [s for s in TRACER.spans(tid) if s.name == "plan"]
    assert [s.attrs["joins"] for s in spans] == [
        KINDS["q05"], KINDS["q09"], KINDS["q03"]]
    after = {k: counter.value(kind=k) for k in kinds}
    assert after == {"dense": before["dense"] + 12,
                     "lookup": before["lookup"] + 1,
                     "expanding": before["expanding"]}


# -- what rebuilds or serialises the node keeps the hints ---------------------

@pytest.mark.parametrize("name", ["q05", "q09"])
def test_hints_survive_serde(planned, name):
    _scale, _e, plans = planned
    wire = json.loads(json.dumps(fragment_to_dict(plans[name])))
    back = fragment_from_dict(wire)
    assert _multijoin(back).dense_keys == _multijoin(
        plans[name]).dense_keys
    assert plan_fingerprint(back) == plan_fingerprint(plans[name])


@pytest.mark.parametrize("name", ["q05", "q09"])
def test_the_cascade_of_unfuse_carries_the_keys_and_refuses(planned,
                                                            name):
    _scale, e, plans = planned
    mj = _multijoin(plans[name])
    cascade = unfuse_multijoin(plans[name])
    assert joins_by_kind(cascade) == joins_by_kind(plans[name])
    joins = []

    def visit(n):
        if isinstance(n, N.Join):
            joins.append(n)
        for s in n.sources():
            visit(s)

    visit(cascade)
    by_build = {_table(j.right): j.dense_key for j in joins}
    assert by_build == {_table(b): mj.leg_dense_key(i)
                        for i, b in enumerate(mj.builds)}
    # re-fusion (the adaptive remainder's second chance) fuses three
    # links from scratch and absorbs two: every hint comes back
    again = _multijoin(collapse_multiway(cascade, e))
    assert again.dense_keys == mj.dense_keys
    assert plan_fingerprint(again) == plan_fingerprint(mj)


def test_the_hints_are_part_of_the_fingerprint(planned):
    _scale, _e, plans = planned
    mj = _multijoin(plans["q05"])
    bare = dataclasses.replace(mj, dense_keys=[])
    assert joins_by_kind(bare)["lookup"] == 5
    assert plan_fingerprint(bare) != plan_fingerprint(mj)


def test_reannotation_keeps_the_hints():
    """cost/adapt.revise_multijoin re-buckets a leg's rows and
    distribution from actuals; the leg's probe does not change."""
    from presto_tpu.cost.adapt import CarrierStats, OverlayStats, \
        reannotate
    e = _engine(1)
    mj = _multijoin(e.plan_sql(SQL["q05"])[0])
    supplier = mj.builds[1]
    carrier = N.TableScan("__exchange__", "side1",
                          {s: s for s in supplier.output_types()},
                          dict(supplier.output_types()))
    builds = list(mj.builds)
    builds[1] = carrier
    poisoned = dataclasses.replace(mj, builds=builds)
    stats = OverlayStats(e, {"side1": CarrierStats(
        mj.build_rows[1] * 64)})
    out = reannotate(poisoned, e, stats, 8)
    assert out.build_rows[1] == mj.build_rows[1] * 64
    assert out.dense_keys == mj.dense_keys


# -- a co-partitioned direct leg over a mesh ----------------------------------

def test_a_copartitioned_direct_leg_over_a_mesh_equals_the_local_answer(
        tpch_tiny):
    import jax
    import numpy as np
    from jax.sharding import Mesh
    devices = jax.devices()
    assert len(devices) >= 8, "conftest forces 8 virtual CPU devices"
    e = Engine()
    e.register_catalog("tpch", tpch_tiny)
    # every build above 64 rows co-partitions or gathers by the
    # cascade's rule: orders is the one leg the spine repartitions for
    e.session.set("broadcast_join_threshold_rows", 64)
    sql = SQL["q05"]
    plan, _ = e.plan_sql(sql, nshards=8)
    mj = _multijoin(plan)
    assert mj.distributions[0] == "partitioned"
    assert mj.leg_dense_key(0) is not None
    want = e.execute(sql)
    assert want
    got = e.execute(sql, mesh=Mesh(np.array(devices[:8]), ("d",)))
    assert got == want
