"""Dynamic filtering: build-side join-key bloom masks prune probe scans
before the join (trace-time analog of the reference's
DynamicFilterService.java:102 + DynamicFilterSourceOperator.java:55).
A probe key is tested once: a leg whose own probe is a direct address
registers no mask, nor does a build as wide as the mask.
Correctness is oracle-checked; effectiveness is asserted via EXPLAIN
ANALYZE probe-scan row counts; which legs registered is read off the
``execute`` span and the labelled counter."""

import re
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from presto_tpu import Engine
from presto_tpu import types as T
from presto_tpu.exec.executor import (DF_OUTCOMES, PlanInterpreter,
                                      collect_scans, make_traced)
from presto_tpu.exec.operators import DTable
from presto_tpu.expr.compile import Val
from presto_tpu.obs.metrics import REGISTRY
from presto_tpu.obs.trace import TRACER
from presto_tpu.plan import nodes as N
from presto_tpu.testing import assert_query

from tpch_queries import QUERIES

# lineitem probes a FILTERED partsupp on its composite key: the build
# is unique and no single column of the key is dense, so the leg is a
# sorted lookup and its filter must still prune
PS_COMPOSITE = (
    "select sum(l_extendedprice) as s from lineitem, partsupp "
    "where ps_partkey = l_partkey and ps_suppkey = l_suppkey "
    "and ps_availqty < 100")
# a build key that is not unique (four suppliers a part): the
# expanding join, whose output capacity follows the live count
PS_NONUNIQUE = (
    "select sum(l_extendedprice) as s from lineitem, partsupp "
    "where ps_partkey = l_partkey and ps_supplycost < 5")
# a dense unique build key: the leg's own probe is a direct address
Q17_LIKE = (
    "select sum(l_extendedprice) / 7.0 as avg_yearly "
    "from lineitem, part where p_partkey = l_partkey "
    "and p_brand = 'Brand#23' and p_container = 'MED BOX'")


def make_engine(tpch_tiny, df: bool) -> Engine:
    e = Engine()
    e.register_catalog("tpch", tpch_tiny)
    e.session.set("enable_dynamic_filtering", df)
    return e


def scan_rows(text: str, table: str) -> int:
    for line in text.splitlines():
        if f"TableScan[tpch.{table}]" in line:
            m = re.search(r"rows: (\d+)", line)
            if m:
                return int(m.group(1))
    raise AssertionError(f"no annotated scan of {table} in:\n{text}")


@pytest.mark.parametrize("qname", ["q05", "q09", "q12"])
def test_df_results_unchanged(qname, tpch_tiny):
    on = make_engine(tpch_tiny, True)
    off = make_engine(tpch_tiny, False)
    assert on.execute(QUERIES[qname]) == off.execute(QUERIES[qname])


def lineitem_rows_on_off(tpch_tiny, sql: str) -> tuple[int, int]:
    """Rows EXPLAIN ANALYZE reports out of the lineitem scan with the
    filter on and off (the two engines' answers must agree)."""
    on = make_engine(tpch_tiny, True)
    off = make_engine(tpch_tiny, False)
    rows = [scan_rows(e.execute(f"explain analyze {sql}")[0][0],
                      "lineitem") for e in (on, off)]
    assert on.execute(sql) == off.execute(sql)
    return rows[0], rows[1]


def dyn_filter_counts(e: Engine, sql: str) -> tuple[dict, list]:
    """(legs by what the ``execute`` spans' ``dynfilters=`` say of
    them, summed over the statement's programs; the spans' cache_hit
    flags)."""
    tid = f"t{time.monotonic_ns()}"
    with TRACER.trace(tid, "query"):
        e.execute(sql)
    total = {"registered": 0, "direct": 0, "wide": 0}
    hits = []
    for s in TRACER.spans(tid):
        if s.name != "execute":
            continue
        hits.append(s.attrs["cache_hit"])
        for part in filter(None, s.attrs.get("dynfilters", "").split(",")):
            kind, n = part.split(":")
            total[kind] += int(n)
    return total, hits


def inner_dense_legs(plan: N.PlanNode) -> int:
    n = 0
    for node in N.preorder(plan):
        if isinstance(node, N.Join):
            n += (node.join_type == N.JoinType.INNER
                  and node.dense_key is not None)
        elif isinstance(node, N.MultiJoin):
            n += sum(node.leg_dense_key(i) is not None
                     for i in range(len(node.builds)))
    return n


def bloom_scatters(jaxpr) -> list:
    """Every scatter into a boolean vector in ``jaxpr`` and the
    programs nested in it: a dynamic filter's mask is the only one."""
    found = []
    for eqn in jaxpr.eqns:
        if (eqn.primitive.name.startswith("scatter")
                and eqn.outvars[0].aval.dtype == jnp.bool_):
            found.append(eqn)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found.extend(bloom_scatters(sub))
    return found


def traced_jaxpr(e: Engine, sql: str):
    plan, _ = e.plan_sql(sql)
    scans = collect_scans(plan, e)
    fn, flat, _meta = make_traced(scans, plan, {}, e.session)
    return jax.make_jaxpr(fn)(*flat).jaxpr


@pytest.mark.parametrize("qname", ["q03", "q05", "q09", "q10", "q18"])
def test_df_on_off_and_oracle_agree(qname, tpch_tiny, oracle):
    on = make_engine(tpch_tiny, True)
    off = make_engine(tpch_tiny, False)
    assert on.execute(QUERIES[qname]) == off.execute(QUERIES[qname])
    assert_query(on, oracle, QUERIES[qname])


@pytest.mark.parametrize("sql", [PS_COMPOSITE, PS_NONUNIQUE],
                         ids=["composite-lookup", "non-unique-key"])
def test_df_prunes_probe_scan_rows(sql, tpch_tiny, oracle):
    """A leg with no dense hint (a sorted lookup on a composite key, an
    expanding join on a key that is not unique) over a filtered build
    still registers, and the probe scan reports the pruning."""
    rows_on, rows_off = lineitem_rows_on_off(tpch_tiny, sql)
    # the partsupp filter keeps about 1/100 of its rows; the bloom mask
    # must cut the lineitem probe to a small fraction
    assert rows_on < rows_off / 5, (rows_on, rows_off)
    on = make_engine(tpch_tiny, True)
    assert dyn_filter_counts(on, sql)[0] == {
        "registered": 1, "direct": 0, "wide": 0}
    assert dyn_filter_counts(make_engine(tpch_tiny, False), sql)[0] == {
        "registered": 0, "direct": 0, "wide": 0}
    assert_query(on, oracle, sql)


def test_df_prunes_q9_probe_by_its_lookup_leg(tpch_tiny):
    """Q9 over a filtered partsupp: four legs are direct addresses and
    register nothing; partsupp's, the sorted one, registers both spine
    keys and the lineitem scan is pruned by them alone."""
    sql = QUERIES["q09"].replace(
        "p_name like '%green%'",
        "p_name like '%green%' and ps_availqty < 100")
    assert sql != QUERIES["q09"]
    rows_on, rows_off = lineitem_rows_on_off(tpch_tiny, sql)
    assert rows_on < rows_off / 5, (rows_on, rows_off)
    assert dyn_filter_counts(make_engine(tpch_tiny, True), sql)[0] == {
        "registered": 1, "direct": 4, "wide": 0}


def test_direct_leg_scan_reports_its_own_selectivity(tpch_tiny):
    """In front of a direct-address leg a scan reports the rows its own
    predicate keeps, with the filter on as with it off."""
    rows_on, rows_off = lineitem_rows_on_off(tpch_tiny, Q17_LIKE)
    assert rows_on == rows_off


@pytest.mark.parametrize("qname", ["q03", "q05", "q10"])
def test_direct_legs_register_nothing(qname, tpch_tiny):
    e = make_engine(tpch_tiny, True)
    plan, _ = e.plan_sql(QUERIES[qname])
    legs = inner_dense_legs(plan)
    assert legs >= 2
    counter = REGISTRY.counter("presto_tpu_dynamic_filters_total")
    before = {k: counter.value(outcome=k) for k in DF_OUTCOMES}
    counts, hits = dyn_filter_counts(e, QUERIES[qname])
    assert counts == {"registered": 0, "direct": legs, "wide": 0}
    assert not any(hits)
    after = {k: counter.value(outcome=k) for k in DF_OUTCOMES}
    assert after == {**before,
                     "skipped_direct": before["skipped_direct"] + legs}
    # the counts ride the program's cache entry: a hit says them too
    again, hits = dyn_filter_counts(e, QUERIES[qname])
    assert again == counts and all(hits)
    assert counter.value(outcome="skipped_direct") \
        == before["skipped_direct"] + 2 * legs


def test_wide_build_registers_nothing():
    """A mask that would hold under a bit a build row is not built: the
    test is on static shapes, the build's width against the mask's
    after the ``max_bits`` cap."""
    def build(n):
        return DTable({"b": Val(T.BIGINT, jnp.arange(n))}, None, n)

    interp = PlanInterpreter({}, {})
    assert interp._collect_dyn_filters([("a", "b")], None, build(63),
                                       max_bits=64) == ["a"]
    assert interp.dyn_filters["a"].shape == (64,)
    for n in (64, 65, 1000):
        assert interp._collect_dyn_filters([("c", "b")], None, build(n),
                                           max_bits=64) == []
    assert "c" not in interp.dyn_filters
    # a direct-address leg registers nothing whatever its width
    assert interp._collect_dyn_filters([("d", "b")], (0, 0, 62),
                                       build(63), max_bits=64) == []
    assert interp.df_counts == {"registered": 1, "skipped_direct": 1,
                                "skipped_wide": 3}
    # under the default cap a build this narrow gets four bits a row
    assert interp._collect_dyn_filters([("e", "b")], None,
                                       build(1000)) == ["e"]
    assert interp.dyn_filters["e"].shape == (4096,)


def test_dense_only_program_holds_no_bloom_mask(tpch_tiny):
    """No mask is scattered and none is gathered from: the traced
    program of a plan whose every leg is a direct address holds no
    boolean scatter at all, with the filter on exactly as with it off;
    a sorted-lookup leg's program holds one per criterion."""
    on = make_engine(tpch_tiny, True)
    off = make_engine(tpch_tiny, False)
    for sql in (Q17_LIKE, QUERIES["q05"]):
        jaxpr = traced_jaxpr(on, sql)
        assert bloom_scatters(jaxpr) == []
        assert str(jaxpr) == str(traced_jaxpr(off, sql))
    masks = bloom_scatters(traced_jaxpr(on, PS_COMPOSITE))
    assert len(masks) == 2
    assert all(eqn.outvars[0].aval.ndim == 1 for eqn in masks)
    assert bloom_scatters(traced_jaxpr(off, PS_COMPOSITE)) == []


def test_df_distributed_matches(tpch_tiny, oracle):
    from presto_tpu.sql.parser import parse_statement
    from presto_tpu.sql.sqlite_dialect import to_sqlite
    from presto_tpu.testing.oracle import rows_equal

    devices = jax.devices()
    mesh = Mesh(np.array(devices[:8]), ("d",))
    e = make_engine(tpch_tiny, True)
    e.session.set("join_distribution_type", "PARTITIONED")
    tid = f"t{time.monotonic_ns()}"
    with TRACER.trace(tid, "query"):
        got = e.execute(QUERIES["q05"], mesh=mesh)
    want = oracle.query(to_sqlite(parse_statement(QUERIES["q05"])))
    ok, msg = rows_equal(got, want, ordered=True)
    assert ok, msg
    # the mesh's join bodies are the single chip's, so the same rule:
    # the legs planned as direct addresses register nothing
    spans = TRACER.spans(tid)
    planned = [s.attrs["joins"] for s in spans if s.name == "plan"]
    said = [s.attrs["dynfilters"] for s in spans
            if s.name == "execute" and "dynfilters" in s.attrs]
    assert planned == ["dense:1,lookup:0,expanding:4"]
    assert said == ["registered:4,direct:1,wide:0"]
