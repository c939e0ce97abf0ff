"""Skew-aware join distribution + multi-way star-schema joins.

Covers the two halves of the skew work end to end against the sqlite
oracle and the ``optimizer_join_reordering_strategy=NONE``
cascaded-binary plans:

- the fused :class:`MultiJoin` operator (plan/optimizer.py
  collapse_multiway -> exec/operators.apply_multi_join and the
  parallel lowering), over uniform AND Zipf-skewed TPC-H data;
- hybrid distribution (cost/skew.py decision, runtime count-sketch
  heavy-hitter detection in parallel/executor._hybrid_join) including
  the empty-hot-key-set and all-keys-hot edge cases, plus salted
  partitioned exchanges for unique and expanding joins.
"""

from __future__ import annotations

import dataclasses

import jax
import numpy as np
import pytest
from jax.sharding import Mesh

from presto_tpu import Engine
from presto_tpu import types as T
from presto_tpu.connectors.memory import MemoryConnector
from presto_tpu.connectors.tpch import TpchConnector
from presto_tpu.parallel.executor import execute_plan_distributed
from presto_tpu.plan import nodes as N
from presto_tpu.sql.parser import parse_statement
from presto_tpu.sql.sqlite_dialect import to_sqlite
from presto_tpu.testing.oracle import SqliteOracle, rows_equal

from tpch_queries import QUERIES


@pytest.fixture(scope="module")
def mesh():
    devices = jax.devices()
    assert len(devices) >= 8, "conftest forces 8 virtual CPU devices"
    return Mesh(np.array(devices[:8]), ("d",))


@pytest.fixture(scope="module")
def tpch_zipf() -> TpchConnector:
    return TpchConnector(scale=0.01, skew="zipf:1.3")


@pytest.fixture(scope="module")
def zipf_oracle(tpch_zipf) -> SqliteOracle:
    o = SqliteOracle()
    o.load_connector(tpch_zipf)
    return o


def make_engine(conn, **props) -> Engine:
    e = Engine()
    e.register_catalog("tpch", conn)
    for k, v in props.items():
        e.session.set(k, v)
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


# forces plan-time "partitioned" at tiny scale, then the skew decision
SKEW_PROPS = dict(broadcast_join_threshold_rows=64,
                  skew_hot_key_threshold=64)


# -- MultiJoin collapse + oracle checks --------------------------------------


def test_multijoin_collapse_and_gates(tpch_tiny):
    """Q5's 5-join star chain fuses into one MultiJoin under the
    defaults; NONE reordering and multiway_join=false both keep the
    cascaded binary shape."""
    plan, _ = make_engine(tpch_tiny).plan_sql(QUERIES["q05"])
    mjs = _nodes(plan, N.MultiJoin)
    assert len(mjs) == 1 and len(mjs[0].builds) == 5
    assert not _nodes(plan, N.Join)

    for props in (dict(optimizer_join_reordering_strategy="NONE"),
                  dict(multiway_join=False)):
        p, _ = make_engine(tpch_tiny, **props).plan_sql(QUERIES["q05"])
        assert not _nodes(p, N.MultiJoin)
        assert _nodes(p, N.Join)


@pytest.mark.parametrize("qname", ["q05", "q09"])
def test_multijoin_oracle_uniform(tpch_tiny, oracle, qname):
    """Fused plans byte-identical to the sqlite oracle AND to the
    NONE-strategy cascaded-binary plans on uniform data."""
    sql = QUERIES[qname]
    got = make_engine(tpch_tiny).execute(sql)
    want = oracle.query(to_sqlite(parse_statement(sql)))
    ok, msg = rows_equal(got, want, ordered=True)
    assert ok, f"{qname} vs oracle: {msg}"
    cascade = make_engine(
        tpch_tiny,
        optimizer_join_reordering_strategy="NONE").execute(sql)
    assert got == cascade


@pytest.mark.parametrize("qname", ["q05", "q09"])
def test_multijoin_oracle_zipf(tpch_zipf, zipf_oracle, qname):
    """Same checks over Zipf-skewed data: heavy-hitter FKs must not
    change a single output byte."""
    sql = QUERIES[qname]
    got = make_engine(tpch_zipf).execute(sql)
    want = zipf_oracle.query(to_sqlite(parse_statement(sql)))
    ok, msg = rows_equal(got, want, ordered=True)
    assert ok, f"{qname} zipf vs oracle: {msg}"
    cascade = make_engine(
        tpch_zipf,
        optimizer_join_reordering_strategy="NONE").execute(sql)
    assert got == cascade


def test_multijoin_distributed_zipf(tpch_zipf, zipf_oracle, mesh):
    """The distributed MultiJoin lowering (spine sharded, builds
    replicated / at most one co-partitioned) over skewed data matches
    the oracle."""
    sql = QUERIES["q05"]
    eng = make_engine(tpch_zipf)
    got = eng.execute(sql, mesh=mesh)
    assert _nodes(eng.plan_sql(sql, nshards=8)[0], N.MultiJoin)
    want = zipf_oracle.query(to_sqlite(parse_statement(sql)))
    ok, msg = rows_equal(got, want, ordered=True)
    assert ok, msg


# -- hybrid distribution -----------------------------------------------------


def test_hybrid_planned_and_oracle_zipf(tpch_zipf, zipf_oracle, mesh):
    """With partitioned joins forced cheap and a low hot threshold the
    reorderer plans hybrid distribution, and the runtime sketch path
    stays byte-identical to the oracle on Zipf data (the case hybrid
    exists for: hot keys broadcast, cold tail partitions)."""
    eng = make_engine(tpch_zipf, multiway_join=False, **SKEW_PROPS)
    sql = QUERIES["q03"]
    plan, _ = eng.plan_sql(sql, nshards=8)
    dists = [j.distribution for j in _nodes(plan, N.Join)]
    assert "hybrid" in dists, dists
    got = eng.execute(sql, mesh=mesh)
    want = zipf_oracle.query(to_sqlite(parse_statement(sql)))
    ok, msg = rows_equal(got, want, ordered=True)
    assert ok, msg


def test_hybrid_empty_hot_key_set(tpch_tiny, mesh):
    """Estimates may compile the hybrid path while the data holds no
    key over the threshold: the hot side is empty and the join
    degrades to the plain partitioned result (uniform tiny data,
    threshold far above any actual key frequency)."""
    eng = make_engine(tpch_tiny, multiway_join=False,
                      broadcast_join_threshold_rows=64,
                      skew_hot_key_threshold=256)
    sql = QUERIES["q03"]
    plan, _ = eng.plan_sql(sql, nshards=8)
    assert "hybrid" in [j.distribution
                        for j in _nodes(plan, N.Join)]
    got = eng.execute(sql, mesh=mesh)
    want = make_engine(tpch_tiny).execute(sql)
    assert got == want


def test_hybrid_all_keys_hot(tpch_zipf, mesh):
    """threshold=1 classifies every occupied sketch bucket hot: the
    cold tail is empty, every build row broadcasts, probe rows all
    stay local — still byte-identical."""
    eng = make_engine(tpch_zipf, multiway_join=False,
                      broadcast_join_threshold_rows=64,
                      skew_hot_key_threshold=1)
    sql = QUERIES["q03"]
    got = eng.execute(sql, mesh=mesh)
    want = make_engine(tpch_zipf).execute(sql)
    assert got == want


# -- salted exchanges --------------------------------------------------------


def _force_salt(plan, salt):
    """Rewrite every equi Join to a salted partitioned one (white-box:
    the decision is the cost model's; correctness of the salted
    exchange is what this exercises)."""
    def visit(node):
        if isinstance(node, N.Join) and node.criteria:
            return dataclasses.replace(
                node, distribution="partitioned", salt_factor=salt)
        return node

    return N.rewrite_bottom_up(plan, visit)


def test_salted_unique_join(tpch_zipf, mesh):
    """Forced salt on Q3's unique-build partitioned joins: probe rows
    spread over salt sub-buckets, build rows tile per salt, results
    unchanged."""
    eng = make_engine(tpch_zipf, multiway_join=False,
                      skew_hot_key_threshold=0)
    plan, _ = eng.plan_sql(QUERIES["q03"], nshards=8)
    t = execute_plan_distributed(eng, _force_salt(plan, 4), mesh)
    got = [tuple(r) for r in t.to_pylist()]
    want = make_engine(tpch_zipf).execute(QUERIES["q03"])
    assert got == want


@pytest.mark.slow  # ~40 s shard_map compile on the tier-1 container;
# the salted-unique test keeps the salt-correctness path in tier 1
def test_salted_expanding_join(mesh):
    """Salting an EXPANDING join: the salt criterion keeps the tiled
    build copies from double-matching (every (probe, build) pair must
    appear exactly once)."""
    mem = MemoryConnector()
    rng = np.random.default_rng(7)
    n = 4000
    # heavy-hitter key 0 on both sides; duplicates on the build side
    # make the join expanding
    fk = np.where(rng.random(n) < 0.5, 0,
                  rng.integers(0, 50, n)).astype(np.int64)
    dk = np.concatenate([np.zeros(40, np.int64),
                         rng.integers(0, 50, 200)])
    mem.create_table("f", {"k": T.BIGINT, "v": T.BIGINT},
                     {"k": fk, "v": np.arange(n) % 97},
                     {"k": None, "v": None})
    mem.create_table("d", {"dk": T.BIGINT, "w": T.BIGINT},
                     {"dk": dk, "w": np.arange(len(dk))},
                     {"dk": None, "w": None})
    eng = Engine()
    eng.register_catalog("mem", mem)
    eng.session.catalog = "mem"
    sql = ("select k, count(*) as c, sum(w) as s "
           "from f join d on f.k = d.dk group by k order by k")
    plan, _ = eng.plan_sql(sql, nshards=8)
    joins = _nodes(plan, N.Join)
    assert joins and not all(j.build_unique for j in joins)
    t = execute_plan_distributed(eng, _force_salt(plan, 4), mesh)
    got = [tuple(r) for r in t.to_pylist()]
    want = eng.execute(sql)
    assert got == want


def test_fragmenter_unfuses_large_builds(tpch_tiny):
    """The HTTP fragmenter keeps the fused MultiJoin only while every
    build is broadcast-sized; a build the cascade would FIXED_HASH
    co-partition forces the chain back into its binary form so it is
    never shipped whole to every worker."""
    from presto_tpu.parallel.fragmenter import fragment_plan_general

    plan, _ = make_engine(tpch_tiny).plan_sql(QUERIES["q05"])
    assert _nodes(plan, N.MultiJoin)
    fused = fragment_plan_general(plan, "automatic",
                                  broadcast_threshold=1 << 20)
    assert fused is not None
    assert any(_nodes(st.fragment, N.MultiJoin) for st in fused.stages)

    # a leg annotated partitioned (a large build at scale) must de-fuse
    def mark_partitioned(node):
        if isinstance(node, N.MultiJoin):
            return dataclasses.replace(
                node,
                distributions=["partitioned"]
                + list(node.distributions[1:]))
        return node

    cut = fragment_plan_general(
        N.rewrite_bottom_up(plan, mark_partitioned), "automatic",
        broadcast_threshold=1 << 20)
    assert cut is not None
    assert not any(_nodes(st.fragment, N.MultiJoin)
                   for st in cut.stages)
    assert any(_nodes(st.fragment, N.Join) for st in cut.stages)


def test_fused_plan_spills_under_memory_budget(tpch_tiny):
    """An over-budget fused star chain de-fuses back into the binary
    cascade and spills (exec/spill.py + plan/optimizer.unfuse_multijoin)
    instead of failing with 'no spillable join on its root chain'."""
    sql = ("select l_orderkey, l_extendedprice, n_name "
           "from lineitem "
           "join orders on l_orderkey = o_orderkey "
           "join customer on o_custkey = c_custkey "
           "join nation on c_nationkey = n_nationkey "
           "order by l_orderkey, l_extendedprice, n_name "
           "limit 500")
    eng = make_engine(tpch_tiny)
    plan, _ = eng.plan_sql(sql)
    assert _nodes(plan, N.MultiJoin)  # premise: the chain fused
    want = eng.execute(sql)
    budget = make_engine(tpch_tiny, query_max_memory_bytes=1 << 20)
    got = budget.execute(sql)
    assert got == want
    assert budget.last_spill is not None  # it really spilled


# -- the cost-side decision --------------------------------------------------


def test_decide_skew_units():
    from presto_tpu.cost.skew import (NO_SKEW, choose_salt_factor,
                                      decide_skew, estimate_hot_keys)
    from presto_tpu.cost.stats import PlanNodeStatsEstimate, SymbolStats

    # low-NDV key: the Zipf(1) worst-case top frequency clears both
    # the threshold and the per-shard fair share (the two hybrid
    # gates; a high-NDV key's worst-case top key cannot imbalance)
    probe = PlanNodeStatsEstimate(
        1 << 24, {"k": SymbolStats(ndv=1 << 10)})
    build = PlanNodeStatsEstimate(1 << 10,
                                  {"bk": SymbolStats(ndv=1 << 10)})
    crit = [("k", "bk")]
    d = decide_skew(probe, build, crit, True, True, nshards=8,
                    hot_threshold=1 << 12, max_salt=8)
    assert d.hybrid and d.hot_keys is not None
    assert d.hot_keys & (d.hot_keys - 1) == 0  # pow2-bucketed
    assert 1 <= d.salt_factor <= 8
    assert d.salt_factor & (d.salt_factor - 1) == 0

    # disabled thresholds / single shard -> no skew machinery
    assert decide_skew(probe, build, crit, True, True, 1,
                       1 << 12, 8) is NO_SKEW
    assert decide_skew(probe, build, crit, True, True, 8,
                       0, 0) is NO_SKEW
    # expanding builds never go hybrid (salting only)
    d2 = decide_skew(probe, build, crit, False, True, 8,
                     1 << 12, 8)
    assert not d2.hybrid

    assert estimate_hot_keys(0, 100, 1 << 12) == 0
    assert choose_salt_factor(1 << 20, 8, 10.0, 8) == 1  # no heavy key
    assert choose_salt_factor(1 << 20, 8, float(1 << 20), 8) == 8


# -- range-selectivity fix + divergence regression ---------------------------


def test_decimal_range_selectivity(tpch_tiny):
    """The l_quantity < 30 divergence PR 8's ledger exposed (est 1 row
    vs ~35% of the table — the un-scaled literal fell below the
    physical range): numeric comparisons now interpolate in the
    column's physical units."""
    from presto_tpu.cost.stats import StatsCalculator

    eng = make_engine(tpch_tiny)
    sql = "select count(*) from lineitem where l_quantity < 30"
    plan, _ = eng.plan_sql(sql)
    filt = _nodes(plan, N.Filter)[0]
    est = StatsCalculator(eng).stats(filt).row_count
    (actual,), = eng.execute(sql)
    assert actual > 0
    ratio = (est + 1) / (actual + 1)
    assert 1 / 3 <= ratio <= 3, (est, actual)


def test_divergence_ledger_ratio_drop(tpch_tiny):
    """system.plan_divergence regression: the Filter row for the
    decimal range predicate lands near ratio 1 instead of the former
    ~1/17000 (and the observed selectivity immediately seeds the next
    plan of the same shape)."""
    eng = make_engine(tpch_tiny)
    eng.execute("select count(*) from lineitem where l_quantity < 30")
    rows = eng.execute(
        "select node_type, est_rows, actual_rows, ratio "
        "from system.plan_divergence "
        "where node_type = 'Filter' and table_name like '%lineitem'")
    assert rows, "no Filter divergence rows recorded"
    node_type, est, actual, ratio = rows[-1]
    assert actual > 0 and est > 0
    assert 1 / 3 <= ratio <= 3, rows[-1]

    # a literal variant stays in the measured neighborhood (the fixed
    # range rule is literal-aware; the ledger's pooled feedback is
    # reserved for shapes static statistics cannot inform) — never
    # the old 1-row floor
    from presto_tpu.cost.stats import StatsCalculator
    plan, _ = eng.plan_sql(
        "select count(*) from lineitem where l_quantity < 47")
    filt = _nodes(plan, N.Filter)[0]
    est2 = StatsCalculator(eng).stats(filt).row_count
    assert est2 > 1000
