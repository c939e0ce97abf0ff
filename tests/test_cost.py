"""Cost-based join ordering: the planner must pick candidate joins by
estimated OUTPUT rows (unique-build containment vs ndv-based expansion),
not build-side size alone — the ReorderJoins/JoinStatsRule analog
(reference sql/planner/iterative/rule/ReorderJoins.java,
cost/JoinStatsRule.java)."""

from presto_tpu import Engine
from presto_tpu.plan import nodes as N
from tests.tpch_queries import QUERIES


def _joins(plan):
    out = []

    def visit(n):
        if isinstance(n, N.Join):
            out.append(n)
        for s in n.sources():
            visit(s)

    visit(plan)
    return out


def _join_legs(plan):
    """(criteria, build_unique) per join leg, counting a fused
    MultiJoin's builds individually (every absorbed leg is unique-build
    by the collapse rule's construction)."""
    out = []

    def visit(n):
        if isinstance(n, N.Join):
            out.append((list(n.criteria), n.build_unique))
        elif isinstance(n, N.MultiJoin):
            out.extend((list(c), True) for c in n.criteria)
        for s in n.sources():
            visit(s)

    visit(plan)
    return out


def test_q5_avoids_nationkey_expansion(tpch_tiny):
    """Q5's customer leg must join through c_custkey (unique) — joining
    it early through c_nationkey = s_nationkey alone is a many-to-many
    explosion (rows x customers-per-nation). Holds for the fused
    MultiJoin form the default plan now takes AND for the binary
    cascade."""
    eng = Engine()
    eng.register_catalog("tpch", tpch_tiny)
    plan, _ = eng.plan_sql(QUERIES["q05"])
    legs = _join_legs(plan)
    assert len(legs) == 5
    assert all(u for _c, u in legs), legs
    cust = [c for c, _u in legs
            if any("c_custkey" in b for _a, b in c)]
    assert cust, legs  # customer joined through its unique key

    eng.session.set("multiway_join", False)
    plan2, _ = eng.plan_sql(QUERIES["q05"])
    joins = _joins(plan2)
    assert len(joins) == 5
    assert all(j.build_unique for j in joins), [
        (j.criteria, j.build_unique) for j in joins]


def test_q9_all_joins_unique_build(tpch_tiny):
    eng = Engine()
    eng.register_catalog("tpch", tpch_tiny)
    plan, _ = eng.plan_sql(QUERIES["q09"])
    legs = _join_legs(plan)
    assert legs and all(u for _c, u in legs)


def test_flipped_stats_change_join_order():
    """The ordering is driven by stats, not table names: shrinking one
    side's row counts flips which leg becomes the fact table (priced
    for an 8-shard mesh, where the big side must not replicate)."""
    import numpy as np
    from presto_tpu.connectors.memory import MemoryConnector
    from presto_tpu import types as T

    def build(big_left: bool):
        eng = Engine()
        mem = MemoryConnector()
        n_a, n_b = (100000, 50) if big_left else (50, 100000)
        mem.create_table("a", {"a_id": T.BIGINT, "a_x": T.BIGINT},
                         {"a_id": np.arange(n_a), "a_x": np.arange(n_a)},
                         {"a_id": None, "a_x": None})
        mem.create_table("b", {"b_id": T.BIGINT, "b_y": T.BIGINT},
                         {"b_id": np.arange(n_b), "b_y": np.arange(n_b)},
                         {"b_id": None, "b_y": None})
        eng.register_catalog("mem", mem)
        eng.session.catalog = "mem"
        plan, _ = eng.plan_sql(
            "select count(*) from a, b where a_id = b_id", nshards=8)
        return _joins(plan)[0]

    j_big_left = build(True)
    j_big_right = build(False)
    # the probe (left) side of the produced Join is always the larger
    # leg; flipping the stats flips the plan
    left_syms_1 = set(j_big_left.left.output_types())
    left_syms_2 = set(j_big_right.left.output_types())
    assert any(s.startswith("a_") for s in left_syms_1)
    assert any(s.startswith("b_") for s in left_syms_2)
