"""A LIKE pattern over a scanned dictionary column binds, it does not
compile: the second pattern over the same column is a template hit with
no program compiled, and both answers equal the sqlite oracle."""

from __future__ import annotations

import pytest

from presto_tpu import Engine
from presto_tpu.connectors.memory import MemoryConnector
from presto_tpu.obs.metrics import REGISTRY
from presto_tpu.obs.trace import TRACER
from presto_tpu.templates.analysis import LikePattern, parameterize
from presto_tpu.testing.oracle import SqliteOracle, rows_equal

_COMPILED = REGISTRY.counter("presto_tpu_programs_compiled_total")
_TPL_HITS = REGISTRY.counter("presto_tpu_template_cache_hits_total")

PART = "select count(*), sum(p_partkey) from part where p_name {}"
CUST = "select count(*), sum(c_custkey) from customer where c_name {}"
# name -> (text with {} for the predicate, first predicate, second)
CASES = {
    "contains": (PART, "like '%green%'", "like '%red%'"),
    "prefix": (PART, "like 'green%'", "like 'blue%'"),
    "suffix": (PART, "like '%green'", "like '%almond'"),
    "underscore": (PART, "like '_reen%'", "like '_lue%'"),
    "escape": (CUST, "like 'Customer##0000000_1' escape '#'",
               "like 'Customer##0000001_%' escape '#'"),
    "matches_nothing": (PART, "like '%green%'", "like '%no such word%'"),
    "not_like": (PART, "not like '%green%'", "not like '%red%'"),
    "nulls": ("select count(*), sum(k) from memory.default.pn "
              "where name {}",
              "like '%green%'", "like '%e%'"),
}


@pytest.fixture(scope="module")
def oracle(oracle):
    # sqlite's LIKE folds ASCII case by default; SQL's does not
    oracle.conn.execute("PRAGMA case_sensitive_like = ON")
    return oracle


@pytest.fixture(scope="module")
def with_nulls(tpch_tiny):
    """``pn``: part's keys and names in a memory table, every third name
    NULL; the engine that made it and an oracle that holds it."""
    mem = MemoryConnector()
    e = Engine()
    e.register_catalog("tpch", tpch_tiny)
    e.register_catalog("memory", mem)
    e.execute("create table memory.default.pn as select p_partkey as k, "
              "case when p_partkey % 3 <> 0 then p_name end as name "
              "from part")
    o = SqliteOracle()
    o.load_connector(mem)
    o.conn.execute("PRAGMA case_sensitive_like = ON")
    return mem, o


@pytest.mark.parametrize("case", sorted(CASES))
def test_second_pattern_is_a_template_hit(tpch_tiny, oracle, with_nulls,
                                          case):
    text, first, second = CASES[case]
    e = Engine()
    e.register_catalog("tpch", tpch_tiny)
    want_from = oracle
    if case == "nulls":
        mem, want_from = with_nulls
        e.register_catalog("memory", mem)
    got_first = e.execute(text.format(first))
    c0, h0 = _COMPILED.value(), _TPL_HITS.value()
    got_second = e.execute(text.format(second))
    assert _COMPILED.value() == c0, f"{second!r} compiled a program"
    assert _TPL_HITS.value() == h0 + 1
    for pred, got in ((first, got_first), (second, got_second)):
        ok, msg = rows_equal(got, want_from.query(
            text.format(pred).replace("memory.default.", "")), True)
        assert ok, f"{pred}: {msg}"
    if case == "matches_nothing":
        assert got_second[0][0] == 0
    if case == "nulls":  # NULL names satisfy neither LIKE nor NOT LIKE
        total = e.execute(
            "select count(*) from memory.default.pn")[0][0]
        nulls = e.execute("select count(*) from memory.default.pn "
                          "where name is null")[0][0]
        without = e.execute(text.format("not like '%e%'"))[0][0]
        assert nulls > 0 and got_second[0][0] + without == total - nulls


def test_the_pattern_leaves_the_fingerprint_and_binds_under_a_span(
        tpch_tiny):
    e = Engine()
    e.register_catalog("tpch", tpch_tiny)
    t1, t2 = (parameterize(e.plan_sql(PART.format(p))[0])
              for p in ("like '%green%'", "like 'a_b%' escape '!'"))
    assert t1.fingerprint() == t2.fingerprint()
    (v1,), (v2,) = ([s.value for s in t.params] for t in (t1, t2))
    assert isinstance(v1, LikePattern) and v1.pattern == "%green%"
    assert (v2.pattern, v2.escape) == ("a_b%", "!")
    with TRACER.trace("like-binds-test", "query"):
        e.execute(PART.format("like '%green%'"))
    masks = [s for s in TRACER.spans("like-binds-test")
             if s.name == "dict-mask"]
    names = tpch_tiny.table("part").columns["p_name"].dictionary
    assert [s.attrs["entries"] for s in masks] == [len(names)]
    assert masks[0].attrs["matched"] == sum("green" in n for n in names)
    # the mask's length is a power of two, so the program's shape does
    # not follow the exact count of distinct names a seed happens to give
    from presto_tpu.templates.runtime import mask_length
    assert (mask_length(range(1_999_647)) == mask_length(range(2_000_000))
            == 1 << 21)
