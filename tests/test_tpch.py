"""Full TPC-H suite (tiny scale) through the SQL frontend, cross-checked
against the sqlite oracle — the engine-level analog of the reference's
TpchQueryRunner + H2 assertQuery flow
(testing/trino-tests/.../tpch/TpchQueryRunner.java,
AbstractTestQueryFramework.assertQuery)."""

import pytest

from presto_tpu.testing.oracle import assert_query

from tpch_queries import QUERIES

# queries whose single-query compile+run exceeded ~10 s on the 2-vCPU
# tier-1 container (profiled 2026-08): they ride the `slow` (nightly)
# tier so the full tier-1 suite fits its 870 s budget. The remaining
# 20 TPC-H shapes keep the oracle sweep's coverage in tier 1.
SLOW = {"q19", "q21"}


@pytest.mark.parametrize("qname", [
    pytest.param(q, marks=pytest.mark.slow) if q in SLOW else q
    for q in sorted(QUERIES)])
def test_tpch_query(qname, engine, oracle):
    assert_query(engine, oracle, QUERIES[qname])


def test_connector_holds_only_the_tables_it_is_given():
    """``tables``: the deployment's tables; a statement over another is
    one over an unknown table, and a name TPC-H does not have is refused
    when the connector is built."""
    from presto_tpu import Engine
    from presto_tpu.connectors import TpchConnector
    conn = TpchConnector(scale=0.01, tables=["region", "nation"])
    assert sorted(conn.table_names()) == ["nation", "region"]
    e = Engine()
    e.register_catalog("tpch", conn)
    assert e.execute("SELECT count(*) FROM nation, region "
                     "WHERE n_regionkey = r_regionkey") == [(25,)]
    with pytest.raises(Exception, match="(?i)lineitem"):
        e.execute("SELECT count(*) FROM lineitem")
    assert len(TpchConnector(scale=0.01).table_names()) == 8
    with pytest.raises(ValueError, match="lineitems"):
        TpchConnector(scale=0.01, tables=["lineitems"])
