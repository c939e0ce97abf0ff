"""TPC-H generator sanity: shapes, FK integrity, distributions, oracle load."""

import numpy as np
import pytest

from presto_tpu import types as T
from presto_tpu.connectors.tpch import SCHEMAS, TpchConnector, _ps_suppkey


def test_table_shapes(tpch_tiny):
    gen = tpch_tiny.gen
    assert tpch_tiny.stats("region").row_count == 5
    assert tpch_tiny.stats("nation").row_count == 25
    assert tpch_tiny.stats("supplier").row_count == gen.n_supplier
    assert tpch_tiny.stats("part").row_count == gen.n_part
    assert tpch_tiny.stats("partsupp").row_count == gen.n_part * 4
    assert tpch_tiny.stats("orders").row_count == gen.n_orders
    li = tpch_tiny.stats("lineitem").row_count
    assert gen.n_orders <= li <= 7 * gen.n_orders


def test_fk_integrity(tpch_tiny):
    raw = tpch_tiny._raw
    gen = tpch_tiny.gen
    assert raw("orders")["o_custkey"].min() >= 1
    assert raw("orders")["o_custkey"].max() <= gen.n_customer
    assert (raw("orders")["o_custkey"] % 3 != 0).all()
    assert raw("lineitem")["l_partkey"].max() <= gen.n_part
    assert raw("lineitem")["l_suppkey"].max() <= gen.n_supplier
    # l_suppkey must be one of the 4 partsupp suppliers for that part (Q9 join)
    lpk = raw("lineitem")["l_partkey"][:1000]
    lsk = raw("lineitem")["l_suppkey"][:1000]
    candidates = np.stack(
        [_ps_suppkey(lpk, np.full(len(lpk), i), gen.n_supplier)
         for i in range(4)])
    assert (candidates == lsk).any(axis=0).all()


def _strings(col) -> np.ndarray:
    """Raw column -> unicode values (generators may emit pre-encoded
    EncodedStrings)."""
    if hasattr(col, "decode"):
        return col.decode().astype("U")
    return col.astype("U")


def test_distributions(tpch_tiny):
    raw = tpch_tiny._raw
    disc = raw("lineitem")["l_discount"]
    assert disc.min() >= 0 and disc.max() <= 10
    qty = raw("lineitem")["l_quantity"]
    assert qty.min() >= 100 and qty.max() <= 5000  # scaled by 100
    flags = set(np.unique(_strings(raw("lineitem")["l_returnflag"])))
    assert flags == {"R", "A", "N"}
    assert set(np.unique(_strings(raw("orders")["o_orderstatus"]))) <= {
        "O", "F", "P"}


def test_deterministic():
    a = TpchConnector(scale=0.01)._raw("lineitem")
    b = TpchConnector(scale=0.01)._raw("lineitem")
    assert (a["l_extendedprice"] == b["l_extendedprice"]).all()


def test_dictionary_sorted(tpch_tiny):
    col = tpch_tiny.table("lineitem").columns["l_shipmode"]
    d = col.dictionary
    assert list(d) == sorted(d)
    # codes decode back to original values
    raw = tpch_tiny._raw("lineitem")["l_shipmode"]
    assert (d[np.asarray(col.data)] == _strings(raw)).all()


def test_oracle_loads(oracle, tpch_tiny):
    n = oracle.query("SELECT count(*) FROM lineitem")[0][0]
    assert n == tpch_tiny.stats("lineitem").row_count
    rows = oracle.query(
        "SELECT l_shipdate FROM lineitem ORDER BY l_shipdate LIMIT 1")
    assert rows[0][0] >= "1992-01-01"


def test_decimal_decode(tpch_tiny):
    t = tpch_tiny.table("lineitem").select(["l_discount"])
    sub = t.to_pylist()[:100]
    for (d,) in sub:
        assert 0.0 <= d <= 0.10


def test_schemas_cover_all_tables():
    assert set(SCHEMAS) == {
        "region", "nation", "supplier", "part", "partsupp",
        "customer", "orders", "lineitem"}


def _digest(col) -> str:
    """SHA-256 of a raw column: dtype, shape and bytes (a dictionary
    column's codes, then its dictionary)."""
    import hashlib
    h = hashlib.sha256()
    parts = ([col] if isinstance(col, np.ndarray)
             else [col.codes, np.asarray([str(s) for s in col.dictionary])])
    for a in parts:
        if a.dtype == object:
            a = np.asarray([str(s) for s in a])
        h.update(f"{a.dtype.str}{a.shape}".encode())
        h.update(np.ascontiguousarray(a).tobytes())
    return h.hexdigest()


@pytest.mark.parametrize("block", [None, 4096])
@pytest.mark.parametrize("orders", [True, False])
@pytest.mark.parametrize("seed", [19920101, 2147485301])
@pytest.mark.parametrize("skew", [None, "zipf:1.1"])
def test_orders_and_lineitem_bytes_are_pinned(skew, seed, orders, block,
                                              monkeypatch):
    """Every cell's data comes from ``orders_and_lineitem``, and the
    references read the same data, so ``correct`` cannot see a changed
    distribution: each column is held to the SHA-256 recorded from the
    generator of commit 3abae69 (before it computed columns in blocks
    on threads), whole and in blocks of 4,096 rows, with ``orders`` and
    as a deployment that holds ``lineitem`` alone."""
    import json
    from pathlib import Path
    from presto_tpu.connectors import tpch
    want = json.loads((Path(__file__).parent
                       / "tpch_datagen_digests.json").read_text())
    if block:
        monkeypatch.setattr(tpch, "_GEN_BLOCK", block)
    conn = TpchConnector(scale=0.01, seed=seed, skew=skew,
                         tables=None if orders else ["lineitem"])
    for table in ("orders", "lineitem") if orders else ("lineitem",):
        got = {name: _digest(col) for name, col in conn._raw(table).items()}
        assert got == want[f"{skew or 'uniform'}/{seed}/{table}"], table
    if not orders:
        assert "orders" not in conn._cache
