"""The cell ``tpch_sf10_alltables.joins5``: its two NumPy references
held to the sqlite oracle at SF 0.01 (sqlite shares no code with them
and computes in floating point, hence the relative 1e-9), and the cell
rehearsed on the CPU at a toy scale factor in a temporary copy."""

import json

import pytest
import refdata
import traffic
import verify
from conftest import ROOT
from test_rehearsal import copy_of_the_benchmark, rehearse

CELL = "tpch_sf10_alltables.joins5"
# what a CPU rehearsal cannot read: the device's trace and its memory
NEEDS_DEVICE = {
    "compile.traced_compile_share", "device.busy_ms_per_query",
    "device.peak_bytes", "q05_roofline", "q09_roofline", "q05_join_ms",
    "q09_join_ms", "q05_aggregate_ms", "q09_aggregate_ms",
    "q05_top_build_ms", "q09_top_build_ms"}


@pytest.fixture(scope="module")
def conn():
    from presto_tpu.connectors.tpch import TpchConnector
    return TpchConnector(scale=0.01, seed=19920101)


@pytest.fixture(scope="module")
def oracle(conn):
    from presto_tpu.testing.oracle import SqliteOracle
    o = SqliteOracle()
    o.load_connector(conn)
    o.conn.execute("PRAGMA case_sensitive_like = ON")
    return o


@pytest.mark.parametrize("cls_name,points", [
    ("q05", (2, 8, 14, 20)), ("q09", (0, 33, 58, 91))])
def test_reference_equals_sqlite(conn, oracle, cls_name, points):
    from presto_tpu.sql.parser import parse_statement
    from presto_tpu.sql.sqlite_dialect import to_sqlite
    cls = traffic.load_class(cls_name)
    answer = verify.load_reference(cls_name)
    data = refdata.Columns(conn)
    for point in points:
        params = traffic.params_at(cls, point, 0)
        want = oracle.query(to_sqlite(parse_statement(
            traffic.statement(cls, params))))
        got = answer(data, params)
        assert got and len(got) == len(want), params
        for g, w in zip(got, want):
            assert g[:-1] == list(w[:-1]), params
            assert float(g[-1]) == pytest.approx(float(w[-1]), rel=1e-9)
            assert len(g[-1].split(".")[1]) == 4  # a decimal at scale 4


def test_the_domains_are_the_specifications():
    q05, q09 = traffic.load_class("q05"), traffic.load_class("q09")
    assert traffic.domain_size(q05) == 25
    assert traffic.domain_size(q09) == 92
    from presto_tpu.connectors.tpch import COLORS
    assert [c["COLOR"] for c in q09["axes"][0]] == list(COLORS)


@pytest.fixture(scope="module")
def toy(tmp_path_factory):
    """A copy in which the cell has a twin at SF 0.02."""
    root = tmp_path_factory.mktemp("joins5")
    manifest = copy_of_the_benchmark(root)
    (entry,) = [c for c in manifest["configs"]
                if c["name"] == "tpch_sf10_alltables"]
    body = json.loads((ROOT / entry["file"]).read_text())
    body["scale_factor"] = 0.02
    (root / "benchmark" / "configs" / "toy_alltables.json").write_text(
        json.dumps(body))
    manifest["configs"].append({
        **entry, "name": "toy_alltables",
        "file": "benchmark/configs/toy_alltables.json"})
    (cell,) = [w for w in manifest["workloads"] if w["name"] == CELL]
    manifest["workloads"].append({
        **cell, "name": "toy_alltables.joins5", "config": "toy_alltables"})
    for m in manifest["per_layer"]:
        if CELL in m.get("workloads", []):
            m["workloads"].append("toy_alltables.joins5")
    (root / "BENCHMARK.json").write_text(json.dumps(manifest))
    return root


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_of_the_cell(toy, trace):
    out = rehearse(toy, "toy_alltables.joins5", trace)
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] >= 4  # two whole rounds of Q5, Q9
    manifest = json.loads((toy / "BENCHMARK.json").read_text())
    kind = "per_layer" if trace else "end_to_end"
    declared = {m["name"] for m in manifest[kind]
                if "toy_alltables.joins5" in m.get(
                    "workloads", ["toy_alltables.joins5"])}
    if not trace:
        assert set(out["metrics"]) == declared == {
            "setup_s", "geomean_ms", "qph"}
        return
    assert declared - set(out["metrics"]) == NEEDS_DEVICE
    assert out["metrics"]["compile.window_compiles"]["value"] == 0
    assert out["metrics"]["plan.dict_mask_ms"]["value"] > 0


class ConnectorBefore:
    """``TpchConnector``'s signature at the parent of PR 27."""

    def __init__(self, scale=0.01, seed=19920101, skew=None):
        pass


def test_the_configuration_names_its_tables_to_the_connector():
    """The eight tables reach ``TpchConnector`` as ``tables``: a program
    whose connector does not take the key (the parent of PR 27, which
    cannot serve Q9's colors either) ends in ``run.make_catalogs``,
    before any data is made, not after a window of timeouts."""
    import run
    config = traffic.load_config("tpch_sf10_alltables")
    read = {t for c in ("q05", "q09") for t in traffic.load_class(c)["reads"]}
    assert set(config["tables"]) == read and len(read) == 8
    conn = run.make_catalogs(config, 7)["tpch"]
    assert sorted(conn.table_names()) == sorted(read)
    before = {**config, "catalogs": {"tpch": {
        **config["catalogs"]["tpch"],
        "connector": f"{__name__}:ConnectorBefore"}}}
    with pytest.raises(TypeError, match="tables"):
        run.make_catalogs(before, 7)
