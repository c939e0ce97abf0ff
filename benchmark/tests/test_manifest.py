"""BENCHMARK.json names only files that exist and only legal names."""

import json
import re

import pytest
import traffic
from conftest import BENCH, ROOT

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def manifest():
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def test_keys_and_limits(manifest):
    assert set(manifest) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert manifest["paths"] == ["benchmark"]
    assert 1 <= manifest["run_seconds"] <= 51
    assert (ROOT / manifest["command"][1]).is_file()
    assert len((ROOT / "BENCHMARK.json").read_bytes()) <= 64 * 1024


def test_names_units_and_sources(manifest):
    metrics = manifest["end_to_end"] + manifest["per_layer"]
    names = [m["name"] for m in metrics]
    assert len(names) == len(set(names))
    cells = {w["name"] for w in manifest["workloads"]}
    e2e = {m["name"] for m in manifest["end_to_end"]}
    for m in metrics:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
        assert set(m.get("workloads", cells)) <= cells
    for m in manifest["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    assert "setup_s" in e2e
    for m in manifest["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["moves"] in e2e
        assert 1 <= len(m["layer"]) <= 200 and "\n" not in m["layer"]
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"


def test_every_file_a_cell_needs_exists(manifest):
    configs = {c["name"]: c for c in manifest["configs"]}
    used = set()
    for w in manifest["workloads"]:
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
        used.add(w["config"])
        mix = traffic.load_mix(w["traffic"])  # refuses an unread key
        assert mix["loop"] in ("closed", "open")
        for c in mix["classes"]:
            for path in (f"queries/{c['name']}.sql",
                         f"queries/{c['name']}.json",
                         f"reference/{c['name']}.py"):
                assert (BENCH / path).is_file(), path
    assert used == set(configs)
    for c in configs.values():
        body = traffic.load_config(c["name"])
        assert c["file"] == f"benchmark/configs/{c['name']}.json"
        assert body["source"] == c["source"] and len(c["source"]) <= 200
        assert body["reduced"] == c["reduced"]
        assert body["chips"] == 1
    pairs = [(w["config"], w["traffic"]) for w in manifest["workloads"]]
    assert len(pairs) == len(set(pairs))
    assert sum(w["chips"] == 4 for w in manifest["workloads"]) == 0


def test_every_per_layer_metric_has_a_reader(manifest):
    """A file of the metric's name, or the one that has CLASS where the
    name has a query class; and no reader is orphaned."""
    classes = {p.stem for p in (BENCH / "queries").glob("*.sql")}
    used = set()
    for m in manifest["per_layer"]:
        names = [m["name"]] + [m["name"].replace(c, "CLASS")
                               for c in classes if c in m["name"]]
        found = [n for n in names
                 if (BENCH / "layers" / f"{n}.py").is_file()]
        assert found, m["name"]
        used.add(found[0])
    assert {p.name[:-3] for p in (BENCH / "layers").glob("*.py")} == used


def test_peaks_table():
    peaks = json.loads((BENCH / "peaks.json").read_text())
    v5e = peaks["TPU v5 lite"]
    assert v5e["flops_per_s_bf16"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    assert v5e["source"]
