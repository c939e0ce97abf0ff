"""Each mix once on the CPU at SF 0.01 (through tiny twins of the
configurations in a temporary copy), and the benchmark driven by data: a
configuration, a mix, a class and a per-layer metric added as files."""

import json
import os
import shutil
import subprocess
import sys

import pytest
from conftest import BENCH, ROOT

DEVICE_METRICS = ("device.busy_ms_per_query", "device.peak_bytes",
                  "q01_roofline", "q06_roofline", "q03_roofline")


def copy_of_the_benchmark(root):
    shutil.copytree(BENCH, root / "benchmark",
                    ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    os.symlink(ROOT / "presto_tpu", root / "presto_tpu")
    return json.loads((ROOT / "BENCHMARK.json").read_text())


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    """A copy in which every configuration has a twin at SF 0.01
    (``tiny_sf1`` for ``tpch_sf1``) and every cell a twin under it, with
    the metrics the cell has."""
    root = tmp_path_factory.mktemp("tiny")
    manifest = copy_of_the_benchmark(root)
    for c in list(manifest["configs"]):
        body = json.loads((ROOT / c["file"]).read_text())
        body["scale_factor"] = 0.01
        name = c["name"].replace("tpch", "tiny")
        (root / "benchmark" / "configs" / f"{name}.json").write_text(
            json.dumps(body))
        manifest["configs"].append({
            **c, "name": name, "file": f"benchmark/configs/{name}.json"})
    for w in list(manifest["workloads"]):
        manifest["workloads"].append({
            **w, "name": w["name"].replace("tpch", "tiny"),
            "config": w["config"].replace("tpch", "tiny")})
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        if "workloads" in m:
            m["workloads"] += [w.replace("tpch", "tiny")
                               for w in m["workloads"]]
    (root / "BENCHMARK.json").write_text(json.dumps(manifest))
    return root


def rehearse(root, workload, trace):
    env = {**os.environ, "BENCH_ALLOW_CPU": "1", "JAX_PLATFORMS": "cpu"}
    proc = subprocess.run(
        [sys.executable, str(root / "benchmark" / "run.py"),
         "--workload", workload, "--seed", "7", "--seconds", "2",
         "--trace", str(trace)],
        cwd=root, env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-2000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("workload,trace", [
    ("tiny_sf10.scan", 0), ("tiny_sf10.join", 1),
    ("tiny_sf1.power", 1), ("tiny_sf1.dash", 0), ("tiny_sf1.dash", 1)])
def test_rehearsal_of_each_mix(tiny, workload, trace):
    out = rehearse(tiny, workload, trace)
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] >= 3
    assert out["device"]["platform"] == "cpu"
    assert "busy_s" not in out["device"] and "breakdown" not in out
    manifest = json.loads((tiny / "BENCHMARK.json").read_text())
    kind = "per_layer" if trace else "end_to_end"
    declared = {m["name"]: m for m in manifest[kind]}
    assert out["metrics"] and set(out["metrics"]) <= set(declared)
    for name, m in out["metrics"].items():
        assert m["unit"] == declared[name]["unit"]
        assert isinstance(m["value"], (int, float))
    assert not set(out["metrics"]) & set(DEVICE_METRICS)
    if not trace:
        assert {"setup_s", "geomean_ms", "qph"} <= set(out["metrics"])
    else:  # one reader serves every class.<class>_ms (a class with a
        # rate of its own, 0.08/s, has no statement in a 2 s window)
        classes = {c["name"] for c in json.loads(
            (BENCH / "traffic" / f"{workload.split('.')[1]}.json")
            .read_text())["classes"] if "rate" not in c}
        assert {f"class.{c}_ms" for c in classes} <= set(out["metrics"])


def test_no_cpu_fallback():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("BENCH_ALLOW_CPU", None)
    proc = subprocess.run(
        [sys.executable, str(BENCH / "run.py"), "--workload",
         "tpch_sf1.power", "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0 and proc.stdout.strip() == ""


def test_nothing_runs_without_the_program(tmp_path):
    shutil.copytree(BENCH, tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    proc = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload",
         "tpch_sf1.power", "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=dict(os.environ, BENCH_ALLOW_CPU="1"),
        capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0 and proc.stdout.strip() == ""


def test_a_later_pr_adds_files_and_entries_only(tmp_path):
    """One new configuration (another scale, a skewed generator: an
    argument of a catalog, and a session property), mix, class (with
    reference) and per-layer metric, each a file of its own plus one
    entry of BENCHMARK.json; no file that was there is edited, and the
    new cell runs, with the per-class metrics of its new class."""
    manifest = copy_of_the_benchmark(tmp_path)
    bench = tmp_path / "benchmark"
    before = {p: p.read_bytes() for p in bench.rglob("*") if p.is_file()}

    tiny = json.loads((bench / "configs" / "tpch_sf1.json").read_text())
    tiny["scale_factor"] = 0.01
    tiny["catalogs"]["tpch"]["args"]["skew"] = "$skew"
    tiny["skew"] = "zipf:1.3"
    tiny["session"] = {"result_cache": "false"}
    (bench / "configs" / "tpch_tiny.json").write_text(json.dumps(tiny))
    (bench / "traffic" / "count.json").write_text(json.dumps({
        "loop": "closed", "clients": 1, "session": {},
        "classes": [{"name": "cnt"}, {"name": "q06"}],
        "draw": {"kind": "uniform"}, "min_per_class": 3,
        "trace": {"start_s": 0.0, "seconds": 1.0}}))
    (bench / "queries" / "cnt.sql").write_text(
        "select count(*) from orders where o_orderdate < date '{DATE}'")
    (bench / "queries" / "cnt.json").write_text(json.dumps({
        "ordered": True, "reads": {"orders": ["o_orderdate"]},
        "axes": [[{"DATE": f"1995-0{m}-01"} for m in range(1, 10)]]}))
    (bench / "reference" / "cnt.py").write_text(
        "from reference.common import days\n"
        "def answer(data, params, state=None):\n"
        "    d = data.col('orders', 'o_orderdate')\n"
        "    return [[int((d < days(params['DATE'])).sum())]]\n")
    (bench / "layers" / "extra.cnt_statements.py").write_text(
        "def read(ctx):\n"
        "    return sum(r['cls'] == 'cnt' for r in ctx.records)\n")
    # a reader that fails, and one with no finite number, cost their own
    # metric and not the run's line
    (bench / "layers" / "extra.broken.py").write_text(
        "def read(ctx):\n    return ctx.records[0]['no such key']\n")
    (bench / "layers" / "extra.nan.py").write_text(
        "def read(ctx):\n    return float('nan')\n")

    manifest["configs"].append({
        "name": "tpch_tiny", "source": tiny["source"],
        "file": "benchmark/configs/tpch_tiny.json",
        "reduced": tiny["reduced"], "why": "test"})
    manifest["workloads"].append({
        "name": "tpch_tiny.count", "config": "tpch_tiny",
        "traffic": "count", "chips": 1, "why": "test"})
    manifest["per_layer"].append({
        "name": "extra.cnt_statements", "unit": "count", "better": "higher",
        "source": "program_counter", "layer": "test",
        "moves": "geomean_ms", "workloads": ["tpch_tiny.count"]})
    for name in ("extra.broken", "extra.nan"):
        manifest["per_layer"].append({
            "name": name, "unit": "count", "better": "higher",
            "source": "program_counter", "layer": "test",
            "moves": "geomean_ms", "workloads": ["tpch_tiny.count"]})
    manifest["per_layer"].append({  # no new reader: class.CLASS_ms.py
        "name": "class.cnt_ms", "unit": "ms", "better": "lower",
        "source": "host_clock", "layer": "whole statement",
        "moves": "geomean_ms", "workloads": ["tpch_tiny.count"]})
    for m in manifest["per_layer"]:  # an entry that is there gains a cell
        if m["name"] == "server.cache_hit_share":
            m["workloads"].append("tpch_tiny.count")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(manifest))

    e2e = rehearse(tmp_path, "tpch_tiny.count", 0)
    assert e2e["correct"] and "geomean_ms" in e2e["metrics"]
    layers = rehearse(tmp_path, "tpch_tiny.count", 1)
    assert layers["correct"]
    assert layers["metrics"]["extra.cnt_statements"]["value"] >= 3
    assert layers["metrics"]["class.cnt_ms"]["value"] > 0
    assert "class.q06_ms" not in layers["metrics"]  # not listed for the cell
    assert not {"extra.broken", "extra.nan"} & set(layers["metrics"])
    # the session property came from the configuration: no cache lookups
    assert "server.cache_hit_share" not in layers["metrics"]
    after = {p: p.read_bytes() for p in before}
    assert after == before


@pytest.mark.parametrize("kind,name,edit,message", [
    ("traffic", "power", {"order": "random"}, "order"),
    ("configs", "tpch_sf1", {"replicas": 3}, "replicas"),
    ("configs", "tpch_sf1", {"mesh": [2, 2]}, "mesh"),
    ("configs", "tpch_sf1", {"chips": 4}, "chip"),
    ("configs", "tpch_sf1", {"compile_cache_in_window": "maybe"},
     "compile_cache_in_window")])
def test_a_key_nothing_reads_or_a_value_nothing_honours_is_refused(
        tmp_path, kind, name, edit, message):
    copy_of_the_benchmark(tmp_path)
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    path = tmp_path / "benchmark" / kind / f"{name}.json"
    path.write_text(json.dumps({**json.loads(path.read_text()), **edit}))
    proc = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload",
         "tpch_sf1.power", "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env={**os.environ, "BENCH_ALLOW_CPU": "1",
                           "JAX_PLATFORMS": "cpu"},
        capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0 and proc.stdout.strip() == ""
    assert message in proc.stderr
