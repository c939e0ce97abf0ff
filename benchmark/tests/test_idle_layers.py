"""The per-layer readers of PR 25 on hand-made contexts, ``opnames`` on
a small trace recorded on the v5e, and the CPU rehearsal's traced line."""

import gzip
import json
import os
import shutil
import subprocess
import sys
import types

import opnames
import pytest
import verify
from conftest import BENCH
from test_rehearsal import copy_of_the_benchmark

LAYERS = BENCH / "layers"
MICRO = BENCH / "tests" / "data" / "micro_scopes.xplane.pb.gz"
MICRO_EXPECTED = BENCH / "tests" / "data" / "micro_scopes.expected.json"


def reader(name):
    return verify.load_attr(LAYERS / f"{name}.py", "read")


def span(name, t0, t1, sid=None, parent="root", **attrs):
    return {"name": name, "id": sid or f"{name}@{t0}", "parent": parent,
            "t0": t0, "t1": t1, "attrs": attrs}


def statement(qid, cls, sent, done):
    return {"qid": qid, "cls": cls, "due": sent, "sent": sent, "done": done,
            "rows": []}


def context(records, spans, counters=None, classes=None, loop="closed"):
    classes = classes or {r["cls"]: {} for r in records}
    return types.SimpleNamespace(
        records=records, spans=spans, counters=counters or {},
        classes=classes, mix={"loop": loop}, trace=None, ramp=[], t0=0.0,
        cell={"name": "none"})


def root(t0, t1):
    return span("query", t0, t1, sid="root", parent=None)


# Two classes. a: statements of 1.0 s and 3.0 s; b: one of 2.0 s.
RECORDS = [statement("a1", "a", 0.0, 1.0), statement("a2", "a", 10.0, 13.0),
           statement("b1", "b", 20.0, 22.0),
           statement("hit", "a", 30.0, 30.002)]  # a fast hit: no trace
SPANS = {
    "a1": [root(0.0, 1.0), span("admission", -0.010, 0.0),
           span("query", 0.0, 1.0, parent="root", sid="inner"),
           span("plan", 0.0, 0.1, parent="inner"),
           span("block-input", 0.1, 0.2, block=0),
           span("block-input", 0.5, 0.6, block=1),
           span("transfer", 0.2, 0.25, block=0),
           span("compile", 0.25, 0.45), span("segment", 0.45, 0.5),
           span("execute", 0.6, 0.9, sid="ex"),
           span("sync/ok-ladder", 0.7, 0.9, parent="ex"),
           span("sync/result-demux", 0.9, 0.95),
           span("encode", 0.95, 0.97), span("page-wait", 0.97, 1.0)],
    "a2": [root(10.0, 13.0), span("admission", 9.970, 10.0),
           span("block-input", 10.0, 10.4), span("transfer", 10.4, 10.5),
           span("compile", 10.5, 11.1),
           span("sync/ok-ladder", 11.1, 11.7),
           span("encode", 12.0, 12.05),
           span("scan-collect", 12.1, 12.2), span("bucket-pad", 12.2, 12.5),
           span("pin", 12.5, 12.6), span("dedup-wait", 12.6, 12.9)],
    "b1": [root(20.0, 22.0), span("admission", 19.996, 20.0),
           span("execute", 20.0, 22.0, sid="ex"),
           span("sync/ok-ladder", 20.5, 21.0, parent="ex"),
           span("encode", 21.9, 22.0, parent="ex")],
}


def geomean(*xs):
    out = 1.0
    for x in xs:
        out *= x
    return out ** (1.0 / len(xs))


@pytest.mark.parametrize("name,want", [
    # a: median of (100 + 100, 400) ms; b has no such span
    ("stream.block_input_ms", 300.0),
    ("stream.transfer_ms", 75.0),                       # (50, 100)
    ("compile.span_ms", 400.0),                         # (200, 600)
    # a: median of (200 + 50, 600); b: 500
    ("hostsync.sync_ms", geomean(425.0, 500.0)),
    ("server.admission_ms", geomean(20.0, 4.0)),        # a (10, 30); b 4
    ("server.encode_ms", geomean(35.0, 100.0)),         # a (20, 50); b 100
    ("server.dedup_wait_ms", 300.0),                    # a2 alone
    ("execute.scan_prep_ms", 400.0),                    # a2: 100 + 300
    ("execute.pin_ms", 100.0)])
def test_span_reader_gives_the_number_reckoned_by_hand(name, want):
    ctx = context(RECORDS, SPANS)
    assert reader(name)(ctx) == pytest.approx(want)
    # a statement the program kept no trace of has nothing to read
    assert reader(name)(context(RECORDS[-1:], SPANS)) is None


def test_unattributed_is_the_root_minus_what_its_descendants_cover():
    mod = verify.load_attr(LAYERS / "server.unattributed_ms.py",
                           "unattributed_ms")
    # a1: plan .. page-wait cover [0, 1.0] without a hole; the inner
    # span named query covers everything and counts for nothing
    assert mod(SPANS["a1"]) == pytest.approx(0.0, abs=1e-9)
    # a2: covered 10.0-11.7, 12.0-12.05, 12.1-12.9 of 10.0-13.0
    assert mod(SPANS["a2"]) == pytest.approx(3000 - 1700 - 50 - 800)
    assert mod(SPANS["b1"]) == pytest.approx(0.0, abs=1e-9)
    assert mod([root(0.0, 2.0)]) == pytest.approx(2000.0)
    assert mod([span("plan", 0.0, 1.0)]) is None  # no root kept
    # per class: a median of (0 -> 1e-6, 450); b 0 -> 1e-6
    got = reader("server.unattributed_ms")(context(RECORDS, SPANS))
    assert got == pytest.approx(geomean((1e-6 + 450.0) / 2, 1e-6))


def test_fast_path_readers_take_the_window_delta_of_the_histograms():
    classes = {"a": {}, "b": {}, "w": {"writes": True}}
    records = RECORDS + [statement("w1", "w", 40.0, 40.2),
                         statement("w2", "w", 50.0, 50.2),
                         {**statement("w3", "w", 60.0, 60.2),
                          "error": "timeout"}]
    counters = {"presto_tpu_fast_hit_seconds_sum": 0.5,
                "presto_tpu_fast_hit_seconds_count": 250.0,
                "presto_tpu_fast_path_plan_seconds_sum": 0.9}
    ctx = context(records, SPANS, counters, classes, loop="open")
    assert reader("server.fast_hit_ms")(ctx) == pytest.approx(2.0)
    # 900 ms of re-planning over the two INSERTs that were acknowledged
    assert reader("server.fast_path_plan_ms")(ctx) == pytest.approx(450.0)
    # a program without the histograms (the parent): nothing, no error
    bare = context(records, SPANS, {}, classes, loop="open")
    assert reader("server.fast_hit_ms")(bare) is None
    assert reader("server.fast_path_plan_ms")(bare) is None
    # and no write in the window: no denominator
    assert reader("server.fast_path_plan_ms")(
        context(RECORDS, SPANS, counters, classes)) is None


def test_device_readers_read_nothing_without_a_device_trace():
    ctx = context(RECORDS, SPANS)
    assert reader("kernels.named_share")(ctx) is None
    for kind in ("join", "aggregate", "topn"):
        read = verify.load_attr(LAYERS / f"CLASS_{kind}_ms.py", "read")
        assert read(ctx, "a") is None


def test_scope_and_kind_of_an_op_name():
    path = "jit(output_e840251b)/Output#0/TopN#1/Aggregate#5/Join#6/gather:"
    assert opnames.scope_of(path) == "Output#0/TopN#1/Aggregate#5/Join#6"
    assert opnames.kind_of(opnames.scope_of(path)) == "Join"
    assert opnames.scope_of("jit(f)/while/body/dynamic_slice:") == ""
    assert opnames.kind_of("") == opnames.UNNAMED
    # a node without a position carries its kind alone
    assert opnames.kind_of(opnames.scope_of("jit(f)/Output#0/Values/add")
                           ) == "Values"


@pytest.mark.skipif(not MICRO.is_file(), reason="no recorded trace")
def test_recorded_micro_trace_gives_seconds_by_operator(tmp_path):
    """A trace of one small program recorded on the v5e: two calls of a
    jitted function that sorts under ``TopN#1/Aggregate#2/Join#3``, takes
    a cumulative sum under ``Aggregate#2`` (XLA turned it into
    reduce-windows and left them without an ``op_name``) and a ``top_k``
    under ``TopN#1``. The expected file lists its 46 device operations
    one by one, as ``jax.profiler.ProfileData`` gives them, each with
    the scope assigned by hand from the program's source; the seconds by
    kind and by scope are their sums."""
    want = json.loads(MICRO_EXPECTED.read_text())
    path = tmp_path / "t.xplane.pb"
    with gzip.open(MICRO, "rb") as src, open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    red = opnames.reduce(path)
    by_hand: dict = {}
    count: dict = {}
    for ev in want["events"]:  # every device operation, as the dump has it
        by_hand[ev["kind"]] = by_hand.get(ev["kind"], 0.0) + ev["self_ns"]
        count[ev["kind"]] = count.get(ev["kind"], 0) + 1
    assert len(want["events"]) == 46 and set(red.kinds) == set(by_hand)
    assert {"TopN", "Join", opnames.UNNAMED} == set(red.kinds)
    # ProfileData cuts each duration to a whole nanosecond, the file
    # holds picoseconds: up to 1 ns an event
    for kind, ns in by_hand.items():
        assert red.kinds[kind] == pytest.approx(
            ns * 1e-9, abs=count[kind] * 1e-9)
    assert by_hand["Join"] == 44273 + 44390
    assert by_hand["TopN"] == 44454 + 231 + 44526 + 231
    total = sum(by_hand.values())
    assert red.total_s == pytest.approx(total * 1e-9, abs=46e-9)
    assert red.named_s / red.total_s == pytest.approx(
        (by_hand["Join"] + by_hand["TopN"]) / total, abs=1e-3)
    assert {m for m, _s in red.scopes} == {want["module"]}
    assert ({s for _m, s in red.scopes}
            == {"TopN#1", "TopN#1/Aggregate#2/Join#3", opnames.UNNAMED})
    # per call, on the records' clock: each of the two windows holds one
    # call's sort under Join#3 (44,273 and 44,390 ns)
    a, b = want["windows"]
    per_call = [red.kind_s_between(lo, hi, ("Join",)) for lo, hi in (a, b)]
    assert per_call == pytest.approx([44273e-9, 44390e-9], abs=1e-9)
    assert sum(per_call) == pytest.approx(red.kinds["Join"], rel=1e-9)


# -- the rehearsal's traced line ----------------------------------------------

SPAN_METRICS = {
    "scan": {"stream.block_input_ms", "stream.transfer_ms",
             "compile.span_ms", "hostsync.sync_ms", "server.admission_ms",
             "server.encode_ms", "server.unattributed_ms",
             "execute.execute_ms", "compile.window_compiles"},
    "join": {"hostsync.sync_ms", "server.admission_ms", "server.encode_ms",
             "server.unattributed_ms"},
    "power": {"hostsync.sync_ms", "server.admission_ms", "server.encode_ms",
              "server.unattributed_ms"},
    "dash": {"hostsync.sync_ms", "server.admission_ms", "server.encode_ms",
             "server.unattributed_ms", "server.dedup_wait_ms",
             "execute.scan_prep_ms", "execute.pin_ms", "server.fast_hit_ms",
             "server.fast_path_plan_ms"},
}


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    """A copy with one twin of each cell at SF 0.01; the twin of
    ``tpch_sf10`` streams (``scan_block_rows`` under lineitem's 60k
    rows), as SF10 does on the chip."""
    root_dir = tmp_path_factory.mktemp("tiny25")
    manifest = copy_of_the_benchmark(root_dir)
    for c in list(manifest["configs"]):
        body = json.loads((BENCH.parent / c["file"]).read_text())
        body["scale_factor"] = 0.01
        if c["name"] == "tpch_sf10":
            body["session"] = {"scan_block_rows": 16384}
        name = c["name"].replace("tpch", "tiny")
        (root_dir / "benchmark" / "configs" / f"{name}.json").write_text(
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
    (root_dir / "BENCHMARK.json").write_text(json.dumps(manifest))
    return root_dir


def rehearse(root_dir, workload, seconds):
    env = {**os.environ, "BENCH_ALLOW_CPU": "1", "JAX_PLATFORMS": "cpu"}
    proc = subprocess.run(
        [sys.executable, str(root_dir / "benchmark" / "run.py"),
         "--workload", workload, "--seed", "2147483659",
         "--seconds", str(seconds), "--trace", "1"],
        cwd=root_dir, env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-2000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("workload,seconds", [
    ("tiny_sf10.scan", 2), ("tiny_sf10.join", 2), ("tiny_sf1.power", 2),
    ("tiny_sf1.dash", 6)])  # 6 s: one INSERT (0.1/s) falls in the window
def test_rehearsals_traced_line_holds_the_new_span_metrics(
        tiny, workload, seconds):
    out = rehearse(tiny, workload, seconds)
    assert out["correct"] is True and out["failed"] == 0
    want = SPAN_METRICS[workload.split(".")[1]]
    assert want <= set(out["metrics"]), want - set(out["metrics"])
    for name in want:
        assert out["metrics"][name]["value"] >= 0
    if workload == "tiny_sf10.scan":
        # every streamed statement builds a program, and is now counted
        assert (out["metrics"]["compile.window_compiles"]["value"]
                >= out["attempted"])
    # device metrics need a device trace: none on the rehearsal CPU
    assert not {"kernels.named_share", "q03_join_ms", "q03_aggregate_ms",
                "q03_topn_ms"} & set(out["metrics"])
