"""The set-up readers of PR 36: on a span store filled by hand, and in
the traced line of a CPU rehearsal (the tiny twins of
``test_idle_layers``)."""

import json
import os
import re
import subprocess
import sys
import types

import pytest
import verify
from conftest import BENCH
from test_idle_layers import tiny  # noqa: F401 - a fixture

from presto_tpu.obs.metrics import REGISTRY

LAYERS = BENCH / "layers"
SPAN_READERS = ("setup.import_s", "setup.trace_lower_s",
                "setup.xla_compile_s", "setup.cache_load_s", "setup.pin_s",
                "setup.unattributed_s")
NEW = SPAN_READERS + ("compile.window_backend_compiles",)
EVICTIONS = REGISTRY.counter("presto_tpu_trace_evictions_total")


def reader(name):
    return verify.load_attr(LAYERS / f"{name}.py", "read")


def test_the_manifest_lists_the_seven_in_every_cell():
    manifest = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    cells = [w["name"] for w in manifest["workloads"]]
    last = manifest["per_layer"][-len(NEW):]
    assert tuple(m["name"] for m in last) == NEW
    for m in last:
        assert m["workloads"] == cells and m["better"] == "lower"
        assert (LAYERS / f"{m['name']}.py").is_file()
        counter = m["name"] == "compile.window_backend_compiles"
        assert m["moves"] == ("geomean_ms" if counter else "setup_s")
        assert m["layer"] == ("compile and program cache" if counter
                              else "set-up")
        assert (m["unit"], m["source"]) == (
            ("count", "program_counter") if counter
            else ("s", "program_span"))


@pytest.fixture()
def store(monkeypatch):
    """The program's tracer, emptied: set-up of 100 s that ends at
    t0 = 1100 on the harness's clock. The process starts at 1000; the
    import takes [1000.5, 1004.5], two tables [1010, 1030] and
    [1030, 1040] (a block of the second nested in it); the first
    statement is admitted at 1050 and runs [1051, 1081] with two builds
    and two pins; the second [1085, 1095] with one build and one
    shard-pin; a third straddles t0 and builds too."""
    from presto_tpu.obs import trace as OT
    tracer = OT.Tracer()
    monkeypatch.setattr(OT, "TRACER", tracer)
    # whatever this process's tests have evicted before
    monkeypatch.setattr(EVICTIONS, "value", lambda: 0.0)
    at = OT.from_monotonic

    def span(tid, name, t0, t1, sid, parent, **attrs):
        return {"trace_id": tid, "span_id": sid, "parent_id": parent,
                "name": name, "attrs": attrs, "t0": at(t0), "t1": at(t1)}

    root = tracer.spans("process")[0]
    root.t0 = at(1000.0)
    tracer.add_process_span("import", at(1000.5), at(1004.5), jax_s=3.0)
    tracer.import_spans([
        span("process", "datagen", 1010, 1030, "d1", root.span_id),
        span("process", "datagen", 1030, 1040, "d2", root.span_id),
        span("process", "block", 1031, 1039, "d3", "d2"),
        span("a", "query", 1051, 1081, "ra", None),
        span("a", "admission", 1050, 1051, "aa", "ra"),
        span("a", "compile", 1052, 1062, "c1", "ra", trace_s=1.0,
             lower_s=2.0, xla_s=0.0, cache_load_s=6.5),
        span("a", "compile", 1063, 1070, "c2", "ra", trace_s=0.5,
             lower_s=0.25, xla_s=6.0, cache_load_s=0.0),
        span("a", "pin", 1070, 1071, "p1", "ra"),
        span("a", "pin", 1071, 1071.5, "p2", "ra"),
        span("b", "query", 1085, 1095, "rb", None),
        span("b", "compile", 1086, 1088, "c3", "rb", trace_s=0.125,
             lower_s=0.125, xla_s=1.5, cache_load_s=0.0),
        span("b", "shard-pin", 1088, 1090, "p3", "rb"),
        span("c", "query", 1099, 1103, "rc", None),
        span("c", "compile", 1099.5, 1099.75, "c4", "rc", trace_s=64.0,
             lower_s=64.0, xla_s=64.0, cache_load_s=64.0),
        span("c", "pin", 1099.75, 1099.8, "p4", "rc")])
    tracer.instant_for("shed", "shed", create=True)
    return tracer


def context():
    return types.SimpleNamespace(t0=1100.0, setup_s=100.0, counters={})


@pytest.mark.parametrize("name,want", [
    ("setup.import_s", 4.0),
    ("setup.trace_lower_s", 1.0 + 2.0 + 0.5 + 0.25 + 0.125 + 0.125),
    ("setup.xla_compile_s", 6.0 + 1.5),
    ("setup.cache_load_s", 6.5),
    ("setup.pin_s", 1.0 + 0.5 + 2.0),
    # named: 4 (import) + 30 (tables) + 31 (a, admission to end) + 10
    # (b) + 1 (c, up to t0) of 100
    ("setup.unattributed_s", 100.0 - 4.0 - 30.0 - 31.0 - 10.0 - 1.0)])
def test_span_reader_gives_the_number_reckoned_by_hand(store, name, want):
    assert reader(name)(context()) == pytest.approx(want)


def test_the_span_readers_read_nothing_from_a_store_that_evicted(
        store, monkeypatch, capsys):
    monkeypatch.setattr(EVICTIONS, "value", lambda: 3.0)
    for name in SPAN_READERS:
        assert reader(name)(context()) is None
        assert f"{name}: the span store evicted" in capsys.readouterr().err


def test_a_program_without_the_spans_or_the_counter_gives_nothing(
        monkeypatch):
    """The parent of PR 36: no process trace, no ``trace_ids``, no
    phase attributes, no backend counter. Nothing is read and nothing
    raises."""
    from presto_tpu.obs import trace as OT
    bare = types.SimpleNamespace(spans=lambda tid: [])
    monkeypatch.setattr(OT, "TRACER", bare)
    for name in NEW:
        assert reader(name)(context()) is None
    ctx = context()
    ctx.counters["presto_tpu_jax_backend_compiles_total"] = 3.0
    assert reader("compile.window_backend_compiles")(ctx) == 3.0


def test_compile_spans_without_phases_give_no_phase_metric(store):
    for spans in store._traces.values():
        for s in spans:
            if s.name == "compile":
                s.attrs.clear()
    for name in ("setup.trace_lower_s", "setup.xla_compile_s",
                 "setup.cache_load_s"):
        assert reader(name)(context()) is None
    assert reader("setup.pin_s")(context()) == pytest.approx(3.5)


EVICTING = (
    "import runpy, sys\n"
    "sys.path[:0] = ['.', 'benchmark']\n"
    "from presto_tpu.obs.trace import TRACER\n"
    "TRACER.max_traces = 2\n"
    "sys.argv = ['benchmark/run.py'] + sys.argv[1:]\n"
    "runpy.run_path('benchmark/run.py', run_name='__main__')\n")


def traced_rehearsal(root, workload, script=None):
    """(the run's line, its stderr): ``run.py`` itself, or ``script``
    in its place."""
    env = {**os.environ, "BENCH_ALLOW_CPU": "1", "JAX_PLATFORMS": "cpu"}
    proc = subprocess.run(
        [sys.executable,
         *(["-c", script] if script else [str(root / "benchmark" / "run.py")]),
         "--workload", workload, "--seed", "2147483659", "--seconds", "2",
         "--trace", "1"],
        cwd=root, env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-2000:]
    return json.loads(proc.stdout.strip().splitlines()[-1]), proc.stderr


@pytest.mark.parametrize("workload", ["tiny_sf1.power", "tiny_sf10.scan"])
def test_rehearsals_traced_line_holds_the_setup_metrics(tiny, workload):
    out, stderr = traced_rehearsal(tiny, workload)
    assert out["correct"] is True and out["failed"] == 0
    assert set(NEW) <= set(out["metrics"]), set(NEW) - set(out["metrics"])
    got = {name: out["metrics"][name]["value"] for name in NEW}
    assert all(v >= 0 for v in got.values())
    assert got["setup.import_s"] > 0 and got["setup.trace_lower_s"] > 0
    # the rehearsal's persistent cache is whatever the environment
    # gives it: what was not compiled was loaded
    assert got["setup.xla_compile_s"] + got["setup.cache_load_s"] > 0
    # a traced line has no ``setup_s``: the harness says it on stderr
    setup_s = float(re.search(r"window opens at \+([\d.]+)s", stderr)[1])
    # the child's start-up alone is 1 s of it
    assert 1.0 <= got["setup.unattributed_s"] < setup_s
    # what the spans name and what they do not make up the whole
    named = (got["setup.import_s"] + out["metrics"]["setup.datagen_s"]["value"]
             + out["metrics"]["setup.first_exec_s"]["value"])
    assert named + got["setup.unattributed_s"] == pytest.approx(
        setup_s, abs=0.5)
    # the window builds nothing (warm-up covered every shape), by the
    # count of what went through ``compiling()`` and by JAX's own
    assert (got["compile.window_backend_compiles"]
            >= out["metrics"]["compile.window_compiles"]["value"] == 0)


def test_a_store_forced_to_evict_leaves_the_six_out_of_the_line(tiny):
    """The harness run under a store of two traces: the warm-up alone
    overflows it, the six span readers say so on stderr and leave their
    metrics out, the counter's reader still reads."""
    out, stderr = traced_rehearsal(tiny, "tiny_sf1.power", EVICTING)
    assert out["correct"] is True
    assert not set(SPAN_READERS) & set(out["metrics"])
    assert "compile.window_backend_compiles" in out["metrics"]
    for name in SPAN_READERS:
        assert f"{name}: the span store evicted" in stderr
        assert f"{name}: left out of the line" in stderr
