"""Set-up traced from inside (PR 36): the process trace for what runs
before any statement, a ``compile`` span that says what its build was
made of (JAX's own monitoring events), the two process-wide counters
the same events feed, the slow-build line in the log, and a span store
bounded by spans as well as by traces.

Structure and counts only: which spans there are, under what, with
which attributes, and which counters move by how much. On the CPU a
phase is asserted to be above or equal to zero, never how long it is.
"""

from __future__ import annotations

import json
import logging
import os
import re
import subprocess
import sys
import time
import urllib.request
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from presto_tpu import Engine
from presto_tpu.connectors.tpch import TpchConnector
from presto_tpu.exec import executor as EX
from presto_tpu.ft.faults import FAULTS
from presto_tpu.obs import trace as OT
from presto_tpu.obs.metrics import REGISTRY
from presto_tpu.obs.trace import PROCESS_TRACE_ID, TRACER, Tracer
from presto_tpu.server.server import CoordinatorServer

REPO = Path(__file__).resolve().parent.parent

Q6 = ("select sum(l_extendedprice * l_discount) as revenue from lineitem "
      "where l_shipdate >= date '1994-01-01' "
      "and l_shipdate < date '1995-01-01' "
      "and l_discount between 0.05 and 0.07 and l_quantity < 24")

PHASES = ("trace_s", "lower_s", "xla_s", "cache_load_s")
SECONDS = REGISTRY.counter("presto_tpu_jax_compile_seconds_total")
BACKEND = REGISTRY.counter("presto_tpu_jax_backend_compiles_total")
EVICTIONS = REGISTRY.counter("presto_tpu_trace_evictions_total")
DROPS = REGISTRY.counter("presto_tpu_process_trace_dropped_spans_total")


def _named(spans, name):
    return [s for s in spans if s.name == name]


def _trace_id() -> str:
    return f"s{time.monotonic_ns()}"


# -- the process trace --------------------------------------------------------

def test_the_process_trace_starts_with_the_process_and_holds_the_import():
    spans = TRACER.spans(PROCESS_TRACE_ID)
    root = spans[0]
    assert (root.name, root.parent_id, root.t1) == ("process", None, None)
    imported = _named(spans, "import")
    assert len(imported) == 1 and imported[0].parent_id == root.span_id
    # the package's import lies inside the process, on the spans' clock
    assert root.t0 <= imported[0].t0 <= imported[0].t1 <= OT.now()
    # pytest imported jax first (conftest.py), so none of it is ours
    assert imported[0].attrs["jax_s"] == 0.0
    assert abs(OT.to_monotonic(OT.now()) - time.monotonic()) < 0.01
    assert OT.from_monotonic(OT.to_monotonic(root.t0)) == pytest.approx(
        root.t0)


def test_the_process_trace_outlives_every_eviction_and_counts_its_overflow():
    tracer = Tracer(max_spans=8)
    with tracer.process_span("datagen", table="t"):
        pass
    for i in range(OT.MAX_TRACES + 1):
        with tracer.trace(f"e{i}", "query"):
            pass
    assert tracer.spans("e0") == [] and tracer.spans("e1")
    assert [s.name for s in tracer.spans(PROCESS_TRACE_ID)] == [
        "process", "datagen"]
    # the root and seven more fit; the rest is dropped, and counted
    before = DROPS.value()
    for i in range(10):
        with tracer.process_span(f"p{i}"):
            pass
    assert len(tracer.spans(PROCESS_TRACE_ID)) == 8
    assert DROPS.value() - before == 4
    # and the process trace is no statement trace
    assert PROCESS_TRACE_ID not in [tid for tid, _ in tracer.trace_ids()]


def test_process_span_nests_under_a_statement_or_under_the_process():
    tracer = Tracer()
    root = tracer.spans(PROCESS_TRACE_ID)[0]
    with tracer.process_span("outer", k=1) as outer:
        # no statement is ambient: span() stays the no-op it was
        with tracer.span("x") as x:
            assert x is None
        assert OT.current_context() is None
        with tracer.process_span("inner") as inner:
            pass
    assert outer.trace_id == PROCESS_TRACE_ID
    assert outer.parent_id == root.span_id and outer.attrs == {"k": 1}
    assert inner.parent_id == outer.span_id
    assert outer.t0 <= inner.t0 <= inner.t1 <= outer.t1
    with tracer.trace("q1", "query") as query:
        with tracer.process_span("datagen") as under:
            with tracer.span("y") as y:
                pass
    assert (under.trace_id, under.parent_id) == ("q1", query.span_id)
    assert y.parent_id == under.span_id
    assert [s.name for s in tracer.spans(PROCESS_TRACE_ID)] == [
        "process", "outer", "inner"]


def test_an_interval_handed_over_can_move_the_process_start_back():
    tracer = Tracer()
    root = tracer.spans(PROCESS_TRACE_ID)[0]
    start = root.t0
    tracer.add_process_span("import", start - 5.0, start - 1.0, jax_s=2.0)
    assert root.t0 == start - 5.0
    tracer.add_process_span("later", start + 1.0, start + 2.0)
    assert root.t0 == start - 5.0
    assert [(s.name, s.parent_id) for s in tracer.spans(PROCESS_TRACE_ID)[1:]
            ] == [("import", root.span_id), ("later", root.span_id)]


def test_the_server_exports_the_process_trace(tpch_tiny):
    engine = Engine()
    engine.register_catalog("tpch", tpch_tiny)
    srv = CoordinatorServer(engine).start()
    try:
        with urllib.request.urlopen(
                f"http://127.0.0.1:{srv.port}/v1/query/process/trace",
                timeout=30) as resp:
            body = json.loads(resp.read())
    finally:
        srv.stop()
    events = {e["name"]: e for e in body["traceEvents"] if e["ph"] == "X"}
    assert "import" in events and "jax_s" in events["import"]["args"]
    # the root is still open, and starts no later than anything in it
    assert events["process"]["args"]["in_progress"] is True
    assert events["process"]["ts"] <= events["import"]["ts"]
    assert events["import"]["args"]["parent_id"] == \
        events["process"]["args"]["span_id"]


@pytest.fixture()
def fresh_tracer(monkeypatch):
    """A tracer of the test's own in the program's place (the process
    trace of a long test process may be full)."""
    tracer = Tracer()
    monkeypatch.setattr(OT, "TRACER", tracer)
    return tracer


def test_an_engine_made_outside_a_statement_is_an_engine_init_span(
        fresh_tracer):
    Engine()
    with fresh_tracer.trace("q", "query") as query:
        Engine()
    (outside,) = _named(fresh_tracer.spans(PROCESS_TRACE_ID), "engine-init")
    (inside,) = _named(fresh_tracer.spans("q"), "engine-init")
    assert outside.parent_id == fresh_tracer.spans(PROCESS_TRACE_ID)[0].span_id
    assert inside.parent_id == query.span_id


def test_a_generated_table_is_one_datagen_span(fresh_tracer):
    TRACER = fresh_tracer
    conn = TpchConnector(scale=0.001, tables=["lineitem", "orders",
                                              "nation"])
    nrows = conn.table("lineitem").nrows
    conn.table("lineitem")  # the table cache holds it: no second span
    conn.table("orders")
    made = _named(TRACER.spans(PROCESS_TRACE_ID), "datagen")
    assert [s.attrs["table"] for s in made] == ["lineitem", "orders"]
    line, orders = made
    assert line.attrs["rows"] == nrows and line.attrs["bytes"] > 8 * nrows
    assert line.attrs["threads"] >= 2
    # one pass makes both tables' columns: the first span holds it
    assert line.attrs["made"] == "lineitem,orders"
    assert orders.attrs["made"] == ""
    # under a statement the span is the statement's
    tid = _trace_id()
    with TRACER.trace(tid, "query") as query:
        conn.table("nation")
    (nation,) = _named(TRACER.spans(tid), "datagen")
    assert nation.parent_id == query.span_id
    assert nation.attrs["table"] == "nation" and nation.attrs["rows"] == 25


# -- the store ----------------------------------------------------------------

def test_the_store_evicts_by_spans_as_well_as_by_traces():
    tracer = Tracer(max_traces=100, max_total_spans=10)
    before = EVICTIONS.value()
    for t in range(3):
        with tracer.trace(f"t{t}", "query"):
            for _ in range(3):
                with tracer.span("x"):
                    pass
    # 12 spans do not fit in 10: the oldest trace went, whole
    assert EVICTIONS.value() - before == 1
    assert tracer.spans("t0") == []
    assert len(tracer.spans("t1")) == len(tracer.spans("t2")) == 4
    # a trace larger than the store alone evicts the others, not itself
    with tracer.trace("big", "query"):
        for _ in range(20):
            with tracer.span("x"):
                pass
    assert EVICTIONS.value() - before == 3
    assert [tid for tid, _ in tracer.trace_ids()] == ["big"]
    assert len(tracer.spans("big")) == 21
    # the defaults hold a whole benchmark run
    assert (OT.MAX_TRACES, OT.MAX_SPANS) == (4096, 65536)


def test_trace_ids_lists_the_retained_traces_with_their_roots():
    tracer = Tracer()
    with tracer.trace("a", "query") as a:
        with tracer.span("x"):
            pass
    tracer.instant_for("shed", "shed", create=True)
    with tracer.trace("b", "query") as b:
        pass
    assert tracer.trace_ids() == [("a", a), ("shed", None), ("b", b)]


# -- the compile span ---------------------------------------------------------

def _engine(tpch_tiny, **session) -> Engine:
    e = Engine()
    e.register_catalog("tpch", tpch_tiny)
    for k, v in session.items():
        e.session.set(k, v)
    return e


def _phase_totals() -> dict[str, float]:
    return {p: SECONDS.value(phase=p[:-2]) for p in PHASES}


@pytest.mark.parametrize("path,session,attrs", [
    ("prepare_plan", {}, {}),
    ("streamed", {"scan_block_rows": 16384}, {"streamed": True}),
    ("mesh", {"mesh_devices": 4}, {"distributed": True, "devices": 4}),
    ("analyze", {}, {"analyze": True})])
def test_a_compile_span_says_what_its_build_was_made_of(
        path, session, attrs, tpch_tiny):
    e = _engine(tpch_tiny, **session)
    sql = "explain analyze " + Q6 if path == "analyze" else Q6
    seconds0, compiled0 = _phase_totals(), BACKEND.value(outcome="compiled")
    tid = _trace_id()
    with TRACER.trace(tid, "query"):
        e.execute(sql)
    built = _named(TRACER.spans(tid), "compile")
    assert built, "the statement built no program"
    first = built[0]
    for k, v in attrs.items():
        assert first.attrs[k] == v
    for s in built:
        assert re.fullmatch(r"jit_\w+", s.attrs["program"])
        assert s.attrs["root"] and s.attrs["attempt"] >= 0
        # the suite runs with JAX's persistent cache off (conftest.py)
        assert s.attrs["persistent_cache"] == "off"
        assert s.attrs["trace_s"] > 0 and s.attrs["lower_s"] > 0
        assert s.attrs["xla_s"] > 0 and s.attrs["cache_load_s"] == 0.0
        # the phases lie inside the span
        assert sum(s.attrs[p] for p in PHASES) <= s.t1 - s.t0 + 1e-3
    if path == "streamed":
        assert first.attrs["program"].startswith("jit_stream_")
    # the process-wide counters rose by at least what the spans say
    # (they also see what was built outside any compile span)
    seconds1 = _phase_totals()
    for p in PHASES:
        assert seconds1[p] - seconds0[p] >= sum(
            s.attrs[p] for s in built) - 1e-6
    assert BACKEND.value(outcome="compiled") - compiled0 >= len(built)


def test_a_bare_jit_helper_is_counted_with_no_compile_span():
    @jax.jit
    def helper_of_no_compiling(x):
        return x * 3 + 1

    x = jnp.arange(7)
    x.block_until_ready()
    seconds0, compiled0 = _phase_totals(), BACKEND.value(outcome="compiled")
    tid = _trace_id()
    with TRACER.trace(tid, "query"):
        helper_of_no_compiling(x)
    assert BACKEND.value(outcome="compiled") - compiled0 == 1
    seconds1 = _phase_totals()
    for p in ("trace_s", "lower_s", "xla_s"):
        assert seconds1[p] > seconds0[p]
    assert _named(TRACER.spans(tid), "compile") == []
    # a call of what JAX already holds reports nothing
    helper_of_no_compiling(x)
    assert BACKEND.value(outcome="compiled") - compiled0 == 1
    assert _phase_totals() == seconds1


def test_nested_and_eager_builds_count_once_under_the_outermost_phase():
    """A jitted function traced inside another's trace reports its own
    duration inside the outer one's: the span's ``trace_s`` is the
    outermost event's seconds, not the sum."""
    @jax.jit
    def inner(x):
        return jnp.sin(x) + 1

    def outer(x):
        return inner(x) * inner(x + 1)

    outer.__name__ = "outer_of_two"
    tid = _trace_id()
    with TRACER.trace(tid, "query"):
        EX.compile_traced(outer, [jnp.ones((5,))], attempt=0, root="T")
    (span,) = _named(TRACER.spans(tid), "compile")
    assert span.attrs["program"] == "jit_outer_of_two"
    assert 0 < span.attrs["trace_s"] <= span.t1 - span.t0
    assert sum(span.attrs[p] for p in PHASES) <= span.t1 - span.t0 + 1e-3


_CACHE_PROBE = """
import json, sys
import jax
jax.config.update("jax_platforms", "cpu")
from presto_tpu import Engine
from presto_tpu.connectors.tpch import TpchConnector
from presto_tpu.obs.trace import TRACER
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
conn = TpchConnector(scale=0.001, tables=["lineitem"])
out = []
for i in range(2):
    e = Engine()
    e.register_catalog("tpch", conn)
    with TRACER.trace(f"probe{i}", "query"):
        e.execute(sys.argv[1])
    out.append([s.attrs for s in TRACER.spans(f"probe{i}")
                if s.name == "compile"])
print(json.dumps(out))
"""


def test_a_second_engine_of_the_process_loads_from_the_persistent_cache(
        tmp_path):
    """Two engines of one process over an empty persistent cache: the
    first compiles and writes, the second (no program cache of its own
    yet) traces and lowers again and loads the executable. A child
    process, because the suite keeps JAX's cache off in its own."""
    env = {**os.environ, "JAX_COMPILATION_CACHE_DIR": str(tmp_path),
           "JAX_ENABLE_COMPILATION_CACHE": "true", "JAX_PLATFORMS": "cpu",
           "PYTHONPATH": str(REPO)}
    env.pop("XLA_FLAGS", None)
    done = subprocess.run([sys.executable, "-c", _CACHE_PROBE, Q6], env=env,
                          capture_output=True, text=True, timeout=300)
    assert done.returncode == 0, done.stderr[-2000:]
    first, second = json.loads(done.stdout.strip().splitlines()[-1])
    assert len(first) == len(second) >= 1
    for miss, hit in zip(first, second):
        assert miss["persistent_cache"] == "miss"
        assert miss["xla_s"] > 0 and miss["cache_load_s"] == 0.0
        assert hit["program"] == miss["program"]
        assert hit["persistent_cache"] == "hit"
        assert hit["cache_load_s"] > 0 and hit["xla_s"] == 0.0
        # the plan is walked and lowered again, warm or cold
        assert hit["trace_s"] > 0 and hit["lower_s"] > 0


def test_a_slow_build_leaves_a_line_in_the_log(tpch_tiny, monkeypatch,
                                               caplog):
    monkeypatch.setattr(EX, "SLOW_BUILD_S", 0.2)
    e = _engine(tpch_tiny)
    sql = ("select count(*) as c from lineitem where l_quantity < 7 "
           "and l_discount > 0.031")
    tid = _trace_id()
    FAULTS.arm("compile-slow", prob=1.0, delay_s=0.3, match="")
    try:
        with caplog.at_level(logging.WARNING, logger="presto_tpu"), \
                TRACER.trace(tid, "query"):
            e.execute(sql)
    finally:
        FAULTS.disarm("compile-slow")
    built = _named(TRACER.spans(tid), "compile")
    lines = [r.getMessage() for r in caplog.records
             if r.name == "presto_tpu" and "slow build" in r.getMessage()]
    assert len(lines) == len(built) >= 1
    for line, span in zip(lines, built):
        # the fault point sleeps inside the span
        assert span.t1 - span.t0 >= 0.3
        assert f"program={span.attrs['program']} " in line
        assert f"attempt={span.attrs['attempt']} " in line
        assert f"statement={tid}" in line
        assert "persistent_cache=off" in line
        for phase in ("trace", "lower", "xla", "cache_load"):
            assert re.search(rf" {phase}=\d+\.\ds", line), line
    # a build under the limit says nothing
    monkeypatch.setattr(EX, "SLOW_BUILD_S", 3600.0)
    caplog.clear()
    with caplog.at_level(logging.WARNING, logger="presto_tpu"):
        e.execute(sql.replace("0.031", "0.032").replace("count(*)",
                                                        "sum(l_tax)"))
    assert not [r for r in caplog.records if "slow build" in r.getMessage()]


def test_every_compile_span_is_opened_by_compiling():
    opened = [
        str(p.relative_to(REPO))
        for p in sorted((REPO / "presto_tpu").rglob("*.py"))
        for line in p.read_text(encoding="utf-8").splitlines()
        if re.search(r"""span\(\s*["']compile["']""", line)]
    assert opened == ["presto_tpu/exec/executor.py"]


def test_the_retired_gauges_and_histogram_are_gone(tpch_tiny):
    engine = _engine(tpch_tiny)
    srv = CoordinatorServer(engine).start()
    try:
        with urllib.request.urlopen(
                f"http://127.0.0.1:{srv.port}/metrics", timeout=30) as resp:
            text = resp.read().decode()
    finally:
        srv.stop()
    names = {line.split("{", 1)[0].split(" ", 1)[0]
             for line in text.splitlines() if not line.startswith("#")}
    assert "presto_tpu_process_uptime_seconds" in names
    assert "presto_tpu_program_cache_entries" in names
    for gone in ("presto_tpu_uptime_seconds", "presto_tpu_compiled_programs",
                 "presto_tpu_compile_seconds_sum"):
        assert gone not in names
