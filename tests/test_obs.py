"""Observability subsystem (presto_tpu/obs/): metrics registry
contracts, span tracer + context propagation, Chrome trace export,
structured JSON logging, the metric-name lint rule, and the
coordinator's /metrics + /v1/query/{id}/trace endpoints."""

from __future__ import annotations

import io
import json
import textwrap
import threading
import urllib.request
from pathlib import Path

import pytest

from presto_tpu.obs.metrics import (MetricError, MetricsRegistry,
                                    validate_metric_name)
from presto_tpu.obs.trace import (TRACE_HEADER, Tracer,
                                  current_context, parse_context)

REPO = Path(__file__).resolve().parent.parent


# -- metrics registry -------------------------------------------------------

def test_registry_counter_gauge_histogram_render():
    reg = MetricsRegistry()
    c = reg.counter("presto_tpu_widgets_total", "widgets")
    c.inc()
    c.inc(2, kind="a")
    g = reg.gauge("presto_tpu_depth_bytes")
    g.set(7, node="w0")
    h = reg.histogram("presto_tpu_wait_seconds", buckets=(0.1, 1.0))
    h.observe(0.05)
    h.observe(5.0)
    text = reg.render()
    assert "# TYPE presto_tpu_widgets_total counter" in text
    assert "presto_tpu_widgets_total 1" in text
    assert 'presto_tpu_widgets_total{kind="a"} 2' in text
    assert 'presto_tpu_depth_bytes{node="w0"} 7' in text
    assert 'presto_tpu_wait_seconds_bucket{le="0.100000"} 1' in text
    assert 'presto_tpu_wait_seconds_bucket{le="+Inf"} 2' in text
    assert "presto_tpu_wait_seconds_count 2" in text
    assert "presto_tpu_wait_seconds_sum 5.05" in text


def test_registry_rejects_bad_names_and_duplicates():
    reg = MetricsRegistry()
    with pytest.raises(MetricError):
        reg.counter("widgets_total")  # missing prefix
    with pytest.raises(MetricError):
        reg.counter("presto_tpu_widgets")  # counter without _total
    with pytest.raises(MetricError):
        reg.gauge("presto_tpu_widgets_total")  # gauge WITH _total
    with pytest.raises(MetricError):
        reg.histogram("presto_tpu_wait")  # histogram without unit
    reg.counter("presto_tpu_things_total")
    # get-or-create: same kind returns the same instrument
    assert reg.counter("presto_tpu_things_total") is \
        reg.counter("presto_tpu_things_total")
    with pytest.raises(MetricError):
        reg.gauge("presto_tpu_things")  # fine
        reg.histogram("presto_tpu_things_seconds")  # fine
        reg.gauge("presto_tpu_things_seconds")  # kind clash


def test_counter_is_monotonic():
    reg = MetricsRegistry()
    c = reg.counter("presto_tpu_rows_total")
    c.inc(5)
    with pytest.raises(MetricError):
        c.inc(-1)
    assert c.value() == 5


def test_registry_thread_safety():
    reg = MetricsRegistry()
    c = reg.counter("presto_tpu_hits_total")

    def worker():
        for _ in range(1000):
            c.inc()

    threads = [threading.Thread(target=worker) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert c.value() == 8000


def test_validate_metric_name_is_shared_contract():
    assert validate_metric_name("presto_tpu_x_total", "counter") is None
    assert validate_metric_name("Presto_TPU_x", "gauge") is not None
    assert validate_metric_name("presto_tpu_x-y", "gauge") is not None


# -- tracer -----------------------------------------------------------------

def test_span_noop_without_active_trace():
    tr = Tracer()
    with tr.span("orphan") as sp:
        assert sp is None
    assert current_context() is None


def test_root_span_nesting_and_export():
    tr = Tracer()
    with tr.trace("q1", "query", user="u") as root:
        with tr.span("plan") as plan:
            pass
        with tr.span("execute") as ex:
            with tr.span("kernel") as k:
                pass
    spans = {s.name: s for s in tr.spans("q1")}
    assert spans["plan"].parent_id == root.span_id
    assert spans["execute"].parent_id == root.span_id
    assert spans["kernel"].parent_id == ex.span_id
    assert plan.t1 is not None
    ct = tr.chrome_trace("q1")
    json.dumps(ct)  # valid JSON
    xs = [e for e in ct["traceEvents"] if e["ph"] == "X"]
    assert {e["name"] for e in xs} == {"query", "plan", "execute",
                                       "kernel"}
    for e in xs:
        assert e["dur"] >= 0 and e["ts"] > 0


def test_attach_propagates_across_threads_and_header_roundtrip():
    tr = Tracer()
    out = {}

    with tr.trace("q2", "query"):
        with tr.span("dispatch") as sp:
            ctx = current_context()
            header = f"{ctx[0]}:{ctx[1]}"

        def remote():
            # simulates the worker handler: header -> attach -> span
            parsed = parse_context(header)
            with tr.attach(parsed, node="w7"):
                with tr.span("worker-task") as w:
                    out["span"] = w

        t = threading.Thread(target=remote)
        t.start()
        t.join()
    assert out["span"].trace_id == "q2"
    assert out["span"].parent_id == sp.span_id
    assert out["span"].attrs["node"] == "w7"
    # malformed headers are ignored, not fatal
    assert parse_context(None) is None
    assert parse_context("garbage") is None
    assert parse_context(":") is None


def test_trace_store_bounded():
    tr = Tracer(max_traces=4)
    for i in range(10):
        with tr.trace(f"t{i}", "query"):
            pass
    assert tr.spans("t0") == []
    assert len(tr.spans("t9")) == 1
    assert [tid for tid, _root in tr.trace_ids()] == ["t6", "t7", "t8", "t9"]
    # bounded by spans over all traces too, oldest trace out first
    tr = Tracer(max_traces=4, max_total_spans=6)
    for i in range(3):
        with tr.trace(f"s{i}", "query"):
            with tr.span("a"), tr.span("b"):
                pass
    assert tr.spans("s0") == []
    assert len(tr.spans("s1")) == len(tr.spans("s2")) == 3
    # one trace holds at most max_spans
    tr = Tracer(max_spans=5)
    with tr.trace("wide", "query"):
        for _ in range(9):
            with tr.span("x"):
                pass
    assert len(tr.spans("wide")) == 5


# -- structured JSON logging ------------------------------------------------

def test_jsonlog_writes_one_json_object_per_line():
    from presto_tpu.obs.jsonlog import JsonLogWriter

    buf = io.StringIO()
    log = JsonLogWriter(buf)
    log.log("query_completed", query_id="q_1", rows=3)
    rec = json.loads(buf.getvalue().strip())
    assert rec["event"] == "query_completed"
    assert rec["rows"] == 3 and "ts" in rec


def test_jsonlog_disabled_by_default():
    from presto_tpu.obs.jsonlog import JsonLogWriter

    log = JsonLogWriter()
    log.log("noop")  # must not raise with no sink configured
    assert not log.enabled


# -- metric-name lint rule --------------------------------------------------

def write_pkg(tmp_path: Path, files: dict[str, str]) -> Path:
    for rel, src in files.items():
        p = tmp_path / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(textwrap.dedent(src))
    return tmp_path / "presto_tpu"


def test_metric_name_lint_flags_violations(tmp_path):
    from presto_tpu.lint import run_lint

    pkg = write_pkg(tmp_path, {"presto_tpu/mod.py": """
        from presto_tpu.obs.metrics import REGISTRY
        BAD1 = REGISTRY.counter("presto_tpu_rows", "h")    # no _total
        BAD2 = REGISTRY.gauge("presto_tpu_depth_total",
                              "h")                         # _total gauge
        BAD3 = REGISTRY.histogram("presto_tpu_wait", "h")  # no unit
        BAD4 = REGISTRY.counter("widgets_total", "h")      # no prefix
        BAD5 = REGISTRY.counter(
            "presto_tpu_undoc_total")                      # no HELP
        BAD6 = REGISTRY.counter(
            "presto_tpu_blank_total", help_text="  ")      # blank HELP
        OK = REGISTRY.counter("presto_tpu_widgets_total", "widgets")

        def f():
            OK.inc(-1)                                     # decrement
    """, "presto_tpu/other.py": """
        from presto_tpu.obs.metrics import REGISTRY
        # same name, different kind than mod.py
        CLASH = REGISTRY.gauge("presto_tpu_widgets", "h")
        CLASH2 = REGISTRY.histogram("presto_tpu_widgets_seconds", "h")
    """, "presto_tpu/clash.py": """
        from presto_tpu.obs.metrics import REGISTRY
        X = REGISTRY.gauge("presto_tpu_widgets_seconds",
                           "h")                            # kind clash
    """})
    findings = [f for f in run_lint([pkg])
                if f.rule == "metric-name"]
    messages = "\n".join(f.message for f in findings)
    assert len(findings) == 8, messages
    assert "must end in _total" in messages
    assert "must not end in _total" in messages
    assert "unit suffix" in messages
    assert "must match" in messages
    assert "negative literal" in messages
    assert "the registry raises on whichever loads second" in messages
    assert messages.count("without HELP") == 2


def test_metric_name_lint_clean_code_passes(tmp_path):
    from presto_tpu.lint import run_lint

    pkg = write_pkg(tmp_path, {"presto_tpu/mod.py": """
        from presto_tpu.obs.metrics import REGISTRY
        C = REGISTRY.counter("presto_tpu_rows_total", "rows")
        G = REGISTRY.gauge("presto_tpu_pool_bytes", help_text="bytes")
        H = REGISTRY.histogram("presto_tpu_wait_seconds", "wait")
        # non-literal help is left to the author (runtime carries it)
        D = REGISTRY.counter("presto_tpu_dyn_total", "x" * 3)

        def f(n):
            C.inc(n)
            G.dec(2)
    """})
    assert [f for f in run_lint([pkg])
            if f.rule == "metric-name"] == []


# -- coordinator endpoints --------------------------------------------------

@pytest.fixture(scope="module")
def obs_server(request, tpch_tiny):
    from presto_tpu import Engine
    from presto_tpu.server import CoordinatorServer

    engine = Engine()
    engine.register_catalog("tpch", tpch_tiny)
    srv = CoordinatorServer(engine).start()
    request.addfinalizer(srv.stop)
    return srv


def test_trace_endpoint_returns_chrome_trace(obs_server):
    from presto_tpu.client import Client

    c = Client(f"http://127.0.0.1:{obs_server.port}", user="tester")
    qid, _ = c.submit(
        "select l_returnflag, count(*) from lineitem "
        "group by l_returnflag order by 1")
    import time
    for _ in range(600):
        if c.query_state(qid) == "FINISHED":
            break
        time.sleep(0.05)
    assert c.query_state(qid) == "FINISHED"
    with urllib.request.urlopen(
            f"http://127.0.0.1:{obs_server.port}"
            f"/v1/query/{qid}/trace") as r:
        trace = json.loads(r.read())
    events = [e for e in trace["traceEvents"] if e["ph"] == "X"]
    names = {e["name"] for e in events}
    # coordinator spans: root, admission wait, planning, per-program
    # compile/execute (acceptance: plan + per-segment compile/execute)
    assert {"query", "admission", "plan", "execute"} <= names
    by_id = {e["args"]["span_id"]: e for e in events}
    root = next(e for e in events if e["name"] == "query"
                and "parent_id" not in e["args"])
    # every non-root span reaches the root via parent links
    for e in events:
        cur, hops = e, 0
        while "parent_id" in cur["args"] and hops < 20:
            cur = by_id[cur["args"]["parent_id"]]
            hops += 1
        assert cur is root
    # the run also compiled at least one program on a cold engine
    assert "compile" in names


def test_metrics_endpoint_counters_are_monotonic(obs_server):
    from presto_tpu.client import Client

    c = Client(f"http://127.0.0.1:{obs_server.port}", user="tester")

    def scrape() -> str:
        with urllib.request.urlopen(
                f"http://127.0.0.1:{obs_server.port}/metrics") as r:
            return r.read().decode()

    def counter_value(text: str, name: str) -> float:
        vals = [float(line.rsplit(" ", 1)[1])
                for line in text.splitlines()
                if line.startswith(name) and "{" not in line]
        return vals[0] if vals else 0.0

    c.execute("select n_name from nation order by n_name")
    t1 = scrape()
    rows1 = counter_value(t1, "presto_tpu_result_rows_total")
    assert rows1 >= 25
    c.execute("select n_name from nation order by n_name")
    t2 = scrape()
    rows2 = counter_value(t2, "presto_tpu_result_rows_total")
    assert rows2 >= rows1 + 25  # monotonic, accumulates across queries
    assert 'presto_tpu_query_state_transitions_total{state="finished"}' \
        in t2
    assert "# TYPE presto_tpu_query_duration_seconds histogram" in t2
