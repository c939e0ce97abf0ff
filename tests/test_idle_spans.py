"""Spans and counters where the chip sits idle (PR 25): the streamed
scan, the statement after a write, every host sync, the fast path, on
one clock; and device operations that carry the plan operator's name.

Structure and counts only: which spans a statement has, how many, with
which attributes, and which counters move. No timing thresholds apart
from the cost of one span, which is a count of work.
"""

from __future__ import annotations

import json
import re
import threading
import time
import urllib.request
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from presto_tpu import Engine
from presto_tpu import templates as TPL
from presto_tpu import types as T
from presto_tpu.client import Client
from presto_tpu.connectors.memory import MemoryConnector
from presto_tpu.exec import hostsync as HS
from presto_tpu.exec.executor import (collect_scans, make_traced,
                                      program_name)
from presto_tpu.obs import trace as OT
from presto_tpu.obs.metrics import REGISTRY
from presto_tpu.obs.trace import TRACER, Tracer
from presto_tpu.plan.fingerprint import plan_fingerprint
from presto_tpu.server.server import CoordinatorServer

REPO = Path(__file__).resolve().parent.parent

Q1 = ("select l_returnflag, l_linestatus, sum(l_quantity) as sum_qty, "
      "count(*) as count_order from lineitem "
      "where l_shipdate <= date '1998-09-02' "
      "group by l_returnflag, l_linestatus "
      "order by l_returnflag, l_linestatus")

Q3 = ("select l_orderkey, sum(l_extendedprice * (1 - l_discount)) as "
      "revenue, o_orderdate, o_shippriority "
      "from customer, orders, lineitem "
      "where c_mktsegment = '{seg}' and c_custkey = o_custkey "
      "and l_orderkey = o_orderkey and o_orderdate < date '{day}' "
      "and l_shipdate > date '{day}' "
      "group by l_orderkey, o_orderdate, o_shippriority "
      "order by revenue desc, o_orderdate limit 10")


def _named(spans, name):
    return [s for s in spans if s.name == name]


def _trace_id() -> str:
    return f"t{time.monotonic_ns()}"


# -- the streamed scan --------------------------------------------------------

def test_streamed_statement_has_a_span_per_block_and_per_program(tpch_tiny):
    e = Engine()
    e.register_catalog("tpch", tpch_tiny)
    e.session.set("scan_block_rows", 16384)
    compiles = REGISTRY.counter("presto_tpu_programs_compiled_total")
    before = compiles.value()
    tid = _trace_id()
    with TRACER.trace(tid, "query"):
        e.execute(Q1)
    spans = TRACER.spans(tid)
    nblocks = e.last_streamed_blocks
    assert nblocks == -(-tpch_tiny.table("lineitem").nrows // 16384) >= 3
    for name in ("block-input", "transfer", "execute"):
        per_block = [s for s in _named(spans, name) if "block" in s.attrs]
        assert [s.attrs["block"] for s in per_block] == list(range(nblocks))
    rows = [s.attrs["rows"] for s in _named(spans, "block-input")]
    assert sum(rows) == tpch_tiny.table("lineitem").nrows
    assert all(s.attrs["bytes"] > 0 for s in _named(spans, "transfer"))
    # block 0 is copied before its program runs, every later block
    # while the block before it computes
    assert [s.attrs["ahead"] for s in _named(spans, "transfer")] == [
        i > 0 for i in range(nblocks)]
    assert all(s.attrs.get("streamed")
               for s in _named(spans, "execute") if "block" in s.attrs)
    # one compile span per program built: the block program (streamed)
    # and the final program over the partials, and the counter saw both
    built = _named(spans, "compile")
    assert [bool(s.attrs.get("streamed")) for s in built] == [True, False]
    assert compiles.value() - before == len(built)
    # the per-block syncs lie inside the block's execute span
    for ex in (s for s in _named(spans, "execute") if "block" in s.attrs):
        inner = [s.name for s in spans if s.parent_id == ex.span_id]
        assert inner == ["sync/streaming-ok-ladder", "sync/streaming-demux"]


# -- host syncs ---------------------------------------------------------------

@pytest.mark.parametrize("call,site,want", [
    (HS.fetch, "t-fetch", [1, 2, 3]),
    (HS.fetch_int, "t-fetch-int", 7),
    (HS.wait, "t-wait", None)])
def test_every_sync_under_a_trace_is_one_span(call, site, want):
    x = jnp.asarray(want if want is not None else [1.0, 2.0])
    seconds = REGISTRY.histogram("presto_tpu_device_sync_seconds")
    count0 = HS.SYNCS.value(site=site)
    timed0 = seconds.count(site=site)
    tid = _trace_id()
    with TRACER.trace(tid, "query"):
        for _ in range(3):
            got = call(x, site=site)
    if want is not None:
        assert np.array_equal(got, want)
    spans = _named(TRACER.spans(tid), "sync/" + site)
    assert len(spans) == HS.SYNCS.value(site=site) - count0 == 3
    assert seconds.count(site=site) - timed0 == 3
    assert all(s.t1 is not None and s.t1 >= s.t0 for s in spans)
    # outside a trace the counter and the histogram still move
    call(x, site=site)
    assert HS.SYNCS.value(site=site) - count0 == 4
    assert len(_named(TRACER.spans(tid), "sync/" + site)) == 3


# -- the statement after a write, through the server --------------------------

def _query_ids(base: str, sql: str) -> list[str]:
    req = urllib.request.Request(base + "/v1/query",
                                 headers={"X-Trino-User": "u"})
    with urllib.request.urlopen(req, timeout=10) as resp:
        return [q["queryId"] for q in json.loads(resp.read())
                if q["query"] == sql]


@pytest.fixture()
def served():
    engine = Engine()
    mem = MemoryConnector()
    engine.register_catalog("mem", mem)
    mem.create_table(
        "t", {"x": T.BIGINT, "g": T.BIGINT},
        {"x": np.array([10, 20, 30, 40], dtype=np.int64),
         "g": np.array([0, 1, 0, 1], dtype=np.int64)},
        {"x": None, "g": None})
    srv = CoordinatorServer(engine).start()
    yield engine, srv, f"http://127.0.0.1:{srv.port}"
    srv.stop()


def test_select_after_insert_names_its_parts_and_followers_wait(served):
    _engine, _srv, base = served
    sel = "select g, sum(x) as s from mem.t group by g order by g"
    c = Client(base, user="u")
    assert c.execute(sel)[1] == [[0, 40], [1, 60]]
    c.execute("insert into mem.t select x + 40, g from mem.t where x = 10")
    barrier = threading.Barrier(6)
    answers: list = []

    def run() -> None:
        mine = Client(base, user="u")
        barrier.wait()
        answers.append(mine.execute(sel)[1])

    threads = [threading.Thread(target=run) for _ in range(6)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert all(a == [[0, 90], [1, 60]] for a in answers)
    traces = [TRACER.spans(qid) for qid in _query_ids(base, sel)]
    traces = [spans for spans in traces if spans]
    names = [{s.name for s in spans} for spans in traces]
    # whoever executed over the new version collected its scans, padded
    # the five rows to their bucket and pinned the new arrays
    leaders = [spans for spans, have in zip(traces, names)
               if {"scan-collect", "bucket-pad", "pin"} <= have
               and _named(spans, "scan-collect")[0].attrs["rows"] == 5]
    assert leaders
    pads = [s.attrs["padded_bytes"] for spans in leaders
            for s in _named(spans, "bucket-pad")]
    assert max(pads) > 0
    assert all(s.attrs["bytes"] > 0 for spans in leaders
               for s in _named(spans, "pin"))
    assert _named(leaders[0], "scan-collect")[0].attrs["tables"] == 1
    # and a concurrent duplicate waited for it under a span of its own
    assert any("dedup-wait" in have for have in names)
    # every page was encoded under a span and handed over under another
    for spans in traces:
        encoded = _named(spans, "encode")
        assert encoded and encoded[0].attrs["rows"] == 2
        assert encoded[0].attrs["format"] == "json"
        assert len(_named(spans, "page-wait")) == len(encoded)


def test_fast_path_histograms_count_hits_and_plans_after_a_write(served):
    _engine, _srv, base = served
    hit = REGISTRY.histogram("presto_tpu_fast_hit_seconds")
    plan = REGISTRY.histogram("presto_tpu_fast_path_plan_seconds")
    texts = [f"select x from mem.t where x > {k} order by x"
             for k in (5, 15, 25)]
    c = Client(base, user="u")
    hits0, plans0 = hit.count(), plan.count()
    for sql in texts:
        c.execute(sql)          # memo empty: planned once, then a miss
    assert (hit.count() - hits0, plan.count() - plans0) == (0, 3)
    for _ in range(2):
        for sql in texts:
            c.execute(sql)      # one hit per repeated text, no plan
    assert (hit.count() - hits0, plan.count() - plans0) == (6, 3)
    assert hit.sum() > 0 and plan.sum() > 0
    c.execute("insert into mem.t select x + 40, g from mem.t where x = 10")  # clears the memo
    hits1, plans1 = hit.count(), plan.count()
    for sql in texts:
        c.execute(sql)          # one plan per text after the write
    assert (hit.count() - hits1, plan.count() - plans1) == (0, 3)
    # nothing is observed with the result cache off
    off = Client(base, user="u")
    off.session_properties = {"result_cache": False}
    hits2, plans2 = hit.count(), plan.count()
    off.execute(texts[0])
    assert (hit.count(), plan.count()) == (hits2, plans2)


# -- one clock ----------------------------------------------------------------

def test_now_is_monotone_and_spans_nest_on_it(served):
    readings = [OT.now() for _ in range(1000)]
    assert readings == sorted(readings)
    assert abs(OT.now() - time.time()) < 60  # epoch seconds, as before
    _engine, _srv, base = served
    sql = "select g, count(*) as c from mem.t group by g order by g"
    Client(base, user="u").execute(sql)
    spans = TRACER.spans(_query_ids(base, sql)[0])
    by_id = {s.span_id: s for s in spans}
    root = [s for s in spans if s.parent_id is None][0]
    assert root.name == "query"
    for s in spans:
        assert s.t1 is not None and s.t0 <= s.t1
        parent = by_id.get(s.parent_id)
        if parent is not None and s.name != "admission":
            assert parent.t0 <= s.t0 and s.t1 <= parent.t1, s.name
    admission = _named(spans, "admission")[0]
    # queueing ends where the statement starts: one reading, one clock
    assert admission.t1 == root.t0 and admission.t0 <= admission.t1


def test_the_257th_trace_is_an_eviction_that_is_counted():
    """Until PR 36 the store kept 256 traces and the 257th evicted one;
    it now keeps ``MAX_TRACES`` (4,096) of at most ``MAX_SPANS`` spans
    together, so the 257th evicts nothing and the 4,097th does."""
    evictions = REGISTRY.counter("presto_tpu_trace_evictions_total")
    before = evictions.value()
    tracer = Tracer()
    for i in range(257):
        with tracer.trace(f"e{i}", "query"):
            pass
    assert evictions.value() == before and tracer.spans("e0")
    for i in range(257, OT.MAX_TRACES):
        with tracer.trace(f"e{i}", "query"):
            pass
    assert evictions.value() == before
    with tracer.trace("one-more", "query"):
        pass
    assert evictions.value() == before + 1
    assert tracer.spans("e0") == [] and tracer.spans("e1")
    # a benchmark run's statements fit: 400 of 15 spans, set-up's too
    tracer = Tracer()
    for i in range(400):
        with tracer.trace(f"s{i}", "query"):
            for _ in range(14):
                with tracer.span("x"):
                    pass
    assert evictions.value() == before + 1
    assert len(tracer.spans("s0")) == 15


# -- annotations --------------------------------------------------------------

def test_a_span_with_no_profiler_running_raises_nothing_and_is_cheap():
    tid = _trace_id()
    best = float("inf")
    with TRACER.trace(tid, "query"):
        for _ in range(5):
            t = time.perf_counter()
            for _ in range(500):
                with TRACER.span("x", k=1):
                    pass
            best = min(best, (time.perf_counter() - t) / 500)
    assert len(_named(TRACER.spans(tid), "x")) == 2500
    # a count of work (two clock readings, one record, one annotation
    # that is a flag test), not a chip number: the best of five batches
    assert best < 50e-6, f"one span cost {best * 1e6:.1f} us"


def test_no_annotation_name_reads_as_host_work_to_the_benchmark():
    """``benchmark/tracered.HOST_WORK`` names a gap after the runtime's
    own host events by substring; a span's twin (``pt:<name>``) in the
    same host plane must never match one."""
    needles = ("backend_compile_and_load", "TransferToDevice", "Linearize",
               "DevicePut", "TransferFromDevice", "Delinearize",
               "X64FromTuple", "ToLiteral", "copy_to_host")
    src = "".join(p.read_text() for p in
                  (REPO / "presto_tpu").rglob("*.py"))
    names = set(re.findall(r'TRACER\.(?:span|trace|root_or_span)\(\s*'
                           r'(?:[\w.]+,\s*)?"([^"]+)"', src))
    assert {"compile", "execute", "transfer", "pin", "block-input",
            "scan-collect", "bucket-pad", "dedup-wait", "encode",
            "page-wait"} <= names
    names |= {"sync/" + site for site in
              re.findall(r'site="([^"]+)"', src)}
    for name in names:
        assert not any(n in OT.ANNOTATION_PREFIX + name for n in needles)
    assert "template-hit" not in names


# -- device operations carry the plan operator's name --------------------------

def _q3_lowered(engine, seg: str, day: str):
    plan, _ = engine.plan_sql(Q3.format(seg=seg, day=day))
    scans = TPL.bucket_scans(engine, collect_scans(plan, engine))
    tpl = TPL.parameterize(plan)
    pargs = tpl.example_args()
    fn, flat, _meta = make_traced(scans, tpl.plan, {}, engine.session,
                                  params=pargs)
    fn.__name__ = program_name(tpl.plan, plan_fingerprint(tpl.plan))
    return jax.jit(fn).lower(*flat, *pargs).as_text(debug_info=True)


def test_q3_program_carries_operator_scopes_and_a_stable_name(engine):
    texts = [_q3_lowered(engine, "BUILDING", "1995-03-15"),
             _q3_lowered(engine, "MACHINERY", "1995-03-20")]
    modules = [re.search(r"module @(\S+)", t).group(1) for t in texts]
    assert modules[0] == modules[1]
    assert re.fullmatch(r"jit_output_[0-9a-f]{8}", modules[0])
    scopes = set(re.findall(r"\b([A-Z][A-Za-z]*#\d+)\b", texts[0]))
    kinds = {s.split("#")[0] for s in scopes}
    assert {"Join", "Aggregate", "TopN", "Output"} <= kinds
    # preorder positions, never id(node): small, and the root is 0
    assert "Output#0" in scopes
    assert max(int(s.split("#")[1]) for s in scopes) < 64
    # run() recurses, so the scopes nest along the plan
    assert re.search(r"Output#0/TopN#\d+/[\w#/]*Aggregate#\d+/[\w#/]*"
                     r"Join#\d+", texts[0])


def test_a_program_without_a_template_is_named_by_its_root_kind_alone(
        engine):
    plan, _ = engine.plan_sql(Q1)
    assert program_name(plan) == "output"
    assert program_name(plan, prefix="stream_") == "stream_output"
    assert program_name(plan, "0123456789abcdef") == "output_01234567"
