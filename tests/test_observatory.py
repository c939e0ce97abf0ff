"""Device-cost observatory (obs/devprof.py + server/ui.py): XLA cost
harvesting into progcache meta (warm disk hits in a fresh process still
carry costs), per-operator flops/hbm/roofline columns on
system.operator_stats, the flops-share execute-wall split, live
monotonic query progress, on-demand jax.profiler capture, and the /ui
dashboard + per-query observatory page."""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time
import urllib.error
import urllib.request

import pytest

from presto_tpu import Engine
from presto_tpu.client import Client
from presto_tpu.obs import devprof
from presto_tpu.parallel.coordinator import ClusterCoordinator
from presto_tpu.parallel.worker import WorkerServer
from presto_tpu.server import CoordinatorServer

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

Q5 = """
select n_name, sum(l_extendedprice * (1 - l_discount)) as revenue
from customer, orders, lineitem, supplier, nation, region
where c_custkey = o_custkey and l_orderkey = o_orderkey
  and l_suppkey = s_suppkey and c_nationkey = s_nationkey
  and s_nationkey = n_nationkey and n_regionkey = r_regionkey
  and r_name = 'ASIA' and o_orderdate >= date '1994-01-01'
  and o_orderdate < date '1995-01-01'
group by n_name order by revenue desc
"""


# -- harvest + attribution units ---------------------------------------------

def test_harvest_live_compiled_program_and_pickles():
    """harvest() reads a real AOT Compiled's cost/memory analyses into
    a plain picklable dict (it rides the progcache meta to disk)."""
    import pickle

    import jax
    import jax.numpy as jnp

    compiled = jax.jit(
        lambda x: jnp.dot(x, x).sum()).lower(
            jnp.ones((64, 64), jnp.float32)).compile()
    cost = devprof.harvest(compiled)
    assert cost is not None
    assert cost.get("flops", 0) > 0
    assert devprof.program_bytes(cost) > 0
    pickle.loads(pickle.dumps(cost))  # must survive the disk tier
    # duck-typed: an object without the analyses yields None, not a
    # crash (cost harvesting must never fail a compile)
    assert devprof.harvest(object()) is None


def test_device_peaks_table_and_env_override(monkeypatch, tpch_tiny):
    monkeypatch.delenv(devprof.ENV_PEAK_FLOPS, raising=False)
    monkeypatch.delenv(devprof.ENV_PEAK_BW, raising=False)
    # the suite runs on the cpu platform: its (labelled) table entry
    assert devprof.device_peaks() == devprof.DEVICE_PEAKS["cpu"]
    assert devprof.DEVICE_PEAKS["TPU v5 lite"] == (197e12, 819e9)
    pb = devprof.DEVICE_PEAKS["cpu"][1]
    monkeypatch.setenv(devprof.ENV_PEAK_FLOPS, "1e12")
    monkeypatch.setenv(devprof.ENV_PEAK_BW, "garbage")
    assert devprof.device_peaks() == (1e12, pb)  # garbage -> table
    # a device that is not in the table has no peaks and no roofline:
    # never a ratio computed from another device's figures
    monkeypatch.setattr(devprof, "DEVICE_PEAKS", {})
    assert devprof.device_peaks() is None
    per_node, _w = devprof.attribute(
        {"flops": 1e6, "bytes": 1e6}, [("Join", 10, 10, 80)])
    assert per_node[0]["flops"] > 0 and "roofline" not in per_node[0]
    # ... and SQL shows NULL for it, not a 0.0 ratio
    engine = Engine()
    engine.register_catalog("tpch", tpch_tiny)
    stats = ("select query_id, roofline from system.operator_stats "
             "where flops > 0")
    earlier = {r[0] for r in engine.execute(stats)}  # process-wide table
    engine.execute("select count(*) from region where r_regionkey < 3")
    costed = [r for r in engine.execute(stats) if r[0] not in earlier]
    assert costed and all(r[1] is None for r in costed), costed
    monkeypatch.setenv(devprof.ENV_PEAK_BW, "2e10")  # both overridden
    assert devprof.device_peaks() == (1e12, 2e10)


def test_wall_split_regression_cheap_wide_vs_expensive_narrow():
    """THE satellite-1 pin: under the old rows-proportional split a
    cheap-wide TableScan absorbed an expensive-narrow Join's wall
    (equal rows-through => equal wall). With a cost summary available
    the split uses kind-weighted flop shares, so the Join's share
    rises strictly above its rows share and dominates."""
    nodes = [("TableScan", 0, 10_000, 80_000),
             ("Join", 10_000, 100, 800)]
    rows_w = [0 + 10_000 + 1, 10_000 + 100 + 1]
    join_rows_share = rows_w[1] / sum(rows_w)

    cost = {"flops": 1e9, "bytes": 1e8}
    per_node, fw = devprof.attribute(cost, nodes)
    assert fw is not None and len(fw) == 2
    join_flops_share = fw[1] / sum(fw)
    # rows split: ~50/50 (the absorption bug); flops split: Join ~8x
    assert join_rows_share < 0.55
    assert join_flops_share > 0.85
    assert join_flops_share > join_rows_share

    # attributed figures are positive, conserve the program total
    # (within rounding), and carry intensity/roofline
    for op in per_node:
        assert op["flops"] > 0 and op["hbmBytes"] > 0
        assert op["intensity"] > 0 and op["roofline"] > 0
    assert abs(sum(op["flops"] for op in per_node) - 1e9) < 2
    # the flop split rides the wall split downstream: simulate it
    wall = [100.0 * w / sum(fw) for w in fw]
    assert wall[1] > wall[0]  # the Join owns the wall now


def test_attribute_without_cost_falls_back_to_rows():
    """No cost summary (pre-cost1 meta, backend without cost_analysis)
    => empty per-op cost dicts and a None weight vector, telling
    qstats to keep the rows-proportional split."""
    nodes = [("TableScan", 0, 100, 800), ("Filter", 100, 10, 80)]
    per_node, fw = devprof.attribute(None, nodes)
    assert per_node == [{}, {}]
    assert fw is None
    assert devprof.attribute({"bytes": 5.0}, nodes)[1] is None
    assert devprof.attribute(None, []) == ([], None)


# -- live progress: recorder semantics ---------------------------------------

def test_recorder_progress_monotonic_across_replan():
    """The 0..1 estimate never goes backwards: dispatched stages count
    half their weight, an adaptive replan that re-weights (even
    shrinking the instantaneous fraction) is absorbed by the floor,
    0.99 caps while RUNNING, and 1.0 appears only on FINISHED."""
    from presto_tpu.obs.qstats import QueryRecorder

    qr = QueryRecorder("qprog_unit", "select 1", "tester")
    assert qr.progress() == 0.0
    qr.progress_plan({"s0": 100.0, "s1": 100.0})
    assert qr.progress() == 0.0
    qr.note_stage_dispatched("s0")
    p1 = qr.progress()
    assert 0.0 < p1 < 0.5  # half of s0's weight
    qr.note_stage_completed("s0")
    p2 = qr.progress()
    assert p2 > p1
    # adaptive replan triples the remaining work: the instantaneous
    # fraction would DROP (100/400 < 100/200); the floor holds it
    qr.progress_plan({"s0": 100.0, "s1": 300.0})
    p3 = qr.progress()
    assert p3 >= p2
    # a stage the plan never named still counts (default weight)
    qr.note_stage_completed("speculative-extra")
    qr.note_stage_completed("s1")
    p4 = qr.progress()
    assert p3 <= p4 <= 0.99  # all work done, still RUNNING: capped
    qr.close()
    assert qr.progress() == 1.0
    assert qr.snapshot()["progress"] == 1.0


# -- cluster fixture ---------------------------------------------------------

@pytest.fixture(scope="module")
def obs_cluster(tpch_tiny, tmp_path_factory, request):
    """2-worker cluster with a persistent program cache + profile dir:
    the fixture runs one cold distributed Q5 so its programs (and
    their harvested cost summaries) are on disk for the warm
    fresh-process acceptance test."""
    cache_dir = str(tmp_path_factory.mktemp("obs_progcache"))
    prof_dir = str(tmp_path_factory.mktemp("obs_profiles"))
    saved = {k: os.environ.get(k)
             for k in ("PRESTO_TPU_PROGRAM_CACHE_DIR",
                       "PRESTO_TPU_PROFILE_DIR")}
    os.environ["PRESTO_TPU_PROGRAM_CACHE_DIR"] = cache_dir
    os.environ["PRESTO_TPU_PROFILE_DIR"] = prof_dir
    workers = [
        WorkerServer({"tpch": tpch_tiny}, node_id=f"obsw{i}").start()
        for i in range(2)]
    engine = Engine()
    engine.register_catalog("tpch", tpch_tiny)
    engine.session.catalog = "tpch"
    coord = ClusterCoordinator(engine, heartbeat_interval_s=0.2).start()
    for w in workers:
        coord.add_worker(w.uri)
    srv = CoordinatorServer(engine, cluster=coord).start()

    def teardown():
        srv.stop()
        coord.stop()
        for w in workers:
            try:
                w.stop()
            except Exception:  # noqa: BLE001
                pass
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v

    request.addfinalizer(teardown)
    q5_qid = _run_to_finish(srv, Q5)  # cold: compiles + persists
    return srv, coord, workers, engine, cache_dir, q5_qid


def _get_json(url: str):
    with urllib.request.urlopen(url, timeout=30) as r:
        return json.loads(r.read())


def _get_html(url: str) -> tuple[int, str]:
    try:
        with urllib.request.urlopen(url, timeout=30) as r:
            return r.status, r.read().decode()
    except urllib.error.HTTPError as e:
        return e.code, e.read().decode()


def _post_json(url: str):
    req = urllib.request.Request(url, data=b"", method="POST")
    try:
        with urllib.request.urlopen(req, timeout=30) as r:
            return r.status, json.loads(r.read() or b"{}")
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read() or b"{}")


def _run_to_finish(srv, sql: str) -> str:
    c = Client(f"http://127.0.0.1:{srv.port}", user="tester")
    qid, _ = c.submit(sql)
    for _ in range(2400):
        if c.query_state(qid) not in ("QUEUED", "RUNNING"):
            break
        time.sleep(0.1)
    assert c.query_state(qid) == "FINISHED", c.query_state(qid)
    return qid


# -- cost columns on the distributed stats tree ------------------------------

def test_distributed_q5_operator_cost_columns(obs_cluster):
    """After a distributed Q5, system.operator_stats carries positive
    flops/hbm_bytes (and intensity/roofline derived from them) on the
    worker-stage operators — the compile-time harvest attributed over
    the plan, fetched back through worker TaskStats."""
    _srv, _coord, _workers, engine, _cache, qid = obs_cluster
    ops = engine.execute(
        f"select node_type, flops, hbm_bytes, intensity, roofline "
        f"from system.operator_stats where query_id = '{qid}'")
    assert ops
    costed = [r for r in ops if r[1] > 0]
    assert costed, ops  # at least the fragment programs harvested
    kinds = {r[0] for r in costed}
    assert "TableScan" in kinds
    for _nt, flops, hbm, intensity, roofline in costed:
        assert flops >= 1 and hbm >= 1
        assert intensity > 0 and roofline > 0
        # intensity is flops/bytes (scaled into SQL as a double)
        assert abs(intensity - flops / hbm) / max(intensity, 1e-9) < 0.01


def test_warm_fresh_process_q5_cost_columns(obs_cluster):
    """THE acceptance check: a FRESH process sharing the program-cache
    dir runs distributed Q5 with ZERO XLA compiles (pure disk hits)
    and system.operator_stats still reports positive flops/hbm_bytes —
    the cost summary rode the pickled progcache meta, it was not
    re-derived from a live Compiled."""
    _srv, _coord, _workers, _engine, cache_dir, _qid = obs_cluster
    assert [f for f in os.listdir(cache_dir) if f.endswith(".prog")]
    env = dict(os.environ,
               PRESTO_TPU_PROGRAM_CACHE_DIR=cache_dir,
               JAX_ENABLE_COMPILATION_CACHE="false", JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "-c", _WARM_CHILD], capture_output=True,
        text=True, timeout=540, cwd=REPO, env=env)
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["state"] == "FINISHED", out
    assert out["compiled"] == 0, out  # warm: zero XLA compiles
    assert out["disk_hits"] >= 1
    costed = [r for r in out["ops"] if r[1] > 0]
    assert costed, out["ops"]
    assert sum(r[1] for r in costed) > 0  # flops
    assert sum(r[2] for r in costed) > 0  # hbm_bytes


_WARM_CHILD = r"""
import json, sys, time
import jax
jax.config.update("jax_platforms", "cpu")
from presto_tpu import Engine
from presto_tpu.client import Client
from presto_tpu.connectors.tpch import TpchConnector
from presto_tpu.obs.metrics import REGISTRY
from presto_tpu.parallel.coordinator import ClusterCoordinator
from presto_tpu.parallel.worker import WorkerServer
from presto_tpu.server import CoordinatorServer

Q5 = '''
select n_name, sum(l_extendedprice * (1 - l_discount)) as revenue
from customer, orders, lineitem, supplier, nation, region
where c_custkey = o_custkey and l_orderkey = o_orderkey
  and l_suppkey = s_suppkey and c_nationkey = s_nationkey
  and s_nationkey = n_nationkey and n_regionkey = r_regionkey
  and r_name = 'ASIA' and o_orderdate >= date '1994-01-01'
  and o_orderdate < date '1995-01-01'
group by n_name order by revenue desc
'''

tpch = TpchConnector(scale=0.01)
workers = [WorkerServer({"tpch": tpch}, node_id=f"obsw{i}").start()
           for i in range(2)]
engine = Engine()
engine.register_catalog("tpch", tpch)
engine.session.catalog = "tpch"
coord = ClusterCoordinator(engine, heartbeat_interval_s=0.2).start()
for w in workers:
    coord.add_worker(w.uri)
srv = CoordinatorServer(engine, cluster=coord).start()
try:
    c = Client(f"http://127.0.0.1:{srv.port}", user="tester")
    qid, _ = c.submit(Q5)
    for _ in range(2400):
        if c.query_state(qid) not in ("QUEUED", "RUNNING"):
            break
        time.sleep(0.1)
    state = c.query_state(qid)
    # read the counters BEFORE the system-table probe below (which may
    # legitimately compile its own scan program)
    compiled = REGISTRY.counter(
        "presto_tpu_programs_compiled_total").value()
    disk_hits = REGISTRY.counter(
        "presto_tpu_program_cache_hits_total").value(tier="disk")
    ops = engine.execute(
        "select node_type, flops, hbm_bytes, intensity, roofline "
        "from system.operator_stats where query_id = '%s'" % qid)
    print(json.dumps({
        "state": state, "compiled": compiled, "disk_hits": disk_hits,
        "ops": [[r[0], float(r[1]), float(r[2]), float(r[3]),
                 float(r[4])] for r in ops]}))
finally:
    srv.stop()
    coord.stop()
    for w in workers:
        try:
            w.stop()
        except Exception:
            pass
"""


# -- live progress over HTTP -------------------------------------------------

def test_progress_monotonic_over_http_task_mode(obs_cluster):
    """Progress on GET /v1/query/{id} (and the protocol stats blob) is
    monotonically non-decreasing across polls of a multi-stage
    TASK-mode query and lands exactly at 1.0 on FINISHED."""
    srv, _coord, _workers, _engine, _cache, _qid = obs_cluster
    base = f"http://127.0.0.1:{srv.port}"
    c = Client(base, user="tester")
    c.session_properties["retry_policy"] = "TASK"
    qid, _ = c.submit(Q5)
    samples: list[float] = []
    for _ in range(2400):
        info = _get_json(f"{base}/v1/query/{qid}")
        p = info.get("stats", {}).get("progress")
        assert p is not None
        samples.append(float(p))
        if info.get("state") not in ("QUEUED", "RUNNING"):
            break
        time.sleep(0.02)
    assert info["state"] == "FINISHED", info.get("state")
    assert samples == sorted(samples), samples  # monotone
    assert samples[-1] == 1.0
    assert all(0.0 <= p <= 1.0 for p in samples)
    # the query listing carries it too
    listing = _get_json(f"{base}/v1/query")
    mine = next(q for q in listing if q["queryId"] == qid)
    assert mine["progress"] == 1.0

    # protocol path: client.execute streams the same monotone estimate
    # through on_progress and leaves 1.0 on last_progress
    seen: list[float] = []
    c2 = Client(base, user="tester")
    c2.execute("select count(*) from lineitem where l_quantity < 30",
               on_progress=seen.append)
    assert c2.last_progress == 1.0
    assert seen == sorted(seen)


# -- Web UI ------------------------------------------------------------------

def test_ui_dashboard_serves(obs_cluster):
    srv, _coord, _workers, _engine, _cache, _qid = obs_cluster
    status, html = _get_html(f"http://127.0.0.1:{srv.port}/ui")
    assert status == 200
    assert "presto-tpu coordinator" in html
    assert "Resource groups" in html
    # the dashboard polls the cluster + query APIs client-side
    assert "/v1/cluster" in html and "/v1/query" in html
    # / serves the same page
    status2, html2 = _get_html(f"http://127.0.0.1:{srv.port}/")
    assert status2 == 200 and "presto-tpu coordinator" in html2


def test_ui_query_page_renders_stats(obs_cluster):
    """The per-query observatory page embeds the stats snapshot: the
    Stage->Task->Operator tree with the device-cost columns and the
    trace export link."""
    srv, _coord, _workers, _engine, _cache, qid = obs_cluster
    status, html = _get_html(
        f"http://127.0.0.1:{srv.port}/ui/query/{qid}")
    assert status == 200
    assert qid in html
    for col in ("flops", "hbmBytes", "roofline", "wallMillis"):
        assert col in html, col
    assert f"/v1/query/{qid}/trace" in html
    # the embedded snapshot carries the finished stats tree
    assert '"state": "FINISHED"' in html

    status404, _ = _get_html(
        f"http://127.0.0.1:{srv.port}/ui/query/no_such_query")
    assert status404 == 404


# -- on-demand profiler ------------------------------------------------------

def test_profile_endpoints_produce_artifact(obs_cluster):
    """POST /v1/profile/start + /stop on the coordinator wrap live
    execution in a programmatic jax.profiler trace and return the
    artifact directory (skip-guarded: hosts without profiler support
    answer 503 on start)."""
    srv, _coord, workers, _engine, _cache, _qid = obs_cluster
    base = f"http://127.0.0.1:{srv.port}"
    status, res = _post_json(f"{base}/v1/profile/start")
    if status != 200 or not res.get("started"):
        _post_json(f"{base}/v1/profile/stop")
        pytest.skip(f"device profiler unsupported here: {res}")
    try:
        assert res["profiling"] is True
        # a second start is idempotent, reporting the live capture
        status2, res2 = _post_json(f"{base}/v1/profile/start")
        assert status2 == 200
        assert res2["dir"] == res["dir"] and not res2["started"]
        _run_to_finish(srv, "select count(*) from nation")
    finally:
        status3, res3 = _post_json(f"{base}/v1/profile/stop")
    assert status3 == 200
    artifact = res3.get("artifact")
    assert artifact == res["dir"]
    files = [os.path.join(r, f)
             for r, _d, fs in os.walk(artifact) for f in fs]
    assert files, f"empty profile artifact {artifact}"
    # stopping again is a clean no-op
    _status4, res4 = _post_json(f"{base}/v1/profile/stop")
    assert res4.get("artifact") is None

    # the worker exposes the same pair (its own process)
    statusw, resw = _post_json(f"{workers[0].uri}/v1/profile/start")
    if statusw == 200 and resw.get("started"):
        _statusw2, resw2 = _post_json(
            f"{workers[0].uri}/v1/profile/stop")
        assert resw2.get("artifact") == resw["dir"]


def test_device_profile_session_property(obs_cluster):
    """SET SESSION device_profile=true wraps each query in its own
    capture; the artifact directory lands on the query record
    (snapshot 'profile') without entering the program cache key."""
    from presto_tpu.exec import progcache as PC
    from presto_tpu.obs import qstats as QS

    assert "device_profile" not in PC.TRACE_RELEVANT_PROPERTIES
    srv, _coord, _workers, _engine, _cache, _qid = obs_cluster
    c = Client(f"http://127.0.0.1:{srv.port}", user="tester")
    c.session_properties["device_profile"] = "true"
    qid, _ = c.submit("select count(*) from region")
    for _ in range(600):
        if c.query_state(qid) not in ("QUEUED", "RUNNING"):
            break
        time.sleep(0.05)
    assert c.query_state(qid) == "FINISHED"
    rec = QS.STORE.get(qid)
    assert rec is not None
    artifact = rec.snapshot().get("profile")
    if artifact is None:
        pytest.skip("device profiler unsupported here")
    assert os.path.isdir(artifact)
    files = [f for _r, _d, fs in os.walk(artifact) for f in fs]
    assert files, f"empty per-query profile artifact {artifact}"
