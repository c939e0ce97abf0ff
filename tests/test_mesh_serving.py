"""The served path over a mesh: with ``mesh_devices=4`` in a statement's
session, ``/v1/statement`` plans it for four shards and runs it as one
shard_map program over four of the CPU's virtual devices, its scanned
columns pinned row-sharded across them. TPC-H Q1 and Q6 at SF 0.01 equal
the benchmark's plain NumPy references and the one-chip answers; the four
shards' partial aggregate states, merged on the host, equal the
reference; a table version is pinned once; and with the default property
the one-chip programs and their cache keys are what they were."""

from __future__ import annotations

import dataclasses
import hashlib
import os
import sys

import jax
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "benchmark"))

import protocol  # noqa: E402
import refdata  # noqa: E402
import traffic  # noqa: E402
import verify  # noqa: E402
from reference.common import avg_half_up, dec  # noqa: E402

from presto_tpu import Engine  # noqa: E402
from presto_tpu.connectors.memory import MemoryConnector  # noqa: E402
from presto_tpu.connectors.tpch import TpchConnector  # noqa: E402
from presto_tpu.exec import operators as OP  # noqa: E402
from presto_tpu.exec import progcache as PC  # noqa: E402
from presto_tpu.exec.executor import collect_scans, preorder_index  # noqa: E402
from presto_tpu.obs.metrics import REGISTRY  # noqa: E402
from presto_tpu.obs.trace import TRACER  # noqa: E402
from presto_tpu.parallel import executor as PX  # noqa: E402
from presto_tpu.parallel import pins  # noqa: E402
from presto_tpu.plan import nodes as N  # noqa: E402
from presto_tpu.server.server import CoordinatorServer  # noqa: E402

SEED = 2147483693
DEVICES = 4
MESH = {"mesh_devices": DEVICES, "result_cache": "false"}
ONE = {"result_cache": "false"}
# points spread over each class's domain (clauses 2.4.6.3 and 2.4.1.3)
Q06 = [{"DATE": d, "DISC_LO": lo, "DISC_HI": hi, "QUANTITY": q}
       for d, lo, hi, q in (("1993-01-01", "0.01", "0.03", "24"),
                            ("1994-01-01", "0.05", "0.07", "24"),
                            ("1996-01-01", "0.04", "0.06", "25"),
                            ("1997-01-01", "0.08", "0.10", "25"))]
Q01 = [{"DELTA": d} for d in ("60", "75", "90", "120")]
CASES = [("q06", p) for p in Q06] + [("q01", p) for p in Q01]


def counter(name: str) -> float:
    return REGISTRY.counter(name).total()


@pytest.fixture(scope="module")
def conn():
    return TpchConnector(scale=0.01, seed=SEED, tables=["lineitem"])


@pytest.fixture(scope="module")
def served(conn):
    """(engine, ask): ``ask(sql, session) -> (query id, rows)`` over the
    harness's own protocol client, so the session properties travel in
    the header and are typed by ``coerce_property``."""
    engine = Engine()
    engine.register_catalog("tpch", conn)
    engine.register_catalog("memory", MemoryConnector())
    server = CoordinatorServer(engine).start()
    links: dict = {}

    def ask(sql, session):
        key = tuple(sorted(session.items()))
        if key not in links:
            links[key] = protocol.Connection(server.uri, session,
                                             timeout_s=600.0)
        return links[key].execute(sql)

    try:
        yield engine, ask
    finally:
        for link in links.values():
            link.close()
        server.stop()


@pytest.mark.parametrize(
    "cls_name,params", CASES,
    ids=[f"{c}-{'-'.join(p.values())}" for c, p in CASES])
def test_mesh_answer_equals_reference_and_one_chip(served, conn, cls_name,
                                                   params):
    _engine, ask = served
    sql = traffic.statement(traffic.load_class(cls_name), params)
    want = verify.load_reference(cls_name)(refdata.Columns(conn), params)
    before = REGISTRY.counter("presto_tpu_mesh_statements_total").value(
        devices=DEVICES)
    qid, rows = ask(sql, MESH)
    assert rows == want
    assert ask(sql, ONE)[1] == want
    # the statement ran on the mesh, priced for its four shards, and
    # the one-chip statement did not
    assert REGISTRY.counter("presto_tpu_mesh_statements_total").value(
        devices=DEVICES) == before + 1
    spans = {s.name: s for s in TRACER.spans(qid)}
    assert spans["plan"].attrs["nshards"] == DEVICES
    assert spans["execute"].attrs["devices"] == DEVICES


def test_a_table_version_is_pinned_once(served):
    _engine, ask = served
    cls = traffic.load_class("q06")
    ask(traffic.statement(cls, Q06[0]), MESH)
    pinned = (counter("presto_tpu_shard_pins_total"),
              counter("presto_tpu_shard_pin_bytes_total"))
    assert pinned[0] > 0
    qid, _rows = ask(traffic.statement(cls, Q06[1]), MESH)
    qid2, _rows = ask(traffic.statement(traffic.load_class("q01"), Q01[0]),
                      MESH)
    assert (counter("presto_tpu_shard_pins_total"),
            counter("presto_tpu_shard_pin_bytes_total")) == pinned
    assert not [s for q in (qid, qid2) for s in TRACER.spans(q)
                if s.name == "shard-pin"]


def test_an_insert_makes_the_next_statement_pin_again_and_see_the_rows(
        served):
    _engine, ask = served
    ask("create table memory.default.mesh_t as select l_orderkey, "
        "l_quantity from lineitem where l_orderkey < 500", MESH)
    total = "select count(*), sum(l_quantity) from memory.default.mesh_t"
    n, qty = ask(total, MESH)[1][0]
    assert (n, qty) == tuple(ask(total, ONE)[1][0])
    pinned = counter("presto_tpu_shard_pins_total")
    assert ask(total, MESH)[1] == [[n, qty]]
    assert counter("presto_tpu_shard_pins_total") == pinned
    more = ("select l_orderkey, l_quantity from lineitem "
            "where l_orderkey between 500 and 520")
    m, mqty = ask(f"select count(*), sum(l_quantity) from ({more})",
                  ONE)[1][0]
    assert m % DEVICES  # the table grows by no multiple of the mesh
    ask(f"insert into memory.default.mesh_t {more}", MESH)
    qid, rows = ask(total, MESH)
    cents = lambda d: int(d.replace(".", ""))  # noqa: E731
    assert rows == [[n + m, dec(cents(qty) + cents(mqty), 2)]]
    assert counter("presto_tpu_shard_pins_total") > pinned
    pin_spans = [s for s in TRACER.spans(qid) if s.name == "shard-pin"]
    assert pin_spans and all(
        s.attrs["devices"] == DEVICES and s.attrs["table"] == "mesh_t"
        and s.attrs["bytes"] > 0 and s.attrs["column"]
        for s in pin_spans)


def test_a_column_is_placed_in_table_order_and_padded_with_dead_rows(conn):
    engine = Engine()
    engine.session.set("mesh_devices", DEVICES)
    mesh = engine.session_mesh()
    assert engine.session_mesh() is mesh and mesh.devices.size == DEVICES
    # the mesh is the deployment's layout: one, for every statement
    engine.session.set("mesh_devices", 2)
    with pytest.raises(ValueError, match="deployment's mesh"):
        engine.session_mesh()
    engine.session.set("mesh_devices", 1)
    assert engine.session_mesh() is None
    col = np.asarray(conn.table("lineitem").columns["l_quantity"].data)
    n = col.shape[0]
    assert n % DEVICES  # the case in which the last shard is padded
    placed = pins.place(col, mesh)
    per = pins.shard_rows(n, DEVICES)
    # a device's share, rounded up by at most 1/32 (the shape that the
    # tables of other seeds share), and no shard without a live row
    assert n / DEVICES <= per <= n / DEVICES * (1 + 1 / 32) + 1
    assert placed.shape == (per * DEVICES,) and (DEVICES - 1) * per < n
    assert pins.shard_rows(n + 100, DEVICES) == per
    assert pins.shard_rows(45_001_125 * 4, 4) == pins.shard_rows(
        44_997_148 * 4, 4) == 43 << 20
    shards = sorted(placed.addressable_shards, key=lambda s: s.index)
    assert [s.data.shape for s in shards] == [(per,)] * DEVICES
    back = np.concatenate([np.asarray(s.data) for s in shards])
    assert (back[:n] == col).all() and not back[n:].any()


def shard_states(engine, sql):
    """(state column names, their values stacked shard after shard, the
    slots' live mask): each shard's PARTIAL aggregate over its own rows
    of the pinned table, as the mesh program computes it before the
    exchange, through the program's own interpreter and operators."""
    mesh = engine.session_mesh()
    plan, _ = engine.plan_sql(sql, nshards=DEVICES)
    agg = plan
    while not isinstance(agg, N.Aggregate):
        (agg,) = agg.sources()
    (scan,) = collect_scans(plan, engine)
    arrays = PX._pinned_scan_arrays(engine, scan, mesh)
    partial = dataclasses.replace(agg, step=N.AggStep.PARTIAL)
    names: list = []

    def shard(rows, *cols):
        interp = PX.ShardedInterpreter(
            {id(scan.node): (scan, dict(zip(arrays, cols)), rows)}, {},
            DEVICES, engine.session, preorder_index(plan))
        interp.collect_counts = False
        out, _ok = OP.apply_aggregate(interp.run(agg.source).dt, partial,
                                      16 if agg.group_keys else 1)
        names[:] = list(out.cols)
        return tuple(v.data for v in out.cols.values()), out.live_mask()

    cols, live = jax.jit(jax.shard_map(
        shard, mesh=mesh,
        in_specs=(P(),) + tuple(P(PX.AXIS) for _ in arrays),
        out_specs=P(PX.AXIS), check_vma=False))(
        np.int32(scan.nrows), *arrays.values())
    return names, [np.asarray(c) for c in cols], np.asarray(live)


def merged(names, cols, live, sym):
    """Per group slot, the sum over the four shards of one aggregate's
    state as Python ints: a short sum's ``$sum``, or a long decimal's
    three limbs (``$a`` + ``$b`` << 32 + ``$hi`` << 64)."""
    by = dict(zip(names, cols))
    slots = live.shape[0] // DEVICES

    def total(field):
        return [sum(int(by[f"{sym}${field}"][k * slots + g])
                    for k in range(DEVICES) if live[k * slots + g])
                for g in range(slots)]

    if f"{sym}$sum" in by:
        return total("sum")
    return [a + (b << 32) + (hi << 64) for a, b, hi in
            zip(total("a"), total("b"), total("hi"))]


@pytest.mark.parametrize("params", [Q01[0], Q01[2]],
                         ids=lambda p: p["DELTA"])
def test_q01_shard_states_merged_on_the_host_equal_the_reference(
        conn, params):
    engine = Engine()
    engine.register_catalog("tpch", conn)
    engine.session.set("mesh_devices", DEVICES)
    engine.session.set("plan_templates", False)
    sql = traffic.statement(traffic.load_class("q01"), params)
    names, cols, live = shard_states(engine, sql)
    by = dict(zip(names, cols))
    aggs = {}  # output name -> the aggregate's symbol, in select order
    for n in names:
        if "$" in n:
            aggs.setdefault(n.split("$")[0], None)
    (s_qty, s_base, s_disc_price, s_charge, a_qty, a_price, a_disc,
     count) = list(aggs)
    slots = live.shape[0] // DEVICES
    counts = [sum(int(by[f"{count}$count"][k * slots + g])
                  for k in range(DEVICES) if live[k * slots + g])
              for g in range(slots)]
    # dead rows pad the last shard only: every live row is counted once
    assert sum(counts) == sum(r[-1] for r in verify.load_reference("q01")(
        refdata.Columns(conn), {"DELTA": params["DELTA"]}))
    rf = [k for k in names if k.startswith("l_returnflag")][0]
    ls = [k for k in names if k.startswith("l_linestatus")][0]
    data = refdata.Columns(conn)
    rows = []
    for g in range(slots):
        if not counts[g]:
            continue
        n = counts[g]
        rows.append([
            str(data.dictionary("lineitem", "l_returnflag")[by[rf][g]]),
            str(data.dictionary("lineitem", "l_linestatus")[by[ls][g]]),
            dec(merged(names, cols, live, s_qty)[g], 2),
            dec(merged(names, cols, live, s_base)[g], 2),
            dec(merged(names, cols, live, s_disc_price)[g], 4),
            dec(merged(names, cols, live, s_charge)[g], 6),
            dec(avg_half_up(merged(names, cols, live, a_qty)[g], n), 2),
            dec(avg_half_up(merged(names, cols, live, a_price)[g], n), 2),
            dec(avg_half_up(merged(names, cols, live, a_disc)[g], n), 2),
            n])
    assert rows == verify.load_reference("q01")(data, params)


def test_q06_shard_states_merged_on_the_host_equal_the_reference(conn):
    engine = Engine()
    engine.register_catalog("tpch", conn)
    engine.session.set("mesh_devices", DEVICES)
    engine.session.set("plan_templates", False)
    sql = traffic.statement(traffic.load_class("q06"), Q06[1])
    names, cols, live = shard_states(engine, sql)
    (sym,) = {n.split("$")[0] for n in names}
    assert live.shape[0] == DEVICES and live.all()  # one slot a shard
    assert [[dec(merged(names, cols, live, sym)[0], 4)]] == \
        verify.load_reference("q06")(refdata.Columns(conn), Q06[1])


def test_the_default_property_leaves_the_one_chip_programs_as_they_were(
        conn, monkeypatch):
    """``mesh_devices`` is in no program-cache key and the default takes
    the path it took: without the property, and with it at 1, Q1 and Q6
    lower to the same text under the same keys, resident and streamed,
    and no statement runs on a mesh. (That these equal the parent
    commit's was held by hand, PERF.md section 6, PR 34.)"""
    assert "mesh_devices" not in PC.TRACE_RELEVANT_PROPERTIES
    seen: list = []
    compile_ = jax.stages.Lowered.compile
    lookup = PC.ProgramCache.lookup

    def compiling(self, *a, **k):
        seen.append(hashlib.sha256(self.as_text().encode()).hexdigest())
        return compile_(self, *a, **k)

    def looking(self, key, *a, **k):
        seen.append(hashlib.sha256(repr(key).encode()).hexdigest())
        return lookup(self, key, *a, **k)

    monkeypatch.setattr(jax.stages.Lowered, "compile", compiling)
    monkeypatch.setattr(PC.ProgramCache, "lookup", looking)
    mesh_statements = counter("presto_tpu_mesh_statements_total")

    def programs(**props):
        engine = Engine()
        engine.register_catalog("tpch", conn)
        for k, v in props.items():
            engine.session.set(k, v)
        del seen[:]
        for cls_name, params in (("q06", Q06[0]), ("q01", Q01[0])):
            engine.execute(traffic.statement(traffic.load_class(cls_name),
                                             params))
        return list(seen)

    for streamed in ({}, {"scan_block_rows": 16384}):
        default = programs(**streamed)
        assert len(default) >= 4  # a key and a program a statement
        assert programs(mesh_devices=1, **streamed) == default
    assert counter("presto_tpu_mesh_statements_total") == mesh_statements


# -- the cell, rehearsed ------------------------------------------------------

CELL = "tpch_sf30_mesh4.scan4"
NEW_METRICS = {"mesh.shard_pin_s", "mesh.repinned_bytes_per_query",
               "mesh.collective_ms", "mesh.busy_skew",
               "mesh.q06_roofline", "mesh.q01_roofline"}
# what of them a run without a device trace can give
CPU_METRICS = {"mesh.shard_pin_s", "mesh.repinned_bytes_per_query"}
# PR 36's, listed in every cell: set-up read from the program's spans
SETUP_METRICS = {"setup.import_s", "setup.trace_lower_s",
                 "setup.xla_compile_s", "setup.cache_load_s",
                 "setup.pin_s", "setup.unattributed_s",
                 "compile.window_backend_compiles"}


@pytest.fixture(scope="module")
def tiny_cell(tmp_path_factory):
    """A copy of the benchmark in which ``tpch_sf30_mesh4`` has a twin
    at SF 0.01 and the cell a twin under it, with the metrics it has."""
    import json
    import shutil
    root = tmp_path_factory.mktemp("tiny_mesh")
    shutil.copytree(os.path.join(REPO, "benchmark"), root / "benchmark",
                    ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    os.symlink(os.path.join(REPO, "presto_tpu"), root / "presto_tpu")
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    (config,) = [c for c in manifest["configs"]
                 if c["name"] == "tpch_sf30_mesh4"]
    with open(os.path.join(REPO, config["file"])) as f:
        body = json.load(f)
    assert body["chips"] == DEVICES and body["mesh"] is None
    assert body["session"] == {"mesh_devices": DEVICES}
    body["scale_factor"] = 0.01
    (root / "benchmark" / "configs" / "tiny_mesh4.json").write_text(
        json.dumps(body))
    manifest["configs"].append({
        **config, "name": "tiny_mesh4",
        "file": "benchmark/configs/tiny_mesh4.json"})
    (cell,) = [w for w in manifest["workloads"] if w["name"] == CELL]
    assert cell["chips"] == DEVICES and cell["traffic"] == "scan4"
    with open(os.path.join(REPO, "benchmark", "traffic", "scan4.json")) as f:
        # ISSUE 34's traced sub-window, not a shorter one
        assert json.load(f)["trace"] == {"start_s": 0.0, "seconds": 10.0}
    manifest["workloads"].append({**cell, "name": "tiny_mesh4.scan4",
                                  "config": "tiny_mesh4"})
    listed = set()
    for m in manifest["per_layer"]:
        if CELL in m.get("workloads", ()):
            m["workloads"].append("tiny_mesh4.scan4")
            listed.add(m["name"])
    assert listed == NEW_METRICS | SETUP_METRICS
    (root / "BENCHMARK.json").write_text(json.dumps(manifest))
    return root


@pytest.mark.parametrize("trace", [0, 1], ids=["untraced", "traced"])
def test_rehearsal_of_the_cell_on_the_cpu(tiny_cell, trace):
    import json
    import subprocess
    env = {**os.environ, "BENCH_ALLOW_CPU": "1", "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": f"--xla_force_host_platform_device_count={DEVICES}"}
    proc = subprocess.run(
        [sys.executable, str(tiny_cell / "benchmark" / "run.py"),
         "--workload", "tiny_mesh4.scan4", "--seed", "2147483777",
         "--seconds", "2", "--trace", str(trace)],
        cwd=tiny_cell, env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-2000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] >= 6
    assert out["device"]["platform"] == "cpu"
    assert out["device"]["count"] == DEVICES
    got = set(out["metrics"])
    if not trace:
        assert {"setup_s", "geomean_ms", "qph"} <= got
        return
    assert got & NEW_METRICS == CPU_METRICS
    assert out["metrics"]["mesh.shard_pin_s"]["value"] > 0
    # the store still holds set-up when the window has closed, and the
    # spans' seconds hold what the histogram summed from them
    assert SETUP_METRICS <= got
    assert (out["metrics"]["setup.pin_s"]["value"]
            >= out["metrics"]["mesh.shard_pin_s"]["value"] - 1e-6)
    assert out["metrics"]["compile.window_backend_compiles"]["value"] == 0
    # the table lives on the mesh: no statement of the window moved it
    assert out["metrics"]["mesh.repinned_bytes_per_query"]["value"] == 0
    assert out["metrics"]["compile.window_compiles"]["value"] == 0
    assert {"execute.execute_ms", "plan.plan_ms", "server.other_ms",
            "hostsync.syncs_per_query", "setup.datagen_s",
            "setup.first_exec_s"} <= got
