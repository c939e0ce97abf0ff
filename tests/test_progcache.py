"""Compile-latency subsystem (exec/progcache.py): cache-key hygiene,
LRU bounding + metrics, persistent AOT disk store (fresh-process warm
start with ZERO XLA compiles), corruption fallback, and cross-worker
disk-store sharing."""

from __future__ import annotations

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from presto_tpu import Engine
from presto_tpu import types as T
from presto_tpu.connectors.memory import MemoryConnector
from presto_tpu.exec import executor as ex
from presto_tpu.exec import progcache as PC
from presto_tpu.obs.metrics import REGISTRY

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_COMPILED = REGISTRY.counter("presto_tpu_programs_compiled_total")
_HITS = REGISTRY.counter("presto_tpu_program_cache_hits_total")
_MISSES = REGISTRY.counter("presto_tpu_program_cache_misses_total")
_EVICTIONS = REGISTRY.counter(
    "presto_tpu_program_cache_evictions_total")
_DISK_ERRORS = REGISTRY.counter(
    "presto_tpu_program_cache_disk_errors_total")


def mem_engine(nrows: int = 4096, cache_dir=None) -> Engine:
    if cache_dir is not None:
        os.environ[PC.ENV_DIR] = str(cache_dir)
    conn = MemoryConnector()
    conn.create_table(
        "t", {"k": T.BIGINT, "v": T.BIGINT},
        {"k": np.arange(nrows) % 7, "v": np.arange(nrows)})
    e = Engine()
    e.register_catalog("mem", conn)
    e.session.catalog = "mem"
    return e


# -- cache-key hygiene -------------------------------------------------------

def test_key_stable_across_replans(tpch_tiny):
    e = Engine()
    e.register_catalog("tpch", tpch_tiny)
    sql = "select count(*) from lineitem where l_quantity < 10"
    p1, _ = e.plan_sql(sql)
    p2, _ = e.plan_sql(sql)
    s1 = ex.collect_scans(p1, e)
    s2 = ex.collect_scans(p2, e)
    assert ex._cache_key(e, p1, s1, {}) == ex._cache_key(e, p2, s2, {})


def test_key_changes_with_plan_fingerprint(tpch_tiny):
    e = Engine()
    e.register_catalog("tpch", tpch_tiny)
    p1, _ = e.plan_sql("select count(*) from lineitem")
    p2, _ = e.plan_sql("select count(*) from orders")
    k1 = ex._cache_key(e, p1, ex.collect_scans(p1, e), {})
    k2 = ex._cache_key(e, p2, ex.collect_scans(p2, e), {})
    assert k1 != k2


def test_key_tracks_trace_relevant_session_only(tpch_tiny):
    e = Engine()
    e.register_catalog("tpch", tpch_tiny)
    plan, _ = e.plan_sql("select count(*) from lineitem")
    scans = ex.collect_scans(plan, e)
    base = ex._cache_key(e, plan, scans, {})
    # host-side limit: not read at trace time, must NOT shift the key
    e.session.set("query_max_run_time", 123.0)
    assert ex._cache_key(e, plan, scans, {}) == base
    # dynamic filtering changes the traced program: MUST shift the key
    e.session.set("enable_dynamic_filtering", False)
    assert ex._cache_key(e, plan, scans, {}) != base


def test_tracekey_rule_proves_cache_key_sound():
    """THE drift guard for the canonical session key, whole-tree: the
    tracekey provenance lint (lint/tracekey.py) must report zero
    findings on the real tree — every ambient input a trace-reachable
    unit reads (session property, env var, mutable module global,
    across aliases/parameters/helper calls) is either in
    TRACE_RELEVANT_PROPERTIES, folded into another key component, or
    exempted with a justification in TRACE_KEY_EXEMPT; and every
    TRACE_RELEVANT_PROPERTIES entry is genuinely read at trace time.
    This subsumes the retired two-class AST scan that inspected only
    direct ``self.session.get`` calls inside the interpreters
    (tests/test_lint.py keeps that shape as a positive fixture)."""
    from presto_tpu.lint import run_lint
    findings = run_lint([os.path.join(REPO, "presto_tpu")],
                        rules=["tracekey"])
    assert findings == [], "\n".join(f.format() for f in findings)


def test_pruned_property_shares_cached_program(tpch_tiny):
    """use_connector_partitioning was pruned from
    TRACE_RELEVANT_PROPERTIES on the tracekey stale-key-entry
    analysis: no trace-reachable code reads it (the bucketing decision
    it drives is host-side and rides the distributed key as the
    explicit per-scan ``(part_cols, bucketed)`` component). Two
    sessions differing ONLY in that property must therefore share one
    cached program — flipping it costs zero recompiles."""
    assert "use_connector_partitioning" not in \
        PC.TRACE_RELEVANT_PROPERTIES
    e = Engine()
    e.register_catalog("tpch", tpch_tiny)
    sql = "select count(*) from lineitem where l_quantity < 10"
    plan, _ = e.plan_sql(sql)
    scans = ex.collect_scans(plan, e)
    base = ex._cache_key(e, plan, scans, {})
    want = e.execute(sql)
    e.session.set("use_connector_partitioning", False)
    assert ex._cache_key(e, plan, scans, {}) == base
    c0 = _COMPILED.value()
    assert e.execute(sql) == want
    assert _COMPILED.value() == c0  # cache hit, zero recompiles


def test_key_changes_with_dictionary_content():
    """Traced programs embed dictionary codes as constants, so a data
    rewrite at constant shape/dtype must MISS — the disk store
    outlives process restarts, where identity-based invalidation
    cannot reach."""
    def key_for(values):
        conn = MemoryConnector()
        conn.create_table(
            "t", {"s": T.VARCHAR, "v": T.BIGINT},
            {"s": np.array(values, object), "v": np.arange(3)})
        e = Engine()
        e.register_catalog("mem", conn)
        e.session.catalog = "mem"
        plan, _ = e.plan_sql("select s, sum(v) from t group by s")
        return ex._cache_key(e, plan, ex.collect_scans(plan, e), {})

    assert key_for(["a", "b", "a"]) == key_for(["a", "b", "a"])
    assert key_for(["a", "b", "a"]) != key_for(["a", "c", "a"])


def test_capacities_bucket_to_pow2():
    k = (3, "table")
    assert PC.bucket_capacities({k: 100}) == PC.bucket_capacities(
        {k: 128})
    assert PC.bucket_capacities({k: 100}) != PC.bucket_capacities(
        {k: 300})
    # the bucketed value is what the trace uses, so idempotence matters
    assert PC.bucket_capacities({k: 128}) == ((k, 128),)


def test_digest_changes_with_platform_and_mesh():
    key = ("fp", (), ())
    local = PC.platform_fingerprint()
    import jax
    from jax.sharding import Mesh
    devs = jax.devices()
    meshed = PC.platform_fingerprint(
        mesh_shape=PC.mesh_key(Mesh(np.array(devs[:4]), ("d",))))
    assert PC.entry_digest(key, local) != PC.entry_digest(key, meshed)
    # same shape and axis names over other devices: another executable
    other = PC.platform_fingerprint(
        mesh_shape=PC.mesh_key(Mesh(np.array(devs[4:8]), ("d",))))
    assert PC.entry_digest(key, meshed) != PC.entry_digest(key, other)
    other_ver = ("jax-9.9.9",) + tuple(local[1:])
    assert PC.entry_digest(key, local) != PC.entry_digest(
        key, other_ver)
    assert PC.entry_digest(key, local) == PC.entry_digest(key, local)


# -- LRU bounding + metrics --------------------------------------------------

def test_lru_bounds_entries_and_counts_evictions():
    cache = PC.ProgramCache(max_entries=2, disk_dir=None)
    ev0 = _EVICTIONS.value()
    for i in range(4):
        cache.insert(("k", i), object(), {"i": i}, persist=False)
    assert len(cache) == 2
    assert _EVICTIONS.value() - ev0 == 2
    # LRU order: 0 and 1 evicted, 2 and 3 resident
    m0 = _MISSES.value()
    assert cache.lookup(("k", 0)) is None
    assert cache.lookup(("k", 3)) is not None
    assert _MISSES.value() - m0 == 1
    assert cache.stats()["bytes"] > 0
    g = REGISTRY.gauge("presto_tpu_program_cache_resident_bytes")
    assert g.value() >= 0


def test_lookup_refreshes_lru_recency():
    cache = PC.ProgramCache(max_entries=2, disk_dir=None)
    cache.insert(("k", "a"), object(), {}, persist=False)
    cache.insert(("k", "b"), object(), {}, persist=False)
    assert cache.lookup(("k", "a")) is not None  # a becomes newest
    cache.insert(("k", "c"), object(), {}, persist=False)  # evicts b
    assert cache.lookup(("k", "a")) is not None
    assert cache.lookup(("k", "b")) is None


def test_engine_program_cache_is_bounded(tpch_tiny):
    # the two queries must differ STRUCTURALLY: a literal-only change
    # is a plan-template hit now (templates/), which is exactly one
    # cached program and no eviction
    e = Engine()
    e.register_catalog("tpch", tpch_tiny)
    e.session.set("program_cache_entries", 1)
    ev0 = _EVICTIONS.value()
    for agg in ("count(*)", "sum(l_tax)"):
        e.execute(f"select {agg} from lineitem "
                  f"where l_quantity < 10")
    assert len(e._program_cache) == 1
    assert _EVICTIONS.value() > ev0


# -- persistent disk store ---------------------------------------------------

_CHILD = r"""
import json, sys
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np
from presto_tpu import Engine
from presto_tpu import types as T
from presto_tpu.connectors.memory import MemoryConnector
from presto_tpu.obs.metrics import REGISTRY

conn = MemoryConnector()
n = 4096
conn.create_table("t", {"k": T.BIGINT, "v": T.BIGINT},
                  {"k": np.arange(n) % 7, "v": np.arange(n)})
e = Engine()
e.register_catalog("mem", conn)
e.session.catalog = "mem"
rows = e.execute("select k, sum(v) from t group by k order by k")
print(json.dumps({
    "rows": [[float(x) for x in r] for r in rows],
    "compiled": REGISTRY.counter(
        "presto_tpu_programs_compiled_total").value(),
    "disk_hits": REGISTRY.counter(
        "presto_tpu_program_cache_hits_total").value(tier="disk")}))
"""


def _run_child(cache_dir) -> dict:
    env = dict(os.environ,
               PRESTO_TPU_PROGRAM_CACHE_DIR=str(cache_dir),
               JAX_ENABLE_COMPILATION_CACHE="false", JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "-c", _CHILD], capture_output=True,
        text=True, timeout=240, cwd=REPO, env=env)
    assert proc.returncode == 0, proc.stderr[-2000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_warm_process_compiles_nothing(tmp_path):
    """THE acceptance check: with PRESTO_TPU_PROGRAM_CACHE_DIR set, a
    second run of the same query in a FRESH process performs zero XLA
    compiles (presto_tpu_programs_compiled_total stays 0) and still
    returns identical rows."""
    cold = _run_child(tmp_path)
    assert cold["compiled"] >= 1
    assert [f for f in os.listdir(tmp_path) if f.endswith(".prog")]
    warm = _run_child(tmp_path)
    assert warm["compiled"] == 0, warm
    assert warm["disk_hits"] >= 1
    assert warm["rows"] == cold["rows"]


def test_disk_hit_runs_on_one_device_among_many(tmp_path, monkeypatch):
    """jax 0.9.0's deserialize_and_load binds an executable to EVERY
    device of the backend unless told which; a one-device program
    loaded that way "expects 8 shards" in this 8-virtual-device suite
    (and on a four-chip host). The store names the devices. A sortless
    program, so the CPU client can serialise it at all."""
    monkeypatch.setenv(PC.ENV_DIR, str(tmp_path))
    sql = "select sum(v), count(*) from t where k < 5"
    want = mem_engine().execute(sql)
    assert [f for f in os.listdir(tmp_path) if f.endswith(".prog")]
    d0, c0 = _HITS.value(tier="disk"), _COMPILED.value()
    assert mem_engine().execute(sql) == want  # fresh memory tier
    assert _HITS.value(tier="disk") - d0 >= 1
    assert _COMPILED.value() == c0


def test_mesh_disk_hit_loads_onto_the_mesh_devices(tmp_path,
                                                  monkeypatch):
    """A shard_map program is bound to its mesh's devices, whichever
    they are: a mesh over devices 4..7 must disk-hit in a fresh memory
    tier (loading onto the first four devices instead fails the load),
    and a same-shaped mesh over devices 0..3 is another program."""
    import jax
    from jax.sharding import Mesh
    monkeypatch.setenv(PC.ENV_DIR, str(tmp_path))
    sql = "select sum(v), count(*) from t where k < 5"
    upper = Mesh(np.array(jax.devices()[4:8]), ("d",))
    lower = Mesh(np.array(jax.devices()[:4]), ("d",))
    first = mem_engine()
    want = first.execute(sql, mesh=upper)
    d0, c0 = _HITS.value(tier="disk"), _COMPILED.value()
    e0 = _DISK_ERRORS.value(op="load")
    assert mem_engine().execute(sql, mesh=upper) == want
    assert _HITS.value(tier="disk") - d0 == 1
    assert _COMPILED.value() == c0
    assert _DISK_ERRORS.value(op="load") == e0
    assert first.execute(sql, mesh=lower) == want
    assert _COMPILED.value() - c0 == 1  # neither tier served it


def test_disk_hit_then_corruption_fallback(tmp_path, monkeypatch):
    """One disk-store lifecycle: engine A compiles + persists; engine B
    (fresh memory tier) disk-hits with zero new compiles; after the
    stored executables are truncated, engine C falls back to a live
    compile (miss + disk error counted, no crash, same rows)."""
    monkeypatch.setenv(PC.ENV_DIR, str(tmp_path))
    sql = "select k, sum(v) from t group by k order by k"
    want = mem_engine().execute(sql)
    progs = [f for f in os.listdir(tmp_path) if f.endswith(".prog")]
    assert progs
    d0 = _HITS.value(tier="disk")
    c0 = _COMPILED.value()
    got = mem_engine().execute(sql)
    assert got == want
    assert _COMPILED.value() == c0  # zero new compiles
    assert _HITS.value(tier="disk") - d0 >= 1
    for f in progs:  # truncate every stored executable mid-payload
        p = os.path.join(tmp_path, f)
        with open(p, "rb") as fh:
            blob = fh.read()
        with open(p, "wb") as fh:
            fh.write(blob[:max(len(blob) // 3, 1)])
    err0 = _DISK_ERRORS.value(op="load")
    c0 = _COMPILED.value()
    got = mem_engine().execute(sql)  # fresh engine: no memory tier
    assert got == want
    assert _COMPILED.value() - c0 >= 1  # live compile fallback
    assert _DISK_ERRORS.value(op="load") >= err0 + 1


def test_old_format_entry_misses_and_recompiles(tmp_path, monkeypatch):
    """PROGRAM_FORMAT ("dynf1": which join legs register a dynamic
    filter, and their counts in meta) rides the platform fingerprint,
    so entries persisted by an older engine land at a DIFFERENT digest
    — a clean miss, never a mis-unpack. And an old-shape blob that
    somehow sits at the current digest (hand-copied store, digest
    collision) degrades to disk_error + miss + live compile, not a
    crash."""
    # the format string participates in the digest
    key = ("fp", (), ())
    fp = PC.platform_fingerprint()
    assert PC.PROGRAM_FORMAT == "dynf1"
    assert PC.PROGRAM_FORMAT in fp
    old_fp = tuple("cost1" if x == PC.PROGRAM_FORMAT else x for x in fp)
    assert PC.entry_digest(key, fp) != PC.entry_digest(key, old_fp)

    monkeypatch.setenv(PC.ENV_DIR, str(tmp_path))
    sql = "select k, sum(v) from t group by k order by k"
    want = mem_engine().execute(sql)
    progs = [f for f in os.listdir(tmp_path) if f.endswith(".prog")]
    assert progs
    # rewrite every stored entry as an "old-format" blob: a valid
    # pickle whose shape predates the {key, payload, in_tree,
    # out_tree, meta} contract
    import pickle
    for f in progs:
        with open(os.path.join(tmp_path, f), "wb") as fh:
            pickle.dump(("payload", "in_tree", "out_tree"), fh)
    err0 = _DISK_ERRORS.value(op="load")
    m0 = _MISSES.value()
    c0 = _COMPILED.value()
    got = mem_engine().execute(sql)  # fresh engine: no memory tier
    assert got == want
    assert _COMPILED.value() - c0 >= 1  # live compile fallback
    assert _MISSES.value() - m0 >= 1
    assert _DISK_ERRORS.value(op="load") >= err0 + 1
    # the poisoned files were unlinked and re-stored by the fallback
    # compile, so the NEXT engine disk-hits again
    d0 = _HITS.value(tier="disk")
    assert mem_engine().execute(sql) == want
    assert _HITS.value(tier="disk") - d0 >= 1


# -- cross-worker sharing ----------------------------------------------------

def test_two_worker_cluster_shares_disk_store(tmp_path, monkeypatch):
    """A fragment compiled on one worker is a disk-cache hit on the
    other: both workers' engines consult the shared store, so a
    cluster compiles each fragment once, not once per worker."""
    import dataclasses as DC

    from presto_tpu.exec.streaming import _find_streamable
    from presto_tpu.parallel.coordinator import RemoteWorker
    from presto_tpu.parallel.wire import bytes_to_columns
    from presto_tpu.parallel.worker import WorkerServer
    from presto_tpu.plan import nodes as N
    from presto_tpu.plan.serde import fragment_to_dict

    monkeypatch.setenv(PC.ENV_DIR, str(tmp_path))
    conn = MemoryConnector()
    n = 4096  # even split: both shards get identical shapes
    conn.create_table(
        "t", {"k": T.BIGINT, "v": T.BIGINT},
        {"k": np.arange(n) % 5, "v": np.arange(n)})

    local = Engine()
    local.register_catalog("mem", conn)
    local.session.catalog = "mem"
    plan, _ = local.plan_sql("select k, sum(v) from t group by k")
    agg, _scan = _find_streamable(plan)
    frag = fragment_to_dict(DC.replace(agg, step=N.AggStep.PARTIAL))

    workers = [WorkerServer({"mem": conn}, node_id=f"pw{i}").start()
               for i in range(2)]
    try:
        remotes = [RemoteWorker(w.uri) for w in workers]
        c0 = _COMPILED.value()
        out0 = remotes[0].post_task_any(
            {"fragment": frag, "shard": 0, "nshards": 2})
        compiled_by_first = _COMPILED.value() - c0
        assert compiled_by_first >= 1
        d0 = _HITS.value(tier="disk")
        out1 = remotes[1].post_task_any(
            {"fragment": frag, "shard": 1, "nshards": 2})
        # second worker: fresh engine, no memory tier — disk hit, zero
        # additional compiles
        assert _COMPILED.value() - c0 == compiled_by_first
        assert _HITS.value(tier="disk") - d0 >= 1
        # both halves produced real partial states
        rows0 = bytes_to_columns(out0)[1]
        rows1 = bytes_to_columns(out1)[1]
        assert rows0 > 0 and rows1 > 0
    finally:
        for w in workers:
            try:
                w.stop()
            except Exception:  # noqa: BLE001
                pass
