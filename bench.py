"""Benchmark entry: TPC-H throughput on the local accelerator.

Prints ONE JSON line:
  {"metric": ..., "value": N, "unit": ..., "vs_baseline": N, "detail": {...}}

Headline: TPC-H Q1 lineitem rows/sec at SF10 through the full SQL path
(scan->filter->project->group-aggregate->sort), steady-state (arrays
pinned on device, program cached) — BASELINE.md ladder config 3's scale
on one chip; the analog of the reference's in-process benchmark harness
(testing/trino-benchmark/.../HandTpchQuery1.java, BenchmarkSuite).

Every query measures in its OWN SUBPROCESS, a habit from an earlier
backend whose SF10 sequences failed in one process; on the current
stack chip_smoke.py runs Q6, Q1 and Q3 at SF10 in ONE process (CHANGES.md
PR 21), so the split is owed a second look (ROADMAP D9). A TPU belongs
to one process at a time: the parent must stay off JAX while children
run. The persistent XLA compile cache (presto_tpu/__init__.py) keeps
the per-process compile cost to cache loads; the table datagen cache
keeps data loads to seconds.

``vs_baseline`` compares against a single-threaded vectorized NumPy
implementation of the same query at the same SF measured on this host —
the stand-in for BASELINE.json config 1 ("CPU Java-equivalent
operators"), since the reference repo publishes no absolute numbers
(BASELINE.md). Join queries (q03 3-way, q05 six-way) get their own
NumPy baselines (sort + searchsorted merge joins — the vectorized best
case for a CPU) so the driver's "Q1/Q3/Q5 vs baseline" metric has a
ratio per query, not just Q1.

Measurement order puts the JOIN queries first among details — rounds 3
and 4 exhausted the budget before ever measuring a join at SF10
(VERDICT r04 item 1). Q9 — the 6-relation join the cost-based
reorderer (presto_tpu/cost/) exists for — gets a RESERVED budget slice
ahead of lower-priority q06: five consecutive rounds reported it
"skipped: bench time budget exhausted" because everything before it
consumed the budget; now q01's child timeout AND q03/q05 may not eat
into its reserve, q06 runs last on whatever remains, and if the
reserve is starved anyway (datagen overrun, timeout floors) the run
reports ``q09_reserve_starved`` (seconds missing) instead of hiding
the gap behind the generic skip message.

Each query reports cold AND warm: after the cold compile+run, the
query reruns in a fresh process against the persistent AOT program
cache (exec/progcache.py, PRESTO_TPU_PROGRAM_CACHE_DIR — bench
defaults it to /tmp/presto_tpu_progcache), emitting
``qNN_warm_rows_per_sec`` with ``qNN_warm_compiles`` (0 when the
cache held) plus the real ``compile_s``/``execute_s`` split from the
obs compile histogram. The store persists across bench invocations,
so repeat runs' "cold" measurements are warm too — which is what
finally fits Q9 inside the budget.

Q3/Q5 additionally measure a LITERAL VARIANT in the same (cold) child
process — the same query with a shifted date / different region —
reporting ``qNN_variant_warm_rows_per_sec`` and
``qNN_variant_compiles``: with plan templates (presto_tpu/templates/)
the variant hits the executable compiled for the original literals,
so variant_compiles must be 0 and the variant wall is pure execute.

``bench.py --serve`` (also folded into the default run as serve_*
detail keys, in its own subprocess) drives N concurrent HTTP clients
through the real protocol against an in-process coordinator and
reports sustained queries/sec, p50/p99 latency, and error counts —
the concurrent-serving scale metric. One client drives in ARROW
result mode (X-Presto-TPU-Result: arrow, binary result pages), and
the serve report ends with a STREAMED full-table SELECT
(``qstream_rows_per_sec`` + ``qstream_peak_queue_pages``: the page
queue must peak at its bound regardless of result size — the O(page)
coordinator-memory claim of the streaming data plane). The default
run also reports ``wire_{arrow,npz}_mb_per_sec`` — exchange page
round-trip MB/s per codec (parallel/wire.py). Knobs:
PRESTO_TPU_BENCH_SERVE_CLIENTS (4), PRESTO_TPU_BENCH_SERVE_S (20),
PRESTO_TPU_BENCH_SERVE_SF (0.01).

Each measured query also reports its compile-time device-cost totals
(``qNN_flops``/``qNN_hbm_bytes``/``qNN_roofline`` — obs/devprof
harvest of XLA cost_analysis, attributed over the plan and summed), so
a wall regression is attributable: costs moved = the plan changed,
costs flat = runtime/scheduling. ``bench.py --compare OLD.json
NEW.json [threshold]`` diffs two BENCH files and prints per-key
regressions beyond the threshold (default 10%), exiting nonzero for
CI gating.

``PRESTO_TPU_BENCH_SKEW=zipf:<s>`` additionally measures q05/q09
against a Zipf(s)-skewed datagen variant (lineitem part/supplier FKs
and orders custkeys follow bounded Zipf over the key space),
reporting ``qNN_skew_rows_per_sec`` and ``qNN_skew_vs_uniform`` — the
skew-aware join work (cost/skew.py hybrid distribution + salting,
MultiJoin) is graded on that ratio staying near 1.

Env knobs: PRESTO_TPU_BENCH_SF (default 10), PRESTO_TPU_BENCH_REPS (2),
PRESTO_TPU_BENCH_BUDGET_S (default 600), PRESTO_TPU_BENCH_Q9_RESERVE_S
(default 150 — Q9's guaranteed slice), PRESTO_TPU_TPCH_CACHE (default
/tmp/presto_tpu_tpch_cache — table datagen cache; generated on first
run, ~4 min at SF10, fast raw-npy load afterwards),
PRESTO_TPU_PROGRAM_CACHE_DIR (persistent AOT program store).
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

import numpy as np

os.environ.setdefault("PRESTO_TPU_TPCH_CACHE",
                      "/tmp/presto_tpu_tpch_cache")

CUTOFF_Q1 = int((np.datetime64("1998-09-02")
                 - np.datetime64("1970-01-01")).astype(int))
DATE_Q3 = int((np.datetime64("1995-03-15")
               - np.datetime64("1970-01-01")).astype(int))
D5_LO = int((np.datetime64("1994-01-01")
             - np.datetime64("1970-01-01")).astype(int))
D5_HI = int((np.datetime64("1995-01-01")
             - np.datetime64("1970-01-01")).astype(int))

_CHILD = r"""
import json, os, sys, time
import numpy as np
from presto_tpu import Engine
from presto_tpu.connectors.tpch import TpchConnector
from presto_tpu.exec.executor import run_plan_live
from presto_tpu.obs.metrics import REGISTRY
from tests.tpch_queries import QUERIES

name = sys.argv[1]
sf = float(sys.argv[2])
reps = int(sys.argv[3])
engine = Engine()
# skew mode (PRESTO_TPU_BENCH_SKEW): the parent arms this for the
# dedicated q05/q09 skew measurements only
engine.register_catalog("tpch", TpchConnector(
    scale=sf, skew=os.environ.get("PRESTO_TPU_BENCH_SKEW_ACTIVE") or None))
# kernel backend override (PRESTO_TPU_BENCH_KERNEL_BACKEND): the
# parent forces pallas/xla for the per-backend q05/q09 comparison
from presto_tpu import kernels as _K
_kb = os.environ.get("PRESTO_TPU_BENCH_KERNEL_BACKEND")
if _kb:
    engine.session.set("kernel_backend", _kb)
plan, _ = engine.plan_sql(QUERIES[name])
compiles = REGISTRY.counter("presto_tpu_programs_compiled_total")
compile_hist = REGISTRY.histogram("presto_tpu_compile_seconds")
hits = REGISTRY.counter("presto_tpu_program_cache_hits_total")
t0 = time.perf_counter()
# host materialization = real device sync
np.asarray(run_plan_live(engine, plan))
first = time.perf_counter() - t0
times = []
for _ in range(reps):
    t0 = time.perf_counter()
    np.asarray(run_plan_live(engine, plan))
    times.append(time.perf_counter() - t0)
top_ops = None
device_syncs = None
cost_totals = None
if reps:
    # ONE extra steady run under a qstats scope, OUTSIDE the timed
    # samples, so the child can report the top operators by
    # attributed wall (which operator dominates —
    # system.operator_stats' per-kernel split) without the stats
    # recording ever inflating steady_s
    from presto_tpu.obs import qstats as QS
    syncs = REGISTRY.counter("presto_tpu_device_syncs_total")
    s0 = int(syncs.total())
    with QS.query("bench-" + name, QUERIES[name], "bench") as qr:
        np.asarray(run_plan_live(engine, plan))
    # host round-trips per steady execute, through the counted
    # exec/hostsync boundary (lint/devicesync.py proves there are no
    # uncounted ones): each is ~a full device round-trip of latency
    device_syncs = int(syncs.total()) - s0
    snap = qr.snapshot()
    ops = [o for st in snap["stages"] for t in st["tasks"]
           for o in t["operators"]]
    ops.sort(key=lambda o: -(o.get("wallMillis") or 0))
    top_ops = [{"node": o["nodeType"], "label": o["label"],
                "wall_ms": o.get("wallMillis"),
                "kernel": o.get("kernel") or ""}
               for o in ops[:3]]
    # device-cost totals from the new operator attribution
    # (obs/devprof.py): query flops, bytes moved, and the roofline
    # ratio of the whole query's arithmetic intensity against the
    # configured device peaks
    qflops = sum(int(o.get("flops") or 0) for o in ops)
    qbytes = sum(int(o.get("hbmBytes") or 0) for o in ops)
    if qflops:
        from presto_tpu.obs import devprof
        cost_totals = {"flops": qflops, "hbm_bytes": qbytes}
        peaks = devprof.device_peaks()
        if peaks is not None:
            cost_totals["roofline"] = round(
                (qflops / max(1, qbytes)) * peaks[1] / peaks[0], 4)
    else:
        cost_totals = None
_cap_total = int(REGISTRY.counter(
    "presto_tpu_capacity_overflow_retries_total").total())
out = {
    "name": name, "first_s": round(first, 3),
    "kernel_backend": _K.resolve(engine.session),
    # real compile/execute attribution: XLA compile wall from the obs
    # histogram (exec/executor + parallel/executor both feed it), not
    # the first-minus-steady approximation
    "compile_s": round(compile_hist.sum(), 1),
    "programs_compiled": int(compiles.value()),
    # capacity-overflow retry rungs (each one is a recompile on the
    # hot path): the adaptive-execution tier's "overflow retries go
    # to ~zero" claim is graded on this staying 0 across the suite
    "capacity_overflow_retries": _cap_total,
    "cache_hits_disk": int(hits.value(tier="disk")),
    "cache_hits_memory": int(hits.value(tier="memory"))}
if times:  # reps=0 = warm-start probe: first_s is the measurement
    out["steady_s"] = min(times)
if top_ops is not None:
    out["top_operators"] = top_ops
if device_syncs is not None:
    out["device_syncs"] = device_syncs
if cost_totals is not None:
    out.update(cost_totals)
variant = sys.argv[4] if len(sys.argv) > 4 else ""
if variant:
    # literal-variant warm measurement (plan templates): the same
    # query shape with a different date/region, run in THIS process —
    # variant_compiles must be 0 on a template hit (templates/)
    old, new = variant.split("=>")
    vplan, _ = engine.plan_sql(QUERIES[name].replace(old, new))
    c0 = int(compiles.value())
    t0 = time.perf_counter()
    np.asarray(run_plan_live(engine, vplan))
    out["variant_s"] = round(time.perf_counter() - t0, 3)
    out["variant_compiles"] = int(compiles.value()) - c0
    out["template_hits"] = int(REGISTRY.counter(
        "presto_tpu_template_cache_hits_total").value())
    out["template_misses"] = int(REGISTRY.counter(
        "presto_tpu_template_cache_misses_total").value())
print(json.dumps(out))
"""

# literal-variant specs per query ("old=>new" textual swap): the
# serving scenario the plan-template subsystem exists for — same query
# shape, different constants
VARIANTS = {
    "q03": "date '1995-03-15'=>date '1995-03-22'",
    "q05": "'ASIA'=>'EUROPE'",
}


def measure_query(name: str, sf: float, reps: int,
                  timeout_s: float, skew: str | None = None,
                  kernel_backend: str | None = None) -> dict:
    """One query's (first, steady) walls + compile attribution and
    program-cache counters, isolated in a subprocess. With
    PRESTO_TPU_PROGRAM_CACHE_DIR set (bench default) a SECOND call for
    the same query measures the warm start: the fresh process loads
    the AOT executables from the persistent store instead of
    compiling. ``skew`` ("zipf:<s>") points the child at the
    Zipf-skewed datagen variant (PRESTO_TPU_BENCH_SKEW mode);
    ``kernel_backend`` forces the child's kernel dispatch (the
    pallas-vs-xla per-backend comparison)."""
    t0 = time.perf_counter()
    argv = [sys.executable, "-c", _CHILD, name, str(sf), str(reps)]
    if name in VARIANTS and reps > 0 and not skew and not kernel_backend:
        # variant rides the COLD child only: the warm-start probe
        # (reps=0) measures the persistent cache, not templates, and
        # the per-backend comparison reruns read only steady_s
        argv.append(VARIANTS[name])
    env = dict(os.environ)
    env.pop("PRESTO_TPU_BENCH_SKEW_ACTIVE", None)
    env.pop("PRESTO_TPU_BENCH_KERNEL_BACKEND", None)
    if skew:
        env["PRESTO_TPU_BENCH_SKEW_ACTIVE"] = skew
    if kernel_backend:
        env["PRESTO_TPU_BENCH_KERNEL_BACKEND"] = kernel_backend
    try:
        proc = subprocess.run(
            argv, capture_output=True, text=True, timeout=timeout_s,
            env=env,
            cwd=os.path.dirname(os.path.abspath(__file__)))
    except subprocess.TimeoutExpired:
        return {"error": "timed out"}
    if proc.returncode != 0:
        tail = (proc.stderr or "").strip().splitlines()[-1:]
        return {"error": (tail[0] if tail else "subprocess failed")[:200]}
    line = (proc.stdout or "").strip().splitlines()[-1]
    out = json.loads(line)
    out["wall_s"] = round(time.perf_counter() - t0, 1)
    return out


def warm_metrics(detail: dict, name: str, nrows: int, sf: float,
                 budget_left: float) -> None:
    """Warm-start rerun of ``name`` in a FRESH process: the persistent
    program cache should make it execute-dominated (zero compiles).
    Fills qNN_warm_rows_per_sec / qNN_warm_* detail keys."""
    if budget_left <= 45:
        detail[f"{name}_warm_skipped"] = "bench time budget exhausted"
        return
    # reps=0: the warm-start wall IS first_s, a steady rep would just
    # double the budget cost of every warm measurement
    r = measure_query(name, sf, 0, min(budget_left - 10, 240))
    if "error" in r:
        detail[f"{name}_warm_error"] = r["error"]
        return
    # first_s of a warm process = upload + execute (compile skipped);
    # floor it so a sub-millisecond tiny-SF warm run cannot divide by
    # the child's rounded-to-zero wall
    detail[f"{name}_warm_rows_per_sec"] = round(
        nrows / max(r["first_s"], 1e-3))
    detail[f"{name}_warm_compiles"] = r.get("programs_compiled")
    detail[f"{name}_warm_cache_hits_disk"] = r.get("cache_hits_disk")
    detail[f"{name}_warm_compile_s"] = r.get("compile_s")


# -- exchange wire throughput per codec (parallel/wire.py) -------------------
# Host-side only (pure numpy/pyarrow, no device): encode+decode a
# representative exchange page — ints, short decimals, dictionary
# varchar, a nullable double — per codec, reporting round-trip MB/s.
# The Arrow data plane is graded on this ratio: columnar IPC removes
# the serde term that left the link idle (PAPERS.md 2204.03032).


def wire_metrics(detail: dict) -> None:
    from presto_tpu import types as T
    from presto_tpu.block import Column
    from presto_tpu.parallel import wire

    n = 1 << 18  # ~5 MB of raw column bytes, one exchange-page scale
    rng = np.random.default_rng(0)
    cols = {
        "k": Column(T.BIGINT, rng.integers(0, 1 << 40, n)),
        "p": Column(T.DecimalType(12, 2), rng.integers(0, 10**7, n)),
        "s": Column(T.VARCHAR, rng.integers(0, 64, n, dtype=np.int32),
                    None,
                    np.asarray([f"val{i:03d}" for i in range(64)],
                               object)),
        "v": Column(T.DOUBLE, rng.random(n), rng.random(n) > 0.1),
    }
    raw = sum(np.asarray(c.data).nbytes for c in cols.values())
    for codec in (wire.WIRE_ARROW, wire.WIRE_NPZ):
        if codec == wire.WIRE_ARROW and not wire.have_arrow():
            detail["wire_arrow_skipped"] = "pyarrow unavailable"
            continue
        blob = wire.columns_to_bytes(cols, codec=codec)  # warm
        t0 = time.perf_counter()
        reps = 0
        while time.perf_counter() - t0 < 0.5:
            blob = wire.columns_to_bytes(cols, codec=codec)
            wire.bytes_to_columns(blob)
            reps += 1
        wall = time.perf_counter() - t0
        detail[f"wire_{codec}_mb_per_sec"] = round(
            raw * reps / wall / 1e6, 1)
        detail[f"wire_{codec}_page_bytes"] = len(blob)
    a = detail.get("wire_arrow_mb_per_sec")
    z = detail.get("wire_npz_mb_per_sec")
    if a and z:
        detail["wire_arrow_vs_npz"] = round(a / z, 2)


# -- per-kernel microbench + interpret-mode parity (bench.py --kernels) ------
# Pallas-vs-XLA rows/s for each kernel in the dispatch table
# (presto_tpu/kernels/), plus Q5/Q9 result parity between the two
# backends at tiny SF. On TPU the microbench grades the real Mosaic
# lowering; on CPU-only containers the Pallas numbers are interpret
# mode — correctness evidence, not speed (which is exactly what the
# acceptance asks for there).


def run_kernel_bench() -> dict:
    import jax
    import jax.numpy as jnp

    from presto_tpu import Engine
    from presto_tpu import kernels as K
    from presto_tpu.connectors.tpch import TpchConnector
    from tests.tpch_queries import QUERIES

    detail: dict = {"kernel_auto_pallas": K.auto_pallas_here()}
    rng = np.random.default_rng(7)
    n = int(os.environ.get("PRESTO_TPU_BENCH_KERNEL_ROWS",
                           str(1 << 15)))
    bh = jnp.asarray(rng.integers(0, n, n).astype(np.uint64))
    ph = jnp.asarray(rng.integers(0, 2 * n, n).astype(np.uint64))
    ones = jnp.ones((n,), bool)
    vals = jnp.asarray(rng.integers(-(1 << 40), 1 << 40, n))
    sids = jnp.asarray(rng.integers(0, 64, n).astype(np.int32))
    keep = jnp.asarray(rng.random(n) > 0.5)
    cols = {"a": vals, "b": keep}

    def timed_rows_per_sec(fn) -> float:
        fn()  # warm: compile outside the timed window
        t0 = time.perf_counter()
        reps = 0
        while time.perf_counter() - t0 < 0.4:
            fn()
            reps += 1
        return round(n * reps / (time.perf_counter() - t0))

    for be in ("pallas", "xla"):
        with K.use_backend(be):
            join_fn = jax.jit(lambda: K.dispatch("join_lookup")(
                bh, ones, ph, ones, 2 * n)[0])
            agg_fn = jax.jit(lambda: K.dispatch("agg_sum")(
                vals, sids, 64))
            cmp_fn = jax.jit(lambda: K.dispatch("compact")(
                keep, cols, n)["a"])
            for kname, fn in (("join", join_fn), ("agg", agg_fn),
                              ("compact", cmp_fn)):
                try:
                    detail[f"kernel_{kname}_{be}_rows_per_sec"] = \
                        timed_rows_per_sec(lambda f=fn: np.asarray(f()))
                except Exception as exc:  # noqa: BLE001 - additive
                    detail[f"kernel_{kname}_{be}_error"] = \
                        repr(exc)[:200]

    # Q5/Q9 parity: byte-identical results pallas (interpret on CPU)
    # vs xla through the full SQL path at tiny SF
    conn = TpchConnector(scale=0.01)
    for qname in ("q05", "q09"):
        try:
            results = {}
            for be in ("xla", "pallas"):
                e = Engine()
                e.register_catalog("tpch", conn)
                e.session.set("kernel_backend", be)
                results[be] = e.execute(QUERIES[qname])
            detail[f"{qname}_pallas_parity"] = (
                results["xla"] == results["pallas"])
        except Exception as exc:  # noqa: BLE001 - additive metric
            detail[f"{qname}_parity_error"] = repr(exc)[:200]
    return detail


def kernel_metrics(detail: dict, budget_left: float) -> None:
    """Run the per-kernel microbench + parity check in its OWN
    subprocess (same device-isolation rationale as measure_query)."""
    if budget_left <= 90:
        detail["kernel_bench_skipped"] = "bench time budget exhausted"
        return
    try:
        proc = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--kernels"],
            capture_output=True, text=True,
            timeout=min(budget_left - 10, 300),
            cwd=os.path.dirname(os.path.abspath(__file__)))
        line = (proc.stdout or "").strip().splitlines()[-1]
        detail.update(json.loads(line).get("detail") or {})
    except Exception as exc:  # noqa: BLE001 - additive metrics
        detail["kernel_bench_error"] = repr(exc)[:200]


# -- concurrent-serving QPS bench (bench.py --serve) -------------------------
# Drives N concurrent HTTP clients through the REAL protocol (POST
# /v1/statement + nextUri polling) against an in-process coordinator,
# reporting sustained queries/sec and p50/p99 latency — the scale
# metric ROADMAP item 1 asks for alongside rows/s. The query mix is
# deliberately small-shape (compiled once in a warmup pass) so the
# numbers measure the SERVING path — dispatch, admission, session
# overrides, result paging — not XLA compile.

SERVE_QUERIES = (
    "select count(*) from nation",
    "select r_name, count(*) as c from region group by r_name "
    "order by r_name",
    "select n_regionkey, count(*) as c from nation "
    "group by n_regionkey order by n_regionkey",
    "select count(*) from supplier where s_acctbal > 0",
)


def _quantile_ms(sorted_s: list, q: float) -> float:
    if not sorted_s:
        return 0.0
    idx = min(len(sorted_s) - 1, int(q * len(sorted_s)))
    return round(sorted_s[idx] * 1e3, 2)


def _serve_repeat_phase(base: str, repeat: float, nclients: int,
                        duration: float) -> dict:
    """Tenant-scale repeated-query mix (server/serving.py): a
    ``repeat`` fraction of each client's issues re-run an IDENTICAL
    SELECT — protocol-layer result-cache hits after the first pass —
    and the rest are template VARIANTS of one parameterized shape,
    issued under a small ``batch_window_ms`` so concurrent arrivals
    stack into vmapped cross-query batches (exec/batch.py). Reports
    the hit/variant split, batch mean size, and cache hit ratios."""
    import threading

    from presto_tpu.client import Client
    from presto_tpu.obs.metrics import REGISTRY

    hits0 = REGISTRY.counter(
        "presto_tpu_result_cache_hits_total").value()
    miss0 = REGISTRY.counter(
        "presto_tpu_result_cache_misses_total").value()
    hit_lat: list[list] = [[] for _ in range(nclients)]
    var_lat: list[list] = [[] for _ in range(nclients)]
    errors = [0] * nclients
    deadline = time.perf_counter() + duration

    def drive(i: int) -> None:
        c = Client(base, user=f"repeat{i}")
        # variants ride the cross-query batch window; identical
        # re-issues fast-path out of the cache before ever seeing it
        c.session_properties = {"batch_window_ms": 4.0}
        n = 0
        while time.perf_counter() < deadline:
            identical = (n % 100) < int(repeat * 100)
            if identical:
                sql = SERVE_QUERIES[(i + n) % len(SERVE_QUERIES)]
            else:
                # per-client, per-issue literal: same template
                # fingerprint, (almost) never the same cache key
                v = ((i * 9973 + n * 37) % 100000) / 10.0
                sql = ("select count(*) from supplier "
                       f"where s_acctbal > {v}")
            t0 = time.perf_counter()
            try:
                c.execute(sql, poll_interval=0.005)
                (hit_lat if identical else var_lat)[i].append(
                    time.perf_counter() - t0)
            except Exception:  # noqa: BLE001 - keep driving
                errors[i] += 1
            n += 1

    threads = [threading.Thread(target=drive, args=(i,))
               for i in range(nclients)]
    t_start = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.perf_counter() - t_start
    all_hit = sorted(x for per in hit_lat for x in per)
    all_var = sorted(x for per in var_lat for x in per)
    hits = REGISTRY.counter(
        "presto_tpu_result_cache_hits_total").value() - hits0
    misses = REGISTRY.counter(
        "presto_tpu_result_cache_misses_total").value() - miss0
    batch_hist = REGISTRY.histogram("presto_tpu_batch_size_queries")
    batch_count = batch_hist.count()
    completed = len(all_hit) + len(all_var)
    return {
        "serve_repeat_fraction": repeat,
        "serve_repeat_seconds": round(wall, 1),
        "serve_repeat_queries": completed,
        "serve_repeat_qps": round(completed / max(wall, 1e-9), 1),
        "serve_hit_qps": round(len(all_hit) / max(wall, 1e-9), 1),
        "serve_hit_p50_ms": _quantile_ms(all_hit, 0.50),
        "serve_hit_p99_ms": _quantile_ms(all_hit, 0.99),
        "serve_variant_qps": round(len(all_var) / max(wall, 1e-9), 1),
        "serve_batched_queries": int(REGISTRY.counter(
            "presto_tpu_batched_queries_total").value()),
        "serve_batch_mean_size": (
            round(batch_hist.sum() / batch_count, 2)
            if batch_count else 0.0),
        "serve_result_cache_hits": int(hits),
        "serve_result_cache_misses": int(misses),
        "serve_result_cache_hit_ratio": round(
            hits / max(1.0, hits + misses), 3),
        "serve_repeat_errors": sum(errors),
    }


def _serve_scaleout_phase(sf: float, duration: float) -> dict:
    """Elastic scale-out: drive a 2-worker cluster through the HTTP
    coordinator, then JOIN two standby workers mid-run via PUT
    /v1/node (the drain API's mirror image — exactly an autoscaler's
    move) and report first-half vs second-half QPS. The scheduler
    consults live workers per dispatch, so the joined pair picks up
    shards as soon as their first heartbeat flips them active."""
    import threading
    import urllib.request

    from presto_tpu import Engine
    from presto_tpu.client import Client
    from presto_tpu.connectors.tpch import TpchConnector
    from presto_tpu.parallel.coordinator import ClusterCoordinator
    from presto_tpu.parallel.worker import WorkerServer
    from presto_tpu.server import CoordinatorServer

    # below SF 0.1 a shard is ~30k rows and per-task dispatch overhead
    # swamps the shard work, reading as a spurious QPS cliff at the
    # join; >= 0.1 the per-query cost is shard-count-invariant and the
    # halves compare cleanly
    sf = max(sf, 0.1)
    nclients = 4
    workers = [
        WorkerServer({"tpch": TpchConnector(scale=sf)},
                     node_id=f"bw{i}").start()
        for i in range(4)]
    local = Engine()
    local.register_catalog("tpch", TpchConnector(scale=sf))
    coord = ClusterCoordinator(local, heartbeat_interval_s=0.2).start()
    for w in workers:
        coord.add_worker(w.uri)
    srv = CoordinatorServer(local, cluster=coord).start()

    def _put(url: str, payload: dict) -> None:
        req = urllib.request.Request(
            url, method="PUT", data=json.dumps(payload).encode(),
            headers={"Content-Type": "application/json",
                     "X-Trino-User": "scale"})
        urllib.request.urlopen(req, timeout=10).close()

    def _wait_live(n: int) -> None:
        deadline = time.perf_counter() + 10
        while len(coord.live_workers()) != n \
                and time.perf_counter() < deadline:
            time.sleep(0.05)

    try:
        base = f"http://127.0.0.1:{srv.port}"
        sql = ("select l_returnflag, count(*) as c from lineitem "
               "group by l_returnflag order by l_returnflag")
        warm = Client(base, user="scale")
        _wait_live(4)
        warm.execute(sql)  # 4-shard fragment programs compile here
        # drain two workers back out (graceful worker-side drain) so
        # the timed run STARTS at 2 and both shard configurations are
        # warm — the mid-run JOIN then measures rebalancing, not XLA
        for w in workers[2:]:
            _put(w.uri + "/v1/info/state", {"state": "SHUTTING_DOWN"})
        _wait_live(2)
        warm.execute(sql)  # 2-shard fragment programs compile here
        done: list[list] = [[] for _ in range(nclients)]
        t0 = time.perf_counter()
        t_mid = t0 + duration / 2
        t_end = t0 + duration

        def drive(i: int) -> None:
            c = Client(base, user=f"scale{i}")
            while time.perf_counter() < t_end:
                try:
                    c.execute(sql, poll_interval=0.005)
                    done[i].append(time.perf_counter())
                except Exception:  # noqa: BLE001 - keep driving
                    pass

        threads = [threading.Thread(target=drive, args=(i,))
                   for i in range(nclients)]
        for t in threads:
            t.start()
        time.sleep(max(0.0, t_mid - time.perf_counter()))
        # the autoscaler's move: the worker re-activates, then
        # announces itself to the running coordinator over PUT
        # /v1/node (joining -> active on its next heartbeat)
        for w in workers[2:]:
            _put(w.uri + "/v1/info/state", {"state": "ACTIVE"})
            _put(base + "/v1/node", {"uri": w.uri})
        for t in threads:
            t.join()
        stamps = [x for per in done for x in per]
        first = sum(1 for x in stamps if x <= t_mid)
        second = len(stamps) - first
        half = max(duration / 2, 1e-9)
        # structural evidence the rebalance happened: the final query
        # fanned out across the grown cluster. On a single-core
        # container the sharded work time-slices one CPU, so the
        # visible scale-out signal is membership-follow at QPS parity
        # (a real core/chip per worker is what turns it into speedup);
        # serve_scaleout_cpus makes that context part of the record.
        return {
            "serve_scaleout_sf": sf,
            "serve_scaleout_qps_2w": round(first / half, 1),
            "serve_scaleout_qps_4w": round(second / half, 1),
            "serve_scaleout_live_workers": len(coord.live_workers()),
            "serve_scaleout_final_nshards":
                (coord.last_distribution or {}).get("nshards"),
            "serve_scaleout_cpus": len(os.sched_getaffinity(0)),
        }
    finally:
        srv.stop()
        coord.stop()
        for w in workers:
            try:
                w.stop()
            except Exception:  # noqa: BLE001 - teardown best-effort
                pass


def run_serve_bench() -> dict:
    """The --serve mode body: returns (and prints) the serve detail."""
    import threading

    from presto_tpu import Engine
    from presto_tpu.client import Client, QueryFailed
    from presto_tpu.connectors.tpch import TpchConnector
    from presto_tpu.server import CoordinatorServer

    nclients = int(os.environ.get("PRESTO_TPU_BENCH_SERVE_CLIENTS",
                                  "4"))
    duration = float(os.environ.get("PRESTO_TPU_BENCH_SERVE_S", "20"))
    sf = float(os.environ.get("PRESTO_TPU_BENCH_SERVE_SF", "0.01"))
    engine = Engine()
    engine.register_catalog("tpch", TpchConnector(scale=sf))
    srv = CoordinatorServer(engine).start()
    try:
        base = f"http://127.0.0.1:{srv.port}"
        warm = Client(base, user="bench")
        for q in SERVE_QUERIES:
            warm.execute(q)  # compile outside the timed window

        latencies: list[list] = [[] for _ in range(nclients)]
        errors = [0] * nclients
        deadline = time.perf_counter() + duration

        def drive(i: int) -> None:
            # client 0 drives in ARROW result mode: the serving path's
            # binary page delivery gets exercised (and measured) right
            # alongside the JSON one
            c = Client(base, user=f"bench{i}",
                       result_format="arrow" if i == 0 else "json")
            n = 0
            while time.perf_counter() < deadline:
                sql = SERVE_QUERIES[(i + n) % len(SERVE_QUERIES)]
                t0 = time.perf_counter()
                try:
                    c.execute(sql, poll_interval=0.005)
                    latencies[i].append(time.perf_counter() - t0)
                except QueryFailed:
                    errors[i] += 1
                except Exception:  # noqa: BLE001 - transport hiccups
                    # a dead driver thread would silently skew
                    # serve_qps; count the failure and keep driving
                    errors[i] += 1
                n += 1

        threads = [threading.Thread(target=drive, args=(i,))
                   for i in range(nclients)]
        t_start = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        wall = time.perf_counter() - t_start
        all_lat = sorted(x for per in latencies for x in per)
        completed = len(all_lat)
        # template hit/miss counters (templates/): the coordinator
        # runs in-process, so the registry's totals cover exactly the
        # queries this bench drove
        from presto_tpu.obs.metrics import REGISTRY
        out = {
            "serve_clients": nclients,
            "serve_arrow_clients": 1 if nclients else 0,
            "serve_seconds": round(wall, 1),
            "serve_sf": sf,
            "serve_queries_completed": completed,
            "serve_qps": round(completed / max(wall, 1e-9), 1),
            "serve_p50_ms": _quantile_ms(all_lat, 0.50),
            "serve_p99_ms": _quantile_ms(all_lat, 0.99),
            "serve_errors": sum(errors),
            "serve_template_hits": int(REGISTRY.counter(
                "presto_tpu_template_cache_hits_total").value()),
            "serve_template_misses": int(REGISTRY.counter(
                "presto_tpu_template_cache_misses_total").value()),
        }
        # adaptive-execution counters (parallel/adaptive.py +
        # ft/speculate.py + the capacity retry ladder): the overflow
        # total must stay 0 across the serve mix, and the replan/
        # speculation totals make mid-query adaptivity visible in the
        # same BENCH json as everything else (they only move when the
        # serve mix runs TASK-mode cluster queries)
        out["serve_capacity_overflow_retries"] = int(REGISTRY.counter(
            "presto_tpu_capacity_overflow_retries_total").total())
        out["serve_adaptive_replans"] = int(REGISTRY.counter(
            "presto_tpu_adaptive_replans_total").total())
        out["serve_speculative_attempts"] = int(REGISTRY.counter(
            "presto_tpu_speculative_attempts_total").value())

        # streamed full-table SELECT (ROADMAP item 1's acceptance):
        # every lineitem row through the bounded-page-queue protocol
        # in arrow result mode. qstream_peak_queue_pages is the
        # O(page) coordinator-memory proof — it must sit at the
        # RESULT_QUEUE_PAGES cap regardless of result size — and the
        # query-pool peak shows admission charges not scaling with
        # the result either.
        try:
            qc = Client(base, user="qstream", result_format="arrow")
            sql = "select l_orderkey, l_extendedprice from lineitem"
            t0 = time.perf_counter()
            _, qrows = qc.execute(sql, poll_interval=0.005)
            qwall = time.perf_counter() - t0
            peak_pages = 0
            for q in srv.manager.snapshot():
                if q.sql == sql and q.result is not None:
                    peak_pages = max(peak_pages, q.result.peak_depth)
            out.update({
                "qstream_rows": len(qrows),
                "qstream_rows_per_sec": round(
                    len(qrows) / max(qwall, 1e-9)),
                "qstream_peak_queue_pages": peak_pages,
                "qstream_peak_query_pool_bytes":
                    srv.manager.query_pool.peak,
            })
        except Exception as exc:  # noqa: BLE001 - additive metric
            out["qstream_error"] = repr(exc)[:200]

        # tenant-scale serving phases (server/serving.py): the
        # repeated-query mix re-uses the warm in-process server; the
        # scale-out phase boots its own 4-worker cluster
        repeat = float(os.environ.get("PRESTO_TPU_BENCH_SERVE_REPEAT",
                                      "0.8"))
        if repeat > 0:
            try:
                out.update(_serve_repeat_phase(
                    base, repeat, nclients, min(duration, 10.0)))
            except Exception as exc:  # noqa: BLE001 - additive
                out["serve_repeat_error"] = repr(exc)[:200]
        if os.environ.get("PRESTO_TPU_BENCH_SERVE_SCALEOUT",
                          "1") != "0":
            try:
                out.update(_serve_scaleout_phase(sf, min(duration,
                                                         12.0)))
            except Exception as exc:  # noqa: BLE001 - additive
                out["serve_scaleout_error"] = repr(exc)[:200]
        return out
    finally:
        srv.stop()


def serve_metrics(detail: dict, budget_left: float) -> None:
    """Run the QPS bench in its OWN subprocess (the parent stays off
    the device, same isolation rationale as measure_query) and fold
    the serve_* keys into the bench detail."""
    need = float(os.environ.get("PRESTO_TPU_BENCH_SERVE_S", "20")) + 60
    if budget_left <= need:
        detail["serve_skipped"] = "bench time budget exhausted"
        return
    try:
        proc = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--serve"],
            capture_output=True, text=True,
            timeout=min(budget_left - 10, need + 120),
            cwd=os.path.dirname(os.path.abspath(__file__)))
        line = (proc.stdout or "").strip().splitlines()[-1]
        out = json.loads(line)
        detail.update(out.get("detail") or {})
    except Exception as exc:  # noqa: BLE001 - serve is additive
        detail["serve_error"] = repr(exc)[:200]


def _cols(table, names):
    return {c: np.asarray(table.columns[c].data) for c in names}


def _strs(table, name):
    col = table.columns[name]
    return np.asarray(col.dictionary)[np.asarray(col.data)]


def numpy_q1(li) -> float:
    """Single-pass vectorized NumPy Q1; returns wall seconds."""
    t0 = time.perf_counter()
    mask = li["l_shipdate"] <= CUTOFF_Q1
    rf = li["l_returnflag"][mask]
    ls = li["l_linestatus"][mask]
    qty = li["l_quantity"][mask]
    price = li["l_extendedprice"][mask]
    disc = li["l_discount"][mask]
    tax = li["l_tax"][mask]
    disc_price = price * (100 - disc)
    charge = disc_price * (100 + tax)
    gid = rf.astype(np.int64) * 64 + ls.astype(np.int64)
    uniq, inv = np.unique(gid, return_inverse=True)
    k = len(uniq)
    for col in (qty, price, disc, disc_price, charge):
        np.bincount(inv, weights=col.astype(np.float64), minlength=k)
    np.bincount(inv, minlength=k)
    return time.perf_counter() - t0


def numpy_q3(li, orders, cust_building) -> float:
    """Vectorized NumPy Q3: searchsorted merge joins + bincount
    group-by + top-10 — the single-threaded CPU best case."""
    t0 = time.perf_counter()
    ck = np.sort(cust_building)
    om = orders["o_orderdate"] < DATE_Q3
    oc = orders["o_custkey"][om]
    pos = np.clip(np.searchsorted(ck, oc), 0, len(ck) - 1)
    om2 = ck[pos] == oc
    okey = orders["o_orderkey"][om][om2]
    odate = orders["o_orderdate"][om][om2]
    oprio = orders["o_shippriority"][om][om2]
    order_sorted = np.argsort(okey)
    oks = okey[order_sorted]
    lm = li["l_shipdate"] > DATE_Q3
    lkey = li["l_orderkey"][lm]
    lpos = np.clip(np.searchsorted(oks, lkey), 0, len(oks) - 1)
    hit = oks[lpos] == lkey
    lkey = lkey[hit]
    rev = (li["l_extendedprice"][lm][hit].astype(np.float64)
           * (100 - li["l_discount"][lm][hit]))
    uniq, inv = np.unique(lkey, return_inverse=True)
    revenue = np.bincount(inv, weights=rev, minlength=len(uniq))
    top = np.argsort(-revenue)[:10]
    _ = (uniq[top], revenue[top],
         odate[order_sorted][np.searchsorted(oks, uniq[top])],
         oprio[order_sorted][np.searchsorted(oks, uniq[top])])
    return time.perf_counter() - t0


def numpy_q9(li, ps, orders, supp, green_part) -> float:
    """Vectorized NumPy Q9: the 6-relation profit join (part,
    supplier, lineitem, partsupp, orders, nation) via dense key
    lookups + a sorted composite-key merge into partsupp — the
    single-threaded CPU best case the reorderer's Q9 number is graded
    against."""
    t0 = time.perf_counter()
    lm = green_part[li["l_partkey"]]
    lpart = li["l_partkey"][lm]
    lsupp = li["l_suppkey"][lm]
    lord = li["l_orderkey"][lm]
    # partsupp lookup by composite (partkey, suppkey)
    smax = int(ps["ps_suppkey"].max()) + 1
    pskey = ps["ps_partkey"].astype(np.int64) * smax + ps["ps_suppkey"]
    order = np.argsort(pskey)
    pskey_sorted = pskey[order]
    cost_sorted = ps["ps_supplycost"][order]
    probe = lpart.astype(np.int64) * smax + lsupp
    pos = np.clip(np.searchsorted(pskey_sorted, probe), 0,
                  len(pskey_sorted) - 1)
    supplycost = cost_sorted[pos]
    # orders lookup: order year by o_orderkey (sorted merge)
    osort = np.argsort(orders["o_orderkey"])
    oks = orders["o_orderkey"][osort]
    years = (orders["o_orderdate"][osort]
             .astype("datetime64[D]").astype("datetime64[Y]")
             .astype(np.int64) + 1970)
    year = years[np.clip(np.searchsorted(oks, lord), 0, len(oks) - 1)]
    # supplier -> nation, dense by suppkey
    snat = np.zeros(int(supp["s_suppkey"].max()) + 1, dtype=np.int64)
    snat[supp["s_suppkey"]] = supp["s_nationkey"]
    nat = snat[lsupp]
    amount = (li["l_extendedprice"][lm].astype(np.float64)
              * (100 - li["l_discount"][lm])
              - supplycost.astype(np.float64) * li["l_quantity"][lm])
    gid = nat * 4096 + (year - 1970)
    uniq, inv = np.unique(gid, return_inverse=True)
    np.bincount(inv, weights=amount, minlength=len(uniq))
    return time.perf_counter() - t0


def numpy_q5(li, orders, cust, supp, asia_nations) -> float:
    """Vectorized NumPy Q5: six-way star join via searchsorted."""
    t0 = time.perf_counter()
    nset = np.sort(asia_nations)

    def in_nations(nk):
        p = np.clip(np.searchsorted(nset, nk), 0, len(nset) - 1)
        return nset[p] == nk

    cm = in_nations(cust["c_nationkey"])
    ckey = np.sort(cust["c_custkey"][cm])
    cnat = cust["c_nationkey"][np.argsort(cust["c_custkey"])][
        np.searchsorted(np.sort(cust["c_custkey"]), ckey)]
    om = ((orders["o_orderdate"] >= D5_LO)
          & (orders["o_orderdate"] < D5_HI))
    oc = orders["o_custkey"][om]
    p = np.clip(np.searchsorted(ckey, oc), 0, len(ckey) - 1)
    hit = ckey[p] == oc
    okey = orders["o_orderkey"][om][hit]
    onat = cnat[p[hit]]
    osort = np.argsort(okey)
    oks, onats = okey[osort], onat[osort]
    lkey = li["l_orderkey"]
    lp = np.clip(np.searchsorted(oks, lkey), 0, len(oks) - 1)
    lhit = oks[lp] == lkey
    snat_by_key = np.zeros(int(supp["s_suppkey"].max()) + 1,
                           dtype=np.int64)
    snat_by_key[supp["s_suppkey"]] = supp["s_nationkey"]
    snat = snat_by_key[li["l_suppkey"][lhit]]
    same = snat == onats[lp[lhit]]
    rev = (li["l_extendedprice"][lhit][same].astype(np.float64)
           * (100 - li["l_discount"][lhit][same]))
    nat = snat[same]
    uniq, inv = np.unique(nat, return_inverse=True)
    np.bincount(inv, weights=rev, minlength=len(uniq))
    return time.perf_counter() - t0


# -- BENCH-file regression compare (bench.py --compare) ----------------------

# direction by key suffix/substring: throughput-like keys regress when
# they FALL, cost/latency-like keys regress when they RISE. Keys that
# match neither pattern (backends, paths, ratios like vs_baseline) are
# informational and never gate.
_HIGHER_BETTER = ("rows_per_sec", "mb_per_sec", "_qps", "qps",
                  "template_hits")
_LOWER_BETTER = ("_s", "_flops", "_hbm_bytes", "_compiles",
                 "_programs_compiled", "_device_syncs", "_page_bytes",
                 "_retries", "_errors", "_misses")
# deliberately ungated: the result cache answers the serve mix at the
# protocol layer, so serve-mode template hits collapsing is the cache
# WORKING, not template sharing regressing (the q*_template_hits keys
# still gate — those phases run with the cache cold)
_UNGATED = ("serve_template_hits", "serve_template_misses",
            "serve_result_cache_misses")


def _compare_direction(key: str) -> int:
    """+1 higher-is-better, -1 lower-is-better, 0 ungated."""
    if key in _UNGATED:
        return 0
    for pat in _HIGHER_BETTER:
        if key.endswith(pat) or pat in key:
            return 1
    for pat in _LOWER_BETTER:
        if key.endswith(pat):
            return -1
    return 0


def _bench_detail(path: str) -> dict:
    """Load a BENCH_rXX.json file: either the bare final JSON object
    or JSON-lines output (last object with a detail wins)."""
    detail: dict = {}
    with open(path) as f:
        text = f.read()
    try:
        obj = json.loads(text)
        objs = obj if isinstance(obj, list) else [obj]
    except ValueError:
        objs = []
        for line in text.splitlines():
            line = line.strip()
            if line.startswith("{"):
                try:
                    objs.append(json.loads(line))
                except ValueError:
                    continue
    # the hand-recorded BENCH_rXX.json wrappers carry the run's final
    # JSON line as a STRING under "tail" — unwrap it, else the compare
    # sees zero keys and the gate is vacuous
    for obj in list(objs):
        if isinstance(obj, dict) and isinstance(obj.get("tail"), str):
            for line in obj["tail"].splitlines():
                line = line.strip()
                if line.startswith("{"):
                    try:
                        objs.append(json.loads(line))
                    except ValueError:
                        continue
    for obj in objs:
        if isinstance(obj, dict) and isinstance(obj.get("detail"), dict):
            detail = obj["detail"]
            if "metric" in obj and isinstance(
                    obj.get("value"), (int, float)):
                detail = {**detail, obj["metric"]: obj["value"]}
    return detail


def run_compare(baseline_path: str, current_path: str,
                threshold: float) -> int:
    """Print per-key regressions beyond ``threshold`` (fractional
    change in the bad direction); return the regression count so the
    CI caller can gate on a nonzero exit."""
    base = _bench_detail(baseline_path)
    cur = _bench_detail(current_path)
    regressions = 0
    for key in sorted(set(base) & set(cur)):
        b, c = base[key], cur[key]
        if not isinstance(b, (int, float)) \
                or not isinstance(c, (int, float)) \
                or isinstance(b, bool) or isinstance(c, bool):
            continue
        direction = _compare_direction(key)
        if direction == 0 or b == 0:
            continue
        change = (c - b) / abs(b)
        bad = -change if direction > 0 else change
        if bad > threshold:
            regressions += 1
            print(f"REGRESSION {key}: {b:g} -> {c:g} "
                  f"({change * 100:+.1f}%, "
                  f"{'higher' if direction > 0 else 'lower'}-is-better,"
                  f" threshold {threshold * 100:.0f}%)")
    missing = sorted(k for k in base if k not in cur
                     and _compare_direction(k) != 0
                     and isinstance(base[k], (int, float)))
    for key in missing:
        print(f"MISSING {key}: present in baseline, absent in current")
    print(f"compared {baseline_path} -> {current_path}: "
          f"{regressions} regression(s), {len(missing)} missing key(s)")
    return regressions


def main() -> None:
    if "--compare" in sys.argv[1:]:
        # bench.py --compare BASELINE.json CURRENT.json [threshold]
        # CI gate: nonzero exit when any gated key moved in the bad
        # direction beyond the threshold (default 10%)
        i = sys.argv.index("--compare")
        rest = sys.argv[i + 1:]
        if len(rest) < 2:
            print("usage: bench.py --compare BASELINE.json "
                  "CURRENT.json [threshold]", file=sys.stderr)
            sys.exit(2)
        thr = float(rest[2]) if len(rest) > 2 else 0.10
        sys.exit(1 if run_compare(rest[0], rest[1], thr) else 0)
    if "--serve" in sys.argv[1:]:
        out = run_serve_bench()
        print(json.dumps({
            "metric": "serve_qps", "value": out["serve_qps"],
            "unit": "queries/s", "detail": out}))
        return
    if "--kernels" in sys.argv[1:]:
        out = run_kernel_bench()
        print(json.dumps({
            "metric": "kernel_bench", "value": 1, "unit": "report",
            "detail": out}))
        return

    sf = float(os.environ.get("PRESTO_TPU_BENCH_SF", "10"))
    reps = int(os.environ.get("PRESTO_TPU_BENCH_REPS", "2"))
    budget = float(os.environ.get("PRESTO_TPU_BENCH_BUDGET_S", "600"))
    t_start = time.perf_counter()

    # persistent AOT program cache (exec/progcache.py), inherited by
    # every child process: warm reruns — and repeat bench invocations,
    # which is what finally fits Q9 in the budget — skip lower+compile
    # entirely instead of re-paying 80-150 s per join query
    os.environ.setdefault("PRESTO_TPU_PROGRAM_CACHE_DIR",
                          "/tmp/presto_tpu_progcache")

    from presto_tpu.connectors.tpch import TpchConnector

    detail: dict = {"sf": sf, "program_cache_dir":
                    os.environ["PRESTO_TPU_PROGRAM_CACHE_DIR"]}

    # materialize the datagen cache BEFORE any timed subprocess (cold
    # cache costs ~4 min at SF10; children then load raw npy in
    # seconds). The connector is host-side only here — no device use,
    # so the children's TPU processes stay pristine.
    t0 = time.perf_counter()
    tpch = TpchConnector(scale=sf)
    lineitem = tpch.table("lineitem")
    nrows = lineitem.nrows
    detail["datagen_s"] = round(time.perf_counter() - t0, 1)

    # exchange wire MB/s per codec (host-side, ~1 s): the data-plane
    # serde term, independent of any query
    try:
        wire_metrics(detail)
    except Exception as exc:  # noqa: BLE001 - additive metric
        detail["wire_bench_error"] = repr(exc)[:200]

    # Q9's reserved slice (PRESTO_TPU_BENCH_Q9_RESERVE_S): read BEFORE
    # anything timed so every earlier measurement's timeout can be
    # shaped around it — five rounds in a row q09 was "skipped: bench
    # time budget exhausted" because q01 (whose child timeout ignored
    # the reserve) and the join queries ate the whole budget first
    q9_reserve = float(os.environ.get("PRESTO_TPU_BENCH_Q9_RESERVE_S",
                                      "150"))

    # headline: Q1 through the full SQL frontend. Its child timeout
    # excludes Q9's reserve too — BENCH_r05's q01 alone burned ~200 s
    # of compile+measure, and the old `left - 120` cap let it spend
    # straight into the slice the joins loop was supposed to protect
    left = budget - (time.perf_counter() - t_start)
    r = measure_query("q01", sf, reps,
                      max(left - q9_reserve - 120, 120))
    if "error" in r:
        # a broken headline is a failed run, not a zero result
        print(f"bench: headline q01 failed: {r['error']}",
              file=sys.stderr)
        sys.exit(1)
    q1_steady = r["steady_s"]
    detail["q01_compile_s"] = r.get("compile_s",
                                    round(r["first_s"] - q1_steady, 1))
    detail["q01_execute_s"] = round(q1_steady, 2)
    detail["q01_programs_compiled"] = r.get("programs_compiled")
    detail["q01_device_syncs"] = r.get("device_syncs")
    if r.get("flops"):
        detail["q01_flops"] = r["flops"]
        detail["q01_hbm_bytes"] = r.get("hbm_bytes")
        detail["q01_roofline"] = r.get("roofline")
    rows_per_sec = nrows / q1_steady

    # single-thread NumPy Q1 baseline (config-1 stand-in)
    li = _cols(lineitem, ("l_shipdate", "l_returnflag", "l_linestatus",
                          "l_quantity", "l_extendedprice", "l_discount",
                          "l_tax"))
    base_best = min(numpy_q1(li) for _ in range(2))
    del li
    headline = {
        "metric": f"tpch_q1_sf{sf:g}_rows_per_sec",
        "value": round(rows_per_sec),
        "unit": "rows/s",
        "vs_baseline": round(base_best / q1_steady, 3),
    }
    # emit the headline NOW: whatever happens later, the last stdout
    # line is a valid result; on success the final line below (with
    # details) replaces it
    print(json.dumps(headline), flush=True)

    # NumPy join baselines (host-side, cheap)
    try:
        li = _cols(lineitem, ("l_orderkey", "l_suppkey", "l_shipdate",
                              "l_extendedprice", "l_discount"))
        orders = _cols(tpch.table("orders"),
                       ("o_orderkey", "o_custkey", "o_orderdate",
                        "o_shippriority"))
        cust = _cols(tpch.table("customer"),
                     ("c_custkey", "c_nationkey"))
        seg = _strs(tpch.table("customer"), "c_mktsegment")
        cust_building = cust["c_custkey"][seg == "BUILDING"]
        supp = _cols(tpch.table("supplier"),
                     ("s_suppkey", "s_nationkey"))
        nat = _cols(tpch.table("nation"), ("n_nationkey", "n_regionkey"))
        reg_names = _strs(tpch.table("region"), "r_name")
        asia = np.asarray(tpch.table("region").columns["r_regionkey"]
                          .data)[reg_names == "ASIA"]
        asia_nations = nat["n_nationkey"][np.isin(nat["n_regionkey"],
                                                  asia)]
        detail["q03_numpy_s"] = round(numpy_q3(li, orders,
                                               cust_building), 2)
        detail["q05_numpy_s"] = round(numpy_q5(li, orders, cust, supp,
                                               asia_nations), 2)
        # Q9 baseline: 6-relation profit join over the green parts
        li9 = _cols(lineitem, ("l_orderkey", "l_partkey", "l_suppkey",
                               "l_quantity", "l_extendedprice",
                               "l_discount"))
        ps = _cols(tpch.table("partsupp"),
                   ("ps_partkey", "ps_suppkey", "ps_supplycost"))
        pnames = _strs(tpch.table("part"), "p_name")
        pkeys = np.asarray(tpch.table("part").columns["p_partkey"].data)
        green_part = np.zeros(int(pkeys.max()) + 1, dtype=bool)
        green_part[pkeys[np.char.find(pnames.astype("U"),
                                      "green") >= 0]] = True
        detail["q09_numpy_s"] = round(numpy_q9(li9, ps, orders, supp,
                                               green_part), 2)
        del li, li9, ps, orders, cust, supp
    except Exception as exc:  # baseline failure must not kill bench
        detail["numpy_join_baseline_error"] = repr(exc)[:200]

    # detail queries, JOINS FIRST (q03/q05 are the driver's metric).
    # q09 runs BEFORE q06 and holds a reserved slice the earlier
    # queries may not consume — five rounds in a row it was skipped as
    # "bench time budget exhausted" without ever being measured.
    for name in ("q03", "q05", "q09", "q06"):
        left = budget - (time.perf_counter() - t_start)
        if name in ("q03", "q05"):
            left -= q9_reserve  # keep q09's slice untouchable
        if name == "q09" and left < q9_reserve:
            # the reserve was eaten anyway (datagen overrun, a slow
            # q01 floor, numpy baselines): FAIL THE RESERVE LOUDLY —
            # a silent generic skip is how five rounds went by with
            # q09 never measured; the starved marker names the gap so
            # the budget regression is attributable, and q09 still
            # runs on whatever remains if it plausibly can
            detail["q09_reserve_starved"] = round(q9_reserve - left, 1)
        if left <= 60:
            detail[f"{name}_skipped"] = "bench time budget exhausted"
            continue
        r = measure_query(name, sf, reps, left - 15)
        if "error" in r:
            detail[f"{name}_error"] = r["error"]
            continue
        detail[f"{name}_rows_per_sec"] = round(nrows / r["steady_s"])
        detail[f"{name}_compile_s"] = r.get(
            "compile_s", round(r["first_s"] - r["steady_s"], 1))
        detail[f"{name}_execute_s"] = round(r["steady_s"], 2)
        detail[f"{name}_programs_compiled"] = r.get("programs_compiled")
        detail[f"{name}_device_syncs"] = r.get("device_syncs")
        detail[f"{name}_capacity_overflow_retries"] = r.get(
            "capacity_overflow_retries")
        # the child's kernel_backend setting (auto resolves per
        # kernel, kernels.AUTO_PALLAS) + its top-3 operators by
        # attributed wall
        detail[f"{name}_kernel_backend"] = r.get("kernel_backend")
        if r.get("top_operators"):
            detail[f"{name}_top_operators"] = r["top_operators"]
        # compile-time XLA cost totals (obs/devprof harvest summed over
        # the query's operator attribution) + query-level roofline
        # ratio: a perf regression that does not move these is a
        # runtime/scheduling regression, one that does is a plan change
        if r.get("flops"):
            detail[f"{name}_flops"] = r["flops"]
            detail[f"{name}_hbm_bytes"] = r.get("hbm_bytes")
            detail[f"{name}_roofline"] = r.get("roofline")
        if "variant_s" in r:
            # literal-variant warm rerun inside the cold child: with
            # plan templates on, variant_compiles MUST be 0 — the
            # variant hit the executable compiled for the original
            # literals (the ROADMAP item 2 serving scenario)
            detail[f"{name}_variant_warm_rows_per_sec"] = round(
                nrows / max(r["variant_s"], 1e-3))
            detail[f"{name}_variant_compiles"] = r["variant_compiles"]
            detail[f"{name}_template_hits"] = r.get("template_hits")
            detail[f"{name}_template_misses"] = r.get(
                "template_misses")
        base = detail.get(f"{name}_numpy_s")
        if base:
            detail[f"{name}_vs_baseline"] = round(
                base / r["steady_s"], 2)

    # per-backend q05/q09 (the kernel-backend comparison): when the
    # run was forced to pallas, measure the XLA fallback too, so the
    # execute-phase kernel speedup is checkable per backend from one
    # BENCH file. Under auto no kernel is Pallas today
    # (kernels.AUTO_PALLAS is empty) and on the CPU platform Pallas
    # is interpret mode — kernel_metrics() below reports
    # interpret-mode PARITY instead (correctness, not speed).
    for name in ("q05", "q09"):
        if detail.get(f"{name}_kernel_backend") != "pallas":
            continue
        left = budget - (time.perf_counter() - t_start)
        if left <= 60:
            detail[f"{name}_xla_skipped"] = "bench time budget " \
                                            "exhausted"
            continue
        r = measure_query(name, sf, reps, left - 15,
                          kernel_backend="xla")
        if "error" in r:
            detail[f"{name}_xla_error"] = r["error"]
            continue
        detail[f"{name}_xla_rows_per_sec"] = round(
            nrows / r["steady_s"])
        pallas_rps = detail.get(f"{name}_rows_per_sec")
        if pallas_rps:
            detail[f"{name}_pallas_vs_xla"] = round(
                pallas_rps / detail[f"{name}_xla_rows_per_sec"], 3)

    # Zipf-skew measurements (PRESTO_TPU_BENCH_SKEW=zipf:<s>): q05/q09
    # rerun against the Zipf-skewed datagen variant, so skew
    # regressions — one hot key collapsing the all_to_all onto a
    # single shard, capacity-overflow retry ladders — become visible
    # the way cold-compile ones did. The skew-aware join paths
    # (cost/skew.py hybrid distribution + salting, MultiJoin) are what
    # keeps these within range of the uniform numbers.
    skew = os.environ.get("PRESTO_TPU_BENCH_SKEW")
    if skew:
        detail["skew"] = skew
        t0 = time.perf_counter()
        try:
            TpchConnector(scale=sf, skew=skew).table("lineitem")
            detail["skew_datagen_s"] = round(time.perf_counter() - t0,
                                             1)
        except Exception as exc:  # bad spec must not kill the bench
            detail["skew_error"] = repr(exc)[:200]
            skew = None
    for name in ("q05", "q09") if skew else ():
        left = budget - (time.perf_counter() - t_start)
        if left <= 60:
            detail[f"{name}_skew_skipped"] = "bench time budget " \
                                             "exhausted"
            continue
        r = measure_query(name, sf, reps, left - 15, skew=skew)
        if "error" in r:
            detail[f"{name}_skew_error"] = r["error"]
            continue
        detail[f"{name}_skew_rows_per_sec"] = round(
            nrows / r["steady_s"])
        detail[f"{name}_skew_programs_compiled"] = r.get(
            "programs_compiled")
        uni = detail.get(f"{name}_rows_per_sec")
        if uni:
            detail[f"{name}_skew_vs_uniform"] = round(
                detail[f"{name}_skew_rows_per_sec"] / uni, 3)

    # warm starts LAST, so they can only spend what the cold
    # measurements (the driver's metrics, budget-shaped exactly as
    # before) left over: each query reruns in a FRESH process against
    # the persistent program cache — the compile-latency subsystem's
    # proof that a warm process is execute-dominated
    for name in ("q01", "q03", "q05", "q09", "q06"):
        if f"{name}_rows_per_sec" in detail or name == "q01":
            warm_metrics(detail, name, nrows, sf,
                         budget - (time.perf_counter() - t_start))

    # per-kernel pallas-vs-xla microbench + Q5/Q9 backend parity
    # (own subprocess, tiny SF)
    kernel_metrics(detail, budget - (time.perf_counter() - t_start))

    # concurrent-serving QPS + latency (own subprocess, tiny SF): the
    # scale numbers ride the same BENCH json as the throughput ones
    serve_metrics(detail, budget - (time.perf_counter() - t_start))

    # suite-wide capacity-overflow retry total (each rung is a
    # recompile): the adaptive-execution acceptance claim is that this
    # stays ZERO across the bench suite — measured, not inferred
    detail["capacity_overflow_retries_total"] = sum(
        v for k, v in detail.items()
        if k.endswith("_capacity_overflow_retries")
        and isinstance(v, int))

    print(json.dumps({**headline, "detail": detail}))


if __name__ == "__main__":
    sys.exit(main())
