"""EXPLAIN ANALYZE: execute the plan with per-node instrumentation.

Analog of the reference's ExplainAnalyzeOperator + OperatorStats rollup
(operator/ExplainAnalyzeOperator.java:34, OperationTimer.java:30). Under
XLA the whole pipeline fuses into one computation, so per-operator wall
time is not individually observable the way the reference times each
getOutput/addInput call; instead the profile reports what the fused model
can: actual row counts flowing out of every plan node (emitted as extra
kernel outputs), plus compile and execute wall times for the whole plan.

Segmented plans (exec/executor.py _find_split) profile per SEGMENT: each
separately compiled segment re-runs under a profiling interpreter, so
per-node actual rows — including pruned probe TableScans, the numbers
the dynamic-filter effectiveness tests read — surface on every segment's
plan, not just the final program.
"""

from __future__ import annotations

import time
import uuid

import jax
import jax.numpy as jnp
import numpy as np

from presto_tpu.cost import row_estimates
from presto_tpu.exec import hostsync as HS
from presto_tpu.exec.executor import (collect_scans, device_outputs,
                                      make_traced, preorder_index)
from presto_tpu.obs.trace import TRACER
from presto_tpu.plan import nodes as N
from presto_tpu.plan.printer import format_plan


def _rows_by_node_id(plan, meta, counts) -> dict[int, int]:
    """Per-node actual rows keyed by id(node) of THIS plan's objects.
    ``meta["count_nodes"]`` keys are stable preorder positions (they
    ride program-cache entries across replans and restarts); EXPLAIN
    ANALYZE's printer annotations key by object id, so invert the
    preorder walk."""
    inv = {pos: nid for nid, pos in preorder_index(plan).items()}
    counts_np = HS.fetch(counts, site="profile-counts")
    return {inv.get(key, key): int(c)
            for key, c in zip(meta["count_nodes"], counts_np)}


def _profiled_compile_run(engine, plan, scans):
    """Shared EXPLAIN ANALYZE ladder: trace, compile OUTSIDE the
    program cache (so the profile's compile/execute walls are really
    measured, not amortized over prior queries), and retry on
    hash-table overflow. Per-node actual rows need no special
    interpreter anymore — every traced program carries them
    (PlanInterpreter.row_counts, the always-on stats contract). The
    capacity vector is SEEDED from what prepare_plan already learned
    for this plan (memory or the caps sidecar), so profiling does not
    replay the overflow ladder with an extra 80-150 s compile per
    rung. Returns (meta, res, live, counts, compile_s, run_s) of the
    successful attempt."""
    from presto_tpu import templates as TPL
    from presto_tpu.exec import executor as EX
    from presto_tpu.exec import progcache as PC

    # seed capacities under the SAME key prepare_plan stores them:
    # with templates on that is the parameterized plan over bucketed
    # scan shapes (the profiling trace itself keeps literals baked)
    kplan, kscans = plan, scans
    if TPL.enabled(engine.session):
        kscans = TPL.bucket_scans(engine, scans)
        tpl = TPL.parameterize(plan)
        if tpl is not None:
            kplan = tpl.plan
    base_key, _ = EX._cache_key(engine, kplan, kscans, {})
    known = engine._caps_memory.get(base_key)
    if known is None:
        known = engine._program_cache.load_caps(
            base_key, PC.platform_fingerprint())
    capacities: dict[tuple, int] = dict(known)
    for _attempt in range(10):
        traced_fn, flat, meta = make_traced(
            scans, plan, capacities, engine.session)
        t0 = time.perf_counter()
        compiled = EX.compile_traced(
            traced_fn, flat, attempt=_attempt,
            root=type(plan).__name__, analyze=True)
        compile_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        with TRACER.span("execute", analyze=True):
            res, live, oks, counts = compiled(*flat)
            # raw measurement syncs (DEVICE_SYNC_EXEMPT, exec/hostsync):
            # the profile measures the readback itself, and must not
            # count into the hot-path device-sync counter
            jax.block_until_ready(live)
            oks_np = np.asarray(oks)
        run_s = time.perf_counter() - t0
        if oks_np.all():
            return meta, res, live, counts, compile_s, run_s
        from presto_tpu.ops.hash import grow_overflowed
        grow_overflowed(capacities, meta["ok_keys"], oks_np,
                        meta["used_capacity"])
    from presto_tpu.ops.hash import HashChainOverflow
    raise HashChainOverflow(
        "hash table capacity retry limit exceeded")


def _profiled_runner(engine, mat, scans, cap_floor=None, stats=None,
                     planned=0):
    """run_plan_device twin for segments: returns (arrays, dicts,
    types, n, {node id: actual rows}). ``cap_floor`` and ``planned``
    keep carrier widths consistent with the production (templated)
    pipeline."""
    meta, res, live, counts, _c, _r = _profiled_compile_run(
        engine, mat, scans)
    node_rows = _rows_by_node_id(mat, meta, counts)
    return device_outputs(meta, res, live, cap_floor, stats,
                          planned) + (node_rows,)


def _annotate(mat, node_rows: dict | None, engine) -> dict[int, str]:
    """Per-node 'rows: actual (est N)' annotations for one segment."""
    if not node_rows:
        return {}
    try:
        estimated = row_estimates(mat, engine)
    except Exception:  # noqa: BLE001 - carrier scans may lack stats
        estimated = {}
    return {nid: (f"rows: {actual}" if estimated.get(nid) is None
                  else f"rows: {actual} (est {estimated[nid]})")
            for nid, actual in node_rows.items()}


def explain_analyze(engine, plan: N.PlanNode) -> str:
    """EXPLAIN ANALYZE with PER-SEGMENT wall-clock attribution: each
    separately compiled segment (many-join splits + pre-aggregation
    compaction boundaries, exec/executor.py _find_split) reports its
    own execute wall, output width, AND per-node actual row counts
    (profiling runner); the final program adds its own row counts.
    Per-operator walls inside one segment are not observable under XLA
    fusion; the segment boundary is the real unit of time on this
    engine (reference analog: operator/OperationTimer.java:30 rolled
    up per operator, ExplainAnalyzeOperator.java:34)."""
    from presto_tpu.exec import executor as EX

    seg_lines: list[str] = []
    total_t0 = time.perf_counter()

    def observe(seg, mat, arrays, n, wall_s, node_rows):
        live = HS.fetch_int(jnp.sum(arrays["__live__"]),
                            site="profile-live")
        seg_lines.append(
            f"Segment {seg} ({wall_s * 1e3:.1f} ms, "
            f"{live} live rows -> s{seg}[{n}])\n"
            + format_plan(mat,
                          annotations=_annotate(mat, node_rows,
                                                engine)))

    pool = getattr(engine, "memory_pool", None)
    tag = "explain-" + uuid.uuid4().hex[:12]
    try:
        plan, carriers = EX._segment_carriers(engine, plan, tag,
                                              observer=observe,
                                              runner=_profiled_runner)
        scan_inputs = EX._collect_with_carriers(plan, engine, carriers)
        final = _explain_one_program(engine, plan, scan_inputs)
    finally:
        if pool is not None:
            pool.free(tag)
    if not seg_lines:
        return final
    total = (time.perf_counter() - total_t0) * 1e3
    return (f"Query plan: {len(seg_lines)} materialized segment(s) + "
            f"final program, total {total:.1f} ms\n"
            + "\n".join(seg_lines)
            + "\nFinal " + final)


def _explain_one_program(engine, plan: N.PlanNode,
                         scan_inputs=None) -> str:
    if scan_inputs is None:
        scan_inputs = collect_scans(plan, engine)
    annotations: dict[int, str] = {}
    estimated = row_estimates(plan, engine)
    meta, _res, _live, counts, compile_s, run_s = \
        _profiled_compile_run(engine, plan, scan_inputs)

    # estimated-vs-actual rows per node: estimation bugs show up in
    # one place (reference PlanPrinter's EXPLAIN ANALYZE estimate
    # columns)
    for nid, actual in _rows_by_node_id(plan, meta, counts).items():
        est = estimated.get(nid)
        annotations[nid] = (f"rows: {actual}" if est is None
                            else f"rows: {actual} (est {est})")
    header = (f"Query plan (compile {compile_s * 1e3:.1f} ms, "
              f"execute {run_s * 1e3:.1f} ms)\n")
    return header + format_plan(plan, annotations=annotations)


def explain_analyze_distributed(engine, plan: N.PlanNode, mesh) -> str:
    """EXPLAIN ANALYZE for the shard_map path: per-node mesh-global row
    counts + distribution tags + compile/run wall times (VERDICT round 2
    #10 — the distributed path previously had no profile at all)."""
    from presto_tpu.parallel.executor import execute_plan_distributed

    profile: dict = {}
    execute_plan_distributed(engine, plan, mesh, profile=profile)
    estimated = row_estimates(plan, engine)
    # profile["node_rows"] keys are stable preorder positions (the
    # program-cache-stable stats keys); the printer wants object ids
    inv = {pos: nid for nid, pos in preorder_index(plan).items()}
    annotations = {}
    for pos, (rows, dist) in profile["node_rows"].items():
        nid = inv.get(pos, pos)
        est = estimated.get(nid)
        annotations[nid] = (
            f"rows: {rows} [{dist}]" if est is None
            else f"rows: {rows} (est {est}) [{dist}]")
    header = (f"Distributed plan over {mesh.devices.size} devices "
              f"(compile {profile['compile_s'] * 1e3:.1f} ms, "
              f"execute {profile['run_s'] * 1e3:.1f} ms)\n")
    return header + format_plan(plan, annotations=annotations)
