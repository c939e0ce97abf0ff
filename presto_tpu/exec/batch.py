"""Cross-query batched execution: one vmapped device dispatch for many
parameter vectors of one plan template.

Serve-mode traffic is dominated by literal variants of a few query
shapes; the template subsystem (templates/) already proves those
variants share ONE traced program whose literals are trailing device
arguments. This module converts that compile-time sharing into a
serving-throughput win: K concurrent queries on the same template
fingerprint stack their bound parameter vectors along a new leading
axis and run ``jax.vmap(traced_fn)`` over it — the scan arrays are
broadcast (in_axes=None, uploaded once), only the parameter axis maps,
and the device executes one program for all K queries (the
vmap-over-row-blocks framing from the original design notes, applied
to the parameter axis). Per-query result slices demux into ordinary
host Tables byte-identical to serial execution.

The batched executable is a DIFFERENT XLA program from the serial one,
so it gets its own program-cache lineage: the canonical base key grows
a ``("batch", K)`` component, with the same capacity-retry ladder on
top (a hash-table overflow in ANY lane grows that table for the whole
batch — the ok flags come back as one (K, k) array and reduce over the
lane axis into the shared grow_overflowed ladder).

Batch widths are BUCKETED to powers of two: a group of 3 pads its
parameter stacks to width 4 by repeating the last member's bindings,
and only the first 3 lanes demux. Without padding every distinct group
size would lower+compile its own vmapped XLA program (serve-mode group
sizes jitter with arrival timing — an open-ended compile treadmill);
with it the program count is log2-bounded per template and the steady
state is pure cache hits. The padded lanes recompute a duplicate
query's answer — wasted FLOPs bounded by <2x, never wrong results.

Eligibility (:func:`batchable`) is deliberately narrow: the plain
single-program execute path only. Plans that would stream, spill, run
grouped, segment, carry MATCH_RECOGNIZE, or aggregate varlen arrays
fall back to serial execution — correctness first, the serving layer
batches the traffic that dominates repeats anyway.
"""

from __future__ import annotations

import jax
import numpy as np

from presto_tpu.block import Column, Table
from presto_tpu.exec import hostsync as HS
from presto_tpu.obs.metrics import REGISTRY
from presto_tpu.obs.trace import TRACER
from presto_tpu.plan import nodes as N
from presto_tpu import types as T

_BATCHED = REGISTRY.counter(
    "presto_tpu_batched_queries_total",
    "queries executed through a cross-query vmapped batch dispatch")
_BATCH_SIZE = REGISTRY.histogram(
    "presto_tpu_batch_size_queries",
    "queries per cross-query batched device dispatch",
    buckets=(1.0, 2.0, 4.0, 8.0, 16.0, 32.0, 64.0))

# the batch retry ladder mirrors prepare_plan's: 6 attempts with
# RETRY_GROWTH overshoot bounds recompiles at ~1 in practice
_MAX_ATTEMPTS = 6


def batchable(engine, plan: N.PlanNode) -> bool:
    """Can ``plan`` take the plain single-program execute path? Only
    then may the serving layer batch it (the gates mirror
    exec.executor.execute_plan's dispatch chain, checked cheaply —
    any estimate-driven doubt answers False and serial execution
    keeps its own gating)."""
    from presto_tpu.exec.executor import (_find_match_recognize,
                                          _find_split)
    from presto_tpu.exec.varlen import find_varlen_aggregate
    sess = engine.session
    if _find_match_recognize(plan) is not None:
        return False
    if find_varlen_aggregate(plan) is not None:
        return False
    if bool(sess.get("grouped_execution")):
        return False
    if int(sess.get("query_max_memory_bytes") or 0):
        return False  # could spill: the budget path owns it
    if _find_split(plan, engine) is not None:
        return False  # segmented pipeline: no single program to vmap
    block = int(sess.get("scan_block_rows") or 0)
    if block > 0 and _largest_scan_estimate(engine, plan) > block:
        return False  # could block-stream: serial path decides
    return True


def _largest_scan_estimate(engine, plan: N.PlanNode) -> int:
    if isinstance(plan, N.TableScan):
        conn = engine.catalogs.get(plan.catalog)
        if conn is None:
            return 0
        try:
            return int(conn.row_count_estimate(plan.table))
        except Exception:  # noqa: BLE001 - unknown estimate = 0
            return 0
    return max((_largest_scan_estimate(engine, s)
                for s in plan.sources()), default=0)


def run_plan_batched(engine, templates: list) -> list[Table]:
    """Execute K literal variants of one plan template as a single
    vmapped device dispatch; returns one host Table per variant, in
    input order. ``templates`` are templates/analysis.Template objects
    sharing one fingerprint (same parameterized plan, each carrying
    its own parameter values); all must hoist at least one parameter.

    Raises on any failure — the serving layer falls back to executing
    each member serially, so a batch-path defect degrades throughput,
    never correctness."""
    import uuid

    from presto_tpu import templates as TPL
    from presto_tpu.exec import progcache as PC
    from presto_tpu.exec.cancel import checkpoint
    from presto_tpu.exec.executor import (RETRY_GROWTH, _cache_key,
                                          _pool_wait, collect_scans,
                                          make_traced)

    k = len(templates)
    plan = templates[0].plan
    n_params = len(templates[0].params)
    if k < 2 or n_params == 0:
        raise ValueError("batch needs >= 2 queries and >= 1 parameter")
    pool = getattr(engine, "memory_pool", None)
    tag = "batch-" + uuid.uuid4().hex[:12]
    if pool is not None:
        from presto_tpu.exec import cancel as _cancel
        block_s, kill_s = _pool_wait(engine)
        scan_bytes = sum(
            a.nbytes
            for scan in TPL.bucket_scans(engine,
                                         collect_scans(plan, engine))
            for a in scan.arrays.values() if isinstance(a, np.ndarray))
        pool.reserve(tag, scan_bytes, block_s=block_s,
                     kill_after_s=kill_s, owner=_cancel.current())
    try:
        return _run_batched(engine, templates, k, plan, n_params)
    finally:
        if pool is not None:
            pool.free(tag)


def _run_batched(engine, templates: list, k: int, plan, n_params: int):
    from presto_tpu import templates as TPL
    from presto_tpu.exec import progcache as PC
    from presto_tpu.exec.cancel import checkpoint
    from presto_tpu.exec.executor import (RETRY_GROWTH, _cache_key,
                                          collect_scans, compile_traced,
                                          make_traced, program_name)

    scan_inputs = TPL.bucket_scans(engine,
                                   collect_scans(plan, engine))
    fpr = PC.platform_fingerprint()
    cache = engine._program_cache
    cache.configure(engine.session)
    serial_key, _ = _cache_key(engine, plan, scan_inputs, {})
    # bucket the batch width to the next power of two (see module
    # docstring): padding lanes repeat the last member's bindings and
    # are dropped at demux
    kp = 1 << (k - 1).bit_length()
    # the batched program's own cache lineage: same canonical plan /
    # shapes / dicts / session components, plus the batch width
    base_key = serial_key + (("batch", kp),)
    known_caps = engine._caps_memory.get(base_key)
    if known_caps is None:
        known_caps = cache.load_caps(base_key, fpr)
    capacities = dict(known_caps)

    # per-position stacks of the K queries' physical parameter values;
    # example args (placeholder string codes) carry the exact shapes
    # and dtypes the real bind will, so lowering on them is sound
    example = _stack_params(
        _pad([t.example_args(scan_inputs) for t in templates], kp))

    for _attempt in range(_MAX_ATTEMPTS):
        checkpoint()
        caps_key = PC.bucket_capacities(capacities)
        entry = cache.lookup((base_key, caps_key), fpr)
        flat_arrays = [
            engine.device_array(scan.arrays[sym])
            if getattr(scan, "cache_device", False) else scan.arrays[sym]
            for scan in scan_inputs for sym in scan.arrays]
        if entry is None:
            traced_fn, _host_arrays, meta = make_traced(
                scan_inputs, plan, capacities, engine.session,
                params=templates[0].example_args(scan_inputs))
            # scans broadcast (uploaded once), parameters map: the
            # whole operator chain vectorizes over the query axis
            batched_fn = jax.vmap(
                traced_fn,
                in_axes=(None,) * len(flat_arrays) + (0,) * n_params)
            batched_fn.__name__ = program_name(
                plan, serial_key[0], prefix=f"batch{kp}_")
            compiled = compile_traced(
                batched_fn, [*flat_arrays, *example], attempt=_attempt,
                root=type(plan).__name__, batch=kp)
            cache.insert((base_key, caps_key), compiled, meta, fpr,
                         persist=False)
            cache_hit = False
        else:
            compiled, meta = entry
            cache_hit = True
        # bind THIS batch's literal values through the trace-recorded
        # string dictionaries, stacked along the query axis
        pargs = _stack_params(
            _pad([t.bind(meta.get("param_bindings"))
                  for t in templates], kp))
        with TRACER.span("execute", cache_hit=cache_hit, batch=kp):
            res, live, oks, counts = compiled(*flat_arrays, *pargs)
            # (K, k) ok flags: a table that overflowed in ANY lane
            # must grow for the whole batch
            oks_np = HS.fetch(oks, site="batch-ok-ladder")
        oks_all = np.asarray(oks_np).all(axis=0)
        if oks_all.all():
            if not cache_hit:
                cache.insert((base_key, caps_key), compiled, meta, fpr)
            if engine._caps_memory.get(base_key) != capacities:
                cache.store_caps(base_key, capacities, fpr)
            engine._caps_memory[base_key] = dict(capacities)
            _BATCHED.inc(k)
            _BATCH_SIZE.observe(float(k))
            return _demux(plan, meta, res, live, k)
        if not cache_hit:
            cache.discard((base_key, caps_key))
        from presto_tpu.ops.hash import grow_overflowed
        grow_overflowed(capacities, meta["ok_keys"], oks_all,
                        meta["used_capacity"], RETRY_GROWTH)
    from presto_tpu.ops.hash import HashChainOverflow
    raise HashChainOverflow(
        "batched hash table capacity retry limit exceeded")


def _pad(binds: list, kp: int) -> list:
    """Fill the padded batch's extra lanes with the last member's
    bindings (their results are discarded at demux)."""
    return binds + [binds[-1]] * (kp - len(binds))


def _stack_params(binds: list[list]) -> list[np.ndarray]:
    """Position-wise stack of K queries' physical parameter vectors:
    the j-th traced parameter becomes a (K, ...)-shaped device input
    mapped by vmap's leading axis."""
    n = len(binds[0])
    return [np.stack([np.asarray(b[j]) for b in binds])
            for j in range(n)]


def _demux(plan: N.PlanNode, meta: dict, res, live,
           k: int) -> list[Table]:
    """Per-lane host Tables from one batched program's outputs: lane i
    of every (K, ...) result array is exactly what the serial program
    would have produced for query i (the unpack mirrors
    exec.executor.run_plan)."""
    from presto_tpu.exec.executor import _rename_outputs

    live_np, res_np = HS.fetch((live, res), site="batch-demux")
    tables: list[Table] = []
    for lane in range(k):
        cols: dict[str, Column] = {}
        i = 0
        for sym, dtype, dictionary, has_valid in meta["out"]:
            data = res_np[i][lane]
            valid = res_np[i + 1][lane]
            i += 2
            if isinstance(dtype, T.ArrayType):
                from presto_tpu.block import lists_from_padded
                lengths, emask = res_np[i][lane], res_np[i + 1][lane]
                i += 2
                data = lists_from_padded(dtype.element, data, lengths,
                                         emask, dictionary)
                cols[sym] = Column(
                    dtype, data,
                    valid if has_valid or not valid.all() else None,
                    None)
                continue
            cols[sym] = Column(
                dtype, data,
                valid if has_valid or not valid.all() else None,
                dictionary)
        lane_live = live_np[lane]
        tables.append(Table(_rename_outputs(plan, cols),
                            len(lane_live), lane_live))
    return tables
