"""Block-streamed scan execution: the split analog.

The reference streams tables through workers as connector splits
(split/SplitManager.java, plugin/trino-tpch/.../TpchSplitManager.java:55)
so no operator ever holds a whole table. The TPU analog: when a plan is a
single big scan feeding (through filters/projections) one aggregation,
execute the scan in fixed-size row blocks through ONE compiled
partial-aggregate kernel, accumulate the per-block partial states
(bounded by the group-count capacity, not the table size), then run the
rest of the plan over the merged partials. The copy runs one block
ahead: block i+1's ``jax.device_put`` is issued once block i's program
is dispatched and before the host waits for it, so the link carries the
next block while the chip computes this one. HBM holds at most two
blocks' arguments, so tables larger than device memory stream through.

A block costs the host nothing: it is a view of ``scan_block_rows`` rows
of every scanned column and two scalars, ``live_lo`` and ``live_hi``,
from which the block program makes its own live mask (rows of the block
in ``[live_lo, live_hi)``). The last block is the table's LAST
``scan_block_rows`` rows, not a padded copy of its tail: the rows it
shares with the block before are switched off by ``live_lo``, so every
block has one shape and one program serves them all.

The block program is a plan template like a resident program
(exec/executor.prepare_plan): with session ``plan_templates`` on, the
literals of the partial-aggregate sub-plan leave it before the program
cache is keyed, and reach the program as trailing device scalars with
every block. A statement whose literals the server has not met runs
the program an earlier variant compiled; only ``plan_templates=false``
still builds one per statement.

Shape requirements (else the whole-table path runs): exactly one
TableScan; only Filter/Project between it and a single-step Aggregate;
anything above the Aggregate (sort/limit/output operate on the small
aggregated result).
"""

from __future__ import annotations

import dataclasses

import jax
import numpy as np

from presto_tpu import types as T
from presto_tpu.exec import hostsync as HS
from presto_tpu.obs.metrics import REGISTRY
from presto_tpu.obs.trace import TRACER
from presto_tpu.plan import nodes as N

_BLOCK_COPIES = REGISTRY.counter(
    "presto_tpu_stream_block_copies_total",
    "Blocks of the streamed scan copied to the device, labeled by when: "
    "ahead (while the previous block's program was in flight) or inline "
    "(a statement's first block, or after the previous block's wait)")


def _chain_to_scan(node: N.PlanNode) -> N.TableScan | None:
    """The TableScan under ``node`` if the path is all Filter/Project."""
    while isinstance(node, (N.Filter, N.Project)):
        node = node.source
    return node if isinstance(node, N.TableScan) else None


def _count_scans(plan: N.PlanNode) -> int:
    n = 1 if isinstance(plan, N.TableScan) else 0
    return n + sum(_count_scans(s) for s in plan.sources())


def _find_streamable(plan: N.PlanNode):
    """Find (aggregate, scan) when the plan is streamable."""
    if _count_scans(plan) != 1:
        return None
    node = plan
    while not isinstance(node, N.Aggregate):
        srcs = node.sources()
        if len(srcs) != 1:
            return None
        node = srcs[0]
    if node.step != N.AggStep.SINGLE:
        return None
    if any(call.distinct for call in node.aggs.values()):
        return None
    scan = _chain_to_scan(node.source)
    if scan is None:
        return None
    return node, scan


def _replace_node(plan: N.PlanNode, target: N.PlanNode,
                  repl: N.PlanNode) -> N.PlanNode:
    if plan is target:
        return repl
    updates = {}
    for f in dataclasses.fields(plan):
        v = getattr(plan, f.name)
        if isinstance(v, N.PlanNode):
            updates[f.name] = _replace_node(v, target, repl)
        elif isinstance(v, list) and v and isinstance(v[0], N.PlanNode):
            updates[f.name] = [_replace_node(x, target, repl) for x in v]
    return dataclasses.replace(plan, **updates) if updates else plan


# The last component of a block program's cache key. The program's
# signature is (columns..., live_lo, live_hi, parameters...); over a
# masked table the last column is the table's own mask.
STREAM_TAG = "stream-range"
STREAM_MASKED_TAG = "stream-range-masked"


def _ranged(traced_fn, block: int, ncols: int, masked: bool):
    """The block program: ``make_traced``'s function, which takes
    ``__live__`` as the scan's last array, behind one that makes it on
    the device from the block's live range. The two bounds are traced
    scalars, so the program is the same for every block."""
    def block_fn(*args):
        cols, (live_lo, live_hi) = args[:ncols], args[ncols:ncols + 2]
        row = jax.lax.iota(np.int32, block)
        live = (row >= live_lo) & (row < live_hi)
        if masked:
            live, cols = live & cols[-1], cols[:-1]
        return traced_fn(*cols, live, *args[ncols + 2:])

    return block_fn


def try_execute_streamed(engine, plan: N.PlanNode):
    """Execute ``plan`` block-streamed, or return None if inapplicable."""
    from presto_tpu import templates as TPL
    from presto_tpu.exec import progcache as PC
    from presto_tpu.exec.executor import (
        ScanInput, _cache_key, collect_scans, compiling, make_traced,
        program_name, run_plan)

    block = int(engine.session.get("scan_block_rows") or 0)
    if block <= 0:
        return None
    found = _find_streamable(plan)
    if found is None:
        return None
    agg, scan_node = found
    scans = collect_scans(plan, engine)
    scan = scans[0]
    if scan.nrows <= block:
        return None

    # -- phase 1: one compiled partial-aggregate program, run per block --
    partial = dataclasses.replace(agg, step=N.AggStep.PARTIAL)
    nblocks = -(-scan.nrows // block)
    partial_cols: list[list[np.ndarray]] = []
    partial_live: list[np.ndarray] = []
    out_schema = None

    # a masked table (executor.collect_scans) brings its own __live__:
    # one more column of the block, after the others, that the program
    # ANDs with the range it makes itself
    masked = "__live__" in scan.arrays
    columns = [a for sym, a in scan.arrays.items() if sym != "__live__"]
    if masked:
        columns.append(scan.arrays["__live__"])

    # what the trace needs of a block is its shapes: a cached program
    # outlives the statement, and a block's arrays are views of the
    # table's columns, the last block's too
    shapes = {sym: jax.ShapeDtypeStruct((block,) + a.shape[1:], a.dtype)
              for sym, a in scan.arrays.items() if sym != "__live__"}
    shapes["__live__"] = jax.ShapeDtypeStruct((block,), np.bool_)
    block_scan = ScanInput(scan.node, shapes, scan.dictionaries,
                           scan.types, block)

    # as prepare_plan: hoist the literals, key the program cache on the
    # template (a sub-plan with nothing to hoist keys it as it is: a
    # replay hits, a variant compiles) and start from the capacities
    # that passed the ok-ladder last time
    templated = TPL.enabled(engine.session)
    cache = engine._program_cache
    fpr = PC.platform_fingerprint()
    tpl = None
    base_key = None
    capacities: dict[tuple, int] = {}
    if templated:
        cache.configure(engine.session)
        with TRACER.span("program-lookup"):
            tpl = TPL.parameterize(partial)
            if tpl is not None:
                partial = tpl.plan
            # the block program returns no row counts (collect_rows
            # off) and takes its live range as two scalars, so it never
            # shares an entry with a resident one
            base_key = (*_cache_key(engine, partial, [block_scan], {})[0],
                        STREAM_MASKED_TAG if masked else STREAM_TAG)
            capacities = dict(engine._caps_memory.get(base_key) or {})

    def place(i: int, ahead: bool) -> list:
        """Block i's device arguments: a view of its rows of every
        column and its live range, copied to the device. ``ahead``
        while the block before is still in flight."""
        # nrows > block (checked above), so the last block can be the
        # table's last ``block`` rows: full width, and the rows the
        # block before has counted are dead by live_lo
        lo = min(i * block, scan.nrows - block)
        live_lo = i * block - lo
        with TRACER.span("block-input", block=i,
                         rows=block - live_lo) as span:
            host_args = [a[lo:lo + block] for a in columns]
            if span is not None:
                # 0 while every array of the block is a view of its
                # column (a check of bounds, not of contents)
                span.attrs["copied_bytes"] = sum(
                    b.nbytes for a, b in zip(columns, host_args)
                    if not np.may_share_memory(a, b))
        host_args += [np.int32(live_lo), np.int32(block)]
        # the host's share of the copy; what is still in flight when
        # this returns falls into the block's ``execute``
        with TRACER.span("transfer", block=i, ahead=ahead,
                         bytes=sum(a.nbytes for a in host_args)):
            dev = jax.device_put(host_args)
        _BLOCK_COPIES.inc(when="ahead" if ahead else "inline")
        return dev

    from presto_tpu.exec.cancel import checkpoint
    compiled = None
    meta = None
    caps_key = None
    pargs: list = []
    placed = None  # block i+1's device arguments, once copied
    for i in range(nblocks):
        checkpoint()
        dev_args = placed if placed is not None else place(i, ahead=False)
        placed = None
        for _attempt in range(10):
            fresh = compiled is None
            if fresh and templated:
                caps_key = PC.bucket_capacities(capacities)
                entry = cache.lookup((base_key, caps_key), fpr)
                if tpl is not None and i == 0 and _attempt == 0:
                    # once a statement, as prepare_plan counts it
                    TPL.note_lookup(hit=entry is not None,
                                    params=len(tpl.params))
                if entry is not None:
                    # a hit: no compile span, nothing compiles, and
                    # this statement's literals ride with every block
                    compiled, meta = entry
                    fresh = False
                    if tpl is not None:
                        pargs = tpl.bind(meta.get("param_bindings"))
            if fresh:
                # collect_rows off: the block program replays per
                # block; run_plan over the concatenated partials (the
                # final program) still records its stats normally
                if tpl is not None:
                    pargs = tpl.example_args([block_scan])
                traced_fn, _flat, meta = make_traced(
                    [block_scan], partial, capacities, engine.session,
                    params=pargs if tpl is not None else None,
                    collect_rows=False)
                # a template's fingerprint holds no literal, so the
                # variants share the name as they share the program
                block_fn = _ranged(traced_fn, block, len(columns), masked)
                block_fn.__name__ = program_name(
                    partial, base_key[0] if tpl is not None else None,
                    prefix="stream_")
                compiled = jax.jit(block_fn)
            outs = None
            if fresh:
                # The first call of a fresh jit traces, lowers, compiles
                # and dispatches, and returns before the device is done
                # (dispatch is asynchronous), so the span holds the
                # building of the program and ``execute`` below the
                # waiting for this block. Not an AOT lower().compile()
                # as in prepare_plan: in the served process that made
                # every streamed statement at SF10 0.9-2.8 s slower on
                # the chip (PERF.md section 6, PR 25), its lowering
                # alone 1.8-3.0 s against 0.2 s on this path; meta
                # fills during the trace. The example arguments it
                # runs on are this statement's own values, but for a
                # string parameter: the dictionary that gives its code
                # is known only once the trace has recorded it, and
                # the block then runs again below, bound.
                with compiling(program=block_fn.__name__,
                               attempt=_attempt,
                               root=type(partial).__name__, streamed=True):
                    outs = compiled(*dev_args, *pargs)
                if tpl is not None and meta.get("param_bindings"):
                    pargs = tpl.bind(meta["param_bindings"])
                    outs = None
                if templated:
                    # the memory tier only: the disk tier stores AOT
                    # executables, and this is the jit wrapper
                    cache.insert((base_key, caps_key), compiled, meta,
                                 fpr, persist=False)
            if outs is None:
                outs = compiled(*dev_args, *pargs)
            if placed is None and i + 1 < nblocks:
                # the next block's copy rides the link while this one
                # computes; its arrays hold no capacity, so a rerun of
                # this block on the ladder leaves them valid
                placed = place(i + 1, ahead=True)
            with TRACER.span("execute", block=i, streamed=True):
                res, live, oks = outs
                oks_np = HS.fetch(oks, site="streaming-ok-ladder")
                if oks_np.all():
                    # one batched transfer per block, not one per
                    # output column
                    res_np, live_np = HS.fetch((list(res), live),
                                               site="streaming-demux")
            if oks_np.all():
                break
            if templated:
                # the remembered capacities only grow, so no statement
                # comes back to this rung: dead weight in the LRU
                cache.discard((base_key, caps_key))
            from presto_tpu.ops.hash import grow_overflowed
            grow_overflowed(capacities, meta["ok_keys"], oks_np,
                            meta["used_capacity"])
            compiled = None  # the program of the grown capacities
        else:
            from presto_tpu.ops.hash import HashChainOverflow
            raise HashChainOverflow(
                "hash table capacity retry limit exceeded")
        out_schema = meta["out"]
        partial_cols.append(res_np)
        partial_live.append(live_np)
    if templated:
        # the next statement of this shape starts on the rung that held
        engine._caps_memory[base_key] = dict(capacities)

    # -- phase 2: rest of the plan over the concatenated partials --------
    carrier_syms = [sym for sym, _t, _d, _v in out_schema]
    carrier_types = {sym: t for sym, t, _d, _v in out_schema}
    carrier = N.TableScan("__stream__", "__partials__",
                          {sym: sym for sym in carrier_syms},
                          carrier_types)
    final_agg = dataclasses.replace(agg, source=carrier,
                                    step=N.AggStep.FINAL)
    plan2 = _replace_node(plan, agg, final_agg)

    arrays2: dict[str, np.ndarray] = {}
    dicts2: dict[str, np.ndarray | None] = {}
    for j, (sym, _t, d, has_valid) in enumerate(out_schema):
        arrays2[sym] = np.concatenate([p[2 * j] for p in partial_cols])
        if has_valid:
            arrays2[f"{sym}$valid"] = np.concatenate(
                [p[2 * j + 1] for p in partial_cols])
        dicts2[sym] = d
    arrays2["__live__"] = np.concatenate(partial_live)
    total = int(arrays2["__live__"].shape[0])
    carrier_input = ScanInput(carrier, arrays2, dicts2, carrier_types,
                              total)
    engine.last_streamed_blocks = nblocks
    return run_plan(engine, plan2, [carrier_input])
