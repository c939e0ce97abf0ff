"""Plan executor: logical plan -> one jitted XLA program -> host Table.

Analog of LocalQueryRunner.executeInternal + createDrivers
(testing/LocalQueryRunner.java:685,745) with the crucial difference that a
fragment is ONE traced computation: XLA fuses the operator chain instead of
pulling pages operator-by-operator (reference Driver.java:354 hot loop).

Hash-table capacities: planner hints (node.capacity when set) or
2 * input-length fallback; on kernel-reported overflow the executor doubles
the capacity and recompiles — the host-side analog of the reference's
rehash (MultiChannelGroupByHash.java:140).
"""

from __future__ import annotations

import contextlib
import dataclasses
import logging
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np

from presto_tpu import types as T
from presto_tpu.block import Column, Table
from presto_tpu.exec import hostsync as HS
from presto_tpu.exec import operators as OP
from presto_tpu.exec.operators import DTable
from presto_tpu.expr.compile import Val
from presto_tpu.obs.metrics import REGISTRY
from presto_tpu.obs.trace import TRACER, current_context
from presto_tpu.ops.hash import next_pow2
from presto_tpu.plan import nodes as N

_COMPILES = REGISTRY.counter(
    "presto_tpu_programs_compiled_total",
    "XLA programs compiled (cache misses + capacity-retry recompiles)")
_JAX_COMPILE_SECONDS = REGISTRY.counter(
    "presto_tpu_jax_compile_seconds_total",
    "seconds JAX spent building programs, whichever code asked for "
    "them, by phase (trace | lower | xla | cache_load), as "
    "jax.monitoring reports them")
_JAX_BACKEND_COMPILES = REGISTRY.counter(
    "presto_tpu_jax_backend_compiles_total",
    "programs handed to the backend, whichever code built them, by "
    "outcome (compiled | cache_hit: loaded from JAX's persistent cache)")
_EXPANDED_ROWS = REGISTRY.counter(
    "presto_tpu_join_expanded_rows_total",
    "rows the expanding (many-to-many) joins of executed programs "
    "emitted, from the per-node counts a program's results fetch")
_EXPAND_PACK = REGISTRY.counter(
    "presto_tpu_expand_pack_total",
    "expanding joins of executed programs by how their payload moved "
    "(packed: in 32-bit words | unpacked: column by column | "
    "range_retry: packed, a value fell outside its stated range and "
    "the program runs again unpacked)")
_DYN_FILTERS = REGISTRY.counter(
    "presto_tpu_dynamic_filters_total",
    "INNER join legs of executed programs by what the trace did with "
    "their dynamic filter (registered | skipped_direct | skipped_wide)")
# what PlanInterpreter._collect_dyn_filters does with a leg: the keys
# of an interpreter's df_counts and the counter's ``outcome`` label
DF_OUTCOMES = ("registered", "skipped_direct", "skipped_wide")


# dispatch-exhaustiveness opt-outs (lint/dispatch.py): node types the
# PlanInterpreter deliberately has no _r_ handler for
DISPATCH_EXEMPT = {
    "MatchRecognize": "execute_plan splits the plan at the "
    "MatchRecognize node before interpretation (host-side NFA, see "
    "_execute_with_match_recognize); a node reaching the interpreter "
    "fails loudly in run()",
}


@dataclasses.dataclass
class ScanInput:
    """Host-side arrays + metadata for one TableScan."""

    node: N.TableScan
    # symbol -> physical data (exec/streaming.py traces its block
    # program from shapes alone: jax.ShapeDtypeStruct in their place)
    arrays: dict[str, np.ndarray]
    dictionaries: dict[str, np.ndarray | None]
    types: dict[str, T.DataType]
    nrows: int
    # True only for connector-owned table arrays (stable identity across
    # executions): those pin device copies via Engine.device_array.
    # Per-execution temporaries (spill partitions, match-recognize
    # carriers) would pollute the pin cache with 0%-hit entries.
    cache_device: bool = False
    # connector-defined partitioning mapped to scan SYMBOLS (set when
    # every partitioning column is scanned); the distributed executor
    # bucket-shards such scans so co-partitioned joins skip exchanges
    part_cols: tuple[str, ...] | None = None
    # set by execute_plan_distributed when this scan was actually
    # bucket-sharded (scan rows placed by key hash, not blocks)
    bucketed: bool = False


def partitioning_symbols(connector, node: "N.TableScan"
                         ) -> tuple[str, ...] | None:
    """Connector-declared partitioning mapped to this scan's symbols,
    or None when undeclared / not fully scanned. Duck-typed: worker-side
    buffer connectors don't subclass the SPI base."""
    declared = getattr(connector, "partitioning", lambda _n: None)(
        node.table)
    if not declared:
        return None
    by_col = {c: s for s, c in node.assignments.items()}
    if not all(c in by_col for c in declared):
        return None
    return tuple(by_col[c] for c in declared)


def collect_scans(plan: N.PlanNode, engine) -> list[ScanInput]:
    out = []

    def visit(node):
        if isinstance(node, N.TableScan):
            connector = engine.catalogs[node.catalog]
            tbl = connector.table(node.table)
            arrays, dicts, types = {}, {}, {}
            for sym, colname in node.assignments.items():
                col = tbl.columns[colname]
                if isinstance(col.dtype, T.ArrayType) and np.asarray(
                        col.data).dtype == object:
                    # host object lists (varlen-aggregate outputs) ->
                    # padded 2D device layout + companion arrays
                    from presto_tpu.block import pad_object_lists
                    d2, lens, emask, d = pad_object_lists(
                        col.dtype.element, np.asarray(col.data))
                    arrays[sym] = d2
                    arrays[f"{sym}$len"] = lens
                    arrays[f"{sym}$emask"] = emask
                    dicts[sym] = d
                else:
                    arrays[sym] = np.asarray(col.data)
                    dicts[sym] = col.dictionary
                if col.valid is not None:
                    # NULL masks ship as sibling arrays (spi Block.isNull)
                    arrays[f"{sym}$valid"] = np.asarray(col.valid)
                types[sym] = col.dtype
            if tbl.mask is not None:
                # table-level row mask (padded exchange buffers ship a
                # dead row so empty relations keep static shape >= 1)
                arrays["__live__"] = np.asarray(tbl.mask)
            out.append(ScanInput(
                node, arrays, dicts, types, tbl.nrows,
                cache_device=True,
                part_cols=partitioning_symbols(connector, node)))
        for s in node.sources():
            visit(s)

    with TRACER.span("scan-collect") as span:
        visit(plan)
        if span is not None:
            span.attrs.update(tables=len(out),
                              rows=sum(s.nrows for s in out))
    return out


def _df_hash(v: Val):
    """Content hash of a key column for dynamic-filter blooms."""
    from presto_tpu.ops import hash as H
    if v.is_string:
        return H.hash_string_column(v.data, v.dictionary, v.valid)
    return H.hash_int_column(v.data, v.valid)


def program_name(plan: N.PlanNode, template_fp: str | None = None,
                 prefix: str = "") -> str:
    """A stable name for the jitted program of ``plan``: its root
    operator's kind and, for a templated plan, the first 8 hex digits
    of the template's fingerprint (hoisted literals are out of it, so
    literal variants share the name; a plan that still holds its
    literals gets the kind alone). It names the XLA module, which is
    how a profiler capture tells one program from another."""
    name = prefix + type(plan).__name__.lower()
    return f"{name}_{template_fp[:8]}" if template_fp else name


# -- what JAX says a build was made of -----------------------------------------
#
# jax.monitoring reports each phase of a build as it ends, on the thread
# that ran it: the Python trace of the function, its lowering to an MLIR
# module, and the backend's share (XLA's compile, or a load from the
# persistent cache, which JAX times inside the same event). The
# listeners below feed two process-wide counters, whichever code built
# the program (a bare ``jax.jit`` helper too), and the ``compile`` span
# that is open on the thread. They run only when JAX traces or compiles,
# never on a call of a program it already holds.

_PHASE_OF_EVENT = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "xla",
}
_CACHE_USED_EVENT = "/jax/compilation_cache/compile_requests_use_cache"
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
_PHASES = ("trace", "lower", "xla", "cache_load")

# a build whose ``compile`` span is longer says so in the log
SLOW_BUILD_S = 10.0
_LOG = logging.getLogger("presto_tpu")


class _BuildState(threading.local):
    """What the listeners know on one thread: how many timed events are
    open (a jitted function traced inside another's trace reports its
    own duration inside the outer one's, and an operation dispatched
    eagerly during a trace is lowered and compiled inside it: only the
    outermost event's seconds count, under its own phase), what the
    persistent cache said of the backend request in flight, and the
    ``compile`` span's collector."""

    depth = 0
    cache_used = False
    cache_hit = False
    build: dict | None = None


_STATE = _BuildState()


def _on_event_start(event: str, _value, **_kw) -> None:
    # jax records a scalar (the start time) as a timed event opens
    if event in _PHASE_OF_EVENT:
        _STATE.depth += 1


def _on_event(event: str, **_kw) -> None:
    if event == _CACHE_USED_EVENT:
        _STATE.cache_used, _STATE.cache_hit = True, False
    elif event == _CACHE_HIT_EVENT:
        _STATE.cache_hit = True


def _on_event_duration(event: str, seconds: float, **_kw) -> None:
    phase = _PHASE_OF_EVENT.get(event)
    if phase is None:
        return
    st = _STATE
    st.depth = max(st.depth - 1, 0)
    outcome = None
    if phase == "xla":
        # a hit's event holds the cache key, the read and the
        # deserialisation, and no compile: all of it is the load
        outcome = ("hit" if st.cache_hit
                   else "miss" if st.cache_used else "off")
        st.cache_used = st.cache_hit = False
        _JAX_BACKEND_COMPILES.inc(
            outcome="cache_hit" if outcome == "hit" else "compiled")
        if outcome == "hit":
            phase = "cache_load"
    if st.depth:
        return
    _JAX_COMPILE_SECONDS.inc(seconds, phase=phase)
    if st.build is not None:
        st.build[phase] += seconds
        if outcome is not None:
            # the last program of the span is the one it was opened for
            st.build["persistent_cache"] = outcome


jax.monitoring.register_scalar_listener(_on_event_start)
jax.monitoring.register_event_listener(_on_event)
jax.monitoring.register_event_duration_secs_listener(_on_event_duration)


@contextlib.contextmanager
def compiling(program: str = "", **attrs):
    """A ``compile`` span around the building of one program, counted in
    ``programs_compiled_total``: the one way every path that builds a
    program says so. When it closes it carries what JAX reported on
    this thread meanwhile: ``program`` (the XLA module's name: ``jit_``
    and the function's), ``trace_s``, ``lower_s``, ``xla_s``,
    ``cache_load_s`` and ``persistent_cache`` (hit | miss | off, of the
    span's last program). A build longer than ``SLOW_BUILD_S`` leaves a
    line in the log, so a process killed while it compiles has said
    which programs it finished."""
    from presto_tpu.ft.faults import FAULTS
    build = dict.fromkeys(_PHASES, 0.0)
    outer, _STATE.build = _STATE.build, build
    t0 = time.perf_counter()
    span = None
    try:
        with TRACER.span("compile", **attrs) as span:
            # compile-latency chaos point (ft/faults.py): lets the
            # chaos suite provoke slow compiles deterministically
            FAULTS.delay("compile-slow", key=attrs.get("root", ""))
            yield
        _COMPILES.inc()
    finally:
        _STATE.build = outer
        took = time.perf_counter() - t0
        name = f"jit_{program}"  # the XLA module's name
        cache = build.pop("persistent_cache", None)
        if span is not None:
            span.attrs.update(
                {f"{k}_s": v for k, v in build.items()}, program=name,
                **({"persistent_cache": cache} if cache else {}))
        if took > SLOW_BUILD_S:
            ctx = current_context()
            _LOG.warning(
                "slow build: program=%s attempt=%s took=%.1fs trace=%.1fs "
                "lower=%.1fs xla=%.1fs cache_load=%.1fs "
                "persistent_cache=%s statement=%s", name,
                attrs.get("attempt"), took, *(build[k] for k in _PHASES),
                cache, ctx[0] if ctx is not None else None)


def note_dyn_filters(meta: dict, span) -> None:
    """Say what an executed program's trace did with its join legs'
    dynamic filters (``PlanInterpreter.df_counts``, carried in the
    program's meta so a cache hit says it too): on the labelled counter
    and, where the program has such a leg, on its ``execute`` span as
    ``dynfilters=registered:0,direct:5,wide:0``."""
    counts = meta.get("dynfilters")
    if not counts or not any(counts.values()):
        return
    for outcome, n in counts.items():
        if n:
            _DYN_FILTERS.inc(n, outcome=outcome)
    if span is not None:
        span.attrs["dynfilters"] = ",".join(
            f"{outcome.removeprefix('skipped_')}:{n}"
            for outcome, n in counts.items())


def note_expand_pack(meta: dict, oks) -> None:
    """Count each expanding join an executed program ran on
    ``presto_tpu_expand_pack_total`` by its outcome; ``oks`` are the
    program's ok flags, fetched (a failed "pack" flag is a
    range_retry)."""
    packs = meta.get("expand_pack")
    if not packs:
        return
    failed = {pos for (pos, kind), ok in zip(meta["ok_keys"], oks)
              if kind == "pack" and not ok}
    for pos, (packed, *_words) in packs.items():
        _EXPAND_PACK.inc(outcome="range_retry" if pos in failed
                         else "packed" if packed else "unpacked")


def compile_traced(fn, args: list, **attrs):
    """Explicit AOT lower+compile of ``fn`` for ``args`` (not a first
    jit-wrapper call), so compile and execute attribute separately."""
    with compiling(program=fn.__name__, **attrs):
        return jax.jit(fn).lower(*args).compile()


def preorder_index(plan: N.PlanNode) -> dict[int, int]:
    """id(node) -> stable preorder position. Capacity-override keys use
    this instead of id() so a successful capacity vector transfers to a
    structurally identical re-plan of the same query (program cache)."""
    order: dict[int, int] = {}

    def visit(node):
        order[id(node)] = len(order)
        for s in node.sources():
            visit(s)

    visit(plan)
    return order


class PlanInterpreter:
    """Walks the plan during trace, building the XLA computation."""

    def __init__(self, scans: dict[int, tuple[ScanInput, dict]],
                 capacities: dict[tuple, int], session=None,
                 node_order: dict[int, int] | None = None):
        from presto_tpu.session import Session
        self.scans = scans  # id(node) -> (ScanInput, traced arrays)
        self.capacities = capacities  # (node pos, kind) -> forced capacity
        self.node_order = node_order or {}
        self.session = session or Session()
        self.ok_flags: list = []
        self.ok_keys: list[tuple] = []
        self.used_capacity: dict[tuple, int] = {}
        # always-on runtime stats (obs/qstats.py): live rows out of
        # EVERY plan node, keyed by stable preorder position so the
        # counts survive replans and ride program-cache entries across
        # process restarts. Collected on the normal cached/templated
        # path — a handful of mask sums per program, no extra compiles.
        self.collect_rows = True
        self.row_counts: list[tuple[object, object]] = []
        # dynamic filtering: probe-key symbol -> (min, max) from the
        # already-traced build side; applied at the FIRST probe-subtree
        # node that outputs the symbol (i.e. the scan), the trace-time
        # analog of the reference's DynamicFilterService pushdown
        # (server/DynamicFilterService.java:102,
        # operator/DynamicFilterSourceOperator.java:55)
        self.dyn_filters: dict[str, tuple] = {}
        self._df_applied: set[str] = set()
        # INNER join legs by what _collect_dyn_filters did with them;
        # rides the program's meta like used_capacity (make_traced)
        self.df_counts = dict.fromkeys(DF_OUTCOMES, 0)
        # (preorder position, probe rows, output capacity) of every
        # expanding join the program runs (meta["expand_nodes"])
        self.expand_nodes: list[tuple[int, int, int]] = []
        # preorder position -> (packed, probe words, build words,
        # unpacked words) of each (meta["expand_pack"])
        self.expand_pack: dict[int, tuple] = {}

    def run(self, node: N.PlanNode) -> DTable:
        kind = type(node).__name__
        m = getattr(self, "_r_" + kind.lower())
        # device operations carry the plan operator's name: run()
        # recurses, so the scopes nest (TopN#1/Aggregate#2/Join#3/...)
        # and an operation belongs to the innermost Kind#n of its path
        pos = self.node_order.get(id(node))
        scope = kind if pos is None else f"{kind}#{pos}"
        with jax.named_scope(scope):
            dt = m(node)
        if self.dyn_filters:
            dt = self._apply_dyn_filters(dt)
        if self.collect_rows:
            self.row_counts.append(
                (self.node_order.get(id(node), id(node)),
                 jnp.sum(dt.live_mask().astype(jnp.int64))))
        return dt

    def _apply_dyn_filters(self, dt: DTable) -> DTable:
        keep = None
        for sym, bits in self.dyn_filters.items():
            v = dt.cols.get(sym)
            if v is None or sym in self._df_applied:
                continue
            self._df_applied.add(sym)
            m = jnp.uint64(bits.shape[0])
            h = (_df_hash(v) % m).astype(jnp.int32)
            k = bits[h]
            if v.valid is not None:
                # NULL keys never match an inner join
                k = k & v.valid
            keep = k if keep is None else (keep & k)
        if keep is None:
            return dt
        live = keep if dt.live is None else (dt.live & keep)
        return DTable(dt.cols, live, dt.n)

    def _collect_dyn_filters(self, criteria: list[tuple[str, str]],
                             dense_key: tuple | None, build: DTable,
                             max_bits: int = 1 << 22) -> list[str]:
        """Build a one-hash bloom mask of the build-side key set per
        equi-key before the probe subtree is traced. False positives
        only cost the pruning (the join re-verifies); false negatives
        are impossible. Returns the registered probe symbols (a symbol
        may be re-registered by a later join over the same key).

        A probe key is tested once, so two kinds of leg register
        nothing. One whose own probe is a direct address (``dense_key``,
        the hint apply_join and apply_multi_join take _direct_probe
        for): that gather answers exactly what the mask's hash, modulus
        and gather would answer approximately, its other criteria are
        compared by value, and at a static width a pruned row spares
        nothing downstream. And one whose build is as wide as the mask
        after the ``max_bits`` cap: under a bit a build row the mask
        passes most keys (the reference gives such a filter up too,
        DynamicFilterSourceOperator's max-distinct-values limit)."""
        if dense_key is not None:
            self.df_counts["skipped_direct"] += 1
            return []
        m = next_pow2(min(4 * max(build.n, 16), max_bits))
        if build.n >= m:
            self.df_counts["skipped_wide"] += 1
            return []
        self.df_counts["registered"] += 1
        live = build.live_mask()
        registered = []
        for lk, rk in criteria:
            v = build.cols[rk]
            w = live if v.valid is None else (live & v.valid)
            h = (_df_hash(v) % jnp.uint64(m)).astype(jnp.int32)
            bits = jnp.zeros((m,), dtype=bool)
            bits = bits.at[jnp.where(w, h, m)].set(True, mode="drop")
            self.dyn_filters[lk] = bits
            registered.append(lk)
        return registered

    def _node_key(self, node, kind: str) -> tuple:
        return (self.node_order.get(id(node), id(node)), kind)

    def _capacity(self, node, default: int, kind: str = "table",
                  override: int | None = None) -> int:
        """Host retry override > session override > planner hint >
        default. Planner hints are normalized through next_pow2 so
        used_capacity / overflow-retry keys stay pow2-canonical even
        for hand-written non-pow2 hints (cache-entry MERGING of nearby
        hints happens upstream: cost/reorder.py writes pow2-bucketed
        hints, which is what the plan fingerprint hashes)."""
        cap = self.capacities.get(self._node_key(node, kind))
        if cap is None:
            if override:
                cap = next_pow2(override)
            elif kind == "table":
                hint = getattr(node, "capacity", None)
                cap = next_pow2(hint) if hint else default
            elif kind == "out":
                hint = getattr(node, "output_capacity", None)
                cap = next_pow2(hint) if hint else default
            else:
                cap = default
        self.used_capacity[self._node_key(node, kind)] = cap
        return cap

    def _note_ok(self, node, ok, kind: str = "table"):
        self.ok_flags.append(ok)
        self.ok_keys.append(self._node_key(node, kind))

    def _r_tablescan(self, node: N.TableScan) -> DTable:
        scan, traced = self.scans[id(node)]
        cols = {}
        for sym in node.assignments:
            cols[sym] = Val(scan.types[sym], traced[sym],
                            traced.get(f"{sym}$valid"),
                            scan.dictionaries[sym],
                            traced.get(f"{sym}$len"),
                            traced.get(f"{sym}$emask"))
        # a block of a streamed scan is scan_block_rows wide whatever
        # scan.nrows says; the rows outside its live range are dead
        nrows = next(iter(traced.values())).shape[0] if traced else scan.nrows
        return DTable(cols, traced.get("__live__"), nrows)

    def _r_values(self, node: N.Values) -> DTable:
        cols = {}
        n = len(node.rows)
        for i, sym in enumerate(node.symbols):
            dtype = node.types[sym]
            vals = [r[i] for r in node.rows]
            if isinstance(dtype, T.VarcharType):
                from presto_tpu.block import dictionary_encode
                codes, d = dictionary_encode(np.array(vals, object))
                cols[sym] = Val(dtype, jnp.asarray(codes), None, d)
            else:
                cols[sym] = Val(dtype, jnp.asarray(
                    np.asarray(vals, dtype=dtype.physical_dtype)))
        return DTable(cols, None, n)

    def _r_filter(self, node: N.Filter) -> DTable:
        return OP.apply_filter(self.run(node.source), node.predicate)

    def _r_project(self, node: N.Project) -> DTable:
        return OP.apply_project(self.run(node.source), node.assignments)

    def _r_aggregate(self, node: N.Aggregate) -> DTable:
        src = self.run(node.source)
        if not node.group_keys:
            cap = 1
        else:
            # bounded default: overflow-retry grows it if the real group
            # count exceeds the guess (reference rehash analog)
            override = int(self.session.get("groupby_table_size") or 0)
            cap = self._capacity(
                node, next_pow2(min(2 * src.n, 1 << 22)), override=override)
            if not override:
                # no more groups than input rows: a wider table would
                # only pad the output, and every sort after it
                cap = min(cap, next_pow2(src.n))
        out, ok = OP.apply_aggregate(src, node, cap)
        if node.group_keys:
            self._note_ok(node, ok)
        return out

    def _r_join(self, node: N.Join) -> DTable:
        # build side first so its key range can prune the probe scan
        right = self.run(node.right)
        if (node.join_type == N.JoinType.INNER
                and self.session.get("enable_dynamic_filtering")):
            # the hint counts where apply_join will take it (below)
            self._collect_dyn_filters(
                node.criteria,
                node.dense_key if node.build_unique else None, right)
        left = self.run(node.left)
        cap = self._capacity(node, next_pow2(2 * right.n))
        if node.build_unique and node.join_type != N.JoinType.FULL:
            # FULL always takes the expanding path: it owns the
            # unmatched-build-rows tail pass
            out, ok = OP.apply_join(left, right, node, cap)
            self._note_ok(node, ok)
            return out
        out_cap = self._capacity(node, next_pow2(2 * (left.n + right.n)),
                                 "out")
        pos = self.node_order.get(id(node), id(node))
        self.expand_nodes.append((pos, left.n, out_cap))
        # packed where a column can pack, unless the range check of an
        # earlier run failed: that rung of the ok ladder forces the
        # "pack" key (grow_overflowed)
        pack_key = self._node_key(node, "pack")
        packed = (node.expand_layout is not None
                  and pack_key not in self.capacities
                  and OP.packs_any(left, right, node.expand_layout))
        out, r_ok, o_ok = OP.apply_expand_join(
            left, right, node, cap, out_cap,
            node.expand_layout if packed else None)
        self.expand_pack[pos] = (packed, *OP.expand_words(
            left, right, node.expand_layout, packed))
        if packed:
            # a ladder that grows every failed key (exec/spill.py) reads it
            self.used_capacity[pack_key] = 1
            self._note_ok(node, r_ok, "pack")
        self._note_ok(node, o_ok, "out")
        return out

    def _r_multijoin(self, node: N.MultiJoin) -> DTable:
        """Fused star chain (plan/nodes.MultiJoin): trace every build
        first — registering the key set of each build whose leg is a
        sorted lookup as a dynamic filter, so the spine scan prunes
        against all of them at once — then run the probe walk."""
        builds = []
        for k, (bnode, crit) in enumerate(zip(node.builds, node.criteria)):
            bdt = self.run(bnode)
            builds.append(bdt)
            if self.session.get("enable_dynamic_filtering"):
                # keys referencing earlier builds register harmlessly
                # (applied wherever the symbol first flows)
                self._collect_dyn_filters(crit, node.leg_dense_key(k),
                                          bdt)
        spine = self.run(node.spine)
        out, ok = OP.apply_multi_join(spine, builds, node)
        self._note_ok(node, ok)
        return out

    def _r_semijoin(self, node: N.SemiJoin) -> DTable:
        src = self.run(node.source)
        filt = self.run(node.filter_source)
        cap = self._capacity(node, next_pow2(2 * filt.n))
        out, ok = OP.apply_semijoin(src, filt, node, cap)
        self._note_ok(node, ok)
        return out

    def _r_crossjoin(self, node: N.CrossJoin) -> DTable:
        left = self.run(node.left)
        right = self.run(node.right)
        if node.scalar:
            return OP.apply_cross_scalar(left, right)
        return self._cross_general(node, left, right)

    def _cross_general(self, node: N.CrossJoin, left: DTable,
                       right: DTable) -> DTable:
        """Nested-loop cross join: compact both sides to their estimated
        live sizes (with overflow retry), then take the static product."""
        lcap = self._capacity(
            node, next_pow2(min(left.n, 2 * (node.left_rows or left.n))),
            "left")
        rcap = self._capacity(
            node, next_pow2(min(right.n,
                                2 * (node.right_rows or right.n))),
            "right")
        if lcap < left.n:
            left, lok = OP.compact_dtable(left, lcap)
            self._note_ok(node, lok, "left")
        if rcap < right.n:
            right, rok = OP.compact_dtable(right, rcap)
            self._note_ok(node, rok, "right")
        return OP.apply_cross_general(left, right)

    def _r_union(self, node: N.Union) -> DTable:
        parts = [self.run(s) for s in node.inputs]
        return OP.apply_union(parts, node)

    def _r_window(self, node: N.Window) -> DTable:
        return OP.apply_window(self.run(node.source), node)

    def _r_sort(self, node: N.Sort) -> DTable:
        return OP.apply_sort(self.run(node.source), node.orderings)

    def _r_topn(self, node: N.TopN) -> DTable:
        return OP.apply_topn(self.run(node.source), node.count, node.orderings)

    def _r_limit(self, node: N.Limit) -> DTable:
        return OP.apply_limit(self.run(node.source), node.count,
                              node.offset)

    def _r_distinct(self, node: N.Distinct) -> DTable:
        src = self.run(node.source)
        cap = self._capacity(node, next_pow2(min(2 * src.n, 1 << 22)))
        out, ok = OP.apply_distinct(src, cap)
        self._note_ok(node, ok)
        return out

    def _r_markdistinct(self, node: N.MarkDistinct) -> DTable:
        src = self.run(node.source)
        cap = self._capacity(node, next_pow2(min(2 * src.n, 1 << 22)))
        out, ok = OP.apply_mark_distinct(src, node, cap)
        self._note_ok(node, ok)
        return out

    def _r_unnest(self, node: N.Unnest) -> DTable:
        return OP.apply_unnest(self.run(node.source), node)

    def _r_exchange(self, node: N.Exchange) -> DTable:
        # single-device execution: exchanges are no-ops (the sharded
        # executor in parallel/ lowers them to collectives)
        return self.run(node.source)

    def _r_output(self, node: N.Output) -> DTable:
        src = self.run(node.source)
        return DTable({s: src.cols[s] for s in node.symbols}, src.live, src.n)


def make_traced(scan_inputs: list[ScanInput], plan: N.PlanNode,
                capacities: dict[int, int], session=None,
                interp_factory=None, params: list | None = None,
                collect_rows: bool = True):
    """Build (traced_fn, flat_example_args, meta). ``traced_fn`` is a pure
    jittable function from flat scan arrays to
    (result columns, live mask, ok flags, per-node row counts); ``meta``
    is populated at trace time with output schema and hash-capacity
    bookkeeping.

    ``collect_rows`` (default on — the always-on stats tree): the
    interpreter sums every node's live mask and the traced function
    returns the counts stacked as ONE extra int array (one host
    transfer for the whole plan, same trick as the ok flags), with
    ``meta["count_nodes"]`` listing the stable preorder node positions.
    ``collect_rows=False`` keeps the legacy 3-output contract for
    callers that replay one program over many partitions (spill,
    block streaming) where per-node totals would be misattributed.

    ``interp_factory`` substitutes a PlanInterpreter subclass.

    ``params`` (plan templates): example physical values of the plan's
    hoisted-literal parameter vector. The traced function then takes
    them as TRAILING arguments after the scan arrays, the interpreter
    walk runs under a TraceParams context resolving ir.Parameter
    leaves, and ``meta["param_bindings"]`` records the dictionaries
    VARCHAR parameters bound against (templates/runtime.py)."""
    flat_arrays = [
        scan.arrays[sym] for scan in scan_inputs for sym in scan.arrays]
    node_order = preorder_index(plan)
    # preorder positions of the grouped Aggregates: their rows in the
    # per-node counts are group counts (the segment span's ``groups``)
    meta: dict[str, object] = {"agg_nodes": [
        node_order[id(node)] for node in N.preorder(plan)
        if isinstance(node, N.Aggregate) and node.group_keys]}

    def traced_fn(*args):
        it = iter(args)
        scans = {}
        for scan in scan_inputs:
            traced = {sym: next(it) for sym in scan.arrays}
            scans[id(scan.node)] = (scan, traced)
        interp = (interp_factory or PlanInterpreter)(
            scans, capacities, session, node_order)
        interp.collect_rows = collect_rows
        if params is not None:
            from presto_tpu.templates import runtime as TR
            tp = TR.TraceParams(list(it))
            with TR.active(tp):
                out = interp.run(plan)
            meta["param_bindings"] = dict(tp.bindings)
        else:
            out = interp.run(plan)
        meta["out"] = [
            (sym, v.dtype, v.dictionary, v.valid is not None)
            for sym, v in out.cols.items()]
        meta["ok_keys"] = interp.ok_keys
        meta["used_capacity"] = interp.used_capacity
        meta["dynfilters"] = dict(interp.df_counts)
        meta["expand_nodes"] = list(interp.expand_nodes)
        meta["expand_pack"] = dict(interp.expand_pack)
        res = []
        for sym, v in out.cols.items():
            res.append(v.data)
            res.append(v.valid if v.valid is not None
                       else jnp.ones((out.n,), dtype=bool))
            if v.is_array:
                # arrays ship lengths + element mask after (data, valid)
                res.append(v.lengths)
                res.append(v.elem_valid if v.elem_valid is not None
                           else jnp.ones(v.data.shape, dtype=bool))
        # ok flags ship as ONE stacked array: a tuple of device scalars
        # costs one device round-trip EACH to inspect, a (k,) bool
        # array costs one total
        oks = (jnp.stack(interp.ok_flags) if interp.ok_flags
               else jnp.zeros((0,), dtype=bool))
        if interp.row_counts:
            # stacked like the ok flags: one (k,) array costs one host
            # round-trip for the whole plan's actuals
            meta["count_nodes"] = [key for key, _ in interp.row_counts]
            return (tuple(res), out.live_mask(), oks,
                    jnp.stack([c for _, c in interp.row_counts]))
        return tuple(res), out.live_mask(), oks

    return traced_fn, flat_arrays, meta


def execute_plan(engine, plan: N.PlanNode) -> Table:
    """Compile + run a logical plan on the local device. Plans whose
    dominant scan exceeds the session block size stream block-wise (the
    split analog) when the plan shape allows it."""
    from presto_tpu.exec.spill import try_execute_spilled
    from presto_tpu.exec.streaming import try_execute_streamed
    mr = _find_match_recognize(plan)
    if mr is not None:
        return _execute_with_match_recognize(engine, plan, mr)
    from presto_tpu.exec.varlen import (
        execute_with_varlen, find_varlen_aggregate)
    vl = find_varlen_aggregate(plan)
    if vl is not None:
        return execute_with_varlen(engine, plan, vl)
    # streaming first: a block-streamed scan already bounds its working
    # set, so the memory-budget check must not veto it
    streamed = try_execute_streamed(engine, plan)
    if streamed is not None:
        return streamed
    # the memory budget (host-partitioned spill) outranks both grouped
    # execution and compile-time segmentation: an over-budget join must
    # not device-OOM mid-bucket
    spilled = try_execute_spilled(engine, plan)
    if spilled is not None:
        return spilled
    # grouped execution (lifespans): explicit opt-in, bucket-by-bucket
    # joins over co-bucketed tables
    from presto_tpu.exec.spill import try_execute_grouped
    grouped = try_execute_grouped(engine, plan)
    if grouped is not None:
        return grouped
    if _find_split(plan, engine) is not None:
        return _execute_segmented(engine, plan)
    scan_inputs = collect_scans(plan, engine)
    return run_plan(engine, plan, scan_inputs)


RETRY_GROWTH = 4  # overshoot on overflow to bound recompiles at ~1


def _cache_key(engine, plan, scan_inputs, capacities):
    """Canonical program-cache key: (plan fingerprint, input shapes,
    trace-relevant session properties) + pow2-bucketed capacity
    overrides (exec/progcache.py). The session component resolves
    through Session.get, so per-thread query overrides participate;
    properties the trace never reads (host-side limits, planner
    strategies already captured by the fingerprint) stay out so
    replans under unrelated SET SESSIONs keep hitting."""
    from presto_tpu.exec import progcache as PC
    from presto_tpu.plan.fingerprint import plan_fingerprint
    fp = plan_fingerprint(plan)
    shapes = tuple(
        (sym, a.shape, str(a.dtype))
        for scan in scan_inputs for sym, a in scan.arrays.items())
    sess = PC.trace_session_key(engine.session)
    # dictionary CONTENT digests: traced programs embed dictionary
    # codes as constants, so a data rewrite at constant shape must
    # miss (the persistent store outlives process restarts)
    dicts = PC.scan_dictionary_key(scan_inputs)
    return (fp, shapes, dicts, sess), PC.bucket_capacities(capacities)


def prepare_plan(engine, plan: N.PlanNode, scan_inputs: list[ScanInput]):
    """Resolve hash-table capacities and return
    (compiled, flat_arrays, meta, (res, live, oks, counts)) for a
    plan, reusing the engine's compiled-program cache. ``counts`` is
    the stacked per-node live-row array every program now returns
    (``meta["count_nodes"]`` aligns it with stable preorder
    positions) — the raw material of the always-on runtime stats tree
    (obs/qstats.py), recorded here so EVERY execution path (segments,
    workers, warm cache hits, template hits) feeds the same tree.

    The cache is the analog of the reference's compiled-artifact caches
    (gen/PageFunctionCompiler.java:101): programs key on
    (plan fingerprint, input shapes, session, capacity overrides), and
    the capacity vector that succeeded is remembered per plan so a
    repeat query goes straight to the right program — zero recompiles.
    On overflow, EVERY failed capacity grows RETRY_GROWTH x at once
    (host-side analog of the reference's rehash,
    MultiChannelGroupByHash.java:140, overshooting to bound the number
    of recompiles instead of doubling per node).

    The cache is two-tier (exec/progcache.py): the in-memory LRU
    fronts a persistent AOT disk store (PRESTO_TPU_PROGRAM_CACHE_DIR),
    so a warm process — or another worker sharing the directory —
    deserializes the executable instead of paying lower+compile, and
    the persisted capacity sidecar skips the overflow-retry ladder.

    Plan templates (templates/): with session ``plan_templates`` on,
    hoistable literals leave the plan before the key is computed — the
    cache keys on the parameterized TEMPLATE (plus pow2-bucketed scan
    shapes under ``template_shape_bucketing``), and this query's
    literal values enter the compiled program as trailing device
    scalars. A literal variant of an already-compiled query shape is a
    cache hit: zero compiles."""
    from presto_tpu import templates as TPL
    from presto_tpu.exec import progcache as PC
    from presto_tpu.obs import qstats as QS
    fpr = PC.platform_fingerprint()
    cache = engine._program_cache
    cache.configure(engine.session)
    # the pre-template plan, literals intact: the stats recorder
    # estimates rows on it (the CBO cannot cost Parameter leaves); the
    # tree shape is identical so preorder positions line up
    orig_plan = plan
    tpl = None
    templated = TPL.enabled(engine.session)
    if templated:
        # padded_bytes stays 0 when every pad came from the pad cache
        with TRACER.span("bucket-pad", padded_bytes=0) as span:
            scan_inputs = TPL.bucket_scans(
                engine, scan_inputs,
                stats=span.attrs if span is not None else None)
    # hoisting the literals, the program-cache key (plan fingerprint,
    # shapes, dictionary digests) and the remembered capacities
    with TRACER.span("program-lookup"):
        if templated:
            tpl = TPL.parameterize(plan)
            if tpl is not None:
                plan = tpl.plan
        base_key, _ = _cache_key(engine, plan, scan_inputs, {})
        known_caps = engine._caps_memory.get(base_key)
        if known_caps is None:  # {} is a real answer: no overrides
            known_caps = cache.load_caps(base_key, fpr)
        capacities = dict(known_caps)

    from presto_tpu.exec.cancel import checkpoint
    for _attempt in range(6):
        checkpoint()
        caps_key = PC.bucket_capacities(capacities)
        entry = cache.lookup((base_key, caps_key), fpr)
        if tpl is not None and _attempt == 0:
            TPL.note_lookup(hit=entry is not None,
                            params=len(tpl.params))
        flat_arrays = [
            engine.device_array(scan.arrays[sym])
            if getattr(scan, "cache_device", False) else scan.arrays[sym]
            for scan in scan_inputs for sym in scan.arrays]
        pargs = (tpl.example_args(scan_inputs)
                 if tpl is not None and entry is None else [])
        if entry is None:
            traced_fn, _host_arrays, meta = make_traced(
                scan_inputs, plan, capacities, engine.session,
                params=(pargs if tpl is not None else None))
            traced_fn.__name__ = program_name(
                plan, base_key[0] if tpl is not None else None)
            # meta fills during the trace lower() triggers
            _t0 = time.perf_counter()
            compiled = compile_traced(
                traced_fn, [*flat_arrays, *pargs], attempt=_attempt,
                root=type(plan).__name__)
            last_compile_s = time.perf_counter() - _t0
            # device-cost summary rides the meta into the program
            # cache (and its disk tier): warm hits in a fresh process
            # still attribute flops/bytes without a live Compiled
            from presto_tpu.obs import devprof
            cost = devprof.harvest(compiled)
            if cost is not None:
                meta["cost"] = cost
            # memory tier only for now: failed capacity-retry rungs
            # must not pay serialize+IO (and would pollute the store);
            # the disk persist happens below, on the successful attempt
            cache.insert((base_key, caps_key), compiled, meta, fpr,
                         persist=False)
            cache_hit = False
        else:
            compiled, meta = entry
            cache_hit = True
            last_compile_s = 0.0
        if tpl is not None:
            # bind THIS query's literal values (string parameters
            # resolve through the dictionaries the trace recorded —
            # carried in meta, so disk-tier hits bind too; a LIKE
            # pattern runs over its dictionary here: span dict-mask)
            pargs = tpl.bind(meta.get("param_bindings"))
        _t1 = time.perf_counter()
        with TRACER.span("execute", cache_hit=cache_hit) as span:
            note_dyn_filters(meta, span)
            outs = compiled(*flat_arrays, *pargs)
            # stale-format disk entries cannot reach here (the program
            # format version rides the platform fingerprint), but a
            # defensive unpack keeps a 3-output program non-fatal
            if len(outs) == 4:
                res, live, oks, counts = outs
            else:
                (res, live, oks), counts = outs, None
            # ONE host sync for every flag — also the point the async
            # dispatch actually finishes, so the span covers real
            # device time, not just call overhead
            oks_np = HS.fetch(oks, site="ok-ladder")
        execute_s = time.perf_counter() - _t1
        note_expand_pack(meta, oks_np)
        if oks_np.all():
            if not cache_hit:
                cache.insert((base_key, caps_key), compiled, meta, fpr)
            if engine._caps_memory.get(base_key) != capacities:
                cache.store_caps(base_key, capacities, fpr)
            engine._caps_memory[base_key] = dict(capacities)
            # fold this program into the ambient stats tree (no-op
            # outside a task/query recording scope)
            with TRACER.span("stats-record"):
                QS.record_program(
                    engine, orig_plan, meta, counts, last_compile_s,
                    execute_s, cache_hit, template=tpl is not None,
                    template_hit=tpl is not None and cache_hit)
            return compiled, flat_arrays, meta, (res, live, oks,
                                                 counts)
        if not cache_hit:
            # a failed rung's program is dead weight in the bounded
            # LRU: future runs jump straight to the successful caps
            cache.discard((base_key, caps_key))
        # the LOUD path of what used to be a silent in-kernel
        # give-up: grow every failed capacity and count hash-table
        # overflows, then retry (ops/hash.grow_overflowed — shared by
        # all four retry ladders)
        from presto_tpu.ops.hash import grow_overflowed
        grow_overflowed(capacities, meta["ok_keys"], oks_np,
                        meta["used_capacity"], RETRY_GROWTH)
    from presto_tpu.ops.hash import HashChainOverflow
    raise HashChainOverflow(
        "hash table capacity retry limit exceeded")


# XLA compile time grows superlinearly with program size (a 5-join
# TPC-H Q5 program compiles >10x slower than twice a 2-join Q3); plans
# with more joins than this split into separately compiled segments
# with DEVICE-RESIDENT handoff (no host round trip).
MAX_JOINS_PER_PROGRAM = 2


def _count_joins(node: N.PlanNode) -> int:
    # a MultiJoin counts its fan-in: compile-cost-wise it carries one
    # probe per build (direct or sorted, as a Join counts 1 either
    # way), and counting it whole keeps _find_split
    # from trying to cut inside the fused operator (its children hold
    # no joins, so the splitter materializes the MultiJoin subtree —
    # or, via _find_agg_input_split, the aggregate input above it)
    own = (len(node.builds) if isinstance(node, N.MultiJoin)
           else int(isinstance(node, (N.Join, N.SemiJoin))))
    return own + sum(_count_joins(s) for s in node.sources())


def _find_split(node: N.PlanNode, engine=None):
    """A subtree with <= MAX_JOINS_PER_PROGRAM joins (at least one) to
    materialize first, or None when the plan fits one program."""
    if _count_joins(node) <= MAX_JOINS_PER_PROGRAM:
        return _find_agg_input_split(node, engine)
    if isinstance(node, N.MultiJoin):
        # the fused operator is atomic — never cut inside it. Large
        # inputs materialize it whole (so the aggregate above runs at
        # compacted live width, the same boundary the cascade's
        # aggregate-input split provided); small plans run fused with
        # everything above in one program
        large = engine is None or _subtree_scan_rows(node, engine) \
            >= AGG_SPLIT_MIN_ROWS
        if large and _count_joins(node.spine):
            # its spine is its input, not part of it: joins there run
            # first, in programs of their own (TPC-DS q72's expansion
            # under the MultiJoin of its unique dimension legs)
            inner = _find_split(node.spine, engine)
            return inner if inner is not None else node.spine
        return node if large else None
    kids = node.sources()
    best = max(kids, key=_count_joins)
    c = _count_joins(best)
    if c > MAX_JOINS_PER_PROGRAM:
        return _find_split(best, engine)
    if c < 1:
        return None
    # a grouped aggregate inside the chosen subtree still wants its own
    # pre-compaction boundary (its group-by must not run at join width)
    inner = _find_agg_input_split(best, engine)
    return inner if inner is not None else best


# minimum estimated scan rows under an aggregate before its input gets
# its own compaction boundary: below this, two compiles + a host sync
# cost more than grouping a small buffer at full width
AGG_SPLIT_MIN_ROWS = 1 << 21


def _subtree_scan_rows(node: N.PlanNode, engine) -> int:
    """Largest base-scan row estimate in a subtree. Segment carrier
    scans count as LARGE: a carrier only exists because an earlier
    split materialized a big intermediate, and its static width is the
    width the aggregate would otherwise churn through."""
    if isinstance(node, N.TableScan):
        if node.catalog == "__segment__":
            return 1 << 62
        conn = engine.catalogs.get(node.catalog)
        if conn is None:
            return 0
        try:
            return int(conn.row_count_estimate(node.table))
        except Exception:
            return 0
    return max((_subtree_scan_rows(s, engine) for s in node.sources()),
               default=0)


def _find_agg_input_split(node: N.PlanNode, engine=None):
    """Pre-aggregation compaction boundary: the input subtree of the
    lowest grouped Aggregate that sits above at least one join.

    Joins + selective filters leave most of a static-shape buffer dead
    (TPC-H Q3 keeps ~3M of 60M lineitem rows), yet a monolithic program
    runs the group-by's sort and payload permutations at full width —
    random-access HBM passes at 60M rows cost ~1.5s each on v5e.
    Materializing the aggregate's input as a segment lets
    run_plan_device compact it to pow2(live) first, so grouping runs at
    live width (15-20x narrower on Q3). The reference gets the same
    effect for free from row-at-a-time paging between operators
    (operator/HashAggregationOperator.java consumes compacted Pages);
    a fixed-shape dataflow needs an explicit re-bucketing boundary."""
    for s in node.sources():
        found = _find_agg_input_split(s, engine)
        if found is not None:
            return found
    if isinstance(node, N.Aggregate) and node.group_keys \
            and not isinstance(node.source, N.TableScan) \
            and _count_joins(node.source) >= 1 \
            and (engine is None or _subtree_scan_rows(
                node.source, engine) >= AGG_SPLIT_MIN_ROWS):
        return node.source
    return None


def _collect_with_carriers(plan: N.PlanNode, engine,
                           carriers: dict[int, "ScanInput"]
                           ) -> list["ScanInput"]:
    out: list[ScanInput] = []
    # segment carriers also resolve by their unique table name: the
    # boundary-pruning pass (prune_columns in _prune_subtree) rebuilds
    # every TableScan node, so identity alone cannot find a carrier
    # inside a narrowed later segment
    by_name = {
        si.node.table: si for si in carriers.values()
        if isinstance(si.node, N.TableScan)
        and si.node.catalog == "__segment__"}

    # an explicit stack, not a recursive closure: a function that
    # names itself is a reference cycle, and this one would hold
    # ``carriers`` (the device buffers of every hand-over) until the
    # collector next runs, beside the next statement's
    stack = [plan]
    while stack:
        node = stack.pop()
        if id(node) in carriers:
            out.append(carriers[id(node)])
        elif isinstance(node, N.TableScan):
            if node.catalog == "__segment__" and node.table in by_name:
                out.append(_rebind_carrier(by_name[node.table], node))
            else:
                out.extend(collect_scans(node, engine))
        else:
            stack.extend(reversed(node.sources()))
    return out


def _compact_kernel(live, data, cap: int):
    """Gather live rows to the front of a ``cap``-row buffer (device
    gather; the page-compaction analog). Padding slots hold arbitrary
    dead rows' data and are marked dead in the returned live mask.

    Live positions extract via one (u32 key, index) sort — stable, so
    row order is preserved — then every column gathers at ``cap``
    width. (jnp.nonzero's TPU lowering was measured at 5.4s on a
    60M-row mask, ~20x the cost of the sort it replaces.)"""
    n = live.shape[0]
    key = jnp.where(live, jnp.uint32(0), jnp.uint32(1))
    _, idx = jax.lax.sort(
        (key, jnp.arange(n, dtype=jnp.int32)), num_keys=1,
        is_stable=True)
    idx = idx[:cap]
    out = {k: v[idx] for k, v in data.items()}
    newlive = jnp.arange(cap) < jnp.sum(live)
    return out, newlive


_compact_jit = jax.jit(_compact_kernel, static_argnames=("cap",))


# Narrowest carrier a hand-over compacts to. Under it a width would
# follow the realised count from one pow2 bucket to the next (TPC-H
# Q18's HAVING leaves 434 to 791 rows at SF10 by its QUANTITY and the
# seed: 1,024 or 2,048 slots, two shapes of everything downstream),
# and nothing downstream is cheaper for it.
MIN_CARRIER_ROWS = 1 << 16
# A planned width further than this factor above what the rows need is
# a guess, not a plan (the planner cannot price a HAVING, and reads
# TPC-H Q3's joins 22 times too wide): the rows then size the carrier.
PLANNED_WIDTH_SLACK = 2


def carrier_width(cnt: int, remembered: int, planned: int) -> int:
    """Rows of the buffer a segment hands over (templated sizing),
    before it is held against the program's own width. The
    ``remembered`` width of this segment of this template where the
    ``cnt`` live rows fit in it: reusing it exactly keeps every
    downstream shape. Else pow2 of twice the rows, ``MIN_CARRIER_ROWS``
    at least; but on first sight the ``planned`` width (pow2 of twice
    the planner's row estimate: the same for every seed and, by
    plan/stats, for every literal of a template) where it is that or
    one step above it, so that counts on both sides of a pow2 boundary
    (TPC-H Q10's 1.0 to 1.25 million rows by its DATE) land on one
    width whichever a process meets first."""
    if remembered and cnt <= remembered:
        return int(remembered)
    need = max(MIN_CARRIER_ROWS, next_pow2(2 * max(cnt, 1)))
    if not remembered and need <= planned <= PLANNED_WIDTH_SLACK * need:
        return int(planned)
    return max(need, int(remembered))


def device_outputs(meta, res, live, cap_floor: int | None = None,
                   stats: dict | None = None, planned: int = 0,
                   counts=None):
    """Unpack one program's (meta, res, live) into segment-carrier form
    (arrays incl. $valid/__live__, dicts, types, n). Outputs compact to
    pow2(live count) when that at least halves the buffer, so later
    segments never churn through dead padding.

    ``cap_floor`` (plan templates): None = legacy exact compaction;
    an int (0 when no width is remembered yet) switches to templated
    sizing (:func:`carrier_width`): a carrier's width is a static
    shape of every program downstream, so it must not follow the
    realised count from literal to literal or from seed to seed. A
    remembered width that the count overflows grows, with a 2x margin,
    and counts one capacity retry of kind ``segment`` (the programs
    downstream compile again).

    ``stats`` (the ``segment`` span's attributes) gains ``width``, the
    rows of the buffer handed over, ``live_rows``, how many of them
    are live, and, from the program's per-node ``counts`` fetched in
    the same transfer, ``groups``: the occupied slots of the largest
    grouped Aggregate the segment ran."""
    arrays: dict = {}
    dicts: dict = {}
    types: dict = {}
    i = 0
    for sym, dtype, dictionary, has_valid in meta["out"]:
        arrays[sym] = res[i]
        if has_valid:
            arrays[f"{sym}$valid"] = res[i + 1]
        i += 2
        if isinstance(dtype, T.ArrayType):
            arrays[f"{sym}$len"] = res[i]
            arrays[f"{sym}$emask"] = res[i + 1]
            i += 2
        dicts[sym] = dictionary
        types[sym] = dtype
    n = int(live.shape[0])
    agg_nodes = meta.get("agg_nodes") or ()
    expand_nodes = meta.get("expand_nodes") or ()
    if counts is not None and (expand_nodes
                               or (stats is not None and agg_nodes)):
        cnt, node_rows = HS.fetch((jnp.sum(live), counts),
                                  site="segment-width")
        cnt = int(cnt)
        rows = dict(zip(meta["count_nodes"], node_rows.tolist()))
        if stats is not None and agg_nodes:
            stats["groups"] = max(int(rows.get(pos, 0))
                                  for pos in agg_nodes)
        note_expansions(meta, rows, stats)
    else:
        cnt = HS.fetch_int(jnp.sum(live), site="segment-width")
    if cap_floor is None:
        cap = max(128, next_pow2(max(cnt, 1)))
    else:
        cap = carrier_width(cnt, cap_floor, planned)
        if cap_floor and cap > cap_floor:
            from presto_tpu.ops.hash import note_capacity_retry
            note_capacity_retry("segment")
    if cap <= n // 2:
        arrays, live = _compact_jit(live, arrays, cap=cap)
        n = cap
    arrays["__live__"] = live
    if stats is not None:
        stats.update(width=n, live_rows=cnt)
    return arrays, dicts, types, n


def note_expansions(meta, rows: dict, stats: dict | None) -> None:
    """Count the rows a program's expanding joins emitted (the program's
    per-node counts, already on the host) and hand ``stats`` (a
    ``segment`` span's attributes) the largest of them: ``expanded``
    its rows, ``expand_probe_rows`` its probe width and
    ``expand_capacity`` the output rows it was compiled for."""
    nodes = meta.get("expand_nodes") or ()
    if not nodes:
        return
    out = [(int(rows.get(pos, 0)), probe, cap, pos)
           for pos, probe, cap in nodes]
    _EXPANDED_ROWS.inc(sum(r for r, _p, _c, _pos in out))
    if stats is not None:
        expanded, probe, cap, pos = max(out)
        stats.update(expanded=expanded, expand_probe_rows=probe,
                     expand_capacity=cap)
        pack = (meta.get("expand_pack") or {}).get(pos)
        if pack is not None:
            packed, probe_words, build_words, column_words = pack
            stats.update(expand_pack="packed" if packed else "unpacked",
                         expand_words=probe_words + build_words,
                         expand_column_words=column_words)


def run_plan_device(engine, plan: N.PlanNode,
                    scan_inputs: list["ScanInput"],
                    cap_floor: int | None = None,
                    stats: dict | None = None, planned: int = 0):
    """Like run_plan but keeps results as DEVICE arrays (segment
    handoff); see device_outputs. Returns (arrays, dicts, types, n,
    per-node rows=None) — the runner contract of _segment_carriers."""
    _c, _f, meta, (res, live, _oks, counts) = prepare_plan(
        engine, plan, scan_inputs)
    return device_outputs(meta, res, live, cap_floor, stats, planned,
                          counts) + (None,)


def _pool_wait(engine) -> tuple[float, float]:
    """(block_s, kill_after_s) for memory-pool reservations: how long
    an over-capacity reservation blocks for concurrent queries to free
    bytes, and when sustained exhaustion triggers the low-memory killer
    (memory.MemoryPool.reserve; both 0 by default — the single-query
    fail-fast behavior)."""
    try:
        sess = engine.session
        return (float(sess.get("memory_reserve_timeout_s") or 0.0),
                float(sess.get("low_memory_killer_delay_s") or 0.0))
    except Exception:  # noqa: BLE001 - engines without a session
        return (0.0, 0.0)


def _contains_carrier(node: N.PlanNode, names: set[str]) -> bool:
    """Does a subtree scan any of the named __segment__ carriers?"""
    if isinstance(node, N.TableScan):
        return node.catalog == "__segment__" and node.table in names
    return any(_contains_carrier(s, names) for s in node.sources())


def _cut_segment(plan: N.PlanNode, engine, name: str,
                 wave_names: set[str] = frozenset()):
    """The next subtree of ``plan`` to materialize as carrier ``name``:
    (the subtree, what of it materializes once narrowed to the columns
    the rest consumes, the carrier scan, ``plan`` with the scan in the
    subtree's place); None when what is left fits one program or the
    subtree scans a carrier of ``wave_names`` (it closes the wave)."""
    from presto_tpu.exec.streaming import _replace_node
    sub = _find_split(plan, engine)
    if sub is None or _contains_carrier(sub, wave_names):
        return None
    needed = _needed_above(plan, sub)
    mat = sub  # what actually materializes (possibly narrowed)
    if needed is not None and needed < set(sub.output_symbols):
        mat = _prune_subtree(sub, needed)
    cnode = N.TableScan("__segment__", name,
                        {s: s for s in mat.output_symbols},
                        dict(mat.output_types()))
    return sub, mat, cnode, _replace_node(plan, sub, cnode)


def planned_carriers(engine, plan: N.PlanNode) -> list[tuple]:
    """(materialized subtree, planned width) of every segment
    ``_segment_carriers`` would cut ``plan`` into, in order, by planning
    alone: what a statement's hand-overs are sized to before a row is
    read (``carrier_width``)."""
    from presto_tpu.cost.stats import SegmentStats
    estimates = SegmentStats(engine)
    out: list[tuple] = []
    while True:
        name = f"s{len(out)}"
        cut = _cut_segment(plan, engine, name)
        if cut is None:
            return out
        _sub, mat, _cnode, plan = cut
        out.append((mat, estimates.planned_width(mat, name)))


def _segment_carriers(engine, plan: N.PlanNode, pool_tag: str,
                      observer=None, runner=None):
    """Materialize many-join subtrees as device-resident carrier scans
    until the remaining plan fits one program. Returns the rewritten
    plan + carrier inputs. Carrier bytes are reserved under
    ``pool_tag`` (freed by the caller when the pipeline finishes).

    Segments are discovered structurally WAVE by wave: every split the
    current plan yields that does not consume a carrier of the same
    wave is mutually independent, so the wave's segments compile and
    execute concurrently on a bounded thread pool (session
    ``parallel_compile_width``; XLA compilation releases the GIL). A
    split that scans a same-wave carrier closes the wave — dependency
    order between waves is preserved exactly as the old serial loop.

    ``runner(engine, mat, scans, cap_floor=None, stats=None,
    planned=0) -> (arrays, dicts, types, n, node_rows)`` substitutes
    the per-segment executor
    (EXPLAIN ANALYZE passes a profiling runner); ``observer(seg, mat,
    arrays, n, wall_s, node_rows)`` fires per materialized segment, in
    segment order.

    Carrier widths are remembered per (plan template, segment index)
    in ``engine._carrier_caps`` and only grow: without the floor, a
    literal variant whose intermediate crosses a pow2 compaction
    boundary would shift every downstream segment's input shape and
    recompile. The first width of a segment comes from the planner's
    row estimate where that is of the rows' order (``carrier_width``),
    so that a fresh process lands on the shapes the last one compiled
    whatever literal and data it meets first."""
    from presto_tpu import templates as TPL
    from presto_tpu.cost.stats import SegmentStats
    from presto_tpu.exec import progcache as PC
    from presto_tpu.plan.fingerprint import plan_fingerprint

    pool = getattr(engine, "memory_pool", None)
    run = runner or run_plan_device
    tpl_mode = TPL.enabled(engine.session)
    tpl0 = TPL.parameterize(plan) if tpl_mode else None
    tfp = (tpl0.fingerprint() if tpl0 is not None
           else plan_fingerprint(plan))
    carrier_caps = getattr(engine, "_carrier_caps", None)
    if carrier_caps is None:
        carrier_caps = engine._carrier_caps = {}
    width = max(1, int(engine.session.get("parallel_compile_width")
                       or 1))
    if pool is not None and pool.capacity:
        # an enforced memory budget needs the serial guarantee: each
        # segment's reservation must be able to fail BEFORE the next
        # segment materializes device buffers — concurrent waves could
        # overshoot the budget by (width-1) intermediates
        width = 1
    carriers: dict[int, ScanInput] = {}
    estimates = SegmentStats(engine)
    seg = 0
    while True:
        # -- discover one wave of independent segments structurally --
        wave: list[tuple] = []  # (sub, mat, cnode)
        wave_names: set[str] = set()
        probe = plan
        while True:
            name = f"s{seg + len(wave)}"
            cut = _cut_segment(probe, engine, name, wave_names)
            if cut is None:
                break
            sub, mat, cnode, probe = cut
            wave.append((sub, mat, cnode,
                         estimates.planned_width(mat, name)))
            wave_names.add(name)
        if not wave:
            break

        # -- materialize the wave (parallel when independent > 1) ----
        # pool threads inherit neither threading.locals nor
        # contextvars: hand over the cancel token, the per-thread
        # session override (HTTP queries compile under the submitter's
        # property overrides), and the trace context (spans otherwise
        # vanish for every parallel-compiled segment)
        from presto_tpu.exec import cancel as _cancel
        from presto_tpu.obs import qstats as _qs
        from presto_tpu.obs import trace as _ot
        from presto_tpu.session import (current_override,
                                        install_override)
        _tok = _cancel.current()
        _ov = current_override()
        _ctx = _ot.current_context()
        _task_rec = _qs.current_task()

        def _materialize(item):
            idx, mat, planned = item
            _cancel.install(_tok)
            install_override(_ov)
            # the ambient stats recorder rides along too: segment
            # programs compiled on pool threads must land in the same
            # task's operator list
            _qs.install_task(_task_rec)
            scans = _collect_with_carriers(mat, engine, carriers)
            _t0 = time.perf_counter()
            with TRACER.attach(_ctx), \
                    TRACER.span("segment", index=seg + idx,
                                wave_width=len(wave)) as span:
                floor = (carrier_caps.get((tfp, seg + idx), 0)
                         if tpl_mode else None)
                out = run(engine, mat, scans, cap_floor=floor,
                          stats=None if span is None else span.attrs,
                          planned=planned)
            if pool is not None:
                # reserve inside the job, as the serial loop did: an
                # over-budget pipeline must raise MemoryLimitExceeded
                # before FURTHER segments materialize (with width=1
                # this is exactly the old segment-by-segment guard).
                # Freed by the CALLER's finally (_execute_segmented /
                # run_plan_live / profile.explain_analyze own pool_tag).
                block_s, kill_s = _pool_wait(engine)
                pool.reserve(pool_tag, sum(  # lint: disable=pool-discipline
                    int(a.nbytes) for a in out[0].values()),
                    block_s=block_s, kill_after_s=kill_s, owner=_tok)
            return out + (time.perf_counter() - _t0,)

        results = PC.map_parallel(
            _materialize,
            [(i, mat, planned)
             for i, (_s, mat, _c, planned) in enumerate(wave)], width)

        for (_sub, mat, cnode, _p), (arrays, dicts, types, n, node_rows,
                                     wall_s) in zip(wave, results):
            if observer is not None:
                observer(seg, mat, arrays, n, wall_s, node_rows)
            carriers[id(cnode)] = ScanInput(cnode, arrays, dicts,
                                            types, n)
            # grow-only width memory (benign race: a lost update just
            # costs one extra compile on some later variant)
            prev = carrier_caps.get((tfp, seg))
            if prev is None or n > prev:
                if len(carrier_caps) > 512:
                    carrier_caps.clear()
                carrier_caps[(tfp, seg)] = n
            seg += 1
        # adopt the wave's fully-spliced tree: _replace_node rebuilds
        # every interior node, so re-splicing wave items 2..n into the
        # ORIGINAL plan would miss (their identity only exists in
        # ``probe``); the carrier leaves keep identity through later
        # splices, which is what _collect_with_carriers keys on
        plan = probe
    return plan, carriers


def _rebind_carrier(si: "ScanInput", node: N.TableScan) -> "ScanInput":
    """A carrier ScanInput re-pointed at a rebuilt (possibly
    column-narrowed) copy of its scan node, arrays restricted to the
    surviving symbols (+ their $valid/$len/$emask companions and the
    table-level live mask)."""
    if node is si.node and set(node.assignments) == set(si.types):
        return si
    keep = set(node.assignments)

    def base(k: str) -> str:
        # companion arrays ($valid/$len/$emask) follow their symbol;
        # note partial-agg STATE symbols legitimately contain '$'
        # (e.g. "rev$sum"), so only the companion suffix strips
        if "$" in k:
            b, suf = k.rsplit("$", 1)
            if suf in ("valid", "len", "emask"):
                return b
        return k

    arrays = {k: v for k, v in si.arrays.items()
              if k == "__live__" or base(k) in keep}
    return dataclasses.replace(
        si, node=node, arrays=arrays,
        dictionaries={s: si.dictionaries.get(s) for s in keep},
        types={s: si.types[s] for s in keep})


def _needed_above(plan: N.PlanNode, sub: N.PlanNode):
    """Symbols of ``sub``'s output the rest of ``plan`` actually
    consumes, or None when it cannot be determined.

    A monolithic program gets this for free from XLA dead-code
    elimination; a segment boundary materializes every output column,
    so an unpruned boundary pays full-width gathers for columns only
    ever used BELOW the split (join keys, filter inputs). Reuses the
    optimizer's prune_columns per-node knowledge: splice a placeholder
    scan where ``sub`` stands, prune the outer plan, and read back
    which placeholder columns survived."""
    from presto_tpu.exec.streaming import _replace_node
    from presto_tpu.plan.optimizer import prune_columns

    tag = "__needed_probe__"
    probe = N.TableScan(tag, tag, {s: s for s in sub.output_symbols},
                        dict(sub.output_types()))
    try:
        shadow = _replace_node(plan, sub, probe)
        if isinstance(shadow, N.Output):
            pruned = prune_columns(shadow)
        else:
            pruned = prune_columns(
                shadow, set(shadow.output_symbols))
    except Exception:
        return None  # unprunable shape: materialize everything

    found: list = []

    def visit(node):
        if isinstance(node, N.TableScan) and node.catalog == tag:
            found.append(node)
            return
        for s in node.sources():
            visit(s)

    visit(pruned)
    if len(found) != 1:
        return None
    return set(found[0].assignments)


def _prune_subtree(sub: N.PlanNode, needed: set):
    """Narrow a to-be-materialized subtree to ``needed`` output
    symbols (falling back to the unpruned subtree on any failure).
    An identity Project caps the subtree because relational nodes
    (joins above all) cannot drop their own pass-through columns."""
    from presto_tpu.expr import ir
    from presto_tpu.plan.optimizer import prune_columns
    types = dict(sub.output_types())
    keep = [s for s in sub.output_symbols if s in needed]
    cap = N.Project(sub, {s: ir.ColumnRef(types[s], s) for s in keep})
    try:
        pruned = prune_columns(cap, set(needed))
    except Exception:
        return sub
    if not needed <= set(pruned.output_symbols):
        return sub
    return pruned


def _execute_segmented(engine, plan: N.PlanNode) -> Table:
    """Execute a many-join plan as a pipeline of separately compiled
    segments — the engine's stage materialization (the reference
    streams between stages; here segment outputs stay in HBM and feed
    the next program as inputs)."""
    import uuid

    pool = getattr(engine, "memory_pool", None)
    tag = "seg-" + uuid.uuid4().hex[:12]
    try:
        plan, carriers = _segment_carriers(engine, plan, tag)
        return run_plan(engine, plan,
                        _collect_with_carriers(plan, engine, carriers))
    finally:
        if pool is not None:
            pool.free(tag)


def run_plan_live(engine, plan: N.PlanNode):
    """Run a plan fully on device (segmenting many-join plans) and
    return ONLY the final live mask (device array) — the steady-state
    benchmarking entry: materializing the mask is the host-side sync
    without paying result transfer."""
    import uuid

    pool = getattr(engine, "memory_pool", None)
    tag = "seg-" + uuid.uuid4().hex[:12]
    try:
        plan, carriers = _segment_carriers(engine, plan, tag)
        scans = _collect_with_carriers(plan, engine, carriers)
        _c, _f, _meta, (_res, live, _oks, _counts) = prepare_plan(
            engine, plan, scans)
        return live
    finally:
        if pool is not None:
            pool.free(tag)


def _find_match_recognize(plan: N.PlanNode):
    if isinstance(plan, N.MatchRecognize):
        return plan
    for s in plan.sources():
        found = _find_match_recognize(s)
        if found is not None:
            return found
    return None


def _execute_with_match_recognize(engine, plan: N.PlanNode,
                                  mr) -> Table:
    """Split execution around a MatchRecognize node: run its input
    subplan on device, evaluate the pattern automaton host-side
    (exec/match_recognize.py — vectorized predicates, host NFA), feed
    the matches back through a carrier scan for the rest of the plan
    (the same splice mechanism as the spill driver)."""
    from presto_tpu.exec.match_recognize import evaluate
    from presto_tpu.exec.spill import _carrier_scan
    from presto_tpu.exec.streaming import _replace_node

    input_table = execute_plan(engine, mr.source)
    matched = evaluate(input_table, mr)
    carrier_node, carrier_input = _carrier_scan("__matches__", matched)
    rest = _replace_node(plan, mr, carrier_node)
    return run_plan(engine, rest, [carrier_input])


def run_plan(engine, plan: N.PlanNode,
             scan_inputs: list[ScanInput]) -> Table:
    """Compile + run over prepared scan inputs (shared by the whole-table
    and block-streamed paths). Input and output array bytes are
    reserved in the engine's runtime memory pool for the duration
    (memory/MemoryPool.java:44 tagged-reservation analog)."""
    import uuid

    pool = getattr(engine, "memory_pool", None)
    tag = uuid.uuid4().hex[:12]
    if pool is not None:
        from presto_tpu.exec import cancel as _cancel
        block_s, kill_s = _pool_wait(engine)
        owner = _cancel.current()
        # host (numpy) inputs only: device-resident segment carriers
        # are already reserved under their pipeline's seg- tag
        pool.reserve(tag, sum(
            a.nbytes for scan in scan_inputs
            for a in scan.arrays.values()
            if isinstance(a, np.ndarray)),
            block_s=block_s, kill_after_s=kill_s, owner=owner)
    try:
        _compiled, _flat, meta, (res, live, _oks, _counts) = \
            prepare_plan(engine, plan, scan_inputs)
        if pool is not None:
            # device-side shape math only — no transfer
            pool.reserve(tag, sum(int(r.nbytes) for r in res),
                         block_s=block_s, kill_after_s=kill_s,
                         owner=owner)

        # one batched device->host transfer for every output column:
        # per-array np.asarray pays a device round-trip each (and the
        # per-node counts where an expanding join's rows are counted)
        if meta.get("expand_nodes") and _counts is not None:
            live_np, res_np, counts_np = HS.fetch(
                (live, res, _counts), site="result-demux")
            note_expansions(meta, dict(zip(meta["count_nodes"],
                                           counts_np.tolist())), None)
        else:
            live_np, res_np = HS.fetch((live, res), site="result-demux")
        cols: dict[str, Column] = {}
        i = 0
        for sym, dtype, dictionary, has_valid in meta["out"]:
            data = res_np[i]
            valid = res_np[i + 1]
            i += 2
            if isinstance(dtype, T.ArrayType):
                from presto_tpu.block import lists_from_padded
                lengths, emask = res_np[i], res_np[i + 1]
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
        return Table(_rename_outputs(plan, cols), len(live_np), live_np)
    finally:
        if pool is not None:
            pool.free(tag)


def _rename_outputs(plan: N.PlanNode,
                    cols: dict[str, Column]) -> dict[str, Column]:
    """Key result columns by their declared output names (the symbols are
    internal; CTAS/INSERT and clients need the SQL names)."""
    if isinstance(plan, N.Output):
        return {name: cols[sym]
                for name, sym in zip(plan.names, plan.symbols)}
    return cols
