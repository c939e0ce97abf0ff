"""Distributed plan execution: one shard_map program over a device mesh.

The TPU-native replacement for the reference's distributed execution
stack (fragmenter sql/planner/PlanFragmenter.java:108 + scheduler
execution/scheduler/SqlQueryScheduler.java + HTTP exchange
operator/ExchangeClient.java). Where the reference cuts the plan into
fragments shipped to workers and streams pages over HTTP, here the WHOLE
plan — scans through output — is traced into a single jitted shard_map
computation over the mesh, and every distribution boundary lowers to an
ICI collective:

| reference exchange (SystemPartitioningHandle.java:58-66) | here |
|---|---|
| SOURCE distribution (splits)        | rows block-sharded over mesh axis |
| partial->final aggregation          | local fold -> all_gather of state
|                                       columns -> local merge (psum tree) |
| FIXED_BROADCAST (join build sides)  | lax.all_gather of build shard |
| FIXED_HASH repartition              | bucket + lax.all_to_all
|                                       (exchange.repartition)            |
| GATHER / SINGLE (sort, limit, out)  | lax.all_gather -> replicated      |

Every operator in between runs unchanged on its local shard (the same
kernels as exec/operators.py) — data parallelism over rows is the
engine's analog of DP; hash repartition is its TP/EP.
"""

from __future__ import annotations

import contextlib
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from presto_tpu import types as T
from presto_tpu.block import Column, Table
from presto_tpu.cost.model import decide_join_distribution
from presto_tpu.exec import hostsync as HS
from presto_tpu.exec import operators as OP
from presto_tpu.exec.executor import (DF_OUTCOMES, PlanInterpreter,
                                      ScanInput, collect_scans,
                                      preorder_index)
from presto_tpu.exec.operators import DTable
from presto_tpu.expr.compile import Val
from presto_tpu.obs.metrics import REGISTRY
from presto_tpu.obs.trace import TRACER as _TRACER
from presto_tpu.ops import hash as H
from presto_tpu.ops import segred
from presto_tpu.ops.hash import next_pow2
from presto_tpu.parallel import exchange as EX
from presto_tpu.parallel.pins import shard_rows
from presto_tpu.plan import nodes as N
from presto_tpu.session import Session

AXIS = "d"
# blocks of 256 rows a step of a mesh program's segment sums
# (ops/segred.wide_chunks): a shard of 43 x 2^20 rows folds in 8 steps
# a sum where the default takes 344
_FOLD_BLOCKS = 1 << 15

_MESH_STATEMENTS = REGISTRY.counter(
    "presto_tpu_mesh_statements_total",
    "plans executed as one shard_map program over a mesh, labeled by "
    "its device count")

SHARDED = "sharded"
REPLICATED = "replicated"


@dataclasses.dataclass
class DistTable:
    dt: DTable
    dist: str  # SHARDED (rows split over AXIS) | REPLICATED
    # when SHARDED: the symbol tuple this distribution is hash-
    # partitioned on (rows with equal key tuples co-located), or None
    # for block/round-robin sharding. Set by bucket-sharded scans
    # (connector-defined partitioning) and FIXED_HASH exchanges; lets
    # joins/aggregations on the same keys skip the exchange (reference
    # ConnectorNodePartitioningProvider + AddExchanges partitioning
    # matching).
    part: tuple[str, ...] | None = None


def _gather(dt: DTable, nshards: int) -> DTable:
    """GATHER exchange: all_gather every column -> replicated full table."""
    cols = {}
    for sym, v in dt.cols.items():
        g = jax.lax.all_gather(v.data, AXIS)
        data = g.reshape((-1,) + v.data.shape[1:])
        valid = None
        if v.valid is not None:
            valid = jax.lax.all_gather(v.valid, AXIS).reshape(-1)
        cols[sym] = Val(v.dtype, data, valid, v.dictionary)
    live = jax.lax.all_gather(dt.live_mask(), AXIS).reshape(-1)
    return DTable(cols, live, dt.n * nshards)


class ShardedInterpreter:
    """Trace-time walk of the plan producing a sharded computation.

    Mirrors exec/executor.PlanInterpreter, with a distribution tag per
    intermediate and collectives at distribution boundaries."""

    def __init__(self, scans, capacities, nshards: int,
                 session: Session | None = None,
                 node_order: dict[int, int] | None = None):
        self.scans = scans
        self.capacities = capacities
        self.nshards = nshards
        self.node_order = node_order or {}
        self.session = session or Session()
        self.ok_flags: list = []
        self.ok_keys: list[tuple] = []
        self.used_capacity: dict[tuple, int] = {}
        # dynamic filtering (see exec/executor.PlanInterpreter): probe
        # symbol -> (min, max); ranges are mesh-global (pmin/pmax) so
        # pruning is consistent across shards
        self.dyn_filters: dict[str, tuple] = {}
        self._df_applied: set[str] = set()
        self.df_counts = dict.fromkeys(DF_OUTCOMES, 0)
        # always-on runtime stats (obs/qstats.py): (stable preorder
        # position, mesh-global live-row count, distribution) per plan
        # node — part of EVERY compiled shard_map program, so the
        # cached/templated distributed path reports actuals too (one
        # psum per node; EXPLAIN ANALYZE reads the same outputs)
        self.collect_counts = True
        self.row_counts: list[tuple[object, object, str]] = []
        # preorder position of the plan node being traced (None for a
        # node built during interpretation): what its exchanges are
        # numbered by in a device trace
        self._pos: int | None = None

    # -- plumbing shared with the local interpreter -------------------------

    def _node_key(self, node, kind: str) -> tuple:
        # stable preorder positions (falling back to id for nodes built
        # during interpretation): capacity vectors and overflow retry
        # keys survive replans AND process restarts, so the persistent
        # program cache's capacity sidecar stays meaningful
        return (self.node_order.get(id(node), id(node)), kind)

    def _capacity(self, node, default: int, kind: str = "table",
                  override: int | None = None) -> int:
        """Static capacity for a hash table / exchange bucket: host retry
        override > session override > planner hint > default. Planner
        hints are global-table-sized, so only the whole-table kinds read
        them — per-shard structures (exchange buckets, partitioned
        tables) must use their own per-shard defaults. Hints are
        normalized through next_pow2 so capacity vectors and
        overflow-retry keys stay pow2-canonical."""
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
        # reduce over the mesh so every shard's overflow is reported
        with self._exchange("psum"):
            self.ok_flags.append(
                jax.lax.pmin(ok.astype(jnp.int32), AXIS) > 0)
        self.ok_keys.append(self._node_key(node, kind))

    @contextlib.contextmanager
    def _exchange(self, kind: str):
        """The name an exchange's collectives carry in a device trace:
        ``Exchange#<n>/<kind>`` inside the scope of the plan node
        (number ``n``) that asked for it, so a trace names collectives
        as it names operators (``kind``: gather | all_to_all | psum)."""
        scope = ("Exchange" if self._pos is None
                 else f"Exchange#{self._pos}")
        with jax.named_scope(scope), jax.named_scope(kind):
            yield

    def _gather(self, dt: DTable) -> DTable:
        with self._exchange("gather"):
            return _gather(dt, self.nshards)

    def run(self, node: N.PlanNode) -> DistTable:
        kind = type(node).__name__
        m = getattr(self, "_r_" + kind.lower())
        # as exec/executor.PlanInterpreter.run: device operations carry
        # the plan operator's name, and the scopes nest
        outer, self._pos = self._pos, self.node_order.get(id(node))
        scope = kind if self._pos is None else f"{kind}#{self._pos}"
        with jax.named_scope(scope):
            out = m(node)
            if self.dyn_filters:
                dt = PlanInterpreter._apply_dyn_filters(self, out.dt)
                if dt is not out.dt:
                    out = DistTable(dt, out.dist, out.part)
            if self.collect_counts:
                # mesh-global live rows out of this node: per-shard
                # count psum'd so the total is replicated (for a
                # REPLICATED intermediate every shard holds the same
                # rows — divide)
                c = jnp.sum(out.dt.live_mask().astype(jnp.int64))
                with self._exchange("psum"):
                    total = jax.lax.psum(c, AXIS)
                if out.dist == REPLICATED:
                    total = total // self.nshards
                self.row_counts.append(
                    (self.node_order.get(id(node), id(node)), total,
                     "sharded" if out.dist == SHARDED else "replicated"))
        self._pos = outer
        return out

    def _collect_dyn_filters(self, criteria, dense_key, build: DTable,
                             global_reduce: bool) -> None:
        # smaller bloom under the mesh: the bit array crosses ICI
        registered = PlanInterpreter._collect_dyn_filters(
            self, criteria, dense_key, build, max_bits=1 << 20)
        if global_reduce:
            # union of per-shard key sets — every registration needs it,
            # including re-registrations of a symbol by a later join
            # (shard-local bits would falsely prune other shards' keys)
            for lk in registered:
                bits = self.dyn_filters[lk]
                with self._exchange("psum"):
                    self.dyn_filters[lk] = jax.lax.pmax(
                        bits.astype(jnp.int32), AXIS) > 0

    def replicated(self, node: N.PlanNode) -> DTable:
        out = self.run(node)
        if out.dist == REPLICATED:
            return out.dt
        return self._gather(out.dt)

    def _repart(self, dt: DTable, keys: list[str], node, kind: str
                ) -> DTable:
        """FIXED_HASH exchange: hash-repartition ``dt``'s live rows over
        the mesh axis so rows with equal key tuples land on the same
        shard (reference PartitionedOutputOperator.partitionPage +
        ExchangeOperator; here bucket + lax.all_to_all over ICI).
        Per-destination bucket capacity grows via the host retry loop on
        kernel-reported overflow."""
        # golden-ratio 32-bit mix of the row key: identity int keys
        # (hash_int_column) still spread evenly, and the host scan
        # bucketing (np_partition_id) places by the same bit pattern
        part_id = H.partition_id(OP._row_hash(dt, keys), self.nshards)
        live = dt.live_mask()
        arrays = {}
        for sym, v in dt.cols.items():
            arrays[sym] = v.data
            if v.valid is not None:
                arrays[f"{sym}$valid"] = v.valid
        cap = self._capacity(
            node, next_pow2(2 * max(dt.n // self.nshards, 16)), kind)
        with self._exchange("all_to_all"):
            ex, valid, ok = EX.repartition(
                arrays, live, part_id, self.nshards, cap, AXIS)
        self._note_ok(node, ok, kind)
        cols = {sym: Val(v.dtype, ex[sym], ex.get(f"{sym}$valid"),
                         v.dictionary)
                for sym, v in dt.cols.items()}
        return DTable(cols, valid, self.nshards * cap)

    def _co_located(self, side: "DistTable", keys: list[str]) -> bool:
        """True when ``side`` is already hash-partitioned on exactly the
        join/group keys (connector bucketing or an earlier FIXED_HASH
        exchange on the same hash family) — the exchange is a no-op."""
        return side.part is not None and side.part == tuple(keys)

    def _join_distribution(self, node: N.Join) -> str:
        """Distribution choice, analog of the reference's
        DetermineJoinDistributionType — delegated to the cost model's
        SINGLE decision (cost/model.py), the same one the fragmenter
        and the ReorderJoins rule consult, so the runtime and the
        stage cutter cannot disagree about a join. Returns
        broadcast | partitioned | hybrid (skew-aware refinement of
        partitioned, cost/skew.py)."""
        return decide_join_distribution(
            node.distribution,
            str(self.session.get("join_distribution_type")),
            node.build_rows,
            int(self.session.get("broadcast_join_threshold_rows")))

    def _salt_factor(self, node) -> int:
        """Effective salt fan-out for this join's partitioned
        exchanges: the plan-time annotation (cost/skew.py, pow2)
        capped by the session ``join_salting`` limit (0 disables) AND
        by the real mesh width — the planner sized against its default
        mesh, and tiling more build copies than shards buys nothing."""
        limit = int(self.session.get("join_salting") or 0)
        if limit <= 1 or self.nshards <= 1:
            return 1
        return max(1, min(int(node.salt_factor or 1), limit,
                          self.nshards))

    def _with_salt(self, dt: DTable, salt: int) -> DTable:
        """Probe side of a salted exchange: a ``__salt__`` column
        spreading each key's rows round-robin over ``salt`` sub-
        buckets (deterministic, so replays repartition identically)."""
        cols = dict(dt.cols)
        cols["__salt__"] = Val(
            T.BIGINT,
            (jnp.arange(dt.n, dtype=jnp.int32) % salt))
        return DTable(cols, dt.live, dt.n)

    def _tiled_build(self, dt: DTable, salt: int) -> DTable:
        """Build side of a salted exchange: every build row tiled once
        per salt value, so each probe sub-bucket finds its copy on its
        own shard (the classic skew-salting build replication)."""
        cols = {}
        for sym, v in dt.cols.items():
            reps = (salt,) + (1,) * (getattr(v.data, "ndim", 1) - 1)
            cols[sym] = Val(
                v.dtype, jnp.tile(v.data, reps),
                None if v.valid is None else jnp.tile(v.valid, (salt,)),
                v.dictionary)
        cols["__salt__"] = Val(
            T.BIGINT,
            jnp.repeat(jnp.arange(salt, dtype=jnp.int32), dt.n))
        return DTable(cols, jnp.tile(dt.live_mask(), (salt,)),
                      dt.n * salt)

    @staticmethod
    def _salted_node(node: N.Join) -> N.Join:
        """The join evaluated on salted exchanges: the salt rides as an
        extra equi criterion (a probe row only matches the build copy
        of ITS sub-bucket — for expanding joins this is what keeps the
        tiled copies from double-matching) and any dense hint drops
        (a direct-address table holds one copy per key)."""
        return dataclasses.replace(
            node,
            criteria=list(node.criteria) + [("__salt__", "__salt__")],
            dense_key=None)

    @staticmethod
    def _strip_salt(dt: DTable) -> DTable:
        if "__salt__" not in dt.cols:
            return dt
        return DTable({s: v for s, v in dt.cols.items()
                       if s != "__salt__"}, dt.live, dt.n)

    # -- leaves -------------------------------------------------------------

    def _r_tablescan(self, node: N.TableScan) -> DistTable:
        scan, traced, rows = self.scans[id(node)]
        cols = {}
        for sym in node.assignments:
            cols[sym] = Val(scan.types[sym], traced[sym],
                            traced.get(f"{sym}$valid"),
                            scan.dictionaries[sym])
        # traced arrays are the local shard
        local_n = next(iter(traced.values())).shape[0]
        if rows is None:
            # bucket-placed on the host, which made the mask with it
            live = traced["__live__"]
        else:
            # block-sharded in table order and padded at pin time: the
            # rows past the table's live count are masked here, from
            # the count alone (a masked table ANDs its own mask in)
            row = (jax.lax.axis_index(AXIS).astype(rows.dtype) * local_n
                   + jax.lax.iota(rows.dtype, local_n))
            live = row < rows
            if "__live__" in traced:
                live = live & traced["__live__"]
        part = (scan.part_cols
                if getattr(scan, "bucketed", False) else None)
        return DistTable(DTable(cols, live, local_n), SHARDED, part)

    def _r_values(self, node: N.Values) -> DistTable:
        dt = PlanInterpreter({}, {})._r_values(node)
        return DistTable(dt, REPLICATED)

    # -- elementwise: keep distribution -------------------------------------

    def _r_filter(self, node: N.Filter) -> DistTable:
        src = self.run(node.source)
        return DistTable(OP.apply_filter(src.dt, node.predicate),
                         src.dist, src.part)

    def _r_project(self, node: N.Project) -> DistTable:
        from presto_tpu.expr import ir as _ir
        src = self.run(node.source)
        part = None
        if src.part is not None:
            # follow the partition keys through identity renames; a key
            # not projected (or transformed) loses the co-location fact
            renames = {e.name: s for s, e in node.assignments.items()
                       if isinstance(e, _ir.ColumnRef)}
            mapped = tuple(renames.get(k) for k in src.part)
            if all(m is not None for m in mapped):
                part = mapped
        return DistTable(OP.apply_project(src.dt, node.assignments),
                         src.dist, part)

    # -- aggregation: partial local, merge replicated -----------------------

    def _r_aggregate(self, node: N.Aggregate) -> DistTable:
        ov = int(self.session.get("groupby_table_size") or 0)
        src = self.run(node.source)
        if src.dist == REPLICATED:
            cap = (1 if not node.group_keys else
                   self._capacity(node,
                                  next_pow2(min(2 * src.dt.n, 1 << 22)),
                                  override=ov))
            out, ok = OP.apply_aggregate(src.dt, node, cap)
            if node.group_keys:
                self._note_ok(node, ok)
            return DistTable(out, REPLICATED)
        if (node.group_keys and src.part is not None
                and set(src.part) <= set(node.group_keys)
                and node.step == N.AggStep.SINGLE):
            # equal group tuples are already co-located (connector
            # bucketing / prior exchange on a subset of the keys):
            # aggregate locally, output stays SHARDED — no partial/final
            # split, no exchange (reference AddExchanges partitioning
            # matching on pre-partitioned tables)
            ccap = self._capacity(
                node, next_pow2(min(2 * src.dt.n, 1 << 22)), override=ov)
            out, ok = OP.apply_aggregate(src.dt, node, ccap)
            self._note_ok(node, ok)
            return DistTable(out, SHARDED, src.part)
        cap = (1 if not node.group_keys else
               self._capacity(node, next_pow2(min(2 * src.dt.n, 1 << 22)),
                              override=ov))
        partial_node = dataclasses.replace(node, step=N.AggStep.PARTIAL)
        final_node = dataclasses.replace(node, step=N.AggStep.FINAL)
        if node.step == N.AggStep.SINGLE:
            pass
        elif node.step == N.AggStep.PARTIAL:
            partial_node = node
            final_node = None
        if not self.session.get("partial_aggregation") \
                and final_node is not None:
            # property off: ship raw rows and aggregate replicated (the
            # reference's push_partial_aggregation_through_join=false
            # analog; mainly a debugging/testing escape hatch)
            gathered = self._gather(src.dt)
            out, ok = OP.apply_aggregate(gathered, node, cap)
            if node.group_keys:
                self._note_ok(node, ok)
            return DistTable(out, REPLICATED)
        # partial -> exchange states -> final merge (PushPartialAggregation
        # ThroughExchange; psum-tree analog)
        partial, ok1 = OP.apply_aggregate(src.dt, partial_node, cap)
        if node.group_keys:
            self._note_ok(node, ok1)
        if final_node is None:
            return DistTable(self._gather(partial), REPLICATED)
        est_groups = node.capacity or cap
        if node.group_keys and est_groups >= int(
                self.session.get("partitioned_agg_min_groups")):
            # high cardinality: FIXED_HASH repartition of partial states
            # by group-key hash, final merge local to each shard —
            # per-device state is O(groups/nshards)
            # (AddExchanges.java:215-245)
            ex = self._repart(partial, node.group_keys, node, "agg_exch")
            fcap = self._capacity(
                node, next_pow2(2 * max(est_groups // self.nshards, 16)),
                "final", override=ov)
            out, ok2 = OP.apply_aggregate(ex, final_node, fcap)
            self._note_ok(node, ok2, "final")
            return DistTable(out, SHARDED, tuple(node.group_keys))
        gathered = self._gather(partial)
        fcap = (1 if not node.group_keys else
                self._capacity(node, next_pow2(2 * cap), "final",
                               override=ov))
        out, ok2 = OP.apply_aggregate(gathered, final_node, fcap)
        if node.group_keys:
            self._note_ok(node, ok2, "final")
        return DistTable(out, REPLICATED)

    # -- joins: broadcast or hash-repartitioned build/probe ------------------

    def _r_join(self, node: N.Join) -> DistTable:
        # build side first so its key range can prune the probe scans
        right = self.run(node.right)
        if (node.join_type == N.JoinType.INNER
                and self.session.get("enable_dynamic_filtering")):
            # a salted exchange drops the hint (_salted_node): there
            # the probe is a sorted lookup and the filter stays
            direct = (node.dense_key if node.build_unique
                      and self._salt_factor(node) <= 1 else None)
            self._collect_dyn_filters(node.criteria, direct, right.dt,
                                      right.dist == SHARDED)
        left = self.run(node.left)
        lkeys = [lk for lk, _ in node.criteria]
        rkeys = [rk for _, rk in node.criteria]
        out_part = left.part
        dist = self._join_distribution(node)
        partitioned = (node.criteria and left.dist == SHARDED
                       and right.dist == SHARDED
                       and dist in ("partitioned", "hybrid"))
        if (partitioned and dist == "hybrid" and node.build_unique
                and node.join_type in (N.JoinType.INNER,
                                       N.JoinType.LEFT)
                and self.nshards > 1
                and int(self.session.get("skew_hot_key_threshold")
                        or 0) > 0):
            return self._hybrid_join(node, left, right, lkeys, rkeys)
        if node.join_type == N.JoinType.FULL and not partitioned:
            # FULL with a broadcast build would emit each unmatched build
            # row once PER SHARD; only the FIXED_HASH layout (both sides
            # co-partitioned by key) keeps the unmatched-tail pass
            # correct, so otherwise gather both sides and join replicated
            probe = (left.dt if left.dist == REPLICATED
                     else self._gather(left.dt))
            build = (right.dt if right.dist == REPLICATED
                     else self._gather(right.dt))
            cap = self._capacity(node, next_pow2(2 * build.n))
            out_cap = self._capacity(
                node, next_pow2(2 * (probe.n + build.n)), "out")
            out, t_ok, o_ok = OP.apply_expand_join(probe, build, node,
                                                   cap, out_cap)
            self._note_ok(node, t_ok)
            self._note_ok(node, o_ok, "out")
            return DistTable(out, REPLICATED)
        join_node = node
        if partitioned:
            # FIXED_HASH: repartition both sides by join-key hash so each
            # shard joins only its key range — per-device build memory is
            # O(build/nshards) instead of O(build)
            # (AddExchanges.java:245 partitionedExchange). A side already
            # partitioned on its keys skips its exchange (connector
            # bucketing / reused exchange, AddExchanges partitioning
            # matching). With a cost-model salt annotation the exchange
            # spreads each key over salt sub-buckets (probe rows round-
            # robin, build rows tiled per salt) so one heavy key cannot
            # collapse the all_to_all onto a single shard; FULL keeps
            # the exact co-partition its unmatched-tail pass requires.
            salt = (self._salt_factor(node)
                    if node.join_type != N.JoinType.FULL else 1)
            if salt > 1:
                probe = self._repart(
                    self._with_salt(left.dt, salt),
                    lkeys + ["__salt__"], node, "probe_exch")
                build = self._repart(
                    self._tiled_build(right.dt, salt),
                    rkeys + ["__salt__"], node, "build_exch")
                join_node = self._salted_node(node)
                out_part = None  # partitioned on (keys, salt), not keys
            else:
                probe = (left.dt if self._co_located(left, lkeys)
                         else self._repart(left.dt, lkeys, node,
                                           "probe_exch"))
                build = (right.dt if self._co_located(right, rkeys)
                         else self._repart(right.dt, rkeys, node,
                                           "build_exch"))
                # FULL's unmatched-build tail rows carry NULL probe keys
                # on whichever shard the BUILD key hashed to — the output
                # is NOT partitioned by the probe keys (downstream co-
                # location shortcuts would emit one NULL group per shard)
                out_part = (None if node.join_type == N.JoinType.FULL
                            else tuple(lkeys))
            # per-shard table: must NOT pick up the planner's global-sized
            # capacity hint (kind "ptable" skips it)
            tab_kind, out_kind = "ptable", "pout"
            cap = self._capacity(node, next_pow2(
                2 * max((node.build_rows or build.n) // self.nshards, 16)),
                tab_kind)
        else:
            # FIXED_BROADCAST: replicate the build side
            probe = left.dt
            build = (right.dt if right.dist == REPLICATED
                     else self._gather(right.dt))
            tab_kind, out_kind = "table", "out"
            cap = self._capacity(node, next_pow2(2 * build.n))
        if node.build_unique and node.join_type != N.JoinType.FULL:
            out, ok = OP.apply_join(probe, build, join_node, cap)
            self._note_ok(node, ok, tab_kind)
            return DistTable(self._strip_salt(out), left.dist, out_part)
        out_cap = self._capacity(
            node, next_pow2(2 * (probe.n + build.n)), out_kind)
        out, t_ok, o_ok = OP.apply_expand_join(probe, build, join_node,
                                               cap, out_cap)
        self._note_ok(node, t_ok, tab_kind)
        self._note_ok(node, o_ok, out_kind)
        return DistTable(self._strip_salt(out), left.dist, out_part)

    def _hybrid_join(self, node: N.Join, left: DistTable,
                     right: DistTable, lkeys, rkeys) -> DistTable:
        """Skew-aware hybrid distribution (JSPIM-style): heavy-hitter
        keys are detected AT RUNTIME by a mesh-global count sketch over
        the probe keys; hot keys keep their probe rows LOCAL and
        replicate their build rows (``all_gather``), while the cold
        tail hash-partitions (``all_to_all``, salted when annotated).
        Classification is per sketch BUCKET with the same content hash
        on both sides, so a probe row and its matching build row always
        land on the same path — a collision only promotes a cold key to
        the (also correct) broadcast path. The two joins are both
        probe-preserving (INNER/LEFT unique-build, the only shapes this
        path accepts) and concatenate row-wise; with no key over the
        threshold the hot side is empty and the join degrades to the
        plain partitioned plan it refines."""
        from presto_tpu.cost.skew import SKETCH_BUCKETS
        threshold = int(self.session.get("skew_hot_key_threshold"))
        sb = jnp.uint64(SKETCH_BUCKETS)
        probe_live = left.dt.live_mask()
        key_valid = OP._and_key_valid(left.dt, lkeys, probe_live)
        ph = OP._row_hash(left.dt, lkeys)
        bucket = (ph % sb).astype(jnp.int32)
        counts = jnp.zeros((SKETCH_BUCKETS,), jnp.int32).at[
            jnp.where(key_valid, bucket, SKETCH_BUCKETS)].add(
            1, mode="drop")
        with self._exchange("psum"):
            gcounts = jax.lax.psum(counts, AXIS)
        # a bucket pools ~rows/SKETCH_BUCKETS cold keys besides any
        # heavy hitter, so compare against the threshold PLUS that
        # uniform background — without it, probes over
        # SKETCH_BUCKETS * threshold rows would classify every bucket
        # hot on perfectly uniform data and broadcast the whole build
        background = jnp.sum(gcounts) // SKETCH_BUCKETS
        hot_bucket = gcounts >= threshold + background
        probe_hot = hot_bucket[bucket] & key_valid
        build_live = OP._and_key_valid(right.dt, rkeys,
                                       right.dt.live_mask())
        bh = OP._row_hash(right.dt, rkeys)
        build_hot = hot_bucket[(bh % sb).astype(jnp.int32)] & build_live

        # hot build rows: per-shard compact (overflow-retried — the
        # planner's hot_keys estimate seeds the width) -> all_gather
        est_hot = int(node.hot_keys or 16)
        hot_cap = self._capacity(node, next_pow2(max(
            4 * est_hot // max(self.nshards, 1), 16)), "hot")
        hot_local, h_ok = OP.compact_dtable(
            DTable(right.dt.cols, build_hot, right.dt.n), hot_cap)
        self._note_ok(node, h_ok, "hot")
        hot_build = self._gather(hot_local)
        hcap = self._capacity(node, next_pow2(2 * hot_build.n), "htab")
        out_hot, ok1 = OP.apply_join(
            DTable(left.dt.cols, probe_live & probe_hot, left.dt.n),
            hot_build, node, hcap)
        self._note_ok(node, ok1, "htab")

        # cold tail: strike hot rows out of both sides, then the plain
        # partitioned join (salted when the cost model asked for it)
        cold_probe = DTable(left.dt.cols, probe_live & ~probe_hot,
                            left.dt.n)
        cold_build = DTable(right.dt.cols, build_live & ~build_hot,
                            right.dt.n)
        join_node = node
        salt = self._salt_factor(node)
        if salt > 1:
            cp = self._repart(self._with_salt(cold_probe, salt),
                              lkeys + ["__salt__"], node, "probe_exch")
            cb = self._repart(self._tiled_build(cold_build, salt),
                              rkeys + ["__salt__"], node, "build_exch")
            join_node = self._salted_node(node)
        else:
            # masking hot rows out does not move the survivors, so a
            # side already partitioned on its keys keeps the same
            # exchange-skip the plain partitioned path applies
            cp = (cold_probe if self._co_located(left, lkeys)
                  else self._repart(cold_probe, lkeys, node,
                                    "probe_exch"))
            cb = (cold_build if self._co_located(right, rkeys)
                  else self._repart(cold_build, rkeys, node,
                                    "build_exch"))
        ccap = self._capacity(node, next_pow2(
            2 * max((node.build_rows or cb.n) // self.nshards, 16)),
            "ptable")
        out_cold, ok2 = OP.apply_join(cp, cb, join_node, ccap)
        self._note_ok(node, ok2, "ptable")
        out = OP.concat_dtables([out_hot,
                                 self._strip_salt(out_cold)])
        return DistTable(out, SHARDED, None)

    def _r_multijoin(self, node: N.MultiJoin) -> DistTable:
        """Distributed lowering of the fused star chain: every build
        traces first (each registering its dynamic filter, so the fact
        scan prunes against ALL dimensions), then AT MOST ONE large
        build co-partitions with the fact table — one repartition of
        the fact table where the cascade paid a shuffle per large
        join — and every other build replicates (``all_gather``). The
        fused sequential probe walk then runs shard-locally."""
        builds: list[DistTable] = []
        for k, (bnode, crit) in enumerate(zip(node.builds,
                                              node.criteria)):
            b = self.run(bnode)
            builds.append(b)
            if self.session.get("enable_dynamic_filtering"):
                self._collect_dyn_filters(crit, node.leg_dense_key(k),
                                          b.dt, b.dist == SHARDED)
        spine = self.run(node.spine)
        mode = str(self.session.get("join_distribution_type"))
        thresh = int(self.session.get("broadcast_join_threshold_rows"))
        spine_syms = set(node.spine.output_symbols)
        part_idx, part_rows = None, -1
        if spine.dist == SHARDED:
            for i, (b, crit) in enumerate(zip(builds, node.criteria)):
                rows_i = (node.build_rows[i]
                          if i < len(node.build_rows) else None)
                dist_i = (node.distributions[i]
                          if i < len(node.distributions)
                          else "automatic")
                d = decide_join_distribution(
                    dist_i if dist_i != "automatic" else None,
                    mode, rows_i, thresh)
                if (d in ("partitioned", "hybrid")
                        and b.dist == SHARDED
                        and all(lk in spine_syms for lk, _ in crit)
                        and (rows_i or 0) > part_rows):
                    part_idx, part_rows = i, (rows_i or 0)
        spine_dt = spine.dt
        out_part = spine.part
        part_build_dt = None
        if part_idx is not None:
            crit = node.criteria[part_idx]
            plk = [lk for lk, _ in crit]
            prk = [rk for _, rk in crit]
            if not self._co_located(spine, plk):
                spine_dt = self._repart(spine.dt, plk, node,
                                        "probe_exch")
            bsel = builds[part_idx]
            part_build_dt = (
                bsel.dt if self._co_located(bsel, prk)
                else self._repart(bsel.dt, prk, node,
                                  f"build{part_idx}_exch"))
            out_part = tuple(plk)
        build_dts = []
        for i, b in enumerate(builds):
            if i == part_idx:
                build_dts.append(part_build_dt)
            else:
                build_dts.append(b.dt if b.dist == REPLICATED
                                 else self._gather(b.dt))
        out, ok = OP.apply_multi_join(spine_dt, build_dts, node)
        self._note_ok(node, ok)
        if spine.dist == REPLICATED:
            return DistTable(out, REPLICATED)
        return DistTable(out, SHARDED, out_part)

    def _r_semijoin(self, node: N.SemiJoin) -> DistTable:
        src = self.run(node.source)
        filt = self.replicated(node.filter_source)
        cap = self._capacity(node, next_pow2(2 * filt.n))
        out, ok = OP.apply_semijoin(src.dt, filt, node, cap)
        self._note_ok(node, ok)
        return DistTable(out, src.dist, src.part)

    def _r_crossjoin(self, node: N.CrossJoin) -> DistTable:
        left = self.run(node.left)
        right = self.replicated(node.right)
        if node.scalar:
            return DistTable(OP.apply_cross_scalar(left.dt, right),
                             left.dist, left.part)
        # general nested loop: left stays sharded (each probe row lives
        # on exactly one shard), build replicated — shard-local product
        ldt = left.dt
        lcap = self._capacity(node, next_pow2(
            min(ldt.n, 2 * max((node.left_rows or ldt.n)
                               // max(self.nshards, 1), 16))), "left")
        rcap = self._capacity(node, next_pow2(
            min(right.n, 2 * (node.right_rows or right.n))), "right")
        if lcap < ldt.n:
            ldt, lok = OP.compact_dtable(ldt, lcap)
            self._note_ok(node, lok, "left")
        if rcap < right.n:
            right, rok = OP.compact_dtable(right, rcap)
            self._note_ok(node, rok, "right")
        return DistTable(OP.apply_cross_general(ldt, right),
                         left.dist, left.part)

    # -- replicated-only operators ------------------------------------------

    def _r_distinct(self, node: N.Distinct) -> DistTable:
        src = self.run(node.source)
        cap = self._capacity(node, next_pow2(min(2 * src.dt.n, 1 << 22)))
        if src.dist == SHARDED:
            # local pre-distinct shrinks the exchange, then final distinct
            local, ok1 = OP.apply_distinct(src.dt, cap)
            self._note_ok(node, ok1)
            gathered = self._gather(local)
            fcap = self._capacity(node, next_pow2(2 * cap), "final")
            out, ok2 = OP.apply_distinct(gathered, fcap)
            self._note_ok(node, ok2, "final")
            return DistTable(out, REPLICATED)
        out, ok = OP.apply_distinct(src.dt, cap)
        self._note_ok(node, ok)
        return DistTable(out, REPLICATED)

    def _r_markdistinct(self, node: N.MarkDistinct) -> DistTable:
        src = self.run(node.source)
        if src.dist == SHARDED:
            # global mark correctness needs co-located key tuples:
            # FIXED_HASH repartition by the distinct keys first (skipped
            # when the input is already partitioned on a key subset)
            if src.part is not None and set(src.part) <= set(node.keys):
                ex = src.dt
                out_part = src.part
            else:
                ex = self._repart(src.dt, node.keys, node, "mark_exch")
                out_part = tuple(node.keys)
            cap = self._capacity(
                node, next_pow2(min(2 * ex.n, 1 << 22)))
            out, ok = OP.apply_mark_distinct(ex, node, cap)
            self._note_ok(node, ok)
            return DistTable(out, SHARDED, out_part)
        cap = self._capacity(
            node, next_pow2(min(2 * src.dt.n, 1 << 22)))
        out, ok = OP.apply_mark_distinct(src.dt, node, cap)
        self._note_ok(node, ok)
        return DistTable(out, REPLICATED)

    def _r_window(self, node: N.Window) -> DistTable:
        src = self.run(node.source)
        if src.dist == SHARDED and node.partition_by:
            # FIXED_HASH repartition by the window partition keys, then
            # each shard computes its partitions independently and the
            # output STAYS SHARDED (reference AddExchanges partitioned
            # WindowNode + operator/WindowOperator.java:70). A
            # co-partitioned input skips the exchange.
            if src.part is not None and set(src.part) <= set(
                    node.partition_by):
                return DistTable(OP.apply_window(src.dt, node),
                                 SHARDED, src.part)
            ex = self._repart(src.dt, node.partition_by, node,
                              "win_exch")
            return DistTable(OP.apply_window(ex, node), SHARDED,
                             tuple(node.partition_by))
        dt = (src.dt if src.dist == REPLICATED
              else self._gather(src.dt))
        return DistTable(OP.apply_window(dt, node), REPLICATED)

    def _r_sort(self, node: N.Sort) -> DistTable:
        src = self.run(node.source)
        if src.dist == SHARDED and self.session.get("distributed_sort"):
            # merge exchange (MergeOperator.java:44): the O(n log^2 n)
            # sort network runs on n/nshards rows per device in
            # parallel; the replicated stage only merges presorted runs
            local = OP.apply_sort(src.dt, node.orderings)
            gathered = self._gather(local)
            merged = OP.merge_sorted_runs(gathered, node.orderings,
                                          self.nshards)
            return DistTable(merged, REPLICATED)
        dt = (src.dt if src.dist == REPLICATED
              else self._gather(src.dt))
        return DistTable(OP.apply_sort(dt, node.orderings), REPLICATED)

    def _r_topn(self, node: N.TopN) -> DistTable:
        src = self.run(node.source)
        if src.dist == SHARDED:
            # partial topN per shard, compact to `count` rows, then a
            # final topN over nshards*count gathered candidates — the
            # exchange carries O(count) rows instead of the whole input
            # (reference TopNOperator partial/final split)
            local = OP.head(
                OP.apply_topn(src.dt, node.count, node.orderings),
                node.count)
            gathered = self._gather(local)
            return DistTable(
                OP.apply_topn(gathered, node.count, node.orderings),
                REPLICATED)
        return DistTable(OP.apply_topn(src.dt, node.count, node.orderings),
                         REPLICATED)

    def _r_limit(self, node: N.Limit) -> DistTable:
        src = self.run(node.source)
        take = node.count + node.offset
        if src.dist == SHARDED and take <= src.dt.n:
            # per-shard head of `count+offset` live rows (live-first
            # stable compaction), gather O(nshards*take) candidates,
            # final limit — the exchange carries O(take) rows instead
            # of the whole input (reference LimitNode partial/final)
            local = OP.head(OP.apply_sort(
                OP.apply_limit(src.dt, take), []), take)
            gathered = self._gather(local)
            return DistTable(
                OP.apply_limit(gathered, node.count, node.offset),
                REPLICATED)
        dt = (src.dt if src.dist == REPLICATED
              else self._gather(src.dt))
        return DistTable(OP.apply_limit(dt, node.count, node.offset),
                         REPLICATED)

    def _r_union(self, node: N.Union) -> DistTable:
        parts = [self.run(s) for s in node.inputs]
        if all(p.dist == SHARDED for p in parts):
            out = OP.apply_union([p.dt for p in parts], node)
            return DistTable(out, SHARDED)
        dts = [p.dt if p.dist == REPLICATED
               else self._gather(p.dt) for p in parts]
        return DistTable(OP.apply_union(dts, node), REPLICATED)

    def _r_exchange(self, node: N.Exchange) -> DistTable:
        src = self.run(node.source)
        if node.kind == N.ExchangeType.GATHER and src.dist == SHARDED:
            return DistTable(self._gather(src.dt), REPLICATED)
        if node.kind == N.ExchangeType.REPLICATE and src.dist == SHARDED:
            return DistTable(self._gather(src.dt), REPLICATED)
        if node.kind == N.ExchangeType.REPARTITION and src.dist == SHARDED:
            return DistTable(
                self._repart(src.dt, node.partition_keys, node, "exch"),
                SHARDED)
        return src

    def _r_output(self, node: N.Output) -> DistTable:
        src = self.run(node.source)
        dt = (src.dt if src.dist == REPLICATED
              else self._gather(src.dt))
        return DistTable(
            DTable({s: dt.cols[s] for s in node.symbols}, dt.live, dt.n),
            REPLICATED)


def _plan_exploits_partitioning(plan: N.PlanNode,
                                part: tuple[str, ...]) -> bool:
    """True when some plan operator could skip an exchange because its
    keys match ``part`` (join side, aggregate/window/mark-distinct key
    superset)."""
    found = False

    def visit(node):
        nonlocal found
        if found:
            return
        if isinstance(node, N.Join) and node.criteria:
            if (tuple(lk for lk, _ in node.criteria) == part
                    or tuple(rk for _, rk in node.criteria) == part):
                found = True
        elif isinstance(node, N.Aggregate) and node.group_keys:
            if set(part) <= set(node.group_keys):
                found = True
        elif isinstance(node, N.Window) and node.partition_by:
            if set(part) <= set(node.partition_by):
                found = True
        elif isinstance(node, N.MarkDistinct):
            if set(part) <= set(node.keys):
                found = True
        for s in node.sources():
            visit(s)

    visit(plan)
    return found


def _pinned_scan_arrays(engine, scan: ScanInput, mesh: Mesh):
    """The scan's columns as the mesh holds them: contiguous blocks of
    rows in table order, one a device, placed once per table version
    and padded then to the devices' bucketed share
    (``parallel/pins.py``). No live mask comes with them: the program
    makes it from the scan's row count."""
    return {sym: engine.shard_pins.get(a, mesh, table=scan.node.table,
                                       column=sym)
            for sym, a in scan.arrays.items()}


def _bucket_scan_arrays(scan: ScanInput, nshards: int):
    """Rows placed over shards by key-hash bucket (connector-defined
    partitioning), on the host, with the live mask of the placement:
    the exact bit pattern of the device FIXED_HASH exchange
    (partition_id golden-ratio fold, numpy twins in ops/hash.py), so
    bucket-sharded scans are co-located with each other AND with
    repartitioned intermediates on the same keys."""
    from presto_tpu.ops import hash as H
    n = scan.nrows
    hs = []
    for sym in scan.part_cols:
        valid = scan.arrays.get(f"{sym}$valid")
        if scan.dictionaries.get(sym) is not None:
            hs.append(H.np_hash_string_column(
                scan.arrays[sym], scan.dictionaries[sym], valid))
        else:
            hs.append(H.np_hash_int_column(scan.arrays[sym], valid))
    bucket = H.np_partition_id(H.np_combine_hashes(hs), nshards)
    base_live = scan.arrays.get("__live__")
    if base_live is not None:
        # dead padding rows go to bucket 0 as dead rows
        bucket = np.where(base_live, bucket, 0)
    counts = np.bincount(bucket, minlength=nshards)
    # pow2-bucket the per-shard width (lint/retrace.py): the raw
    # bincount max is a data-dependent int that flows into every
    # sharded input shape, so two datasets with different skew would
    # retrace the same plan; the live mask keeps padding rows inert
    per = next_pow2(max(int(counts.max()), 1))
    order = np.argsort(bucket, kind="stable")
    starts = np.zeros(nshards, dtype=np.int64)
    starts[1:] = np.cumsum(counts)[:-1]
    # position of each (sorted) row inside its destination shard
    within = np.arange(n) - starts[bucket[order]]
    dest = bucket[order] * per + within
    out = {}
    for sym, a in scan.arrays.items():
        if sym == "__live__":
            continue
        buf = np.zeros((nshards * per,) + a.shape[1:], dtype=a.dtype)
        buf[dest] = a[order]
        out[sym] = buf
    live = np.zeros(nshards * per, dtype=bool)
    live[dest] = True if base_live is None else base_live[order]
    out["__live__"] = live
    return out


def execute_plan_distributed(engine, plan: N.PlanNode,
                             mesh: Mesh, profile: dict | None = None
                             ) -> Table:
    """Compile + run a logical plan over every device in ``mesh``.
    ``profile`` (EXPLAIN ANALYZE) is filled with per-node mesh-global
    row counts and compile/run wall times.

    shard_map programs go through the same two-tier program cache as
    the local executor (exec/progcache.py): keyed by plan fingerprint,
    sharded input shapes, scan partitioning, trace-relevant session
    properties, and pow2-bucketed capacities, with the mesh shape in
    the platform fingerprint — so a repeat distributed query (or a
    warm process sharing the disk store) skips lower+compile. EXPLAIN
    ANALYZE (``profile``) bypasses the cache: its row-count outputs
    change the program."""
    import time as _time

    from presto_tpu.exec import progcache as PC
    from presto_tpu.exec.executor import compiling, note_dyn_filters
    from presto_tpu.plan.fingerprint import plan_fingerprint

    nshards = mesh.devices.size
    _MESH_STATEMENTS.inc(devices=nshards)
    # plan templates (templates/): hoist literals before the plan is
    # fingerprinted so literal variants share the shard_map executable;
    # this query's values ride as trailing REPLICATED scalar args.
    # EXPLAIN ANALYZE (profile) bypasses the cache and keeps literals
    # baked — its row-count outputs change the program anyway.
    from presto_tpu import templates as TPL
    orig_plan = plan  # pre-template plan for the stats recorder
    tpl = None
    if profile is None and TPL.enabled(engine.session):
        tpl = TPL.parameterize(plan)
        if tpl is not None:
            plan = tpl.plan
    scan_inputs = collect_scans(plan, engine)
    node_order = preorder_index(plan)

    use_part = bool(engine.session.get("use_connector_partitioning"))
    sharded_arrays = []
    # a block-sharded scan's live rows: one replicated scalar a scan,
    # after the columns (scan number -> its argument)
    rows_of: dict[int, np.integer] = {}
    for i, scan in enumerate(scan_inputs):
        # bucket only when some operator can exploit the co-location:
        # block sharding is a pin the mesh keeps, bucketing is a
        # full-table hash + scatter on host, every statement
        bucketed = (use_part and scan.part_cols is not None
                    and _plan_exploits_partitioning(plan, scan.part_cols))
        scan.bucketed = bucketed  # read by ShardedInterpreter scans
        if bucketed:
            arrays = _bucket_scan_arrays(scan, nshards)
        elif scan.arrays:
            arrays = _pinned_scan_arrays(engine, scan, mesh)
            total = next(iter(arrays.values())).shape[0]
            rows_of[i] = (np.int32 if total < 2 ** 31
                          else np.int64)(scan.nrows)
        else:
            # no column to take the shard's length from (count(*)):
            # the mask is the scan's one array
            total = shard_rows(scan.nrows, nshards) * nshards
            arrays = {"__live__": np.arange(total) < scan.nrows}
        sharded_arrays.append(arrays)
    flat_names = [(i, sym) for i, arrs in enumerate(sharded_arrays)
                  for sym in arrs]
    flat_arrays = [sharded_arrays[i][sym] for i, sym in flat_names]
    row_args = list(rows_of.values())

    use_cache = profile is None
    mesh_key = PC.mesh_key(mesh)
    fpr = PC.platform_fingerprint(mesh_shape=mesh_key)
    cache = engine._program_cache
    base_key = (
        plan_fingerprint(plan),
        tuple((i, sym, a.shape, str(a.dtype))
              for (i, sym), a in zip(flat_names, flat_arrays)),
        PC.scan_dictionary_key(scan_inputs),
        PC.trace_session_key(engine.session),
        tuple((i, scan.part_cols, bool(scan.bucketed))
              for i, scan in enumerate(scan_inputs)),
        "shard_map", mesh_key, _FOLD_BLOCKS)
    capacities: dict[tuple, int] = {}
    if use_cache:
        cache.configure(engine.session)
        known_caps = engine._caps_memory.get(base_key)
        if known_caps is None:  # {} is a real answer: no overrides
            known_caps = cache.load_caps(base_key, fpr)
        capacities = dict(known_caps)

    for _attempt in range(10):
        caps_key = PC.bucket_capacities(capacities)
        entry = (cache.lookup((base_key, caps_key), fpr,
                              devices=mesh.devices.flat)
                 if use_cache else None)
        if tpl is not None and _attempt == 0:
            TPL.note_lookup(hit=entry is not None,
                            params=len(tpl.params))
        pargs = (tpl.example_args(scan_inputs)
                 if tpl is not None else [])
        lowered = None
        cache_hit = entry is not None
        if entry is not None:
            compiled, meta = entry
            compile_s = 0.0
        else:
            meta: dict[str, object] = {}

            def traced_fn(*args):
                it = iter(args)
                scans = {}
                per_scan: dict[int, dict] = {}
                for (i, sym), a in zip(flat_names, it):
                    per_scan.setdefault(i, {})[sym] = a
                rows = dict(zip(rows_of, it))
                for i, scan in enumerate(scan_inputs):
                    scans[id(scan.node)] = (scan, per_scan[i],
                                            rows.get(i))
                interp = ShardedInterpreter(scans, capacities, nshards,
                                            engine.session, node_order)
                if tpl is not None:
                    from presto_tpu.templates import runtime as TR
                    tp = TR.TraceParams(list(it))
                    with TR.active(tp):
                        out = interp.run(plan).dt
                    meta["param_bindings"] = dict(tp.bindings)
                else:
                    out = interp.run(plan).dt
                meta["out"] = [
                    (sym, v.dtype, v.dictionary, v.valid is not None)
                    for sym, v in out.cols.items()]
                meta["ok_keys"] = interp.ok_keys
                meta["used_capacity"] = interp.used_capacity
                meta["dynfilters"] = dict(interp.df_counts)
                meta["count_nodes"] = [
                    (nid, dist) for nid, _, dist in interp.row_counts]
                res = []
                for sym, v in out.cols.items():
                    res.append(v.data)
                    res.append(v.valid if v.valid is not None
                               else jnp.ones((out.n,), dtype=bool))
                # stacked: one replicated (k,) array, one host fetch
                counts = (jnp.stack([c for _, c, _ in
                                     interp.row_counts])
                          if interp.row_counts
                          else jnp.zeros((0,), dtype=jnp.int32))
                # ok flags stacked like the local make_traced: a tuple
                # of device scalars costs one host round-trip EACH on
                # the overflow ladder, a (k,) bool array costs one
                oks = (jnp.stack(interp.ok_flags) if interp.ok_flags
                       else jnp.zeros((0,), dtype=bool))
                return tuple(res), out.live_mask(), oks, counts

            sharded = jax.shard_map(
                traced_fn, mesh=mesh,
                in_specs=(tuple(P(AXIS) for _ in flat_arrays)
                          + tuple(P() for _ in row_args + pargs)),
                out_specs=(P(), P(), P(), P()),
                check_vma=False)
            t0 = _time.perf_counter()
            with compiling(program=traced_fn.__name__, attempt=_attempt,
                           root=type(plan).__name__, devices=nshards,
                           distributed=True), \
                    segred.wide_chunks(_FOLD_BLOCKS):
                lowered = jax.jit(sharded).lower(
                    *flat_arrays, *row_args, *pargs)
                compiled = lowered.compile()
            compile_s = _time.perf_counter() - t0
            # harvest the whole-mesh device cost into meta before the
            # success-path cache insert below: warm (disk-tier) hits
            # in a fresh process attribute flops/bytes from here
            from presto_tpu.obs import devprof
            cost = devprof.harvest(compiled)
            if cost is not None:
                meta["cost"] = cost
        if tpl is not None:
            pargs = tpl.bind(meta.get("param_bindings"))
        t0 = _time.perf_counter()
        with _TRACER.span("execute", devices=nshards,
                          distributed=True) as span:
            note_dyn_filters(meta, span)
            with mesh:
                res, live, oks, node_counts = compiled(
                    *flat_arrays, *row_args, *pargs)
            HS.wait(live, site="dist-execute")
        run_s = _time.perf_counter() - t0
        # ONE host sync for every flag (the stacked (k,) array), not
        # one round-trip per overflow flag
        oks_np = HS.fetch(oks, site="dist-ok-ladder")
        if oks_np.all():
            if use_cache:
                if lowered is not None:
                    # as_text materializes the whole module — pay it
                    # once, on the successful attempt, and keep the
                    # text with the entry so cache hits (and warm
                    # processes) still surface last_dist_hlo
                    meta["hlo"] = lowered.as_text()
                    cache.insert((base_key, caps_key), compiled, meta,
                                 fpr)
                if engine._caps_memory.get(base_key) != capacities:
                    cache.store_caps(base_key, capacities, fpr)
                engine._caps_memory[base_key] = dict(capacities)
            break
        from presto_tpu.ops.hash import grow_overflowed
        grow_overflowed(capacities, meta["ok_keys"], oks_np,
                        meta["used_capacity"])
    else:
        from presto_tpu.ops.hash import HashChainOverflow
        raise HashChainOverflow(
            "hash table capacity retry limit exceeded")

    # introspection for tests/EXPLAIN: the distribution strategy is
    # visible as collectives in the program text
    engine.last_dist_hlo = meta.get("hlo") or (
        lowered.as_text() if lowered is not None else "")
    engine.last_dist_meta = {"used_capacity": dict(meta["used_capacity"])}
    # fold into the ambient stats tree (obs/qstats.py): the distributed
    # path reports per-node mesh-global actuals on cache/template hits
    # exactly like cold compiles
    # ONE batched device->host transfer for the result demux, the
    # per-node actuals, and the live mask: per-array np.asarray pays a
    # device round-trip each
    live_np, res_np, counts_np = HS.fetch(
        (live, list(res), node_counts), site="dist-demux")
    from presto_tpu.obs import qstats as QS
    QS.record_program(engine, orig_plan, meta, counts_np, compile_s,
                      run_s, cache_hit=cache_hit,
                      template=tpl is not None,
                      template_hit=tpl is not None and cache_hit)
    if profile is not None:
        profile["compile_s"] = compile_s
        profile["run_s"] = run_s
        profile["node_rows"] = {
            pos: (int(c), dist)
            for (pos, dist), c in zip(meta["count_nodes"], counts_np)}

    cols: dict[str, Column] = {}
    i = 0
    for sym, dtype, dictionary, has_valid in meta["out"]:
        data = res_np[i]
        valid = res_np[i + 1]
        i += 2
        cols[sym] = Column(dtype, data,
                           valid if has_valid or not valid.all() else None,
                           dictionary)
    from presto_tpu.exec.executor import _rename_outputs
    return Table(_rename_outputs(plan, cols), len(live_np), live_np)
