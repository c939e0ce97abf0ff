"""Tenant-scale serving: result cache, subplan dedup, cross-query batching.

Serve-mode traffic is repetitive — dashboards re-issue identical
SELECTs, template variants differ only in literals. Three rungs turn
that repetition into throughput, each consulted by the coordinator's
local SELECT path BEFORE execution:

1. **Result cache** — a fingerprint-keyed LRU over complete result
   tables. The key is (optimized-plan fingerprint, per-table data
   versions, trace-relevant session key): an identical re-issued SELECT
   against unchanged tables streams the cached pages through the
   ordinary ResultQueue without touching the device. Versions come
   from the connector SPI (``Connector.table_version``): a connector
   that cannot version its tables answers None and the query is simply
   uncacheable — stale hits are structurally impossible, not merely
   unlikely. Writes actively purge: the engine's invalidation listener
   (the same hook that drops the device-array cache) re-checks every
   entry's stored versions after DML. The analog of the reference's
   materialized-view staleness contract, applied to a protocol cache.

2. **Subplan dedup** — concurrent queries whose optimized plans share
   a fingerprint (the root subtree; the dominant duplicate in serve
   traffic) await ONE in-flight execution instead of racing duplicate
   device dispatches. Keyed like the cache — versioned tables only,
   so a write landing between the leader's execution and a follower's
   read cannot hand the follower a result from the wrong version.

3. **Cross-query batching** — queries landing on the SAME template
   fingerprint within ``batch_window_ms`` stack their parameter
   vectors into one vmapped device dispatch (exec/batch.py); per-query
   slices demux into each client's ResultQueue. The first arrival
   leads: it waits out the window, seals the group, executes the
   batch, and distributes lanes. A solo group (or any batch failure)
   falls back to the serial path — batching degrades to ordinary
   execution, never to a wrong answer.

All three honor per-query session toggles (``result_cache``,
``subplan_dedup``, ``batch_window_ms``) resolved under the requesting
user's session overrides. Non-deterministic time functions are safe to
cache: the planner folds now()/current_timestamp to literals, so they
are part of the fingerprint.
"""

from __future__ import annotations

import threading
import time

import numpy as np

from presto_tpu.obs.metrics import REGISTRY
from presto_tpu.obs.trace import TRACER
from presto_tpu.plan import nodes as N

_CACHE_HITS = REGISTRY.counter(
    "presto_tpu_result_cache_hits_total",
    "SELECTs answered from the fingerprint-keyed result cache")
_CACHE_MISSES = REGISTRY.counter(
    "presto_tpu_result_cache_misses_total",
    "cache-eligible SELECTs that had to execute")
_CACHE_INVALIDATIONS = REGISTRY.counter(
    "presto_tpu_result_cache_invalidations_total",
    "result-cache entries purged because a write changed a table "
    "version they depend on")
_DEDUPED = REGISTRY.counter(
    "presto_tpu_deduped_queries_total",
    "queries that awaited an in-flight duplicate instead of executing")
# the fast path runs on the HTTP handler thread before any trace
# exists, so it is timed by histograms (no labels) and not by spans
_FAST_HIT_SECONDS = REGISTRY.histogram(
    "presto_tpu_fast_hit_seconds",
    "handler-thread time of a try_fast_hit call that ended in a "
    "result-cache hit")
_FAST_PLAN_SECONDS = REGISTRY.histogram(
    "presto_tpu_fast_path_plan_seconds",
    "handler-thread time try_fast_hit spent parsing and planning a "
    "text it found no memo for (every text, after each write), "
    "whatever the call's outcome")


def _table_nbytes(table) -> int:
    """Approximate host bytes held by a cached result table (object
    columns — varchar dictionaries, array lists — are charged a flat
    per-cell estimate; the bound needs to be honest, not exact)."""
    total = 0
    for col in table.columns.values():
        for arr in (col.data, col.valid):
            if isinstance(arr, np.ndarray):
                if arr.dtype == object:
                    total += 64 * arr.size
                else:
                    total += arr.nbytes
            elif isinstance(arr, list):
                total += 64 * len(arr)
    return total


class _CacheEntry:
    __slots__ = ("key", "table", "columns", "versions", "nbytes",
                 "hits", "created", "json_rows")

    def __init__(self, key, table, columns, versions, nbytes):
        self.key = key
        self.table = table
        self.columns = columns
        self.versions = versions  # ((catalog, table, version), ...)
        self.nbytes = nbytes
        self.hits = 0
        self.created = time.time()
        # lazily memoized full JSON row encoding (fast-hit path):
        # computed once on the first protocol-layer hit, then every
        # later hit ships the SAME list without re-decoding columns
        self.json_rows = None


class ResultCache:
    """Size-bounded (entries AND bytes) LRU of complete result tables.
    Thread-safe; eviction is LRU on lookup order. Entries carry the
    table versions they were computed against so the post-DML
    invalidation sweep can prove staleness per entry instead of
    flushing wholesale."""

    def __init__(self, max_entries: int = 256,
                 max_bytes: int = 256 << 20):
        self.max_entries = max_entries
        self.max_bytes = max_bytes
        self._lock = threading.Lock()
        self._entries: dict = {}  # key -> _CacheEntry, insertion=LRU
        self._bytes = 0

    def lookup(self, key):
        with self._lock:
            entry = self._entries.pop(key, None)
            if entry is None:
                _CACHE_MISSES.inc()
                return None
            self._entries[key] = entry  # re-insert: most recent
            entry.hits += 1
            _CACHE_HITS.inc()
            return entry

    def insert(self, key, table, columns, versions) -> None:
        nbytes = _table_nbytes(table)
        if nbytes > self.max_bytes:
            return  # one oversized result must not flush everything
        entry = _CacheEntry(key, table, columns, versions, nbytes)
        with self._lock:
            old = self._entries.pop(key, None)
            if old is not None:
                self._bytes -= old.nbytes
            self._entries[key] = entry
            self._bytes += nbytes
            while self._entries and (
                    len(self._entries) > self.max_entries
                    or self._bytes > self.max_bytes):
                _, evicted = next(iter(self._entries.items()))
                del self._entries[evicted.key]
                self._bytes -= evicted.nbytes

    def invalidate_stale(self, engine) -> int:
        """Purge every entry whose recorded table versions no longer
        match the connectors' current ones. Runs on the engine's
        invalidation hook after each data-changing statement."""
        with self._lock:
            entries = list(self._entries.values())
        stale = []
        for entry in entries:
            for catalog, tname, version in entry.versions:
                conn = engine.catalogs.get(catalog)
                current = (conn.table_version(tname)
                           if conn is not None else None)
                if current != version:
                    stale.append(entry.key)
                    break
        purged = 0
        with self._lock:
            for key in stale:
                old = self._entries.pop(key, None)
                if old is not None:
                    self._bytes -= old.nbytes
                    purged += 1
        if purged:
            _CACHE_INVALIDATIONS.inc(purged)
        return purged

    def snapshot(self) -> list[tuple]:
        """(fingerprint, tables, rows, bytes, hits, age_ms) rows for
        ``system.result_cache``, most recently used last."""
        now = time.time()
        with self._lock:
            entries = list(self._entries.values())
        return [
            (str(entry.key[0])[:16],
             ",".join(f"{c}.{t}@{v}" for c, t, v in entry.versions),
             int(entry.table.nrows if entry.table.mask is None
                 else int(np.asarray(entry.table.mask).sum())),
             int(entry.nbytes), int(entry.hits),
             int((now - entry.created) * 1000))
            for entry in entries]


class _Inflight:
    __slots__ = ("event", "table", "error")

    def __init__(self):
        self.event = threading.Event()
        self.table = None
        self.error = None


class _BatchMember:
    __slots__ = ("tpl", "event", "table", "batch_size")

    def __init__(self, tpl):
        self.tpl = tpl
        self.event = threading.Event()
        self.table = None  # None after the event: fall back to serial
        self.batch_size = 0


class _BatchGroup:
    __slots__ = ("members", "sealed")

    def __init__(self):
        self.members: list[_BatchMember] = []
        self.sealed = False


# follower wait ceiling beyond the leader's own window: the leader
# ALWAYS sets the event (try/finally), so this only bounds damage from
# a leader thread killed un-Pythonically
_FOLLOWER_WAIT_S = 600.0

# sql-text -> (plan fingerprint, scanned tables) memo entries kept for
# the protocol fast path; cleared wholesale on overflow and on every
# write (plans depend on stats and schema)
_MEMO_MAX = 512
_MEMO_NEG = object()  # parsed, but not a plain SELECT: never fast-path


class ServingLayer:
    """The coordinator's pre-execution dispatcher for local SELECTs:
    result cache, then batch window, then dedup, then serial. One per
    QueryManager; registers itself as ``engine._serving_view`` so
    ``system.result_cache`` can reflect it."""

    def __init__(self, engine):
        self.engine = engine
        self.cache = ResultCache()
        self._lock = threading.Lock()
        self._inflight: dict = {}  # cache key -> _Inflight
        self._groups: dict = {}  # (tpl fp, session key) -> _BatchGroup
        self._memo: dict = {}  # fast-path sql memo, _MEMO_MAX bounded
        engine.add_invalidation_listener(self._on_write)
        engine._serving_view = self

    def _on_write(self) -> None:
        with self._lock:
            # writes move stats and may move schema: memoized plans
            # (and their fingerprints) are no longer trustworthy
            self._memo.clear()
        self.cache.invalidate_stale(self.engine)

    # -- key derivation ----------------------------------------------------

    def _scan_versions(self, plan) -> list[tuple] | None:
        """(catalog, table, version) per scan, or None when ANY scan's
        connector declines to version it (=> uncacheable, undedupable)."""
        out: list[tuple] = []

        def walk(node) -> bool:
            if isinstance(node, N.TableScan):
                conn = self.engine.catalogs.get(node.catalog)
                version = (conn.table_version(node.table)
                           if conn is not None else None)
                if version is None:
                    return False
                out.append((node.catalog, node.table, version))
            return all(walk(s) for s in node.sources())

        if not walk(plan):
            return None
        return out

    def _cache_key(self, plan):
        from presto_tpu.exec.progcache import trace_session_key
        from presto_tpu.plan.fingerprint import plan_fingerprint
        versions = self._scan_versions(plan)
        if versions is None:
            return None
        return (plan_fingerprint(plan), tuple(sorted(set(versions))),
                trace_session_key(self.engine.session),
                self.engine.session_shards())

    # -- rung 1 fast path: answer hits on the HTTP handler thread ----------

    def try_fast_hit(self, q) -> bool:
        """Protocol-layer cache hit: answer a repeated JSON-mode SELECT
        synchronously on the submitting handler thread — no pool
        dispatch, no recorder/tracer scopes, rows pre-encoded on the
        entry. Parse+plan amortize through a sql-text memo mapping to
        (fingerprint, scanned tables); versions are recomputed FRESH
        per hit, so the memo can never produce a stale answer — at
        worst a changed table version misses and the full path runs.
        Returns True with ``q.columns``/``q.rows``/``q.cache_hit`` set,
        or False to take the ordinary submit path."""
        engine = self.engine
        overrides = dict(q.session_properties)
        with engine.session.as_user(q.user, overrides):
            if not bool(engine.session.get("result_cache")):
                return False  # nothing is observed with the cache off
            t0 = time.perf_counter()
            hit = self._fast_hit(q, overrides)
            if hit:
                _FAST_HIT_SECONDS.observe(time.perf_counter() - t0)
            return hit

    def _fast_hit(self, q, overrides: dict) -> bool:
        """``try_fast_hit`` under the user's session, cache on."""
        engine = self.engine
        sess = engine.session
        from presto_tpu.exec.progcache import trace_session_key
        mkey = (q.sql, sess.catalog,
                tuple(sorted((k, repr(v))
                             for k, v in overrides.items())))
        with self._lock:
            memo = self._memo.get(mkey)
        if memo is None:
            t0 = time.perf_counter()
            memo = self._plan_for_memo(q, mkey)
            _FAST_PLAN_SECONDS.observe(time.perf_counter() - t0)
        if memo is None or memo is _MEMO_NEG:
            return False
        fingerprint, tables = memo
        # the memo shortcut skips plan_sql, which is where the
        # planner authorizes each table scan — re-enforce it here
        # or a cached result would leak to a denied user. Denials
        # fall to the full path, which raises them classified.
        from presto_tpu.security import AccessDeniedError
        try:
            for catalog, tname in tables:
                engine.access_control.check_can_select(
                    q.user, catalog, tname)
        except AccessDeniedError:
            return False
        versions = []
        for catalog, tname in tables:
            conn = engine.catalogs.get(catalog)
            version = (conn.table_version(tname)
                       if conn is not None else None)
            if version is None:
                return False
            versions.append((catalog, tname, version))
        key = (fingerprint, tuple(sorted(set(versions))),
               trace_session_key(sess), engine.session_shards())
        entry = self.cache.lookup(key)
        if entry is None:
            return False
        rows = entry.json_rows
        if rows is None:
            from presto_tpu.server.results import (compact_table,
                                                   json_rows)
            cols, total = compact_table(entry.table)
            rows = json_rows(cols, total)
            entry.json_rows = rows  # atomic publish; idempotent
        q.columns = list(entry.columns)
        q.rows = rows
        q.cache_hit = True
        return True

    def _plan_for_memo(self, q, mkey):
        """Parse and plan a text the memo does not hold and remember
        (fingerprint, scanned tables) for it, or ``_MEMO_NEG`` for
        what is no plain SELECT; None where parse or plan fails (the
        full path reports the error)."""
        from presto_tpu.plan.fingerprint import plan_fingerprint
        from presto_tpu.sql import ast as A
        from presto_tpu.sql.parser import parse_statement
        try:
            stmt = parse_statement(q.sql)
            if not isinstance(stmt, A.QueryStatement):
                memo = _MEMO_NEG
            else:
                plan, _ = self.engine.plan_sql(
                    q.sql, nshards=self.engine.session_shards())
                memo = (plan_fingerprint(plan),
                        tuple(self._scan_tables(plan)))
        except Exception:  # noqa: BLE001 - full path reports it
            return None
        with self._lock:
            if len(self._memo) >= _MEMO_MAX:
                self._memo.clear()
            self._memo[mkey] = memo
        return memo

    def _scan_tables(self, plan) -> list[tuple]:
        """(catalog, table) per TableScan, duplicates preserved."""
        out: list[tuple] = []

        def walk(node) -> None:
            if isinstance(node, N.TableScan):
                out.append((node.catalog, node.table))
            for s in node.sources():
                walk(s)

        walk(plan)
        return out

    # -- the dispatcher ----------------------------------------------------

    def execute(self, q, sql: str):
        """Run a local SELECT through the serving rungs. Must be called
        under the query's ``session.as_user`` scope (the toggles below
        resolve per-request overrides). Returns the result Table and
        marks ``q.cache_hit`` / ``q.batched`` / ``q.deduped``."""
        engine = self.engine
        sess = engine.session
        # the devices the statement runs on: the plan priced at
        # admission is the one a mesh executes, or it is planned again
        nshards = engine.session_shards()
        with engine._cancel_scope(q.cancel_token):
            plan = engine.take_preplanned(sql, nshards)
            if plan is None:
                plan, _ = engine.plan_sql(sql, nshards=nshards)
        use_cache = bool(sess.get("result_cache"))
        use_dedup = bool(sess.get("subplan_dedup"))
        # one key serves both rungs (dedup shares the cache's
        # versioned-tables soundness requirement); either toggle
        # alone still derives it
        with TRACER.span("serving-key"):
            key = (self._cache_key(plan) if (use_cache or use_dedup)
                   else None)
        if use_cache and key is not None:
            entry = self.cache.lookup(key)
            if entry is not None:
                q.cache_hit = True
                return entry.table
        cache_key = key if use_cache else None
        window_s = float(sess.get("batch_window_ms") or 0.0) / 1000.0
        if window_s > 0 and nshards == 1:  # the vmapped batch is one chip's
            table = self._try_batch(q, plan, window_s)
            if table is not None:
                self._insert(cache_key, plan, table)
                return table
        if use_dedup and key is not None:
            table = self._dedup_execute(q, sql, plan, key)
        else:
            table = self._serial(q, sql, plan)
        self._insert(cache_key, plan, table)
        return table

    def _insert(self, key, plan, table) -> None:
        if key is None:
            return
        # re-derive versions at INSERT time: a write that landed during
        # execution bumps them, the key (computed before) won't match a
        # post-write lookup, and the entry dies at the next sweep —
        # either way a stale hit cannot happen
        versions = self._scan_versions(plan)
        if versions is None:
            return
        columns = [{"name": n, "type": str(c.dtype)}
                   for n, c in table.columns.items()]
        self.cache.insert(key, table, columns,
                          tuple(sorted(set(versions))))

    def _serial(self, q, sql: str, plan):
        # execute_table takes the session's mesh itself, and the plan
        # only if it was priced for that many devices
        self.engine.offer_preplanned(sql, plan,
                                     self.engine.session_shards())
        return self.engine.execute_table(sql,
                                         cancel_token=q.cancel_token)

    # -- rung 2: in-flight dedup -------------------------------------------

    def _dedup_execute(self, q, sql: str, plan, key):
        with self._lock:
            flight = self._inflight.get(key)
            leader = flight is None
            if leader:
                flight = self._inflight[key] = _Inflight()
        if not leader:
            with TRACER.span("dedup-wait"):
                self._await(q, flight.event)
            if flight.table is not None:
                q.deduped = True
                _DEDUPED.inc()
                return flight.table
            # the leader failed; surface our own execution's outcome
            return self._serial(q, sql, plan)
        try:
            table = self._serial(q, sql, plan)
            flight.table = table
            return table
        finally:
            flight.event.set()
            with self._lock:
                if self._inflight.get(key) is flight:
                    del self._inflight[key]

    # -- rung 3: cross-query batching --------------------------------------

    def _try_batch(self, q, plan, window_s: float):
        """Join (or open) the batch group for this plan's template;
        returns the demuxed result Table, or None to fall back to the
        serial path (not batchable, solo group, or batch failure)."""
        from presto_tpu import templates as TPL
        from presto_tpu.exec import batch as B
        from presto_tpu.exec.progcache import trace_session_key
        sess = self.engine.session
        if not TPL.enabled(sess):
            return None
        if not B.batchable(self.engine, plan):
            return None
        tpl = TPL.parameterize(plan)
        if tpl is None or not tpl.params:
            return None
        gkey = (tpl.fingerprint(), trace_session_key(sess))
        member = _BatchMember(tpl)
        with self._lock:
            group = self._groups.get(gkey)
            leader = group is None or group.sealed
            if leader:
                group = _BatchGroup()
                self._groups[gkey] = group
            group.members.append(member)
        if not leader:
            self._await(q, member.event)
            if member.table is not None:
                q.batched = member.batch_size
            return member.table
        # leader: wait out the collection window, then seal — late
        # arrivals open a fresh group instead of racing this dispatch
        time.sleep(window_s)
        with self._lock:
            group.sealed = True
            if self._groups.get(gkey) is group:
                del self._groups[gkey]
            members = list(group.members)
        tables = None
        try:
            if len(members) > 1:
                with self.engine._cancel_scope(q.cancel_token):
                    tables = B.run_plan_batched(
                        self.engine, [m.tpl for m in members])
        except Exception:  # noqa: BLE001 - members fall back to serial
            tables = None
        finally:
            for i, m in enumerate(members):
                if tables is not None:
                    m.table = tables[i]
                    m.batch_size = len(members)
                m.event.set()
        if tables is None:
            return None  # solo group or batch failure: serial path
        q.batched = len(members)
        return member.table

    def _await(self, q, event) -> None:
        """Wait for a leader's event while staying cancellable: the
        follower's own cancel token must interrupt the wait."""
        from presto_tpu.exec import cancel as C
        deadline = time.monotonic() + _FOLLOWER_WAIT_S
        with self.engine._cancel_scope(q.cancel_token):
            while not event.wait(timeout=0.05):
                C.checkpoint()
                if time.monotonic() > deadline:
                    return
