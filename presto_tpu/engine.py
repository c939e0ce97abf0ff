"""Engine: the in-process query runner.

Analog of the reference's LocalQueryRunner
(core/trino-main/src/main/java/io/trino/testing/LocalQueryRunner.java:227):
parse -> analyze -> logical plan -> optimize -> compile jitted kernels ->
execute, all in one process. Statement dispatch mirrors the reference's
split between data queries (SqlQueryExecution) and DDL/session statements
(execution/*Task.java executors, sql/rewrite/ShowQueriesRewrite.java).
The distributed path executes plans under shard_map over a jax Mesh
instead of HTTP remote tasks.
"""

from __future__ import annotations

import numpy as np

from presto_tpu import types as T
from presto_tpu.block import Table, _decode_column
from presto_tpu.connectors.base import Connector
from presto_tpu.obs.trace import TRACER
from presto_tpu.session import SYSTEM_SESSION_PROPERTIES, Session


class Engine:
    def __init__(self, session: Session | None = None):
        from presto_tpu.obs.trace import TRACER
        # a process's first engine imports half the package as it goes
        # (0.11 s on the chip's host): ``engine-init`` in the process
        # trace, or under the statement that makes an engine
        with TRACER.process_span("engine-init"):
            from presto_tpu.connectors.information_schema import (
                InformationSchemaConnector, SystemConnector)
            from presto_tpu.events import EventListenerManager

            self.session = session or Session()
            self.catalogs: dict[str, Connector] = {}
            # compiled-program cache: size-bounded LRU fronting an optional
            # persistent AOT disk store (exec/progcache.py; reference
            # analog: gen/PageFunctionCompiler.java:101 compiled-artifact
            # caches). Per-plan successful capacity vectors ride alongside.
            from presto_tpu.exec.progcache import ProgramCache
            self._program_cache = ProgramCache(
                max_entries=int(self.session.get("program_cache_entries")
                                or 64))
            self._caps_memory: dict = {}
            # plan templates: per-(template, segment) carrier-width memory
            # (grow-only; exec/executor._segment_carriers) so literal
            # variants keep stable downstream segment shapes
            self._carrier_caps: dict = {}
            # host->device transfer cache: id(np array) -> (host ref, dev
            # array). The strong host ref pins the id; repeat executions of
            # a query (and bench steady state) reuse HBM-resident inputs
            # instead of re-uploading every run (the reference keeps pages
            # pooled in worker memory the same way)
            self._dev_cache: dict = {}
            self._dev_cache_bytes = 0
            self.dev_cache_limit = 8 << 30  # HBM budget for pinned inputs
            # parallel segment compilation uploads scan arrays from pool
            # threads concurrently; the pin cache + byte ledger + eviction
            # loop must not interleave (two threads popping the same
            # oldest key is a KeyError)
            import threading as _t
            self._dev_cache_lock = _t.Lock()
            # a deployment on several chips (session ``mesh_devices``):
            # its one Mesh, made at first use and kept, and the scanned
            # columns placed on it once, row-sharded (parallel/pins.py);
            # dropped with the one-chip pins when a statement changes
            # table data
            self._mesh = None
            from presto_tpu.parallel.pins import ShardPins
            self.shard_pins = ShardPins()
            # runtime memory ledger: per-program tagged reservations of
            # actual input+output array bytes (memory/MemoryPool.java:44);
            # capacity 0 = unbounded (set memory_pool.capacity to enforce)
            from presto_tpu.memory import MemoryPool
            self.memory_pool = MemoryPool()
            # table-level authorization consulted by the planner at scans
            # and by DML (security/AccessControlManager.java analog)
            from presto_tpu.security import AllowAllAccessControl
            self.access_control = AllowAllAccessControl()
            # session-scoped transactions (transaction.py; reference
            # transaction/InMemoryTransactionManager)
            from presto_tpu.transaction import TransactionManager
            self.transactions = TransactionManager()
            # populated by the spill driver when a query exceeds the memory
            # budget and runs host-partitioned (exec/spill.py)
            self.last_spill: dict | None = None
            # per-THREAD warning handoff: concurrent queries on one engine
            # (the server's worker pool) must not read each other's
            # diagnostics
            import threading as _threading
            self._warn_tl = _threading.local()
            # per-THREAD one-shot plan handoff (offer_preplanned /
            # take_preplanned): the HTTP admission layer plans a query to
            # size its memory reservation; the execution path on the same
            # thread reuses that plan instead of planning twice
            self._preplanned_tl = _threading.local()
            # data-change listeners: the serving layer's result cache
            # registers here so DML actively purges entries built on the
            # pre-write table versions (connector SPI table_version keys
            # make stale hits impossible even without the purge; the
            # listener keeps the cache small and the invalidation counter
            # honest)
            self._invalidation_listeners: list = []
            # query lifecycle events + history (events.py)
            self.events = EventListenerManager()
            # persisted query history + divergence-ledger persistence
            # (obs/qstats.py): finished-query profiles append to a bounded
            # JSONL under PRESTO_TPU_HISTORY_DIR and survive restarts,
            # backing system.query_history
            import os as _os
            self.history = None
            hist_dir = _os.environ.get("PRESTO_TPU_HISTORY_DIR")
            if hist_dir:
                from presto_tpu.obs.qstats import DIVERGENCE, QueryHistory
                try:
                    self.history = QueryHistory(hist_dir)
                    self.events.add_listener(self.history.on_event)
                    DIVERGENCE.attach_dir(hist_dir)
                except OSError:
                    self.history = None  # unwritable dir: run without
            # engine-owned virtual catalogs (reference information_schema +
            # system connectors are engine-side, not plugins)
            self.catalogs["information_schema"] = \
                InformationSchemaConnector(self)
            self.catalogs["system"] = SystemConnector(self)

    def register_catalog(self, name: str, connector: Connector) -> None:
        self.catalogs[name] = connector

    def add_invalidation_listener(self, fn) -> None:
        """``fn()`` runs after every statement that may change table
        data (the same set that invalidates the device cache)."""
        self._invalidation_listeners.append(fn)

    @property
    def last_warnings(self) -> list:
        """Warnings of the CALLING THREAD's most recent query."""
        return getattr(self._warn_tl, "value", [])

    def device_array(self, a):
        """Device copy of a host scan array, cached so repeat
        executions reuse HBM-resident inputs instead of re-uploading
        (the reference keeps pages pooled in worker memory). The
        strong host ref pins the id key; FIFO eviction bounds HBM.
        Thread-safe: parallel segment compilation uploads from pool
        threads concurrently. The transfer itself runs OUTSIDE the
        lock so one wave's uploads overlap (a lost race uploads a
        duplicate once and keeps the first copy — benign)."""
        import jax
        if not isinstance(a, np.ndarray):
            return a  # already a device array (segment carriers)
        with self._dev_cache_lock:
            hit = self._dev_cache.get(id(a))
            if hit is not None and hit[0] is a:
                return hit[1]
        # a miss only: the upload of a table version not seen before
        with TRACER.span("pin", bytes=a.nbytes):
            dev = jax.device_put(a)
        with self._dev_cache_lock:
            hit = self._dev_cache.get(id(a))
            if hit is not None and hit[0] is a:
                return hit[1]  # raced: keep the published copy
            self._dev_cache[id(a)] = (a, dev)
            self._dev_cache_bytes += a.nbytes
            while (self._dev_cache_bytes > self.dev_cache_limit
                   and len(self._dev_cache) > 1):
                k = next(iter(self._dev_cache))
                old, _old_dev = self._dev_cache.pop(k)
                self._dev_cache_bytes -= old.nbytes
            return dev

    def session_shards(self) -> int:
        """Devices a statement under the calling thread's session runs
        on (``mesh_devices``; 1 = this process's one chip, no mesh)."""
        return max(1, int(self.session.get("mesh_devices") or 1))

    def session_mesh(self):
        """The mesh of a statement that was handed none: None for the
        default ``mesh_devices`` = 1, else the engine's one Mesh over
        the first N local devices."""
        n = self.session_shards()
        if n == 1:
            return None
        with self._dev_cache_lock:
            if self._mesh is None:
                import jax
                from jax.sharding import Mesh
                from presto_tpu.parallel.executor import AXIS
                devices = jax.local_devices()
                if len(devices) < n:
                    raise ValueError(
                        f"mesh_devices={n}, but this process has "
                        f"{len(devices)} device(s)")
                self._mesh = Mesh(np.array(devices[:n]), (AXIS,))
            elif self._mesh.devices.size != n:
                raise ValueError(
                    f"mesh_devices={n}, but this deployment's mesh "
                    f"has {self._mesh.devices.size} devices: it is the "
                    "layout of the deployment, one for every statement")
            return self._mesh

    # -- SQL entry points ---------------------------------------------------

    def execute(self, sql: str, mesh=None, cancel_token=None
                ) -> list[tuple]:
        """Run SQL, return result rows as Python tuples. With ``mesh``
        (a jax.sharding.Mesh) query plans execute data-parallel over
        every device — scans row-sharded, exchanges as ICI collectives.
        ``cancel_token`` (exec/cancel.CancelToken) interrupts execution
        at host-side checkpoints."""
        from presto_tpu.sql import ast as A
        from presto_tpu.sql.parser import parse_statement

        from presto_tpu.events import monitored

        from presto_tpu.sql.rewrite import rewrite_statement

        from presto_tpu import warnings as W

        W.push(WC := W.WarningCollector())
        try:
            if mesh is None:
                mesh = self.session_mesh()
            stmt = rewrite_statement(parse_statement(sql), self)
            if isinstance(stmt, A.ExecutePrepared):
                # EXECUTE name USING ...: splice the literals into the
                # stored text and run the result through the normal
                # pipeline — the plan-template machinery keys every
                # variant onto one compiled program (templates/)
                sql = self._resolve_prepared(stmt)
                stmt = rewrite_statement(parse_statement(sql), self)
            with self._cancel_scope(cancel_token):
                if isinstance(stmt, A.QueryStatement):
                    return monitored(
                        self, sql,
                        lambda: self._execute_query(stmt.query,
                                                    mesh).to_pylist())
                return monitored(
                    self, sql,
                    lambda: self._execute_statement(stmt, mesh))
        finally:
            self._warn_tl.value = WC.list()
            W.pop()

    def execute_table(self, sql: str, mesh=None, cancel_token=None
                      ) -> Table:
        from presto_tpu.events import monitored
        from presto_tpu.sql import ast as A
        from presto_tpu.sql.parser import parse_statement

        from presto_tpu.sql.rewrite import rewrite_statement

        from presto_tpu import warnings as W

        W.push(WC := W.WarningCollector())
        try:
            if mesh is None:
                mesh = self.session_mesh()
            # the served path has parsed this text before (the server,
            # then plan_sql): the span shows what parsing it again costs
            with TRACER.span("parse"):
                stmt = rewrite_statement(parse_statement(sql), self)
                if isinstance(stmt, A.ExecutePrepared):
                    sql = self._resolve_prepared(stmt)
                    stmt = rewrite_statement(parse_statement(sql), self)
            if not isinstance(stmt, A.QueryStatement):
                raise ValueError("execute_table expects a SELECT query")
            preplanned = self.take_preplanned(sql, _mesh_shards(mesh))
            with self._cancel_scope(cancel_token):
                return monitored(
                    self, sql,
                    lambda: self._execute_query(stmt.query, mesh,
                                                preplanned=preplanned))
        finally:
            self._warn_tl.value = WC.list()
            W.pop()

    def _cancel_scope(self, token):
        """Install the cancellation token (plus the session's
        query_max_run_time deadline) for the duration of one query."""
        import contextlib
        import time as _time

        from presto_tpu.exec import cancel as C

        limit = float(self.session.get("query_max_run_time") or 0)
        if token is None and limit > 0:
            token = C.CancelToken()
        if token is not None and limit > 0 and token.deadline is None:
            token.deadline = _time.monotonic() + limit

        @contextlib.contextmanager
        def scope():
            C.install(token)
            try:
                yield
            finally:
                C.install(None)

        return scope()

    def plan_sql(self, sql: str, enable_latemat: bool | None = None,
                 nshards: int = 1):
        """Parse, analyze, plan and optimize ``sql`` for execution on
        ``nshards`` devices (1 = this process's one chip, no mesh)."""
        from presto_tpu.sql.parser import parse_statement
        from presto_tpu.sql.analyzer import Analyzer
        from presto_tpu.plan.planner import LogicalPlanner
        from presto_tpu.plan.optimizer import optimize

        import time as _time

        t0 = _time.monotonic()
        with TRACER.span("plan") as span:
            stmt = parse_statement(sql)
            analysis = Analyzer(self).analyze(stmt)
            self._planning_checkpoint(t0)
            plan = LogicalPlanner(self, analysis).plan(stmt)
            self._planning_checkpoint(t0)
            plan = optimize(plan, self, nshards,
                            enable_latemat=enable_latemat, span=span)
            self._planning_checkpoint(t0)
        return plan, analysis

    def offer_preplanned(self, sql: str, plan, nshards: int = 1) -> None:
        """Hand a just-built plan for ``sql`` to THIS THREAD's next
        execution of the same statement (the admission layer plans to
        size its reservation; replanning identical SQL under the same
        session on the same thread would double the planning cost).
        ``nshards`` is the shard count the plan was priced for. One-
        shot: consumed by the next take_preplanned, and cleared by
        clear_preplanned when the offering scope exits."""
        self._preplanned_tl.value = (sql, plan, nshards)

    def take_preplanned(self, sql: str, nshards: int = 1):
        """Consume the thread's offered plan if it matches ``sql`` and
        was priced for the ``nshards`` devices it is about to run on
        (a plan priced for one chip never reaches a mesh)."""
        offered = getattr(self._preplanned_tl, "value", None)
        self._preplanned_tl.value = None
        if offered is not None and offered[0] == sql \
                and offered[2] == nshards:
            return offered[1]
        return None

    def clear_preplanned(self) -> None:
        self._preplanned_tl.value = None

    def _resolve_prepared(self, stmt) -> str:
        """Executable SQL of an EXECUTE against this session's
        prepared-statement registry."""
        from presto_tpu.templates.prepared import resolve_execute
        return resolve_execute(self.session.prepared_statements, stmt)

    def _planning_checkpoint(self, t0: float) -> None:
        """Planning-phase seam: observe cancellation (a reaped or
        killed query stops planning) and enforce the session's
        ``query_max_planning_time`` (reference QueryTracker
        enforceTimeLimits on queries stuck in planning)."""
        import time as _time

        from presto_tpu.exec import cancel as C

        C.checkpoint()
        limit = float(self.session.get("query_max_planning_time") or 0)
        if limit and _time.monotonic() - t0 > limit:
            raise C.TimeLimitExceeded(
                f"query exceeded query_max_planning_time "
                f"({limit:g}s)")

    def explain(self, sql: str) -> str:
        from presto_tpu.cost import explain_estimates
        from presto_tpu.plan.printer import format_plan
        plan, _ = self.plan_sql(sql)
        return format_plan(plan,
                           estimates=explain_estimates(plan, self, 1))

    # -- internals ----------------------------------------------------------

    def _plan_query(self, query, preplanned=None, nshards: int = 1):
        from presto_tpu.plan.optimizer import optimize
        from presto_tpu.plan.planner import LogicalPlanner
        from presto_tpu.sql import ast as A

        from presto_tpu.plan.sanity import validate_plan

        import time as _time

        if preplanned is not None:
            # admission already planned this exact SQL on this thread
            # (plan_sql, same session scope); only the pre-execution
            # invariant validation remains
            validate_plan(preplanned)
            return preplanned
        t0 = _time.monotonic()
        with TRACER.span("plan") as span:
            planner = LogicalPlanner(self, None)
            plan = planner.plan(A.QueryStatement(query))
            self._planning_checkpoint(t0)
            plan = optimize(plan, self, nshards, span=span)
            self._planning_checkpoint(t0)
            # invariant validation before execution (reference
            # PlanSanityChecker runs after every optimizer stage)
            validate_plan(plan)
        return plan

    def _execute_query(self, query, mesh=None, preplanned=None) -> Table:
        self.last_spill = None
        plan = self._plan_query(query, preplanned=preplanned,
                                nshards=_mesh_shards(mesh))
        if mesh is not None:
            from presto_tpu.parallel.executor import (
                execute_plan_distributed)
            return execute_plan_distributed(self, plan, mesh)
        from presto_tpu.exec.executor import execute_plan
        return execute_plan(self, plan)

    def _execute_statement(self, stmt, mesh=None) -> list[tuple]:
        from presto_tpu.sql import ast as A
        try:
            return self._execute_statement_inner(stmt, mesh)
        finally:
            # DML may mutate connector arrays IN PLACE (same object
            # identity), so pinned device copies must not survive it;
            # commit/rollback restore snapshots the same way
            if isinstance(stmt, (A.CreateTableAs, A.InsertStatement,
                                 A.DeleteStatement, A.UpdateStatement,
                                 A.DropTable, A.CommitStatement,
                                 A.RollbackStatement)):
                self.invalidate_device_cache()
                for fn in list(self._invalidation_listeners):
                    fn()

    def invalidate_device_cache(self) -> None:
        with self._dev_cache_lock:
            self._dev_cache.clear()
            self._dev_cache_bytes = 0
        self.shard_pins.clear()
        # the template pad cache is id-keyed the same way and must not
        # serve pre-DML padded copies of in-place-mutated arrays
        from presto_tpu.templates.shapes import invalidate_pad_cache
        invalidate_pad_cache(self)

    def _execute_statement_inner(self, stmt, mesh=None) -> list[tuple]:
        from presto_tpu.plan.printer import format_plan
        from presto_tpu.sql import ast as A

        if isinstance(stmt, A.ExplainStatement):
            if stmt.analyze:
                from presto_tpu.exec.profile import (
                    explain_analyze, explain_analyze_distributed)
                inner = stmt.statement
                if not isinstance(inner, A.QueryStatement):
                    raise ValueError("EXPLAIN ANALYZE expects a query")
                plan = self._plan_query(inner.query,
                                        nshards=_mesh_shards(mesh))
                if mesh is not None:
                    return [(explain_analyze_distributed(
                        self, plan, mesh),)]
                return [(explain_analyze(self, plan),)]
            inner = stmt.statement
            if isinstance(inner, A.QueryStatement):
                from presto_tpu.cost import explain_estimates
                nshards = _mesh_shards(mesh)
                plan = self._plan_query(inner.query, nshards=nshards)
                return [(format_plan(
                    plan, estimates=explain_estimates(
                        plan, self, nshards)),)]
            raise ValueError("EXPLAIN of non-query statements unsupported")

        if isinstance(stmt, A.StartTransaction):
            self.transactions.begin()
            return []
        if isinstance(stmt, A.CommitStatement):
            self.transactions.commit()
            return []
        if isinstance(stmt, A.RollbackStatement):
            self.transactions.rollback()
            return []

        if isinstance(stmt, A.ShowCatalogs):
            return [(name,) for name in sorted(self.catalogs)]

        if isinstance(stmt, A.ShowSession):
            rows = []
            for name, (default, typ, desc) in sorted(
                    SYSTEM_SESSION_PROPERTIES.items()):
                rows.append((name, str(self.session.get(name)),
                             str(default), typ.__name__, desc))
            return rows

        if isinstance(stmt, A.SetSession):
            value = _literal_value(stmt.value)
            self.session.set(stmt.name, value)
            return []

        if isinstance(stmt, A.Prepare):
            self.session.prepared_statements[stmt.name] = stmt.sql
            return []

        if isinstance(stmt, A.Deallocate):
            if self.session.prepared_statements.pop(stmt.name,
                                                    None) is None:
                raise ValueError(
                    f"prepared statement not found: {stmt.name}")
            return []

        if isinstance(stmt, A.CreateTableAs):
            catalog, table = self._resolve_table(stmt.table)
            self.access_control.check_can_write(
                self.session.user, catalog, table)
            conn = self._connector(catalog)
            self.transactions.touch(conn)
            result = self._execute_query(stmt.query, mesh)
            schema, data, valid = _table_to_host(result, self)
            sink = conn.begin_write(table, schema)
            n = _stream_to_sink(sink, data, valid)
            return [(n,)]

        if isinstance(stmt, A.InsertStatement):
            catalog, table = self._resolve_table(stmt.table)
            self.access_control.check_can_write(
                self.session.user, catalog, table)
            conn = self._connector(catalog)
            self.transactions.touch(conn)
            result = self._execute_query(stmt.query, mesh)
            schema, data, valid = _table_to_host(result, self)
            target = conn.table_schema(table)
            names = stmt.columns or list(target)
            renamed = {t: d for t, d in zip(names, data.values())}
            revalid = {t: v for t, v in zip(names, valid.values())}
            sink = conn.begin_write(table, None)
            n = _stream_to_sink(sink, renamed, revalid)
            return [(n,)]

        if isinstance(stmt, A.DeleteStatement):
            # evaluate the predicate per row in table order and hand the
            # connector a delete mask (reference DeleteOperator +
            # ConnectorPageSink rowId delete, trimmed to the host-table
            # connectors this engine mutates in place)
            catalog, table = self._resolve_table(stmt.table)
            self.access_control.check_can_write(
                self.session.user, catalog, table)
            conn = self._connector(catalog)
            self.transactions.touch(conn)
            mask = self._row_mask(stmt.table, stmt.where, mesh)
            return [(conn.delete_rows(table, mask),)]

        if isinstance(stmt, A.UpdateStatement):
            import numpy as np

            catalog, table = self._resolve_table(stmt.table)
            self.access_control.check_can_write(
                self.session.user, catalog, table)
            conn = self._connector(catalog)
            self.transactions.touch(conn)
            target = conn.table_schema(table)
            # one scan computes the new values AND the WHERE mask, so
            # both come from the same row order
            items = []
            for col, expr in stmt.assignments:
                if col not in target:
                    raise ValueError(f"unknown column {col}")
                items.append(A.SelectItem(
                    A.CastExpression(expr, str(target[col])), col))
            pred = (A.BooleanLiteral(True) if stmt.where is None
                    else A.FunctionCall(
                        "coalesce", (stmt.where, A.BooleanLiteral(False))))
            items.append(A.SelectItem(pred, "__pred__"))
            q = A.Query(A.QuerySpec(tuple(items), False,
                                    A.TableRef(stmt.table)))
            result = self._execute_query(q, mesh)
            _, data, valid = _table_to_host(result, self)
            mask = np.asarray(data["__pred__"], dtype=bool)
            values = {col: data[col] for col, _ in stmt.assignments}
            valids = {col: valid[col] for col, _ in stmt.assignments}
            return [(conn.update_rows(table, values, valids, mask),)]

        if isinstance(stmt, A.DropTable):
            catalog, table = self._resolve_table(stmt.table)
            self.access_control.check_can_write(
                self.session.user, catalog, table)
            conn = self._connector(catalog)
            if table not in conn.table_names():
                if stmt.if_exists:
                    return []
                raise ValueError(f"table {table} does not exist")
            self.transactions.touch(conn)
            conn.drop_table(table)
            return []

        raise NotImplementedError(
            f"statement {type(stmt).__name__} not supported")

    def _row_mask(self, table_parts, where, mesh):
        """bool[n] in table row order: WHERE evaluates TRUE (NULL and
        FALSE rows are untouched, SQL DELETE/UPDATE semantics); None
        means every row."""
        import numpy as np

        from presto_tpu.sql import ast as A

        if where is None:
            return None
        pred = A.FunctionCall(
            "coalesce", (where, A.BooleanLiteral(False)))
        q = A.Query(A.QuerySpec(
            (A.SelectItem(pred, "__pred__"),), False,
            A.TableRef(table_parts)))
        result = self._execute_query(q, mesh)
        col = next(iter(result.columns.values()))
        data = np.asarray(col.data, dtype=bool)
        if result.mask is not None:
            # padded execution paths (distributed shards) interleave
            # dead slots; compact to the real table rows
            data = data[np.asarray(result.mask)]
        return data

    def _connector(self, catalog: str) -> Connector:
        conn = self.catalogs.get(catalog)
        if conn is None:
            raise ValueError(f"catalog '{catalog}' does not exist")
        return conn

    def _resolve_table(self, parts: tuple[str, ...]) -> tuple[str, str]:
        if len(parts) == 1:
            return self.session.catalog, parts[0]
        return parts[0], parts[-1]


def _mesh_shards(mesh) -> int:
    """Devices a statement executes on: the mesh it was handed, or the
    process's one chip without one."""
    return 1 if mesh is None else int(mesh.devices.size)


def _literal_value(e):
    from presto_tpu.sql import ast as A

    if isinstance(e, A.StringLiteral):
        return e.value
    if isinstance(e, A.NumericLiteral):
        return float(e.text) if "." in e.text else int(e.text)
    if isinstance(e, A.BooleanLiteral):
        return e.value
    if isinstance(e, A.Identifier):
        return e.name
    raise ValueError("SET SESSION value must be a literal")


# one writer task per this many result cells (rows x columns); the task
# count grows with produced data up to the pool bound — the scaled-
# writers policy (reference ScaledWriterScheduler.java +
# SCALED_WRITER_DISTRIBUTION), applied to this engine's write-side
# bottleneck: device->host materialization and decode of result columns
WRITER_SCALING_CELLS = 1 << 20
WRITER_MAX_TASKS = 8


def _table_to_host(table: Table, engine=None):
    """Result Table -> (schema, host column arrays, validity masks) for
    connector writes. VARCHAR decodes to strings; other types keep their
    physical values (decimals stay scaled, matching column_from_numpy's
    contract). Large results convert with a scaled pool of writer
    tasks (one per column batch)."""
    schema: dict[str, T.DataType] = {}
    data: dict[str, np.ndarray] = {}
    valid: dict[str, np.ndarray | None] = {}
    mask = (np.ones(table.nrows, dtype=bool) if table.mask is None
            else np.asarray(table.mask))

    def convert(item):
        name, col = item
        raw = np.asarray(col.data)[mask]
        if isinstance(col.dtype, T.VarcharType):
            out = _decode_column(col.dtype, raw, col.dictionary)
        else:
            out = raw
        v = None if col.valid is None else np.asarray(col.valid)[mask]
        return name, col.dtype, out, v

    cells = table.nrows * max(len(table.columns), 1)
    writers = min(WRITER_MAX_TASKS,
                  max(1, cells // WRITER_SCALING_CELLS))
    items = list(table.columns.items())
    if writers > 1 and len(items) > 1:
        from concurrent.futures import ThreadPoolExecutor
        with ThreadPoolExecutor(max_workers=writers) as pool:
            # context-free by design: convert() is pure host-side
            # numpy decode — no spans, checkpoints, stats, or session
            # reads happen on the writer threads
            results = list(pool.map(convert, items))  # lint: disable=handoff
    else:
        writers = 1
        results = [convert(i) for i in items]
    if engine is not None:
        engine.last_write = {"writer_tasks": writers,
                             "rows": int(mask.sum())}
    for name, dtype, out, v in results:
        schema[name] = dtype
        data[name] = out
        valid[name] = v
    return schema, data, valid


# rows per page through a connector write sink (the scaled-writer
# analog of the reference's page-at-a-time ConnectorPageSink feed)
WRITE_PAGE_ROWS = 1 << 20


def _stream_to_sink(sink, data: dict, valid: dict) -> int:
    """Feed query output to a PageSink page-by-page, committing on
    finish (reference TableWriterOperator + ConnectorPageSink.java:22
    appendPage/finish). Aborts the sink on failure so connectors never
    see partial commits. The default buffering sink would only
    re-concatenate the pages, so it receives the whole arrays in one
    page (no redundant copy); native sinks get real pages."""
    from presto_tpu.connectors.base import _BufferingPageSink

    total = len(next(iter(data.values()), []))
    if isinstance(sink, _BufferingPageSink):
        try:
            sink.append_page(data, valid)
            return sink.finish()
        except Exception:
            sink.abort()
            raise
    try:
        start = 0
        while start < total or (start == 0 and total == 0):
            stop = min(start + WRITE_PAGE_ROWS, total)
            page = {c: a[start:stop] for c, a in data.items()}
            pvalid = {c: (None if v is None else v[start:stop])
                      for c, v in valid.items()}
            sink.append_page(page, pvalid)
            if total == 0:
                break
            start = stop
        return sink.finish()
    except Exception:
        sink.abort()
        raise
