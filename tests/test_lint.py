"""Static-analysis suite tests (presto_tpu/lint/): the whole package
must lint clean (the enforcement that keeps the rules honest), and
deliberately broken fixtures demonstrate each rule family firing —
including reconstructions of real violations this suite originally
caught in the tree (serde missing MatchRecognize, the RemoteWorker
failure-ratio read, the worker engine-dict iteration race)."""

from __future__ import annotations

import json
import textwrap
from pathlib import Path

import pytest

from presto_tpu.lint import run_lint
from presto_tpu.lint.__main__ import main as lint_main

REPO = Path(__file__).resolve().parent.parent


def write_pkg(tmp_path: Path, files: dict[str, str]) -> Path:
    """Materialize sources under tmp_path with presto_tpu-relative
    names so rule scopes apply to fixtures like to the real tree."""
    for rel, src in files.items():
        p = tmp_path / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(textwrap.dedent(src))
    return tmp_path / "presto_tpu"


def rules_of(findings):
    return {f.rule for f in findings}


# -- enforcement over the real tree -----------------------------------------

def test_package_lints_clean():
    """Zero unsuppressed findings across the whole engine: every rule
    is enforced, not advisory. New violations fail tier-1 here."""
    findings = run_lint([REPO / "presto_tpu"])
    assert findings == [], "\n".join(f.format() for f in findings)


# -- tracer hygiene ---------------------------------------------------------

TRACER_FIXTURE = """
    import jax
    import jax.numpy as jnp
    import numpy as np

    def helper(x):
        return float(jnp.max(x))

    @jax.jit
    def kernel(x):
        if jnp.sum(x) > 0:
            x = np.log(jnp.abs(x))
        return helper(x)

    def host_only(x):
        # identical sins, but never traced: must NOT be flagged
        if jnp.sum(x) > 0:
            return float(jnp.max(x))
        return np.log(jnp.abs(x))
"""


def test_tracer_rules_fire_only_in_reachable_code(tmp_path):
    pkg = write_pkg(tmp_path,
                    {"presto_tpu/exec/broken.py": TRACER_FIXTURE})
    findings = run_lint([pkg])
    assert {"tracer-branch", "tracer-numpy",
            "tracer-concretize"} <= rules_of(findings)
    # reachability precision: the host_only copies stay silent
    host_start = TRACER_FIXTURE.count("\n", 0, TRACER_FIXTURE.index(
        "def host_only"))
    assert all(f.line < host_start for f in findings), \
        [f.format() for f in findings]


def test_tracer_branch_on_lax_callback(tmp_path):
    pkg = write_pkg(tmp_path, {"presto_tpu/ops/broken.py": """
        import jax
        import jax.numpy as jnp

        def body(carry, x):
            if jnp.any(x):
                carry = carry + 1
            return carry, x

        def run(xs):
            return jax.lax.scan(body, 0, xs)
    """})
    assert "tracer-branch" in rules_of(run_lint([pkg]))


def test_tracer_static_arg_rules(tmp_path):
    pkg = write_pkg(tmp_path, {"presto_tpu/ops/broken.py": """
        from functools import partial
        import jax

        @partial(jax.jit, static_argnames=("cfg", "missing"))
        def kern(x, cfg={}):
            return x
    """})
    findings = [f for f in run_lint([pkg])
                if f.rule == "tracer-static-arg"]
    msgs = " | ".join(f.message for f in findings)
    assert "unhashable mutable default" in msgs
    assert "'missing'" in msgs


def test_tracer_ignores_static_jnp_metadata(tmp_path):
    pkg = write_pkg(tmp_path, {"presto_tpu/ops/clean.py": """
        import jax
        import jax.numpy as jnp

        @jax.jit
        def kern(x):
            if jnp.issubdtype(x.dtype, jnp.floating):
                return x * jnp.finfo(x.dtype).eps
            return x
    """})
    assert run_lint([pkg]) == []


# -- lock discipline --------------------------------------------------------

LOCK_FIXTURE = """
    import threading

    class Svc:
        def __init__(self):
            self._lock = threading.Lock()
            self.state = 0
            self.unguarded = 0

        def bump(self):
            with self._lock:
                self.state += 1

        def peek(self):
            return self.state  # racy read

        def fine(self):
            with self._lock:
                return self.state

        def _helper(self):
            return self.state  # every call site holds the lock

        def locked_entry(self):
            with self._lock:
                return self._helper()

        def touch(self):
            self.unguarded += 1  # never lock-guarded anywhere: fine
"""


def test_lock_discipline_flags_bare_access_only(tmp_path):
    pkg = write_pkg(tmp_path,
                    {"presto_tpu/parallel/broken.py": LOCK_FIXTURE})
    findings = run_lint([pkg])
    assert rules_of(findings) == {"lock-discipline"}
    assert len(findings) == 1
    assert "peek" in findings[0].message
    assert "Svc.state" in findings[0].message


def test_lock_discipline_failure_ratio_regression(tmp_path):
    """The shape of the real race this suite caught in
    parallel/coordinator.py: a decayed health ratio written under the
    lock by the heartbeat thread, read bare by scheduling code."""
    pkg = write_pkg(tmp_path, {"presto_tpu/parallel/broken.py": """
        import threading

        class RemoteWorker:
            def __init__(self):
                self.lock = threading.Lock()
                self.failure_ratio = 0.0

            def record(self, failed):
                with self.lock:
                    self.failure_ratio = (0.7 * self.failure_ratio
                                          + 0.3 * float(failed))

            @property
            def alive(self):
                return self.failure_ratio < 0.5
    """})
    findings = run_lint([pkg])
    assert len(findings) == 1
    assert findings[0].rule == "lock-discipline"
    assert "failure_ratio" in findings[0].message


def test_lock_discipline_sees_outer_alias_in_nested_class(tmp_path):
    """The worker-server pattern: `outer = self`, a nested handler
    class touching outer state from request threads."""
    pkg = write_pkg(tmp_path, {"presto_tpu/server/broken.py": """
        import threading

        class Server:
            def __init__(self):
                self._lock = threading.Lock()
                self._engines = {}
                outer = self

                class Handler:
                    def do_GET(self):
                        return list(outer._engines.values())

                def factory(key):
                    with outer._lock:
                        outer._engines[key] = object()
    """})
    findings = run_lint([pkg])
    assert len(findings) == 1
    assert "_engines" in findings[0].message
    assert "do_GET" in findings[0].message


def test_lock_discipline_scope_excludes_sql(tmp_path):
    """Lock scopes cover the threaded subsystems (parallel/, server/,
    exec/, obs/, ft/, templates/, memory.py, engine.py, session.py) —
    the same class in the single-threaded SQL frontend is not
    checked."""
    pkg = write_pkg(tmp_path,
                    {"presto_tpu/sql/whatever.py": LOCK_FIXTURE})
    assert run_lint([pkg]) == []


def test_lock_discipline_no_cross_class_name_pooling(tmp_path):
    """Same-named private methods of unrelated classes must not vouch
    for each other: B's lock-free self._refresh() call must not
    disqualify A._refresh (whose own call sites all hold A's lock),
    and must not be vouched for by A's locked call either."""
    pkg = write_pkg(tmp_path, {"presto_tpu/server/broken.py": """
        import threading

        class A:
            def __init__(self):
                self._lock = threading.Lock()
                self.state = 0

            def entry(self):
                with self._lock:
                    self.state += 1
                    return self._refresh()

            def _refresh(self):
                return self.state  # all A call sites hold the lock

        class B:
            def __init__(self):
                self._lock = threading.Lock()
                self.other = 0

            def bump(self):
                with self._lock:
                    self.other += 1

            def entry(self):
                return self._refresh()  # lock-free, but B's problem

            def _refresh(self):
                return self.other  # real race: B reads unlocked
    """})
    findings = run_lint([pkg])
    assert len(findings) == 1, [f.format() for f in findings]
    assert "B.other" in findings[0].message


def test_lock_discipline_mutual_recursion_cannot_vouch(tmp_path):
    """Least-fixpoint inference: two private helpers whose only call
    sites are each other (the Thread(target=self._loop) pattern — the
    target reference is not a call) must NOT count as lock-held; their
    unguarded reads are exactly the heartbeat-thread race class."""
    pkg = write_pkg(tmp_path, {"presto_tpu/parallel/broken.py": """
        import threading

        class Beat:
            def __init__(self):
                self._lock = threading.Lock()
                self.count = 0

            def start(self):
                threading.Thread(target=self._loop).start()

            def bump(self):
                with self._lock:
                    self.count += 1

            def _loop(self):
                self._step()

            def _step(self):
                if self.count > 3:  # unguarded read on the thread
                    return
                self._loop()
    """})
    findings = run_lint([pkg])
    assert len(findings) == 1, [f.format() for f in findings]
    assert "count" in findings[0].message and "_step" in \
        findings[0].message


def test_tracer_plain_wrapping_decorator_is_not_a_root(tmp_path):
    """A module-local decorator that merely wraps (no dispatch-table
    registration) must not mark host code jit-reachable; a registry
    decorator (stores into a subscript) must."""
    pkg = write_pkg(tmp_path, {"presto_tpu/ops/broken.py": """
        import jax.numpy as jnp

        def timed(label):
            def deco(fn):
                def inner(*a):
                    return fn(*a)
                return inner
            return deco

        TABLE = {}

        def registered(name):
            def deco(fn):
                TABLE[name] = fn
                return fn
            return deco

        @timed("host")
        def host_driver(x):
            if jnp.sum(x) > 0:  # concrete host arrays: legal
                return x
            return x

        @registered("k")
        def kernel(x):
            if jnp.sum(x) > 0:  # traced via TABLE dispatch: flagged
                return x
            return x
    """})
    findings = run_lint([pkg])
    assert len(findings) == 1, [f.format() for f in findings]
    assert "kernel" in findings[0].message


# -- field-level locksets (lockset) -----------------------------------------

LOCKSET_FIXTURE = """
    import threading

    class Mixed:
        def __init__(self):
            self._a_lock = threading.Lock()
            self._b_lock = threading.Lock()
            self.state = 0        # written under BOTH locks: mixed
            self.cache = {}       # mutated under A, read under B
            self.snap = {}        # atomic whole-ref publish: blessed
            self.published = ()   # init-only publication: exempt

        def wa(self):
            with self._a_lock:
                self.state = 1

        def wb(self):
            with self._b_lock:
                self.state = 2

        def mut(self):
            with self._a_lock:
                self.cache["k"] = 1

        def read_wrong_lock(self):
            with self._b_lock:
                return self.cache.get("k")

        def publish(self):
            with self._a_lock:
                self.snap = dict(self.cache)

        def read_snapshot(self):
            with self._b_lock:
                return self.snap  # atomic-swapped reference read

        def read_published(self):
            return self.published  # init-only: immutable after publish
"""


def test_lockset_mixed_and_disjoint_locks(tmp_path):
    """The two defect classes lock-discipline cannot see: a field
    written under two different locks, and a field written under lock
    A but read under disjoint lock B — both sites 'hold a lock', yet
    they do not exclude each other."""
    pkg = write_pkg(tmp_path,
                    {"presto_tpu/parallel/broken.py": LOCKSET_FIXTURE})
    findings = run_lint([pkg], rules=["lockset"])
    assert len(findings) == 2, [f.format() for f in findings]
    msgs = " | ".join(f.message for f in findings)
    assert "Mixed.state" in msgs and "mixed locksets" in msgs
    assert "Mixed.cache" in msgs and "read_wrong_lock" in msgs
    # the blessed idioms stay silent: atomic whole-reference publish
    # read under an unrelated lock, and init-only publication
    assert "snap" not in msgs and "published" not in msgs


def test_lockset_helper_entry_lockset_inferred(tmp_path):
    """locks.py's locked-helper inference feeds the lockset rule: a
    private helper whose every call site holds lock A carries {A} as
    its entry lockset, so its accesses agree with A-guarded writes —
    but a reader under lock B is still disjoint."""
    pkg = write_pkg(tmp_path, {"presto_tpu/server/broken.py": """
        import threading

        class Svc:
            def __init__(self):
                self._a_lock = threading.Lock()
                self._b_lock = threading.Lock()
                self.items = {}

            def put(self, k, v):
                with self._a_lock:
                    self.items[k] = v
                    self._compact()

            def drop(self, k):
                with self._a_lock:
                    self.items.pop(k, None)
                    self._compact()

            def _compact(self):
                self.items.clear()  # entry lockset {_a_lock}: fine

            def peek_wrong(self):
                with self._b_lock:
                    return self.items.get(None)  # disjoint: flagged
    """})
    findings = run_lint([pkg], rules=["lockset"])
    assert len(findings) == 1, [f.format() for f in findings]
    assert "peek_wrong" in findings[0].message
    assert "_b_lock" in findings[0].message


def test_lockset_attribute_store_voids_atomic_publish(tmp_path):
    """`self.snap.field = v` mutates the published object — it must
    void the atomic-swap exemption exactly like a subscript store, or
    disjoint-lock readers of the mutated object pass silently."""
    pkg = write_pkg(tmp_path, {"presto_tpu/parallel/broken.py": """
        import threading

        class Pub:
            def __init__(self):
                self._a_lock = threading.Lock()
                self._b_lock = threading.Lock()
                self.snap = object()

            def publish(self):
                with self._a_lock:
                    self.snap = object()

            def poke(self):
                with self._a_lock:
                    self.snap.field = 5  # mutation, not a swap

            def read_other_lock(self):
                with self._b_lock:
                    return self.snap  # NOT exempt: snap is mutated
    """})
    findings = run_lint([pkg], rules=["lockset"])
    assert len(findings) == 1, [f.format() for f in findings]
    assert "read_other_lock" in findings[0].message


def test_lockset_suppressible_with_justification(tmp_path):
    pkg = write_pkg(tmp_path, {"presto_tpu/parallel/broken.py": """
        import threading

        class Grower:
            def __init__(self):
                self._a_lock = threading.Lock()
                self._b_lock = threading.Lock()
                self.hits = 0

            def wa(self):
                with self._a_lock:
                    self.hits += 1

            def wb(self):
                # benign racy counter: a lost update only skews a
                # diagnostic number
                with self._b_lock:
                    self.hits += 1  # lint: disable=lockset
    """})
    assert run_lint([pkg], rules=["lockset"]) == []


def test_lockset_scope_matches_lock_scopes(tmp_path):
    """exec/ and engine.py are in scope now (parallel segment
    compilation shares them across threads); sql/ stays out."""
    pkg = write_pkg(tmp_path,
                    {"presto_tpu/exec/broken.py": LOCKSET_FIXTURE,
                     "presto_tpu/sql/broken.py": LOCKSET_FIXTURE})
    findings = run_lint([pkg], rules=["lockset"])
    assert {f.path for f in findings} == {"presto_tpu/exec/broken.py"}


# -- ambient-context thread handoff (handoff) --------------------------------

HANDOFF_FIXTURE = """
    import threading
    from concurrent.futures import ThreadPoolExecutor

    from presto_tpu.exec import cancel as CANCEL
    from presto_tpu.obs.trace import TRACER, current_context

    def traced_work(plan):
        with TRACER.span("work"):
            return plan

    def leaky_thread(plan):
        # drops TRACER context AND the cancel token
        t = threading.Thread(target=traced_work, args=(plan,))
        t.start()
        return t

    def leaky_pool(plans):
        with ThreadPoolExecutor(max_workers=2) as pool:
            return list(pool.map(traced_work, plans))

    def careful_thread(plan):
        ctx = current_context()
        tok = CANCEL.current()

        def work():
            CANCEL.install(tok)
            with TRACER.attach(ctx):
                return traced_work(plan)

        threading.Thread(target=work).start()

    def fresh_scope_thread(tid):
        def work():
            with TRACER.trace(tid, "task"):
                return tid

        threading.Thread(target=work).start()

    def suppressed_sweeper():
        # daemon metrics scraper: deliberately context-free
        threading.Thread(target=print, daemon=True).start()  # lint: disable=handoff
"""


def test_handoff_flags_context_dropping_spawns(tmp_path):
    pkg = write_pkg(tmp_path,
                    {"presto_tpu/parallel/broken.py": HANDOFF_FIXTURE})
    findings = run_lint([pkg], rules=["handoff"])
    assert len(findings) == 2, [f.format() for f in findings]
    msgs = " | ".join(f.message for f in findings)
    assert "threading.Thread" in msgs and "pool.map" in msgs
    assert all("ambient" in f.message for f in findings)
    # explicit capture+attach, fresh-scope establishment, and the
    # justified suppression all pass
    lines = {f.line for f in findings}
    src = textwrap.dedent(HANDOFF_FIXTURE)
    for fn in ("careful_thread", "fresh_scope_thread",
               "suppressed_sweeper"):
        start = src.count("\n", 0, src.index(f"def {fn}")) + 1
        assert all(not (start <= ln <= start + 8) for ln in lines), fn


def test_handoff_ignores_ambient_free_modules(tmp_path):
    """A module that never touches ambient context cannot drop it:
    its threads are out of scope by construction."""
    pkg = write_pkg(tmp_path, {"presto_tpu/server/clean.py": """
        import threading

        def serve(httpd):
            threading.Thread(target=httpd.serve_forever,
                             daemon=True).start()
    """})
    assert run_lint([pkg], rules=["handoff"]) == []


def test_handoff_sees_module_level_executor_attr(tmp_path):
    """The QueryManager shape: the pool is constructed in __init__,
    submit happens in another method — the attribute name links them."""
    pkg = write_pkg(tmp_path, {"presto_tpu/server/broken.py": """
        from concurrent.futures import ThreadPoolExecutor
        from presto_tpu.obs.trace import TRACER

        class Manager:
            def __init__(self):
                self.pool = ThreadPoolExecutor(max_workers=4)

            def submit(self, q):
                with TRACER.span("submit"):
                    self.pool.submit(print, q)
    """})
    findings = run_lint([pkg], rules=["handoff"])
    assert len(findings) == 1, [f.format() for f in findings]
    assert "pool.submit" in findings[0].message


# -- stale suppressions ------------------------------------------------------


def test_stale_suppression_reported(tmp_path):
    """A disable comment whose finding was fixed must not outlive the
    code it excused — it would silently swallow the NEXT finding."""
    pkg = write_pkg(tmp_path, {"presto_tpu/exec/fine.py": """
        import urllib.request

        def fine(req):
            return urllib.request.urlopen(req, timeout=5)  # lint: disable=timeout-discipline
    """})
    findings = run_lint([pkg])
    assert [f.rule for f in findings] == ["stale-suppression"]
    assert "timeout-discipline" in findings[0].message


def test_stale_suppression_respects_rule_subset(tmp_path):
    """A --rules subset run cannot judge another rule's suppression:
    the timeout-discipline disable is only stale when that rule ran."""
    pkg = write_pkg(tmp_path, {"presto_tpu/exec/fine.py": """
        x = 1  # lint: disable=timeout-discipline
    """})
    assert run_lint([pkg], rules=["span-discipline"]) == []
    stale = run_lint([pkg], rules=["timeout-discipline"])
    assert [f.rule for f in stale] == ["stale-suppression"]


def test_stale_blanket_suppression_full_run_only(tmp_path):
    pkg = write_pkg(tmp_path, {"presto_tpu/exec/fine.py": """
        x = 1  # lint: disable
    """})
    assert run_lint([pkg], rules=["timeout-discipline"]) == []
    full = run_lint([pkg])
    assert [f.rule for f in full] == ["stale-suppression"]
    assert "blanket" in full[0].message


def test_used_suppression_not_stale(tmp_path):
    pkg = write_pkg(tmp_path, {"presto_tpu/exec/broken.py": """
        import urllib.request

        def bad(req):
            return urllib.request.urlopen(req)  # lint: disable=timeout-discipline
    """})
    assert run_lint([pkg]) == []


# -- timeout discipline -----------------------------------------------------


def test_timeout_discipline_flags_deadline_free_urlopen(tmp_path):
    """Every urlopen/_urlopen call site must spell timeout= — a
    deadline-free internal HTTP call hangs a thread on a dead peer."""
    pkg = write_pkg(tmp_path, {"presto_tpu/parallel/broken.py": """
        import urllib.request
        from presto_tpu.server.httpbase import urlopen as _urlopen

        def bad(req):
            with urllib.request.urlopen(req) as r:  # no deadline
                return r.read()

        def also_bad(req):
            with _urlopen(req) as r:
                return r.read()

        def fine(req):
            with _urlopen(req, timeout=10.0) as r:
                return r.read()

        def threaded_fine(req, timeout):
            return urllib.request.urlopen(req, timeout=timeout)
    """})
    findings = run_lint([pkg], rules=["timeout-discipline"])
    assert len(findings) == 2, [f.format() for f in findings]
    assert all("timeout=" in f.message for f in findings)
    assert {f.line for f in findings} == {6, 10}


def test_timeout_discipline_suppressible(tmp_path):
    pkg = write_pkg(tmp_path, {"presto_tpu/exec/broken.py": """
        import urllib.request

        def bad(req):  # lint: disable on the call line works
            return urllib.request.urlopen(req)  # lint: disable=timeout-discipline
    """})
    assert run_lint([pkg], rules=["timeout-discipline"]) == []


# -- span discipline --------------------------------------------------------


def test_span_discipline_flags_orphaned_tracer_entries(tmp_path):
    """Tracer contextmanagers opened by hand leak the open span AND
    the ambient context on any exception before close; every opening
    call must be a `with` item (or enter_context argument)."""
    pkg = write_pkg(tmp_path, {"presto_tpu/exec/broken.py": """
        from presto_tpu.obs.trace import TRACER
        from presto_tpu.obs import trace as OT

        def leaky(plan):
            cm = TRACER.span("compile")      # orphaned handle
            cm.__enter__()
            return run(plan)

        def leaky_attach(ctx):
            OT.TRACER.attach(ctx).__enter__()  # orphaned attach

        def fine(plan):
            with TRACER.span("compile"):
                return run(plan)

        def fine_multi(ctx):
            with OT.TRACER.attach(ctx), OT.TRACER.span("task"):
                return 1

        def fine_stack(stack, ctx):
            stack.enter_context(TRACER.attach(ctx))

        def unrelated(m):
            return m.span()  # regex Match.span: not a tracer
    """})
    findings = run_lint([pkg], rules=["span-discipline"])
    assert len(findings) == 2, [f.format() for f in findings]
    assert {f.line for f in findings} == {6, 11}
    assert all("with" in f.message for f in findings)


def test_span_discipline_suppressible(tmp_path):
    pkg = write_pkg(tmp_path, {"presto_tpu/exec/broken.py": """
        from presto_tpu.obs.trace import TRACER

        def manual():
            return TRACER.span("x")  # lint: disable=span-discipline
    """})
    assert run_lint([pkg], rules=["span-discipline"]) == []


# -- pool discipline --------------------------------------------------------


POOL_FIXTURE = """
    def leaky(pool, data):
        pool.reserve("q", 100)   # no free at all
        return data

    def freed_but_not_on_error(pool, data):
        pool.reserve("q", 100)
        out = transform(data)
        pool.free("q")           # straight-line: skipped on raise
        return out

    def balanced(pool, data):
        pool.reserve("q", 100)
        try:
            return transform(data)
        finally:
            pool.free("q")

    def balanced_attr(self, data):
        self.query_pool.reserve("q", 100)
        try:
            return transform(data)
        finally:
            self.query_pool.free("q")

    def nested_owner(pool, items):
        # the nested def's reserve is NOT covered by the outer
        # finally: it runs later, on another thread
        def job(item):
            pool.reserve("q", item)
            return item
        try:
            return [job(i) for i in items]
        finally:
            pool.free("q")

    def not_a_pool(connection, data):
        connection.reserve("q", 100)  # receiver is not a memory pool
        return data
"""


def test_pool_discipline_requires_free_in_finally(tmp_path):
    """Every MemoryPool.reserve call site must pair with a free on ALL
    exit paths — i.e. inside a finally of the same function; a
    straight-line free after the work is exactly the leak this rule
    exists for."""
    pkg = write_pkg(tmp_path,
                    {"presto_tpu/server/broken.py": POOL_FIXTURE})
    findings = run_lint([pkg], rules=["pool-discipline"])
    assert len(findings) == 3, [f.format() for f in findings]
    msgs = " | ".join(f.message for f in findings)
    assert "leaky" in msgs
    assert "freed_but_not_on_error" in msgs
    assert "job" in msgs  # the nested def analyzed as its own scope
    assert "balanced" not in msgs and "not_a_pool" not in msgs


def test_pool_discipline_suppressible_for_caller_owned(tmp_path):
    """Ownership transfers (caller frees) carry an explicit per-line
    suppression — the segment-carrier pattern in exec/executor.py."""
    pkg = write_pkg(tmp_path, {"presto_tpu/exec/broken.py": """
        def materialize(pool, tag, out):
            pool.reserve(tag, out.nbytes)  # lint: disable=pool-discipline
            return out
    """})
    assert run_lint([pkg], rules=["pool-discipline"]) == []


# -- dispatch exhaustiveness ------------------------------------------------

DISPATCH_NODES = """
    class PlanNode:
        pass

    class Alpha(PlanNode):
        pass

    class Beta(PlanNode):
        pass

    class Gamma(PlanNode):
        pass
"""


def test_dispatch_isinstance_site(tmp_path):
    pkg = write_pkg(tmp_path, {
        "presto_tpu/plan/nodes.py": DISPATCH_NODES,
        "presto_tpu/plan/printer.py": """
            from presto_tpu.plan import nodes as N

            DISPATCH_EXEMPT = {
                "Gamma": "printed by the fallback on purpose",
                "Alpha": "stale: actually handled below",
                "Omega": "no longer exists",
            }

            def describe(node):
                if isinstance(node, N.Alpha):
                    return "alpha"
                return type(node).__name__
        """})
    findings = run_lint([pkg], rules=["plan-dispatch"])
    msgs = [f.message for f in findings]
    assert any("Beta" in m and "not handled" in m for m in msgs)
    assert any("Alpha" in m and "stale" in m for m in msgs)
    assert any("Omega" in m and "unknown" in m for m in msgs)
    # Gamma is properly exempted: no finding mentions it as missing
    assert not any("Gamma" in m and "not handled" in m for m in msgs)


def test_dispatch_register_site_catches_missing_node(tmp_path):
    """The real violation this rule caught: plan/serde.py had never
    registered MatchRecognize, so serializing such a fragment raised
    'unregistered plan class' at runtime."""
    pkg = write_pkg(tmp_path, {
        "presto_tpu/plan/nodes.py": DISPATCH_NODES,
        "presto_tpu/plan/serde.py": """
            from presto_tpu.plan import nodes as N

            _CLASSES = {}

            def _register(*classes):
                for c in classes:
                    _CLASSES[c.__name__] = c

            _register(N.Alpha, N.Beta)
        """})
    findings = run_lint([pkg], rules=["plan-dispatch"])
    assert len(findings) == 1
    assert "Gamma" in findings[0].message


def test_dispatch_method_prefix_site(tmp_path):
    pkg = write_pkg(tmp_path, {
        "presto_tpu/plan/nodes.py": DISPATCH_NODES,
        "presto_tpu/exec/executor.py": """
            from presto_tpu.plan import nodes as N

            class Interp:
                def run(self, node):
                    return getattr(
                        self, "_r_" + type(node).__name__.lower())(node)

                def _r_alpha(self, node):
                    return 1

                def _r_beta(self, node):
                    return 2
        """})
    findings = run_lint([pkg], rules=["plan-dispatch"])
    assert len(findings) == 1
    assert "Gamma" in findings[0].message


def test_dispatch_generic_site_needs_marker(tmp_path):
    pkg = write_pkg(tmp_path, {
        "presto_tpu/plan/nodes.py": DISPATCH_NODES,
        "presto_tpu/plan/fingerprint.py": """
            import dataclasses

            def tok(x):
                for f in dataclasses.fields(x):
                    pass
        """})
    findings = run_lint([pkg], rules=["plan-dispatch"])
    assert len(findings) == 1
    assert "GENERIC_PLAN_DISPATCH" in findings[0].message


# -- suppressions and CLI ---------------------------------------------------

def test_per_line_suppression(tmp_path):
    pkg = write_pkg(tmp_path, {"presto_tpu/exec/broken.py": """
        import jax
        import jax.numpy as jnp

        @jax.jit
        def kern(x):
            if jnp.sum(x) > 0:  # lint: disable=tracer-branch
                return x
            return x
    """})
    assert run_lint([pkg]) == []


def test_suppression_is_rule_specific(tmp_path):
    """A suppression for rule A does not cover rule B's finding on
    the same line — and naming a nonexistent rule is itself reported
    (the typo'd disable suppresses nothing while looking load-bearing)."""
    pkg = write_pkg(tmp_path, {"presto_tpu/exec/broken.py": """
        import jax
        import jax.numpy as jnp

        @jax.jit
        def kern(x):
            if jnp.sum(x) > 0:  # lint: disable=some-other-rule
                return x
            return x
    """})
    findings = run_lint([pkg])
    assert rules_of(findings) == {"tracer-branch", "stale-suppression"}
    stale = [f for f in findings if f.rule == "stale-suppression"]
    assert "unknown rule 'some-other-rule'" in stale[0].message


def test_cli_exit_codes_and_json(tmp_path, capsys):
    pkg = write_pkg(tmp_path,
                    {"presto_tpu/parallel/broken.py": LOCK_FIXTURE})
    assert lint_main([str(pkg), "--json"]) == 1
    payload = json.loads(capsys.readouterr().out)
    assert payload and payload[0]["rule"] == "lock-discipline"
    assert {"path", "line", "col", "message"} <= set(payload[0])

    clean = write_pkg(tmp_path / "c",
                      {"presto_tpu/exec/nothing.py": "x = 1\n"})
    assert lint_main([str(clean)]) == 0

    assert lint_main([str(pkg), "--rules", "definitely-not-a-rule"]) == 2


def test_cli_rule_subset(tmp_path):
    pkg = write_pkg(tmp_path, {
        "presto_tpu/parallel/broken.py": LOCK_FIXTURE,
        "presto_tpu/exec/broken.py": TRACER_FIXTURE,
    })
    only_locks = run_lint([pkg], rules=["lock-discipline"])
    assert rules_of(only_locks) == {"lock-discipline"}


def _git(cwd, *args):
    import subprocess
    subprocess.run(
        ["git", "-c", "user.email=t@t", "-c", "user.name=t", *args],
        cwd=cwd, check=True, capture_output=True, text=True)


def test_changed_mode_scopes_reporting_to_changed_files(tmp_path,
                                                        capsys):
    """--changed (the pre-commit mode) reports only findings in files
    touched since HEAD — committed-clean files stay quiet even when
    they carry findings, because the full-tree gate still owns them."""
    pkg = write_pkg(tmp_path, {
        "presto_tpu/exec/committed.py": """
            import urllib.request

            def bad(req):
                return urllib.request.urlopen(req)
        """,
    })
    _git(tmp_path, "init", "-q")
    _git(tmp_path, "add", "-A")
    _git(tmp_path, "commit", "-qm", "seed")
    write_pkg(tmp_path, {"presto_tpu/exec/fresh.py": """
        import urllib.request

        def also_bad(req):
            return urllib.request.urlopen(req)
    """})
    assert lint_main([str(pkg), "--changed", "--json",
                      "--rules", "timeout-discipline"]) == 1
    payload = json.loads(capsys.readouterr().out)
    assert {f["path"] for f in payload} == \
        {"presto_tpu/exec/fresh.py"}
    # a clean worktree lints clean instantly
    _git(tmp_path, "add", "-A")
    _git(tmp_path, "commit", "-qm", "more")
    assert lint_main([str(pkg), "--changed"]) == 0
    assert "no changed" in capsys.readouterr().err
    # ...but the fast exit still validates its inputs: a typo'd rule
    # in a pre-commit hook must fail on every run, not only when the
    # worktree happens to be dirty
    assert lint_main([str(pkg), "--changed",
                      "--rules", "definitely-not-a-rule"]) == 2
    assert "unknown lint rules" in capsys.readouterr().err


def test_changed_mode_outside_git_is_usage_error(tmp_path, capsys):
    """Outside a git checkout --changed errors loudly (exit 2): a
    silent 'clean' from a misconfigured pre-commit hook would defeat
    the gate."""
    import subprocess
    probe = subprocess.run(
        ["git", "-C", str(tmp_path), "rev-parse", "--show-toplevel"],
        capture_output=True, text=True)
    if probe.returncode == 0:  # tmp dir landed inside some repo
        pytest.skip("tmp_path is inside a git repo")
    pkg = write_pkg(tmp_path,
                    {"presto_tpu/exec/nothing.py": "x = 1\n"})
    assert lint_main([str(pkg), "--changed"]) == 2
    assert "git" in capsys.readouterr().err


def test_full_suite_wall_time_budget():
    """One shared parsed-AST project model serves every rule — the
    tracekey provenance pass included, riding the tracer family's
    cached call-graph machinery and per-module unit walks: the
    whole-package run must stay inside an interactive budget (locally
    ~3-4 s with all twelve families; the bound leaves headroom for a
    loaded CI container but catches the per-rule re-walk regression
    class, which tripled it)."""
    import time
    t0 = time.perf_counter()
    findings = run_lint([REPO / "presto_tpu"])
    wall = time.perf_counter() - t0
    assert findings == []
    assert wall < 12.0, f"full lint suite took {wall:.1f}s"


def test_subtree_run_still_checks_dispatch_against_real_registry():
    """Running on a subtree (the documented CLI workflow) resolves the
    PlanNode registry from disk relative to the subtree."""
    findings = run_lint([REPO / "presto_tpu" / "plan"])
    assert findings == [], "\n".join(f.format() for f in findings)


def test_unknown_rule_raises():
    with pytest.raises(ValueError):
        run_lint([REPO / "presto_tpu" / "plan"], rules=["nope"])


def test_nonexistent_or_empty_path_is_an_error(tmp_path, capsys):
    """A typo'd path must not read as 'lint clean' (exit 0)."""
    assert lint_main(["/nonexistent/definitely-not-here"]) == 2
    assert "do not exist" in capsys.readouterr().err
    empty = tmp_path / "nopy"
    empty.mkdir()
    assert lint_main([str(empty)]) == 2
    assert "no Python files" in capsys.readouterr().err
    with pytest.raises(ValueError):
        run_lint([empty])


def test_unparseable_file_is_a_usage_error_not_a_traceback(tmp_path,
                                                          capsys):
    bad = tmp_path / "presto_tpu" / "exec"
    bad.mkdir(parents=True)
    (bad / "scratch.py").write_text("def broken(:\n")
    assert lint_main([str(tmp_path / "presto_tpu")]) == 2
    assert "cannot parse" in capsys.readouterr().err


# -- trace-key provenance (tracekey) ----------------------------------------

# the retired tests/test_progcache.py drift guard scanned exactly this
# shape: a direct `self.session.get("...")` lexically inside the
# interpreter class — kept here as the subsumption proof that the
# whole-tree rule still catches it
TRACEKEY_DIRECT_FIXTURE = """
    class PlanInterpreter:
        def run(self, node):
            return getattr(self, "_r_" + type(node).__name__)(node)

        def _r_filter(self, node):
            if self.session.get("mystery_prop"):
                return node
            return node
"""


def test_tracekey_subsumes_retired_direct_read_scan(tmp_path):
    """The old two-class AST scan (direct session.get inside the
    interpreter classes) is a strict subset of the provenance rule:
    the same shape fires as an unsound-read, and adding the key to
    TRACE_RELEVANT_PROPERTIES clears it."""
    pkg = write_pkg(tmp_path, {
        "presto_tpu/exec/broken.py": TRACEKEY_DIRECT_FIXTURE})
    findings = run_lint([pkg], rules=["tracekey"])
    assert len(findings) == 1, [f.format() for f in findings]
    assert "unsound-read" in findings[0].message
    assert "'mystery_prop'" in findings[0].message
    keyed = write_pkg(tmp_path / "ok", {
        "presto_tpu/exec/broken.py": TRACEKEY_DIRECT_FIXTURE,
        "presto_tpu/exec/progcache.py": """
            TRACE_RELEVANT_PROPERTIES = ("mystery_prop",)
        """})
    assert run_lint([keyed], rules=["tracekey"]) == []


def test_tracekey_follows_aliases_and_helper_calls(tmp_path):
    """The interprocedural half the retired scan could not see:
    a local session alias and a helper taking the session under
    ANOTHER parameter name both carry the taint to the read."""
    pkg = write_pkg(tmp_path, {"presto_tpu/exec/broken.py": """
        class PlanInterpreter:
            def run(self, node):
                return getattr(self, "_r_" + type(node).__name__)(node)

            def _r_project(self, node):
                s = self.session
                return s.get("aliased_prop")

            def _r_join(self, node):
                return _threshold(self.session, node)

        def _threshold(sess, node):
            return sess.get("helper_prop")

        def host_driver(engine):
            # identical read, NOT trace-reachable: must stay silent
            return engine.session.get("host_only_prop")
    """})
    findings = run_lint([pkg], rules=["tracekey"])
    keys = {f.message.split("'")[1] for f in findings}
    assert keys == {"aliased_prop", "helper_prop"}, \
        [f.format() for f in findings]


def test_tracekey_env_read_and_unkeyed_global(tmp_path):
    pkg = write_pkg(tmp_path, {"presto_tpu/exec/broken.py": """
        import os

        _LIMITS = {}

        def set_limit(k, v):
            _LIMITS[k] = v  # runtime mutation, no key participation

        class PlanInterpreter:
            def run(self, node):
                return getattr(self, "_r_" + type(node).__name__)(node)

            def _r_scan(self, node):
                return os.environ.get("PRESTO_TPU_SECRET_MODE")

            def _r_aggregate(self, node):
                return _LIMITS.get("cap")
    """})
    findings = run_lint([pkg], rules=["tracekey"])
    msgs = " | ".join(f.message for f in findings)
    assert len(findings) == 2, [f.format() for f in findings]
    assert "'PRESTO_TPU_SECRET_MODE'" in msgs and \
        "platform fingerprint" in msgs
    assert "unkeyed-global" in msgs and "'_LIMITS'" in msgs \
        and "set_limit" in msgs


def test_tracekey_cross_module_mutation(tmp_path):
    """Mutation sites are scanned over the WHOLE analyzed project: a
    module OUTSIDE the trace scopes writing through an import alias
    (`tables.LIMITS[k] = v`) is as unsound as the defining module
    doing it."""
    pkg = write_pkg(tmp_path, {
        "presto_tpu/exec/tables.py": """
            LIMITS = {}
        """,
        "presto_tpu/exec/broken.py": """
            from presto_tpu.exec import tables

            class PlanInterpreter:
                def run(self, node):
                    return getattr(self, "_r_x")(node)

                def _r_x(self, node):
                    return tables.LIMITS.get("cap")
        """,
        "presto_tpu/server/admin.py": """
            from presto_tpu.exec import tables

            def set_limit(k, v):
                tables.LIMITS[k] = v
        """})
    findings = run_lint([pkg], rules=["tracekey"])
    assert len(findings) == 1, [f.format() for f in findings]
    assert "unkeyed-global" in findings[0].message
    assert "'LIMITS'" in findings[0].message
    assert "presto_tpu/server/admin.py:set_limit" in \
        findings[0].message
    assert findings[0].path == "presto_tpu/exec/tables.py"


def test_tracekey_import_time_registry_not_flagged(tmp_path):
    """The SCALARS pattern: a dispatch table mutated only by a
    module-level registration decorator fills at import time — its
    contents are process-constant, not an unkeyed input."""
    pkg = write_pkg(tmp_path, {"presto_tpu/exec/broken.py": """
        TABLE = {}

        def register(name):
            def deco(fn):
                TABLE[name] = fn
                return fn
            return deco

        @register("f")
        def f(node):
            return node

        class PlanInterpreter:
            def run(self, node):
                return getattr(self, "_r_" + type(node).__name__)(node)

            def _r_call(self, node):
                return TABLE["f"](node)
    """})
    assert run_lint([pkg], rules=["tracekey"]) == []


def test_tracekey_stale_key_entry(tmp_path):
    """A TRACE_RELEVANT_PROPERTIES entry no trace-reachable code
    reads recompiles warm programs for nothing and masks drift."""
    pkg = write_pkg(tmp_path, {
        "presto_tpu/exec/progcache.py": """
            TRACE_RELEVANT_PROPERTIES = ("live_prop", "ghost_prop")
        """,
        "presto_tpu/exec/broken.py": """
            class PlanInterpreter:
                def run(self, node):
                    return getattr(self, "_r_x")(node)

                def _r_x(self, node):
                    return self.session.get("live_prop")
        """})
    findings = run_lint([pkg], rules=["tracekey"])
    assert len(findings) == 1, [f.format() for f in findings]
    assert "stale-key-entry" in findings[0].message
    assert "'ghost_prop'" in findings[0].message
    assert findings[0].path == "presto_tpu/exec/progcache.py"


def test_tracekey_exemption_and_staleness(tmp_path):
    """TRACE_KEY_EXEMPT excuses a finding WITH a justification — and
    an exemption that stops matching becomes a finding itself (the
    usual staleness discipline), so the registry cannot rot
    into a blanket waiver."""
    files = {
        "presto_tpu/exec/broken.py": TRACEKEY_DIRECT_FIXTURE,
        "presto_tpu/exec/progcache.py": """
            TRACE_RELEVANT_PROPERTIES = ()
            TRACE_KEY_EXEMPT = {
                "session:mystery_prop": "host control plane only: "
                                        "steers the stage walk",
            }
        """}
    pkg = write_pkg(tmp_path, files)
    assert run_lint([pkg], rules=["tracekey"]) == []
    stale = dict(files)
    stale["presto_tpu/exec/progcache.py"] = """
        TRACE_RELEVANT_PROPERTIES = ("mystery_prop",)
        TRACE_KEY_EXEMPT = {
            "session:mystery_prop": "now keyed: exemption is dead",
        }
    """
    pkg2 = write_pkg(tmp_path / "stale", stale)
    findings = run_lint([pkg2], rules=["tracekey"])
    assert len(findings) == 1, [f.format() for f in findings]
    assert "stale-exemption" in findings[0].message
    assert "session:mystery_prop" in findings[0].message


def test_tracekey_shares_project_model_and_call_graph():
    """Budget mechanics: the tracekey rule rides the SAME cached
    per-module function units as the tracer family (one parsed-AST
    project model, one unit walk per module) instead of re-walking
    the tree — the regression class the wall-time budget exists to
    catch."""
    from presto_tpu.lint import tracekey as TK
    from presto_tpu.lint import tracer as TR
    from presto_tpu.lint.core import Project
    project = Project.load([REPO / "presto_tpu"])
    TR.tracer_branch(project)
    TK.tracekey(project)
    graphs = project._callgraph_cache
    assert set(graphs) == {TR.TRACE_SCOPES, TK.SCOPES}
    g1, g2 = graphs[TR.TRACE_SCOPES], graphs[TK.SCOPES]
    shared = set(g1.units) & set(g2.units)
    assert shared, "scopes stopped overlapping?"
    assert all(g1.units[k] is g2.units[k] for k in shared)


# -- SARIF output -----------------------------------------------------------


def test_sarif_schema_shape_and_suppressions(tmp_path, capsys):
    """--sarif emits SARIF 2.1.0: versioned log, tool driver rule
    table, results with ruleId + physicalLocation, and in-source
    waivers exported as SUPPRESSED results (not dropped) while the
    exit code still ignores them."""
    pkg = write_pkg(tmp_path, {"presto_tpu/exec/broken.py": """
        import urllib.request

        def bad(req):
            return urllib.request.urlopen(req)

        def waived(req):
            return urllib.request.urlopen(req)  # lint: disable=timeout-discipline
    """})
    assert lint_main([str(pkg), "--sarif",
                      "--rules", "timeout-discipline"]) == 1
    log = json.loads(capsys.readouterr().out)
    assert log["version"] == "2.1.0"
    assert "sarif-schema-2.1.0" in log["$schema"]
    (run,) = log["runs"]
    rules = {r["id"] for r in run["tool"]["driver"]["rules"]}
    assert "timeout-discipline" in rules
    active = [r for r in run["results"] if not r["suppressions"]]
    waived = [r for r in run["results"] if r["suppressions"]]
    assert len(active) == 1 and len(waived) == 1
    for r in run["results"]:
        assert r["ruleId"] == "timeout-discipline"
        loc = r["locations"][0]["physicalLocation"]
        assert loc["artifactLocation"]["uri"] == \
            "presto_tpu/exec/broken.py"
        assert loc["region"]["startLine"] > 0
        assert r["message"]["text"]
    assert waived[0]["suppressions"] == [{"kind": "inSource"}]
    # suppressed-only tree: exit 0, results still exported — a waived
    # stale-suppression report included (every rule's waivers export,
    # stale-suppression is not special-cased out of the audit trail)
    clean = write_pkg(tmp_path / "c", {"presto_tpu/exec/only.py": """
        import urllib.request

        def waived(req):
            return urllib.request.urlopen(req)  # lint: disable=timeout-discipline

        x = 1  # lint: disable=stale-suppression,rule-that-never-existed
    """})
    assert lint_main([str(clean), "--sarif",
                      "--rules", "timeout-discipline"]) == 0
    log = json.loads(capsys.readouterr().out)
    results = log["runs"][0]["results"]
    assert [r["suppressions"] for r in results] == \
        [[{"kind": "inSource"}]] * 2
    assert {r["ruleId"] for r in results} == \
        {"timeout-discipline", "stale-suppression"}


def test_sarif_changed_mode_fast_exit_is_valid_sarif(tmp_path, capsys):
    """The pre-commit recipe is `--changed --sarif`: a clean worktree
    must still print a VALID empty SARIF log (CI uploads it verbatim),
    and --json/--sarif together is a usage error."""
    pkg = write_pkg(tmp_path,
                    {"presto_tpu/exec/nothing.py": "x = 1\n"})
    _git(tmp_path, "init", "-q")
    _git(tmp_path, "add", "-A")
    _git(tmp_path, "commit", "-qm", "seed")
    assert lint_main([str(pkg), "--changed", "--sarif"]) == 0
    log = json.loads(capsys.readouterr().out)
    assert log["version"] == "2.1.0" and \
        log["runs"][0]["results"] == []
    assert lint_main([str(pkg), "--json", "--sarif"]) == 2
    assert "mutually exclusive" in capsys.readouterr().err


# -- device-sync boundary (devicesync) ---------------------------------------

DEVICESYNC_FIXTURE = """
    import jax
    import jax.numpy as jnp
    import numpy as np

    def prepare_plan(engine, plan):
        res, oks = _run(plan)
        for o in oks:
            if bool(np.asarray(o)):
                pass
        n = int(jnp.sum(res))
        jax.block_until_ready(res)
        return res, n

    def _run(plan):
        fn = jax.jit(lambda x: x)
        out = fn(plan)
        return out, [out]

    def host_helper(plan):
        # identical sins, NOT reachable from an execute-path root:
        # must stay silent
        out, oks = _run(plan)
        jax.block_until_ready(out)
        return int(jnp.sum(out))
"""


def test_devicesync_flags_syncs_on_execute_path_only(tmp_path):
    """The three hidden-sync shapes — implicit ``__array__`` via
    ``np.asarray`` of a device value, ``int()`` concretization, and
    ``block_until_ready`` — fire in root-reachable code (provenance
    follows the jit-wrapped callable through the helper's return and
    tuple unpacking) and stay silent in unreachable code."""
    pkg = write_pkg(tmp_path, {
        "presto_tpu/exec/executor.py": DEVICESYNC_FIXTURE})
    findings = run_lint([pkg], rules=["device-sync"])
    assert len(findings) == 3, [f.format() for f in findings]
    msgs = " | ".join(f.message for f in findings)
    assert "np.asarray" in msgs
    assert "`int()` of a device value" in msgs
    assert "block_until_ready" in msgs
    assert all("prepare_plan" in f.message for f in findings)


def test_devicesync_metadata_and_boundary_are_clean(tmp_path):
    """Attribute reads (shape math) kill taint, and fetches routed
    through the exec/hostsync boundary are the sanctioned path — both
    lint clean, including inside the boundary module itself."""
    pkg = write_pkg(tmp_path, {
        "presto_tpu/exec/hostsync.py": """
            import jax

            DEVICE_SYNC_EXEMPT = {}

            def fetch(tree, site):
                return jax.device_get(tree)
        """,
        "presto_tpu/exec/executor.py": """
            import jax
            import jax.numpy as jnp
            import numpy as np

            from presto_tpu.exec import hostsync as HS

            def prepare_plan(engine, plan):
                out = jax.jit(lambda x: x)(plan)
                rows = out.shape[0] * out.nbytes  # metadata: host-side
                host = HS.fetch(out, site="demux")
                return np.asarray(host), rows
        """})
    assert run_lint([pkg], rules=["device-sync"]) == [], \
        [f.format() for f in run_lint([pkg], rules=["device-sync"])]


def test_devicesync_suppression_and_exemption_staleness(tmp_path):
    """An in-source waiver works through the central runner; a
    DEVICE_SYNC_EXEMPT entry excuses its finding, and one that stops
    matching becomes a stale-exemption finding itself."""
    files = {
        "presto_tpu/exec/hostsync.py": """
            DEVICE_SYNC_EXEMPT = {
                "presto_tpu/exec/executor.py:prepare_plan:"
                "block_until_ready":
                    "measurement IS the sync: profiling readback",
            }
        """,
        "presto_tpu/exec/executor.py": """
            import jax
            import jax.numpy as jnp

            def prepare_plan(engine, plan):
                out = jax.jit(lambda x: x)(plan)
                jax.block_until_ready(out)
                n = int(jnp.sum(out))  # lint: disable=device-sync
                return n
        """}
    pkg = write_pkg(tmp_path, files)
    assert run_lint([pkg], rules=["device-sync"]) == [], \
        [f.format() for f in run_lint([pkg], rules=["device-sync"])]
    stale = dict(files)
    stale["presto_tpu/exec/executor.py"] = """
        def prepare_plan(engine, plan):
            return plan
    """
    pkg2 = write_pkg(tmp_path / "stale", stale)
    findings = run_lint([pkg2], rules=["device-sync"])
    assert len(findings) == 1, [f.format() for f in findings]
    assert "stale-exemption" in findings[0].message


# -- retrace hazards (retrace) -----------------------------------------------

RETRACE_FIXTURE = """
    import jax.numpy as jnp
    import numpy as np

    from presto_tpu.ops.hash import next_pow2

    def run(counts):
        width = int(counts.max())
        buf = jnp.zeros(width)
        if width > 4:
            pass
        cache_key = ("q", width)
        ok = jnp.zeros(next_pow2(width))  # bucketed: clean
        return buf, ok, cache_key
"""


def test_retrace_shape_branch_and_key_sinks(tmp_path):
    """A raw ``.max()`` reduction reaching a shape constructor, a
    Python branch, and a cache-key tuple fires once per sink kind —
    and the same value routed through ``next_pow2`` is clean."""
    pkg = write_pkg(tmp_path, {
        "presto_tpu/exec/broken.py": RETRACE_FIXTURE})
    findings = run_lint([pkg], rules=["retrace"])
    assert len(findings) == 3, [f.format() for f in findings]
    msgs = " | ".join(f.message for f in findings)
    assert "zeros` shape" in msgs
    assert "Python branch" in msgs
    assert "cache-key" in msgs


def test_retrace_interprocedural_and_shape_derived_clean(tmp_path):
    """Taint crosses helper parameters (the tracekey least-fixpoint);
    sizes derived from ``len()``/``.shape`` are cache-stable by
    construction (input shapes already ride the program-cache key) and
    must stay silent."""
    pkg = write_pkg(tmp_path, {"presto_tpu/exec/broken.py": """
        import jax.numpy as jnp
        import numpy as np

        def driver(counts):
            return _alloc(int(counts.max()))

        def _alloc(n):
            return jnp.zeros(n)

        def clean(x):
            n = len(x)
            m = x.shape[0]
            return jnp.zeros((n, m))
    """})
    findings = run_lint([pkg], rules=["retrace"])
    assert len(findings) == 1, [f.format() for f in findings]
    assert "_alloc" in findings[0].message
    assert "zeros` shape" in findings[0].message


def test_retrace_exemption_and_staleness(tmp_path):
    """RETRACE_EXEMPT excuses a justified hazard; an entry that stops
    matching becomes a finding (same registry discipline as tracekey/
    devicesync)."""
    files = {
        "presto_tpu/exec/broken.py": """
            import numpy as np

            def pick(counts):
                w = int(counts.max())
                if w > 128:
                    return 256
                return 128
        """,
        "presto_tpu/exec/progcache.py": """
            RETRACE_EXEMPT = {
                "presto_tpu/exec/broken.py:pick:branch":
                    "both arms yield fixed bucket widths",
            }
        """}
    pkg = write_pkg(tmp_path, files)
    assert run_lint([pkg], rules=["retrace"]) == [], \
        [f.format() for f in run_lint([pkg], rules=["retrace"])]
    stale = dict(files)
    stale["presto_tpu/exec/broken.py"] = "x = 1\n"
    pkg2 = write_pkg(tmp_path / "stale", stale)
    findings = run_lint([pkg2], rules=["retrace"])
    assert len(findings) == 1, [f.format() for f in findings]
    assert "stale-exemption" in findings[0].message


# -- blocking-under-lock -----------------------------------------------------


def test_blocking_under_lock_lexical_and_entry_lockset(tmp_path):
    """A network round-trip lexically under ``with self._lock`` fires;
    the same call after snapshot-and-release is clean; a private
    helper whose every caller holds the lock inherits the lockset and
    its device drain fires too. Condition-variable ``wait`` — correct
    under a lock by design — stays silent."""
    pkg = write_pkg(tmp_path, {"presto_tpu/parallel/broken.py": """
        import threading
        import urllib.request

        import jax

        class Coordinator:
            def __init__(self):
                self._lock = threading.Lock()
                self._cv = threading.Condition()
                self._peers = []

            def poll(self, req):
                with self._lock:
                    return urllib.request.urlopen(req, timeout=1)

            def snapshot_then_poll(self, req):
                with self._lock:
                    peers = list(self._peers)
                return urllib.request.urlopen(req, timeout=1)

            def park(self):
                with self._cv:
                    self._cv.wait()

            def entry_a(self):
                with self._lock:
                    self._drain()

            def entry_b(self):
                with self._lock:
                    self._drain()

            def _drain(self):
                jax.block_until_ready(self._peers)
    """})
    findings = run_lint([pkg], rules=["blocking-under-lock"])
    assert len(findings) == 2, [f.format() for f in findings]
    msgs = " | ".join(f.message for f in findings)
    assert "urlopen" in msgs and "poll" in msgs
    assert "block_until_ready" in msgs and "_drain" in msgs
    assert "snapshot_then_poll" not in msgs
    assert "park" not in msgs


def test_blocking_under_lock_hostsync_by_resolution(tmp_path):
    """The counted hostsync boundary calls are matched by RESOLVED
    module path — an unrelated ``fetch`` method on another object
    under the same lock must not pool with them."""
    pkg = write_pkg(tmp_path, {"presto_tpu/server/broken.py": """
        import threading

        from presto_tpu.exec import hostsync as HS

        class Results:
            def __init__(self):
                self._lock = threading.Lock()
                self._queue = None

            def page(self, arrays):
                with self._lock:
                    return HS.fetch(arrays, site="serve-page")

            def other(self):
                with self._lock:
                    return self._queue.fetch()
    """})
    findings = run_lint([pkg], rules=["blocking-under-lock"])
    assert len(findings) == 1, [f.format() for f in findings]
    assert "hostsync" in findings[0].message
    assert "page" in findings[0].message
