"""Plan templates (presto_tpu/templates/): literal hoisting, template
cache hits across literal variants, structural-change misses, pow2
shape bucketing, the PREPARE / EXECUTE ... USING surface, metrics, and
the hoistable-set drift guard against expr/compile.py."""

from __future__ import annotations

import ast
import os

import numpy as np
import pytest

from presto_tpu import Engine
from presto_tpu import types as T
from presto_tpu import templates as TPL
from presto_tpu.connectors.memory import MemoryConnector
from presto_tpu.expr import ir
from presto_tpu.obs.metrics import REGISTRY
from presto_tpu.templates.analysis import (HOISTABLE_CALL_FNS,
                                           parameterize)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_COMPILED = REGISTRY.counter("presto_tpu_programs_compiled_total")
_TPL_HITS = REGISTRY.counter("presto_tpu_template_cache_hits_total")
_TPL_MISSES = REGISTRY.counter(
    "presto_tpu_template_cache_misses_total")


def tpch_engine(tpch_tiny, templates: bool = True) -> Engine:
    e = Engine()
    e.register_catalog("tpch", tpch_tiny)
    if not templates:
        e.session.set("plan_templates", False)
    return e


# -- analysis unit level -----------------------------------------------------

def test_parameterize_hoists_values_out_of_fingerprint(tpch_tiny):
    e = tpch_engine(tpch_tiny)
    base = ("select count(*) from lineitem "
            "where l_quantity < {} and l_shipdate > date '{}'")
    p1, _ = e.plan_sql(base.format(10, "1995-03-15"))
    p2, _ = e.plan_sql(base.format(24, "1995-06-01"))
    t1, t2 = parameterize(p1), parameterize(p2)
    assert t1 is not None and t2 is not None
    assert t1.fingerprint() == t2.fingerprint()
    assert [s.dtype for s in t1.params] == [s.dtype for s in t2.params]
    assert ([s.value for s in t1.params]
            != [s.value for s in t2.params])


def test_parameterize_hoists_varchar_equality(tpch_tiny):
    e = tpch_engine(tpch_tiny)
    p, _ = e.plan_sql("select count(*) from region "
                      "where r_name = 'ASIA'")
    t = parameterize(p)
    assert t is not None
    assert any(isinstance(s.dtype, T.VarcharType) for s in t.params)


def test_structural_literals_stay_baked(tpch_tiny):
    """What the compiler still reads host-side at trace time must never
    hoist: regexp patterns, substring bounds, and a LIKE over a
    computed string. (A LIKE pattern over a scanned dictionary column
    is no longer among them: it binds as a mask over the dictionary,
    tests/test_like_binds.py.)"""
    from presto_tpu.plan.fingerprint import plan_fingerprint
    e = tpch_engine(tpch_tiny)

    def fp(sql):
        plan, _ = e.plan_sql(sql)
        t = parameterize(plan)
        return t.fingerprint() if t is not None else plan_fingerprint(plan)

    text = "select count(*) from region where {}"
    for a, b in (("regexp_like(r_name, '^A')", "regexp_like(r_name, '^E')"),
                 ("substring(r_name, 1, 2) = 'AS'",
                  "substring(r_name, 2, 2) = 'AS'"),
                 ("lower(r_name) like 'a%'", "lower(r_name) like 'e%'")):
        assert fp(text.format(a)) != fp(text.format(b)), (a, b)
    # the control: a LIKE over the scanned column itself does hoist
    assert (fp(text.format("r_name like 'A%'"))
            == fp(text.format("r_name like 'E%'")))


# -- end-to-end variant correctness + zero compiles --------------------------

Q3_VARIANT = ("1995-03-15", "1995-03-22")
Q5_VARIANT = ("ASIA", "EUROPE")
Q6_VARIANT = ("0.05 and 0.07", "0.03 and 0.05")


def _variant_pair(name):
    from tests.tpch_queries import QUERIES
    sql = QUERIES[name]
    old, new = {"q03": Q3_VARIANT, "q05": Q5_VARIANT,
                "q06": Q6_VARIANT}[name]
    assert old in sql
    return sql, sql.replace(old, new)


@pytest.mark.parametrize("name", ["q03", "q05", "q06"])
def test_variant_hits_template_and_matches_oracle(tpch_tiny, name):
    """THE acceptance check: after a first run, the same query with
    swapped literals compiles ZERO programs (template hit) and returns
    rows byte-identical to a fresh non-templated engine."""
    base, variant = _variant_pair(name)
    e = tpch_engine(tpch_tiny)
    e.execute(base)
    c0 = _COMPILED.value()
    h0 = _TPL_HITS.value()
    got = e.execute(variant)
    assert _COMPILED.value() == c0, (
        f"{name} literal variant recompiled")
    assert _TPL_HITS.value() > h0
    want = tpch_engine(tpch_tiny, templates=False).execute(variant)
    assert got == want


def test_structural_limit_change_misses(tpch_tiny):
    """LIMIT is a plan-node count, not an expression literal: changing
    it must MISS the template cache (and still answer correctly)."""
    e = tpch_engine(tpch_tiny)
    base = ("select l_orderkey from lineitem "
            "where l_quantity < 10 order by l_orderkey limit {}")
    e.execute(base.format(5))
    c0 = _COMPILED.value()
    got = e.execute(base.format(7))
    assert _COMPILED.value() > c0  # structural change: new program
    want = tpch_engine(tpch_tiny, templates=False).execute(
        base.format(7))
    assert got == want
    assert len(got) == 7


def test_absent_string_literal_matches_nothing(tpch_tiny):
    """A variant whose string value is ABSENT from the dictionary must
    bind to code -1 and return zero rows — not crash, not mis-hit."""
    e = tpch_engine(tpch_tiny)
    sql = "select count(*) from region where r_name = '{}'"
    e.execute(sql.format("ASIA"))
    c0 = _COMPILED.value()
    got = e.execute(sql.format("ATLANTIS"))
    assert _COMPILED.value() == c0
    assert got == [(0,)]


def test_disable_via_session_property(tpch_tiny):
    e = tpch_engine(tpch_tiny, templates=False)
    sql = "select count(*) from nation where n_regionkey = {}"
    e.execute(sql.format(0))
    c0 = _COMPILED.value()
    e.execute(sql.format(2))
    assert _COMPILED.value() > c0  # literals baked: variant recompiles


# -- shape bucketing ---------------------------------------------------------

def test_shape_bucketing_shares_programs_as_table_grows():
    """A table growing WITHIN its pow2 bucket (the serving scenario:
    trickle inserts between queries) keeps hitting the executable
    compiled for the padded bucket shape; results stay exact."""
    conn = MemoryConnector()
    conn.create_table(
        "t", {"k": T.BIGINT, "v": T.BIGINT},
        {"k": np.arange(900) % 7, "v": np.arange(900)})
    e = Engine()
    e.register_catalog("mem", conn)
    e.session.catalog = "mem"
    got_a = e.execute("select sum(v) from t where k < 3")
    c0 = _COMPILED.value()
    conn.insert("t", {"k": np.arange(900, 1000) % 7,
                      "v": np.arange(900, 1000)})  # still in 1024
    got_b = e.execute("select sum(v) from t where k < 3")
    assert _COMPILED.value() == c0, "same-bucket growth recompiled"

    def want(n):
        ks = np.arange(n) % 7
        return int(np.arange(n)[ks < 3].sum())

    assert got_a == [(want(900),)]
    assert got_b == [(want(1000),)]


def test_shape_bucketing_respects_session_toggle(tpch_tiny):
    from presto_tpu.exec.executor import collect_scans
    e = tpch_engine(tpch_tiny)
    plan, _ = e.plan_sql("select count(*) from nation")
    scans = collect_scans(plan, e)
    bucketed = TPL.bucket_scans(e, scans)
    n = scans[0].nrows
    assert bucketed[0].nrows >= n
    assert bucketed[0].nrows & (bucketed[0].nrows - 1) == 0  # pow2
    assert "__live__" in bucketed[0].arrays
    assert int(bucketed[0].arrays["__live__"].sum()) == n
    e.session.set("template_shape_bucketing", False)
    assert TPL.bucket_scans(e, scans) is scans


# -- PREPARE / EXECUTE -------------------------------------------------------

def test_prepare_execute_engine_roundtrip(tpch_tiny):
    e = tpch_engine(tpch_tiny)
    e.execute("prepare q from select count(*) from lineitem "
              "where l_quantity < ? and l_shipdate > ?")
    r1 = e.execute("execute q using 10, date '1995-03-15'")
    c0 = _COMPILED.value()
    r2 = e.execute("execute q using 24, date '1995-06-01'")
    assert _COMPILED.value() == c0  # EXECUTE variants share a program
    want = tpch_engine(tpch_tiny, templates=False).execute(
        "select count(*) from lineitem "
        "where l_quantity < 24 and l_shipdate > date '1995-06-01'")
    assert r2 == want
    assert r1 != r2
    e.execute("deallocate prepare q")
    with pytest.raises(ValueError, match="not found"):
        e.execute("execute q using 1, date '1995-01-01'")


def test_execute_arity_and_literal_checks(tpch_tiny):
    e = tpch_engine(tpch_tiny)
    e.execute("prepare p from select count(*) from nation "
              "where n_regionkey = ?")
    with pytest.raises(ValueError, match="parameter"):
        e.execute("execute p using 1, 2")
    with pytest.raises(ValueError, match="literal"):
        e.execute("execute p using n_regionkey")


def test_question_mark_inside_string_is_not_a_marker(tpch_tiny):
    e = tpch_engine(tpch_tiny)
    e.execute("prepare ps from select count(*) from region "
              "where r_name = '?' or r_name = ?")
    got = e.execute("execute ps using 'ASIA'")
    assert got == [(1,)]


def test_execute_cannot_smuggle_guarded_statements(tpch_tiny):
    """EXECUTE resolves BEFORE the HTTP statement-kind guards: a
    prepared `start transaction` must be rejected exactly like a
    direct one (the TransactionManager is process-global), and a
    prepared PREPARE must land in the client-side registry round trip,
    never in the shared engine session."""
    from presto_tpu.client import Client, QueryFailed
    from presto_tpu.server.server import CoordinatorServer

    e = tpch_engine(tpch_tiny)
    srv = CoordinatorServer(e).start()
    try:
        c = Client(srv.uri, user="alice")
        c.execute("prepare tx from start transaction")
        with pytest.raises(QueryFailed, match="transactions"):
            c.execute("execute tx")
        c.execute("prepare pp from prepare leaked from select 1")
        c.execute("execute pp")
        assert "leaked" not in e.session.prepared_statements
        assert c.prepared_statements.get("leaked") == "select 1"
    finally:
        srv.stop()


def test_prepare_execute_http_protocol(tpch_tiny):
    """Trino-protocol round trip: PREPARE answers with
    addedPreparedStatements, the client replays the registry via the
    X-Trino-Prepared-Statement header, EXECUTE variants land on one
    compiled template, DEALLOCATE retracts."""
    from presto_tpu.client import Client, QueryFailed
    from presto_tpu.server.server import CoordinatorServer

    e = tpch_engine(tpch_tiny)
    srv = CoordinatorServer(e).start()
    try:
        c = Client(srv.uri, user="alice")
        c.execute("prepare hq from select count(*) from orders "
                  "where o_orderdate < ?")
        assert "hq" in c.prepared_statements
        _, r1 = c.execute("execute hq using date '1995-01-01'")
        c0 = _COMPILED.value()
        _, r2 = c.execute("execute hq using date '1996-01-01'")
        assert _COMPILED.value() == c0
        assert r1 != r2
        _, want = c.execute("select count(*) from orders "
                            "where o_orderdate < date '1996-01-01'")
        assert r2 == want
        c.execute("deallocate prepare hq")
        assert "hq" not in c.prepared_statements
        with pytest.raises((QueryFailed, Exception)):
            c.execute("execute hq using date '1995-01-01'")
    finally:
        srv.stop()


def test_serve_mode_literal_variants_compile_once(tpch_tiny):
    """Serve-mode steady state: after the FIRST run of a templated
    query through the HTTP protocol, every subsequent literal variant
    must compile ZERO new programs — the whole point of the template
    cache is that a parameter sweep served to clients costs one XLA
    compile total, and every variant still answers correctly."""
    from presto_tpu.client import Client
    from presto_tpu.server.server import CoordinatorServer

    e = tpch_engine(tpch_tiny)
    srv = CoordinatorServer(e).start()
    try:
        c = Client(srv.uri, user="alice")
        sql = ("select count(*) from lineitem "
               "where l_quantity < {}")
        c.execute(sql.format(10))  # first run compiles the template
        oracle = tpch_engine(tpch_tiny, templates=False)
        for qty in (3, 7, 11, 24, 30):
            # the oracle engine below compiles too (same global
            # counter), so re-baseline before each served variant
            c0 = _COMPILED.value()
            _, rows = c.execute(sql.format(qty))
            assert _COMPILED.value() == c0, (
                f"serve-mode literal variant qty={qty} recompiled")
            want = oracle.execute(sql.format(qty))
            # HTTP rows arrive as JSON lists; engine rows as tuples
            assert [[int(v) for v in r] for r in rows] == \
                [[int(v) for v in r] for r in want]
    finally:
        srv.stop()


# -- metrics -----------------------------------------------------------------

def test_template_metrics_and_params_gauge(tpch_tiny):
    e = tpch_engine(tpch_tiny)
    sql = "select count(*) from nation where n_regionkey = {}"
    m0 = _TPL_MISSES.value()
    h0 = _TPL_HITS.value()
    e.execute(sql.format(1))
    assert _TPL_MISSES.value() > m0
    e.execute(sql.format(3))
    assert _TPL_HITS.value() > h0
    g = REGISTRY.gauge("presto_tpu_template_params_hoisted")
    assert g.value() >= 1


# -- drift guard -------------------------------------------------------------

def _scalar_fns_reading_ir() -> set:
    """Names of registered scalar fns whose body reads ``e.args`` —
    i.e. literal arguments consumed host-side at trace time."""
    path = os.path.join(REPO, "presto_tpu", "expr", "compile.py")
    with open(path, encoding="utf-8") as f:
        tree = ast.parse(f.read())
    out: set = set()
    for node in tree.body:
        if not isinstance(node, ast.FunctionDef):
            continue
        names = []
        for deco in node.decorator_list:
            if (isinstance(deco, ast.Call)
                    and isinstance(deco.func, ast.Name)
                    and deco.func.id == "scalar"
                    and deco.args
                    and isinstance(deco.args[0], ast.Constant)):
                names.append(deco.args[0].value)
        if not names:
            continue
        reads_ir = any(
            isinstance(sub, ast.Attribute) and sub.attr == "args"
            and isinstance(sub.value, ast.Name)
            and sub.value.id == "e"
            for sub in ast.walk(node))
        if reads_ir:
            out.update(names)
    return out


def test_hoistable_fns_never_read_ir_args():
    """Drift guard (ISSUE 7 satellite): every literal class the
    compiler reads at trace time must be structural. A scalar fn that
    reads ``e.args`` (host-side literal consumption — LIKE patterns,
    substring bounds, date units...) must NOT be in the hoistable set;
    adding such a read to a hoistable fn, or whitelisting a reader,
    fails tier-1 here before it can mis-share compiled programs."""
    readers = _scalar_fns_reading_ir()
    assert readers, "no IR-reading scalars found — scope drifted"
    overlap = readers & set(HOISTABLE_CALL_FNS)
    assert not overlap, (
        f"hoistable fns read literal IR at trace time: "
        f"{sorted(overlap)} — their literals would bake stale values "
        f"into shared templates")


def test_literal_reading_compiler_methods_are_classified():
    """ExprCompiler dispatch methods that read literal payloads
    (``.value`` / ``.values``) must be the known structural set: a new
    literal-bearing IR class is either added to the hoistable analysis
    or declared here — never silently both unhoisted and unguarded."""
    path = os.path.join(REPO, "presto_tpu", "expr", "compile.py")
    with open(path, encoding="utf-8") as f:
        tree = ast.parse(f.read())
    readers: set = set()
    for node in ast.walk(tree):
        if not (isinstance(node, ast.ClassDef)
                and node.name == "ExprCompiler"):
            continue
        for fn in node.body:
            if not (isinstance(fn, ast.FunctionDef)
                    and fn.name.startswith("_c_")):
                continue
            for sub in ast.walk(fn):
                if (isinstance(sub, ast.Attribute)
                        and sub.attr in ("value", "values")):
                    readers.add(fn.name)
    assert readers == {"_c_literal", "_c_inlist"}, (
        f"new literal-reading compiler methods {sorted(readers)}: "
        f"classify them in templates/analysis.py (hoistable) or "
        f"extend this structural set deliberately")
