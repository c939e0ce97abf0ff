"""TPC-H Q5 and Q9 through the served path equal the benchmark's plain
NumPy references row for row: as planned at SF 0.01 (one fused program)
and with the MultiJoin materialised as a segment of its own, as SF10
runs it; and the lowered program names each build of the MultiJoin."""

from __future__ import annotations

import os
import re
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "benchmark"))

import refdata  # noqa: E402
import traffic  # noqa: E402
import verify  # noqa: E402

from presto_tpu import Engine  # noqa: E402
from presto_tpu.client import Client  # noqa: E402
from presto_tpu.connectors.tpch import TpchConnector  # noqa: E402
from presto_tpu.exec import executor as EX  # noqa: E402
from presto_tpu.obs.trace import TRACER  # noqa: E402
from presto_tpu.plan import nodes as N  # noqa: E402
from presto_tpu.server.server import CoordinatorServer  # noqa: E402

SEEDS = (19920101, 2147483659)
# three parameter sets a class: domain points spread over the axes
POINTS = {"q05": (0, 12, 24), "q09": (0, 37, 91)}


@pytest.fixture(scope="module", params=SEEDS)
def conn(request):
    return TpchConnector(scale=0.01, seed=request.param)


def _served(conn, cls_name, monkeypatch, split_rows=None):
    """(rows the client got, rows of the reference, the statements'
    query ids) for the class's three parameter sets."""
    if split_rows is not None:
        monkeypatch.setattr(EX, "AGG_SPLIT_MIN_ROWS", split_rows)
    engine = Engine()
    engine.register_catalog("tpch", conn)
    cls = traffic.load_class(cls_name)
    answer = verify.load_reference(cls_name)
    data = refdata.Columns(conn)
    server = CoordinatorServer(engine).start()
    try:
        client = Client(server.uri)
        out = []
        for point in POINTS[cls_name]:
            params = traffic.params_at(cls, point, 0)
            _cols, rows = client.execute(traffic.statement(cls, params))
            out.append((params, rows, answer(data, params)))
        return out
    finally:
        server.stop()


@pytest.mark.parametrize("shape", ["fused", "segmented"])
@pytest.mark.parametrize("cls_name", ["q05", "q09"])
def test_served_answers_equal_the_reference(conn, cls_name, shape,
                                            monkeypatch):
    split = 1 if shape == "segmented" else None
    segments_before = _segment_spans()
    for params, got, want in _served(conn, cls_name, monkeypatch, split):
        assert want, params  # an empty answer would prove nothing
        assert [list(r) for r in got] == want, params
    made = _segment_spans() - segments_before
    assert (made > 0) == (shape == "segmented")


def _segment_spans() -> int:
    with TRACER._lock:
        return sum(1 for spans in TRACER._traces.values()
                   for s in spans if s.name == "segment"
                   and {"width", "live_rows"} <= set(s.attrs))


@pytest.mark.parametrize("cls_name", ["q05", "q09"])
def test_the_lowered_program_names_every_build(cls_name):
    conn = TpchConnector(scale=0.01, seed=SEEDS[0])
    engine = Engine()
    engine.register_catalog("tpch", conn)
    cls = traffic.load_class(cls_name)
    plan, _ = engine.plan_sql(
        traffic.statement(cls, traffic.params_at(cls, 0, 0)))
    joins = []

    def visit(node):
        if isinstance(node, N.MultiJoin):
            joins.append(node)
        for s in node.sources():
            visit(s)

    visit(plan)
    assert [len(j.builds) for j in joins] == [5]
    pos = EX.preorder_index(plan)[id(joins[0])]
    scans = EX.collect_scans(plan, engine)
    traced_fn, flat, _meta = EX.make_traced(scans, plan, {}, engine.session)
    import jax
    text = jax.jit(traced_fn).lower(*flat).as_text(debug_info=True)
    found = set(re.findall(rf"MultiJoin#{pos}/(build\d+)", text))
    assert found == {f"build{k}" for k in range(5)}


@pytest.mark.parametrize("scale", [0.01, 1, 10])
@pytest.mark.parametrize("cls_name,size", [("q05", 25), ("q09", 92)])
def test_the_whole_domain_plans_to_one_template(cls_name, size, scale):
    """Planning needs estimates only, so SF10 plans here too: set-up
    warms one statement a class, and a parameter set that planned to
    another template would compile in the window (Q5's DATE did, until
    a lower and an upper bound on one column were priced as one range:
    plan/stats._sel_and)."""
    from presto_tpu.templates import parameterize
    engine = Engine()
    engine.register_catalog("tpch", TpchConnector(scale=scale))
    cls = traffic.load_class(cls_name)
    assert traffic.domain_size(cls) == size
    prints = set()
    for point in range(size):
        sql = traffic.statement(cls, traffic.params_at(cls, point, 0))
        prints.add(parameterize(engine.plan_sql(sql)[0]).fingerprint())
    assert len(prints) == 1


def test_two_bounds_on_one_column_are_one_range():
    from presto_tpu import types as T
    from presto_tpu.expr import ir
    from presto_tpu.plan.stats import selectivity
    d = ir.ColumnRef(T.DATE, "d")
    other = ir.ColumnRef(T.BIGINT, "k")
    ranges = {"d": (0.0, 1000.0), "k": (0.0, 10.0)}

    def bound(fn, col, v):
        return ir.Call(T.BOOLEAN, fn, (col, ir.Literal(col.dtype, v)))

    def both(*args):
        return ir.Call(T.BOOLEAN, "and", args)

    for lo in (0, 300, 800):  # wherever the range lies, a tenth
        got = selectivity(both(bound("gte", d, lo), bound("lt", d, lo + 100)),
                          {}, ranges)
        assert got == pytest.approx(0.1)
    # bounds on different columns stay independent events
    assert selectivity(both(bound("gte", d, 500), bound("lt", other, 5)),
                       {}, ranges) == pytest.approx(0.25)
    # an empty range, and a nested conjunction
    assert selectivity(both(bound("gte", d, 600), bound("lt", d, 400)),
                       {}, ranges) == pytest.approx(1e-9)
    assert selectivity(both(bound("gte", d, 100), both(
        bound("lt", d, 300), bound("lt", other, 5))), {}, ranges) \
        == pytest.approx(0.1)
