"""Metric arithmetic and traffic draws on hand-made inputs."""

import json
import math

import arith
import pytest
import traffic
from conftest import ROOT


def rec(cls, due, done, sent=None, **kw):
    return {"cls": cls, "due": due, "sent": due if sent is None else sent,
            "done": done, "rows": [], **kw}


def test_geomean_of_class_medians():
    records = [rec("a", 0, 0.010), rec("a", 1, 1.030), rec("a", 2, 2.020),
               rec("b", 3, 4.0), rec("b", 5, 9.0),
               rec("b", 9, 99.0, error="boom"),      # not in any latency
               rec("a", 9, 9.5, wrong="differs")]    # nor a wrong answer
    # medians: a = 20 ms, b = (1000 + 4000) / 2 = 2500 ms
    assert arith.geomean_of_class_medians(records) == pytest.approx(
        math.sqrt(20.0 * 2500.0))


def test_qph_runs_to_the_last_completion():
    records = [rec("a", 10, 11), rec("a", 11, 12.5), rec("a", 12.5, 30),
               rec("a", 30, 90, error="late and failed")]
    # 3 good statements, last one done 20 s after the window's start
    assert arith.qph(records, t0=10.0) == pytest.approx(3 * 3600 / 20.0)


def test_percentile_needs_ten_samples_beyond_it():
    xs = [float(i) for i in range(1, 201)]
    assert arith.percentile(xs, 95.0) == 190.0
    assert arith.percentile(xs[:199], 95.0) is None   # 9 beyond
    assert arith.percentile([], 95.0) is None


def test_median_even_and_odd():
    assert arith.median([3, 1, 2]) == 2
    assert arith.median([4, 1, 2, 3]) == 2.5


CLS = {"name": "c", "sql": "select {A} {B}",
       "axes": [[{"A": "a0"}, {"A": "a1"}],
                [{"B": "b0"}, {"B": "b1"}, {"B": "b2"}]]}


def test_domain_points_cover_the_product():
    assert traffic.domain_size(CLS) == 6
    seen = {tuple(sorted(traffic.params_at(CLS, i, 0).items()))
            for i in range(6)}
    assert len(seen) == 6


def test_sequence_gives_consecutive_days():
    cls = {"name": "w", "axes": [],
           "sequence": {"placeholder": "DAY", "start": "1993-01-30"}}
    days = [traffic.params_at(cls, 0, k)["DAY"] for k in range(3)]
    assert days == ["1993-01-30", "1993-01-31", "1993-02-01"]


@pytest.mark.parametrize("draw", [{"kind": "uniform"},
                                  {"kind": "zipf", "s": 0.99}])
def test_draws_repeat_for_a_seed(draw):
    def take(seed):
        d = traffic.Drawer(CLS, draw, seed, "window")
        return [d.next() for _ in range(200)]
    assert take(5) == take(5)
    assert take(5) != take(6)


def test_zipf_favours_few_points():
    d = traffic.Drawer(CLS, {"kind": "zipf", "s": 0.99}, 1, "window")
    counts = {}
    for _ in range(3000):
        k = tuple(sorted(d.next().items()))
        counts[k] = counts.get(k, 0) + 1
    top = max(counts.values())
    # rank 1 of 6 has weight 1 / H(6, 0.99) = 0.41
    assert 0.36 * 3000 < top < 0.46 * 3000


def test_poisson_schedule_repeats_and_keeps_its_rate():
    mix = {"loop": "open", "rate": 200.0,
           "classes": [{"name": "c", "weight": 3}, {"name": "d", "weight": 1}],
           "draw": {"kind": "uniform"}}
    classes = {"c": CLS, "d": dict(CLS, name="d")}
    a = traffic.schedule(mix, classes, 11, 10.0)
    b = traffic.schedule(mix, classes, 11, 10.0)
    assert a == b and a != traffic.schedule(mix, classes, 12, 10.0)
    # the amount of work is fixed: rate x seconds, each class its share
    assert len(a) == 2000
    dues = [s["due"] for s in a]
    assert dues == sorted(dues) and 0 <= dues[0] and dues[-1] < 10.0
    assert sum(s["cls"] == "c" for s in a) == 1500
    ramped = traffic.schedule(dict(mix, ramp_s=2.0), classes, 11, 10.0)
    assert len(ramped) == 2400
    assert sum(s["due"] < 0 for s in ramped) == 400
    assert [s["i"] for s in ramped] == list(range(2400))


def test_a_class_with_a_rate_of_its_own_ignores_the_mix_rate():
    classes = {"c": CLS, "d": dict(CLS, name="d"), "w": dict(CLS, name="w")}
    for rate in (100.0, 400.0):
        mix = {"loop": "open", "rate": rate, "ramp_s": 10.0, "classes": [
            {"name": "c", "weight": 3}, {"name": "d", "weight": 1},
            {"name": "w", "rate": 0.08}], "draw": {"kind": "uniform"}}
        sts = traffic.schedule(mix, classes, 3, 51.0)
        window = [s["cls"] for s in sts if s["due"] >= 0]
        assert len(window) == round(rate * 51) and window.count("w") == 4
        assert window.count("c") == 3 * window.count("d")
        assert sum(s["cls"] == "w" for s in sts if s["due"] < 0) == 1


def test_traced_span_and_dash_traces_its_whole_window():
    fixed = {"trace": {"start_s": 10.0, "seconds": 5.0}}
    assert traffic.traced_span(fixed) == (10.0, 15.0)
    assert traffic.traced_span({"trace": {"seconds": 4.0}}) == (0.0, 4.0)
    # dash's device work follows its INSERTs, five moments drawn from
    # the seed: only the whole window is sure to hold some
    run_seconds = json.loads(
        (ROOT / "BENCHMARK.json").read_text())["run_seconds"]
    assert traffic.traced_span(traffic.load_mix("dash")) == (
        0.0, float(run_seconds))


def test_closed_schedule_is_round_robin():
    mix = {"loop": "closed", "classes": [{"name": "c"}, {"name": "d"}]}
    classes = {"c": CLS, "d": dict(CLS, name="d")}
    sts = traffic.schedule(mix, classes, 1, 5.0)
    assert [s["cls"] for s in sts[:4]] == ["c", "d", "c", "d"]
    assert sts[0]["sql"].startswith("select a")
