"""The trace reduction on hand-made events and on the recorded trace."""

import gzip
import json
import shutil

import pytest
import tracered
from conftest import BENCH

RECORDED = BENCH / "tests" / "data" / "power_sf1.xplane.pb.gz"
EXPECTED = BENCH / "tests" / "data" / "power_sf1.expected.json"


def test_merge_covered_and_gaps():
    merged = tracered.merge([(5, 6), (1, 3), (2, 4), (6, 7), (9, 9.5)])
    assert merged == [(1, 4), (5, 7), (9, 9.5)]
    assert tracered.covered(merged, 0, 10) == 5.5
    assert tracered.covered(merged, 3, 6) == 2.0
    assert tracered.gaps(merged, 0, 10) == [(0, 1), (4, 5), (7, 9),
                                            (9.5, 10)]
    assert tracered.gaps(merged, 2, 6) == [(4, 5)]
    assert tracered.gaps([], 2, 6) == [(2, 6)]


def test_self_time_leaves_a_loop_what_its_body_does_not_take():
    s = 1e9
    events = [("while", 0 * s, 10 * s), ("fusion", 1 * s, 2 * s),
              ("fusion", 4 * s, 3 * s), ("copy", 20 * s, 1 * s)]
    assert tracered.self_times(events) == {
        "while": 5.0, "fusion": 5.0, "copy": 1.0}


def test_clock_shift_comes_from_the_sync_event():
    planes = {"/host:CPU": {"python": [
        ("other", 5.0, 1.0),
        (f"{tracered.SYNC}{3_000_000_000}", 1_000_000_000.0, 10.0)]}}
    assert tracered.clock_shift_s(planes) == pytest.approx(2.0)
    with pytest.raises(ValueError):
        tracered.clock_shift_s({"p": {"l": [("x", 0.0, 1.0)]}})


def test_gap_label_names_the_statement_and_its_innermost_span():
    records = [{"cls": "q01", "qid": "A", "sent": 0.0, "done": 10.0},
               {"cls": "q06", "qid": "B", "sent": 10.0, "done": 11.0}]
    spans = {"A": [
        {"name": "query", "t0": 0.0, "t1": 10.0},
        {"name": "execute", "t0": 2.0, "t1": 9.0},
        {"name": "plan", "t0": 0.5, "t1": 1.5}]}
    assert tracered.label(3.0, 8.0, records, spans) == "q01: execute"
    assert tracered.label(0.6, 1.4, records, spans) == "q01: plan"
    assert tracered.label(10.2, 10.8, records, spans) == "q06: no span"
    assert tracered.label(20.0, 21.0, records, spans) == (
        "no statement in flight")


@pytest.mark.skipif(not RECORDED.is_file(), reason="no recorded trace")
def test_recorded_trace_reduces_to_the_expected_numbers(tmp_path):
    """A short trace of tpch_sf1.power recorded on the v5e, with the
    records and spans of that run: busy share, top operations and gaps
    are what they were when it was recorded."""
    want = json.loads(EXPECTED.read_text())
    path = tmp_path / "t.xplane.pb"
    with gzip.open(RECORDED, "rb") as src, open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    red = tracered.reduce(path, want["lo"], want["hi"], want["records"],
                          want["spans"])
    assert red.window_s == pytest.approx(want["window_s"])
    assert red.busy_s == pytest.approx(want["busy_s"], rel=1e-9)
    assert 0 < red.busy_s < red.window_s
    got = red.breakdown()
    assert [n for n, _s in got["device_ops"]] == [
        n for n, _s in want["breakdown"]["device_ops"]]
    assert [n for n, _s in got["idle_gaps"]] == [
        n for n, _s in want["breakdown"]["idle_gaps"]]
    for (_n, a), (_m, b) in zip(got["device_ops"] + got["idle_gaps"],
                                want["breakdown"]["device_ops"]
                                + want["breakdown"]["idle_gaps"]):
        assert a == pytest.approx(b, rel=1e-9)
