"""The copied NumPy references equal ``chip_smoke``'s at SF 0.01, and the
memory-table references follow the table's state."""

import numpy as np
import pytest
import refdata
import verify


@pytest.fixture(scope="module")
def conn():
    from presto_tpu.connectors.tpch import TpchConnector
    return TpchConnector(scale=0.01, seed=19920101)


@pytest.fixture(scope="module")
def data(conn):
    return refdata.Columns(conn)


def test_q01_equals_chip_smoke(conn, data):
    import chip_smoke
    for delta, cutoff in ((90, "1998-09-02"), (60, "1998-10-02")):
        assert (verify.load_reference("q01")(data, {"DELTA": str(delta)})
                == chip_smoke.ref_q1(conn, cutoff))


def test_q06_equals_chip_smoke(conn, data):
    import chip_smoke
    for year in ("1994-01-01", "1995-01-01"):
        params = {"DATE": year, "DISC_LO": "0.05", "DISC_HI": "0.07",
                  "QUANTITY": "24"}
        assert (verify.load_reference("q06")(data, params)
                == chip_smoke.ref_q6(conn, year))


def test_q03_equals_chip_smoke(conn, data):
    import chip_smoke
    for date in ("1995-03-15", "1995-03-22"):
        params = {"SEGMENT": "BUILDING", "DATE": date}
        assert (verify.load_reference("q03")(data, params)
                == chip_smoke.ref_q3(conn, date))


def test_sel_and_ins_follow_the_state(conn, data):
    import chip_smoke
    odate = data.col("orders", "o_orderdate")
    d0 = chip_smoke._days("1993-01-01")
    sel = verify.load_reference("sel")
    ins = verify.load_reference("ins")
    assert sel(data, {}, []) == chip_smoke.ref_smoke_select(
        conn, odate < d0)
    state = [{"DAY": "1993-01-01"}, {"DAY": "1993-01-02"}]
    assert sel(data, {}, state) == chip_smoke.ref_smoke_select(
        conn, odate < d0 + 2)
    assert ins(data, {"DAY": "1993-01-02"}) == [
        [int(np.sum(odate == d0 + 1))]]


def test_read_your_writes_states():
    w1 = {"cls": "ins", "params": {"DAY": "a"}, "sent": 1.0, "done": 2.0}
    w2 = {"cls": "ins", "params": {"DAY": "b"}, "sent": 2.5, "done": 4.0}
    w3 = {"cls": "ins", "params": {"DAY": "c"}, "sent": 9.0, "done": 9.5}
    read = {"sent": 3.0, "done": 3.5}
    states = list(verify._states(read, [w1, w2, w3]))
    # w1 acknowledged before the read was sent, w2 in flight, w3 later
    assert states == [[{"DAY": "a"}], [{"DAY": "a"}, {"DAY": "b"}]]
