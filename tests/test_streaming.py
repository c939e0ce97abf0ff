"""Block-streamed scan execution (the split analog,
exec/streaming.py): scans bigger than scan_block_rows stream through one
compiled partial-aggregate kernel; device memory holds one block, not
the table. Reference: split/SplitManager.java,
plugin/trino-tpch/.../TpchSplitManager.java:55."""

import numpy as np
import pytest

from presto_tpu import Engine
from presto_tpu import types as T
from presto_tpu.connectors.memory import MemoryConnector
from presto_tpu.exec import streaming as ST
from presto_tpu.obs.metrics import REGISTRY
from presto_tpu.testing.oracle import rows_equal

_COMPILED = REGISTRY.counter("presto_tpu_programs_compiled_total")
_TPL_HITS = REGISTRY.counter("presto_tpu_template_cache_hits_total")
_TPL_MISSES = REGISTRY.counter(
    "presto_tpu_template_cache_misses_total")


def make_engine(tpch_tiny, block_rows: int) -> Engine:
    e = Engine()
    e.register_catalog("tpch", tpch_tiny)
    e.session.set("scan_block_rows", block_rows)
    return e


Q1 = ("select l_returnflag, l_linestatus, sum(l_quantity) as sum_qty, "
      "sum(l_extendedprice * (1 - l_discount)) as sum_disc_price, "
      "avg(l_discount) as avg_disc, count(*) as count_order "
      "from lineitem where l_shipdate <= date '1998-09-02' "
      "group by l_returnflag, l_linestatus "
      "order by l_returnflag, l_linestatus")

Q6 = ("select sum(l_extendedprice * l_discount) as revenue from lineitem "
      "where l_shipdate >= date '1994-01-01' "
      "and l_shipdate < date '1995-01-01' "
      "and l_discount between 0.05 and 0.07 and l_quantity < 24")

HIGH_CARD = ("select l_orderkey, count(*) as c, sum(l_quantity) as q "
             "from lineitem group by l_orderkey "
             "order by c desc, l_orderkey limit 20")


@pytest.mark.parametrize("sql", [Q1, Q6, HIGH_CARD],
                         ids=["q1", "q6", "high_card_groupby"])
def test_streamed_matches_whole_table(sql, tpch_tiny):
    whole = make_engine(tpch_tiny, 0)
    streamed = make_engine(tpch_tiny, 7000)
    got = streamed.execute(sql)
    # ~60k tiny lineitem rows / 7000 per block
    assert getattr(streamed, "last_streamed_blocks", 0) >= 8
    assert got == whole.execute(sql)


def _assert_oracle(oracle, sql, got, ordered):
    from presto_tpu.sql.parser import parse_statement
    from presto_tpu.sql.sqlite_dialect import to_sqlite

    want = oracle.query(to_sqlite(parse_statement(sql)))
    ok, msg = rows_equal(got, want, ordered=ordered)
    assert ok, msg


@pytest.mark.parametrize("tail", ["mid", "one_row", "all_but_one"])
@pytest.mark.parametrize("sql", [Q1, Q6], ids=["q1", "q6"])
def test_streamed_matches_oracle(sql, tail, tpch_tiny, oracle):
    """The last block is the table's last ``scan_block_rows`` rows, so
    it overlaps the block before by all but the tail: with a tail of
    one row, of all but one, and in between, no row of the overlap is
    counted twice and none of the tail is lost."""
    nrows = tpch_tiny.table("lineitem").nrows
    block = {"mid": 7000, "one_row": (nrows - 1) // 3,
             "all_but_one": -(-(nrows + 1) // 2)}[tail]
    e = make_engine(tpch_tiny, block)
    _assert_oracle(oracle, sql, e.execute(sql), ordered=True)
    assert e.last_streamed_blocks == -(-nrows // block)


def test_join_plan_does_not_stream(tpch_tiny):
    e = make_engine(tpch_tiny, 1000)
    e.last_streamed_blocks = 0
    got = e.execute("select count(*) from lineitem, orders "
                    "where l_orderkey = o_orderkey")
    assert e.last_streamed_blocks == 0  # two scans: whole-table path
    assert got[0][0] > 0


def test_small_scan_does_not_stream(tpch_tiny):
    e = make_engine(tpch_tiny, 1 << 24)
    e.last_streamed_blocks = 0
    e.execute(Q6)
    assert e.last_streamed_blocks == 0


# -- the block program is a plan template -----------------------------------
#
# A streamed statement is two programs: the partial aggregate that runs
# over every block, and the rest of the plan over the merged partials
# (run_plan, templated since PR 7). The cases below are about the first.

Q1_VARIANT = Q1.replace("1998-09-02", "1996-06-01")
Q6_VARIANT = (Q6.replace("1994-01-01", "1996-01-01")
              .replace("1995-01-01", "1997-01-01")
              .replace("0.05 and 0.07", "0.02 and 0.04")
              .replace("< 24", "< 25"))
BY_MODE = ("select l_shipmode, count(*) as c from lineitem "
           "where l_shipmode = '{}' group by l_shipmode")
LIKE = "select count(*) from lineitem where l_shipinstruct like '{}'"


@pytest.mark.parametrize(
    "first, variant, ordered",
    [(Q6, Q6_VARIANT, False), (Q1, Q1_VARIANT, True),
     (BY_MODE.format("AIR"), BY_MODE.format("RAIL"), False),
     (LIKE.format("%BACK%"), LIKE.format("DELIVER%"), False)],
    ids=["q6", "q1", "varchar_equality", "like_pattern"])
def test_literal_variant_compiles_nothing(first, variant, ordered,
                                          tpch_tiny, oracle):
    """Other literals on a shape the engine has streamed before: the
    block program is a template hit, nothing compiles, and the answer
    is the whole-table path's and the oracle's. The two string cases
    also cover the first statement's second run of its first block,
    bound to the dictionary the trace recorded."""
    whole = make_engine(tpch_tiny, 0)
    e = make_engine(tpch_tiny, 7000)
    c0, m0 = _COMPILED.value(), _TPL_MISSES.value()
    got_first = e.execute(first)
    assert _COMPILED.value() > c0  # the block program, at least
    assert _TPL_MISSES.value() == m0 + 1
    assert got_first == whole.execute(first)
    _assert_oracle(oracle, first, got_first, ordered)

    c0, h0, m0 = _COMPILED.value(), _TPL_HITS.value(), _TPL_MISSES.value()
    e.last_streamed_blocks = 0
    got = e.execute(variant)
    assert e.last_streamed_blocks >= 8
    assert _COMPILED.value() == c0, "a literal variant compiled"
    assert _TPL_HITS.value() == h0 + 1
    assert _TPL_MISSES.value() == m0
    assert got != got_first
    assert got == whole.execute(variant)
    _assert_oracle(oracle, variant, got, ordered)


def test_absent_string_literal_hits_and_matches_no_row(tpch_tiny):
    e = make_engine(tpch_tiny, 7000)
    assert e.execute(BY_MODE.format("AIR"))
    c0 = _COMPILED.value()
    assert e.execute(BY_MODE.format("no such mode")) == []
    assert _COMPILED.value() == c0


def _block_programs(engine, tag=ST.STREAM_TAG):
    """The program cache's entries for block programs."""
    return [ent for key, ent in engine._program_cache._entries.items()
            if key[0][-1] == tag]


def test_overflowed_capacities_are_remembered(tpch_tiny):
    """HIGH_CARD has no literal to hoist, so its sub-plan keys the
    cache as it is. With a first rung far too small for a block's
    groups the first statement climbs the ok-ladder; the second starts
    on the rung that held and compiles nothing."""
    whole = make_engine(tpch_tiny, 0)
    e = make_engine(tpch_tiny, 7000)
    e.session.set("groupby_table_size", 512)
    c0 = _COMPILED.value()
    got = e.execute(HIGH_CARD)
    # two rungs or more of the block program, and the final program
    assert _COMPILED.value() - c0 >= 3
    stream_caps = [caps for key, caps in e._caps_memory.items()
                   if key[-1] == ST.STREAM_TAG]
    assert len(stream_caps) == 1
    assert all(cap > 512 for cap in stream_caps[0].values())
    # the rungs that overflowed left the cache with their programs
    assert len(_block_programs(e)) == 1
    c0 = _COMPILED.value()
    assert e.execute(HIGH_CARD) == got
    assert _COMPILED.value() == c0, "the ladder was climbed again"
    assert got == whole.execute(HIGH_CARD)


# -- the copy runs one block ahead ------------------------------------------
#
# Block i+1's ``jax.device_put`` is issued after block i's program is
# dispatched and before the host waits for block i's flags, so the copy
# rides the link while the chip computes. Each block is placed once.

AHEAD_BLOCK = 16384  # four blocks of tiny lineitem's 59,739 rows
_COPIES = REGISTRY.counter("presto_tpu_stream_block_copies_total")


def _spy_copy_order(monkeypatch, conn, block):
    """Record, in order, the streamed scan's ``jax.device_put`` of a
    block as ``("put", i, alive)``, ``alive`` the blocks whose device
    arguments were still referenced just before it, and each
    ``streaming-ok-ladder`` fetch as ``("ok", all flags set, alive)``."""
    import gc
    import weakref

    from presto_tpu.exec import hostsync as HS

    owners = [np.asarray(c.data) for c in conn.table("lineitem")
              .columns.values()]
    events, refs = [], {}
    real_put, real_fetch = ST.jax.device_put, HS.fetch

    def alive():
        gc.collect()
        return sorted(i for i, rs in refs.items()
                      if any(r() is not None for r in rs))

    def put(x, *args, **kwargs):
        if not isinstance(x, list):
            return real_put(x, *args, **kwargs)
        col = x[0]
        owner = next(a for a in owners if np.shares_memory(a, col))
        lo = ((col.__array_interface__["data"][0]
               - owner.__array_interface__["data"][0]) // col.strides[0])
        i = (lo + int(x[-2])) // block
        events.append(("put", i, alive()))
        out = real_put(x, *args, **kwargs)
        refs.setdefault(i, []).extend(weakref.ref(a) for a in out)
        return out

    def fetch(x, site=None, **kwargs):
        got = real_fetch(x, site=site, **kwargs)
        if site == "streaming-ok-ladder":
            events.append(("ok", bool(np.all(got)), alive()))
        return got

    monkeypatch.setattr(ST.jax, "device_put", put)
    monkeypatch.setattr(HS, "fetch", fetch)
    return events


def _check_copy_order(events, nblocks):
    """Each block placed once, in order; block i+1's copy before block
    i's first flags; never more than two blocks' arguments live. The
    ok-ladder fetches that found an overflow, by block."""
    puts = [(k, e) for k, e in enumerate(events) if e[0] == "put"]
    assert [e[1] for _k, e in puts] == list(range(nblocks))
    overflowed, block, first_ok = [], 0, {}
    for k, e in enumerate(events):
        assert len(e[2]) <= (1 if e[0] == "put" else 2), events
        if e[0] == "ok":
            first_ok.setdefault(block, k)
            if e[1]:
                block += 1
            else:
                overflowed.append(block)
    assert block == nblocks
    for i in range(nblocks - 1):
        assert puts[i + 1][0] < first_ok[i], events
    return overflowed


def test_the_next_block_is_copied_before_the_wait(tpch_tiny, monkeypatch):
    whole = make_engine(tpch_tiny, 0)
    e = make_engine(tpch_tiny, AHEAD_BLOCK)
    want = whole.execute(Q1)
    events = _spy_copy_order(monkeypatch, tpch_tiny, AHEAD_BLOCK)
    got = e.execute(Q1)
    assert e.last_streamed_blocks == 4
    assert not _check_copy_order(events, 4)
    assert got == want


def test_an_overflow_reruns_on_its_block_and_keeps_the_next(
        tpch_tiny, oracle, monkeypatch):
    """A block that climbs the ok-ladder reruns on its own device
    arguments; the next block's, placed before the first run, stay
    valid and are not copied again. Per statement the counter reads a
    copy ahead for every block but the first, whether or not the
    ladder was climbed."""
    e = make_engine(tpch_tiny, AHEAD_BLOCK)
    e.session.set("groupby_table_size", 512)
    for first in (True, False):
        events = _spy_copy_order(monkeypatch, tpch_tiny, AHEAD_BLOCK)
        a0, n0 = _COPIES.value(when="ahead"), _COPIES.value(when="inline")
        got = e.execute(HIGH_CARD)
        nblocks = e.last_streamed_blocks
        assert nblocks == 4
        overflowed = _check_copy_order(events, nblocks)
        if first:
            # the first block overflows once its successor is placed
            assert overflowed and overflowed[0] < nblocks - 1
        else:
            assert not overflowed  # the rung that held is remembered
        assert _COPIES.value(when="ahead") - a0 == nblocks - 1
        assert _COPIES.value(when="inline") - n0 == 1
        _assert_oracle(oracle, HIGH_CARD, got, ordered=True)
        monkeypatch.undo()


def test_plan_templates_off_compiles_per_statement(tpch_tiny):
    """The master switch keeps the behaviour from before the template:
    a block program per statement, even for the same text, and none of
    them in the cache."""
    on = make_engine(tpch_tiny, 7000)
    off = make_engine(tpch_tiny, 7000)
    off.session.set("plan_templates", False)
    want = on.execute(Q6), on.execute(Q6_VARIANT)
    h0, m0 = _TPL_HITS.value(), _TPL_MISSES.value()
    for sql, rows in zip((Q6, Q6_VARIANT, Q6), (*want, want[0])):
        c0 = _COMPILED.value()
        assert off.execute(sql) == rows
        assert _COMPILED.value() > c0
    assert (_TPL_HITS.value(), _TPL_MISSES.value()) == (h0, m0)
    assert not _block_programs(off)


def test_insert_that_changes_a_dictionary_misses():
    """The block's shape never changes (it is scan_block_rows), so
    what keeps a program off data it was not traced for is the
    dictionary digest in the key: an INSERT that brings a new string
    misses, compiles and answers with it; one that brings none hits."""
    n = 3000
    conn = MemoryConnector()
    conn.create_table(
        "t", {"s": T.VARCHAR, "x": T.BIGINT},
        {"s": np.array(["a", "b", "c"], dtype=object)[np.arange(n) % 3],
         "x": np.arange(n)})
    e = Engine()
    e.register_catalog("mem", conn)
    e.session.catalog = "mem"
    e.session.set("scan_block_rows", 1000)
    sql = ("select s, count(*) as c, sum(x) as sx from t "
           "where x >= {} group by s order by s")

    def want(lo, extra=()):
        rows = [(s, x) for s, x in zip("abc" * (n // 3), range(n))]
        rows += list(extra)
        out = {}
        for s, x in rows:
            if x >= lo:
                c, sx = out.get(s, (0, 0))
                out[s] = (c + 1, sx + x)
        return [(s, c, sx) for s, (c, sx) in sorted(out.items())]

    assert e.execute(sql.format(10)) == want(10)
    assert e.last_streamed_blocks == 3
    c0 = _COMPILED.value()
    assert e.execute(sql.format(500)) == want(500)
    assert _COMPILED.value() == c0

    e.execute("insert into t select 'b', 7000")  # no new string
    c0 = _COMPILED.value()
    assert e.execute(sql.format(20)) == want(20, [("b", 7000)])
    assert e.last_streamed_blocks == 4
    assert _COMPILED.value() == c0, "same dictionary, same shapes"

    e.execute("insert into t select 'zz', 8000")  # the dictionary grows
    c0, m0 = _COMPILED.value(), _TPL_MISSES.value()
    assert (e.execute(sql.format(30))
            == want(30, [("b", 7000), ("zz", 8000)]))
    assert _COMPILED.value() > c0
    assert _TPL_MISSES.value() > m0


def _reachable_arrays(root):
    """Every ndarray ``root`` keeps alive through what a cached
    program is made of: a jit wrapper's function, closure cells and
    defaults, containers, and the attributes of plain objects (plan
    nodes, ScanInput, the session). Modules and classes are not
    followed: they are alive anyway."""
    import types

    seen, stack, found = set(), [root], []
    while stack:
        obj = stack.pop()
        if id(obj) in seen or isinstance(obj, (type, types.ModuleType)):
            continue
        seen.add(id(obj))
        if isinstance(obj, np.ndarray):
            found.append(obj)
            if obj.base is not None:
                stack.append(obj.base)
        elif isinstance(obj, dict):
            stack.extend(obj.keys())
            stack.extend(obj.values())
        elif isinstance(obj, (list, tuple, set, frozenset)):
            stack.extend(obj)
        elif isinstance(obj, types.FunctionType):
            stack.extend(c.cell_contents for c in obj.__closure__ or ())
            stack.extend(obj.__defaults__ or ())
            stack.append(getattr(obj, "__wrapped__", None))
        else:
            stack.append(getattr(obj, "__wrapped__", None))
            stack.append(getattr(obj, "__dict__", None))
            stack.extend(getattr(obj, s, None)
                         for s in getattr(type(obj), "__slots__", ()))
    return found


def test_cached_program_holds_no_block_of_the_table(tpch_tiny):
    """The cache outlives the statement; a block's arrays are views of
    the table's columns, every block's, so a program that kept one
    would keep the column (0.5 GB each at SF10). The trace gets shapes,
    so after a hit nothing the cache holds reaches an array of a
    block's rows or a column of the table."""
    block = 7000
    e = make_engine(tpch_tiny, block)
    e.execute(Q1)
    e.execute(Q1_VARIANT)  # a hit
    entries = _block_programs(e)
    assert len(entries) == 1
    compiled, meta, _nbytes = entries[0]
    assert getattr(compiled, "__wrapped__", None) is not None
    nrows = tpch_tiny.table("lineitem").nrows
    arrays = _reachable_arrays((compiled, meta))
    # the walk did reach the closure: the dictionaries are in it
    assert any(a.dtype == object for a in arrays)
    held = [a.shape for a in arrays
            if a.ndim and a.shape[0] in (block, nrows)]
    assert not held, held


# -- a block is views and two scalars ----------------------------------------
#
# Block i is rows [i*block, (i+1)*block) of every column; the last one is
# the table's last ``block`` rows, and the rows it shares with the block
# before are dead by ``live_lo``. The live mask is made in the program.

SEAM_BLOCK = 64
SEAM_QUERIES = {
    "count": "select count(*) from t",
    "sum": "select sum(x), sum(y), count(y) from t",
    "minmax": "select min(x), max(x), min(y), max(y) from t",
    "grouped": ("select g, count(*), sum(x), min(x), max(y) from t "
                "group by g order by g"),
}
SEAM_FILTER = " where x % 3 <> 1"


def _seam_table(nrows: int, mask=None):
    """A table whose every row changes every aggregate: x a permutation
    (times 7), y the same with NULLs, g five groups."""
    rng = np.random.default_rng(nrows)
    x = rng.permutation(nrows).astype(np.int64) * 7
    data = {"g": np.arange(nrows, dtype=np.int64) % 5, "x": x,
            "y": x[::-1].copy()}
    valid = {"y": np.arange(nrows) % 4 != 2}

    class Conn(MemoryConnector):
        def table(self, name):
            return super().table(name).with_mask(mask)

    conn = Conn()
    conn.create_table("t", {"g": T.BIGINT, "x": T.BIGINT, "y": T.BIGINT},
                      data, valid)
    return conn, data, valid


def _seam_engine(conn, block_rows: int) -> Engine:
    e = Engine()
    e.register_catalog("mem", conn)
    e.session.catalog = "mem"
    e.session.set("scan_block_rows", block_rows)
    return e


def _seam_want(kind, data, valid, keep):
    """The answer by NumPy over the rows ``keep`` selects."""
    def agg(sel):
        x, y = data["x"][sel], data["y"][sel & valid["y"]]
        return {"count": (int(sel.sum()),),
                "sum": (int(x.sum()), int(y.sum()), len(y)),
                "minmax": (int(x.min()), int(x.max()),
                           int(y.min()), int(y.max()))}
    if kind != "grouped":
        return [agg(keep)[kind]]
    out = []
    for g in range(5):
        sel = keep & (data["g"] == g)
        a = agg(sel)
        out.append((g, a["count"][0], a["sum"][0], a["minmax"][0],
                    a["minmax"][3]))
    return out


@pytest.mark.parametrize("filtered", [False, True],
                         ids=["all_rows", "filtered"])
@pytest.mark.parametrize("kind", list(SEAM_QUERIES))
@pytest.mark.parametrize("r", [1, SEAM_BLOCK // 2, SEAM_BLOCK - 1])
@pytest.mark.parametrize("k", [1, 3])
def test_seam_of_the_last_two_blocks(k, r, kind, filtered):
    nrows = k * SEAM_BLOCK + r
    conn, data, valid = _seam_table(nrows)
    sql = SEAM_QUERIES[kind]
    keep = np.ones(nrows, dtype=bool)
    if filtered:
        head, sep, rest = sql.partition(" group by")
        sql = head + SEAM_FILTER + sep + rest
        keep = data["x"] % 3 != 1
    e = _seam_engine(conn, SEAM_BLOCK)
    got = e.execute(sql)
    assert e.last_streamed_blocks == k + 1
    assert got == _seam_want(kind, data, valid, keep)
    assert got == _seam_engine(conn, 0).execute(sql)


def _spy_block_args(monkeypatch):
    """Record what the streamed scan hands to ``jax.device_put``: one
    list of host arguments a block."""
    calls = []
    real = ST.jax.device_put

    def spy(x, *args, **kwargs):
        if isinstance(x, list):
            calls.append(list(x))
        return real(x, *args, **kwargs)

    monkeypatch.setattr(ST.jax, "device_put", spy)
    return calls


@pytest.mark.parametrize("masked", [False, True], ids=["plain", "masked"])
def test_a_block_is_views_and_two_scalars(masked, monkeypatch):
    """No array of a block is made on the host: each shares memory
    with its column, the last block's too; the live range is two int32
    scalars, and no ``bool[block]`` goes with the block unless the
    table has a mask of its own (then that, as a view)."""
    nrows = 3 * SEAM_BLOCK + 5
    mask = (np.arange(nrows) % 7 != 3) if masked else None
    conn, _data, _valid = _seam_table(nrows, mask)
    e = _seam_engine(conn, SEAM_BLOCK)
    calls = _spy_block_args(monkeypatch)
    e.execute(SEAM_QUERIES["grouped"])
    assert len(calls) == e.last_streamed_blocks == 4
    tbl = conn.table("t")
    owners = [np.asarray(c.data) for c in tbl.columns.values()]
    owners += [np.asarray(c.valid) for c in tbl.columns.values()
               if c.valid is not None]
    if masked:
        owners.append(mask)
    for i, args in enumerate(calls):
        *cols, live_lo, live_hi = args
        assert len(cols) == len(owners)
        for b in cols:
            assert b.shape[0] == SEAM_BLOCK
            assert any(np.shares_memory(b, a) for a in owners), i
        assert sum(b.dtype == np.bool_ for b in cols) == 1 + masked
        for bound in (live_lo, live_hi):
            assert bound.dtype == np.int32 and bound.ndim == 0
        last = i == len(calls) - 1
        assert (int(live_lo), int(live_hi)) == (
            (SEAM_BLOCK - 5) if last else 0, SEAM_BLOCK)


def test_one_program_serves_every_block(tpch_tiny):
    """The live range is traced, not static: the statement's first
    block builds the block program and the others, the last included,
    replay it; so does a literal variant."""
    e = make_engine(tpch_tiny, 7000)
    c0 = _COMPILED.value()
    e.execute(Q6)
    assert e.last_streamed_blocks >= 8
    assert _COMPILED.value() - c0 == 2  # the block program, the final one
    e.execute(Q6_VARIANT)
    assert _COMPILED.value() - c0 == 2
    (compiled, _meta, _nbytes), = _block_programs(e)
    assert compiled._cache_size() == 1


@pytest.mark.parametrize("kind", list(SEAM_QUERIES))
def test_masked_table_streams_under_its_own_mask(kind):
    """A table-level mask (``Table.mask``) is one more column of the
    block, ANDed in the program with the range: dead rows stay dead in
    every block and in the overlap of the last two."""
    nrows = 3 * SEAM_BLOCK + SEAM_BLOCK // 2
    mask = np.arange(nrows) % 3 != 0
    mask[-SEAM_BLOCK:-SEAM_BLOCK // 2] ^= True  # the overlap differs
    conn, data, valid = _seam_table(nrows, mask)
    e = _seam_engine(conn, SEAM_BLOCK)
    got = e.execute(SEAM_QUERIES[kind])
    assert e.last_streamed_blocks == 4
    assert got == _seam_want(kind, data, valid, mask)
    assert got == _seam_engine(conn, 0).execute(SEAM_QUERIES[kind])
    assert len(_block_programs(e, ST.STREAM_MASKED_TAG)) == 1
    assert not _block_programs(e)
