"""The narrow sorts: up to ``ops.hash.NARROW_SORT_MAX_ROWS`` rows a
grouping, compaction or ORDER BY sort of integer keys is a chain of
one-operand sorts (``lexsort_perm``) plus gathers, and ``probe_runs``
sorts by the position in its concatenation where it sorted by side and
index. Each gives the order the wide stable sort gave."""

import jax.numpy as jnp
import numpy as np
import pytest

from presto_tpu import Engine
from presto_tpu import types as T
from presto_tpu.connectors.memory import MemoryConnector
from presto_tpu.ops import hash as H

KEYS = {
    "i32": lambda rng, n: rng.integers(-3, 3, n).astype(np.int32),
    "i64": lambda rng, n: rng.integers(-2**62, 2**62, n) // 2**60,
    "i64_wide": lambda rng, n: rng.integers(-2**63, 2**63 - 1, n,
                                            dtype=np.int64),
    "u64": lambda rng, n: rng.integers(0, 2**64 - 1, n, dtype=np.uint64)
    >> np.uint64(62),
    "bool": lambda rng, n: rng.integers(0, 2, n).astype(bool),
    "u32": lambda rng, n: rng.integers(0, 5, n).astype(np.uint32),
}


@pytest.mark.parametrize("names", [
    ("i32",), ("i64_wide",), ("bool", "i64"), ("u64", "i32", "u32"),
    ("i64", "bool", "u64", "i32"), ("i32", "i32", "i64", "i32", "i64")])
def test_lexsort_perm_is_a_stable_lexicographic_sort(names):
    rng = np.random.default_rng(len(names))
    n = 3_000
    keys = [KEYS[name](rng, n) for name in names]
    want = np.lexsort([np.arange(n)] + keys[::-1])
    got = np.asarray(H.lexsort_perm([jnp.asarray(k) for k in keys]))
    np.testing.assert_array_equal(got, want)


def test_narrow_sortable_takes_integer_keys_up_to_the_limit():
    small = jnp.zeros((H.NARROW_SORT_MAX_ROWS,), jnp.int64)
    assert H.narrow_sortable([small, small.astype(bool)])
    assert not H.narrow_sortable([small.astype(jnp.float32)])
    assert not H.narrow_sortable(
        [jnp.zeros((H.NARROW_SORT_MAX_ROWS + 1,), jnp.int32)])
    assert not H.narrow_sortable([jnp.zeros((8, 2), jnp.uint64)])


def _groups(n_rows, monkeypatch, limit):
    monkeypatch.setattr(H, "NARROW_SORT_MAX_ROWS", limit)
    rng = np.random.default_rng(7)
    k1 = jnp.asarray(rng.integers(0, 40, n_rows))
    k2 = jnp.asarray(rng.integers(-5, 5, n_rows).astype(np.int32))
    valid = jnp.asarray(rng.random(n_rows) > 0.1)
    live = jnp.asarray(rng.random(n_rows) > 0.2)
    value = jnp.asarray(rng.integers(0, 1000, n_rows))
    rh = H.combine_hashes([H.hash_int_column(k1),
                           H.hash_int_column(k2, valid)])
    sg = H.SortedGroups(rh, live, [k1, k2, valid, value], 3)
    cols, occupied = sg.compact(list(sg.payloads), 512)
    return ([np.asarray(a) for a in (sg.sh, sg.sidx, sg.is_new, sg.gidc,
                                     sg.ngroups, occupied)]
            + [np.asarray(a) for a in sg.payloads]
            + [np.asarray(c) for c in cols])


def test_grouping_and_compaction_equal_the_wide_sort(monkeypatch):
    narrow = _groups(4_000, monkeypatch, H.NARROW_SORT_MAX_ROWS)
    wide = _groups(4_000, monkeypatch, 0)
    assert len(narrow) == len(wide)
    for a, b in zip(narrow, wide):
        np.testing.assert_array_equal(a, b)


def _runs_reference(bh, blive, ph, plive):
    live_b = np.sort(bh[blive])
    lo = np.searchsorted(live_b, ph, side="left")
    hi = np.searchsorted(live_b, ph, side="right")
    count = np.where(plive, hi - lo, 0)
    return lo, count, plive & (count > 0)


def test_probe_runs_bounds_equal_a_searchsorted_reference():
    rng = np.random.default_rng(3)
    bh = rng.integers(0, 300, 2_000).astype(np.uint64)
    ph = rng.integers(0, 400, 5_000).astype(np.uint64)
    blive = rng.random(2_000) > 0.1
    plive = rng.random(5_000) > 0.1
    lo, count, found = (np.asarray(a) for a in H.probe_runs(
        jnp.asarray(bh), jnp.asarray(blive), jnp.asarray(ph),
        jnp.asarray(plive)))
    want_lo, want_count, want_found = _runs_reference(bh, blive, ph, plive)
    np.testing.assert_array_equal(found, want_found)
    np.testing.assert_array_equal(count, want_count)
    np.testing.assert_array_equal(lo[found], want_lo[found])
    # the runs index the build side sorted by sort_build_side
    _sh, bsidx = H.sort_build_side(jnp.asarray(bh), jnp.asarray(blive))
    bsidx = np.asarray(bsidx)
    for i in np.flatnonzero(found)[:200]:
        rows = bsidx[lo[i]:lo[i] + count[i]]
        assert (bh[rows] == ph[i]).all() and blive[rows].all()


def test_order_by_through_the_engine_is_exact():
    e = Engine()
    conn = MemoryConnector()
    rng = np.random.default_rng(11)
    n = 6_000
    a = rng.integers(0, 30, n).astype(np.int64)
    b = rng.integers(-50, 50, n).astype(np.int64)
    b_valid = rng.random(n) > 0.05
    conn.create_table("t", {"a": T.BIGINT, "b": T.BIGINT},
                      {"a": a, "b": b}, {"a": None, "b": b_valid})
    e.register_catalog("mem", conn)
    got = e.execute("SELECT a, b, count(*) AS c FROM mem.t GROUP BY a, b "
                    "ORDER BY c DESC, a, b DESC NULLS LAST LIMIT 40")
    groups: dict = {}
    for x, y, v in zip(a, b, b_valid):
        key = (int(x), int(y) if v else None)
        groups[key] = groups.get(key, 0) + 1
    want = sorted(groups.items(), key=lambda kv: (
        -kv[1], kv[0][0], kv[0][1] is None,
        -(kv[0][1] if kv[0][1] is not None else 0)))[:40]
    assert [tuple(r) for r in got] == [(k[0], k[1], c) for k, c in want]
