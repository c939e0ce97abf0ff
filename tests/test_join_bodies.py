"""The operator inner loops that carry the join cells, each against an
oracle that shares no code with it: ops/hash.lookup_join, the MultiJoin
walk, page compaction, the direct-address probe and the expanding join
and the semijoin mark against Python dict joins, set membership and
boolean indexing, then the same bodies
through SQL against sqlite and over an 8-shard mesh. (The folds of
ops/segred.py are held to a scatter reference in tests/test_segred.py.)
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from presto_tpu import Engine
from presto_tpu import types as T
from presto_tpu.cost import model as CM
from presto_tpu.exec import operators as OP
from presto_tpu.expr.compile import Val
from presto_tpu.ops import hash as H
from presto_tpu.plan import nodes as N
from presto_tpu.testing.oracle import assert_query

# -- oracles ----------------------------------------------------------------


def dict_join(bkeys, blive, pkeys, plive) -> np.ndarray:
    """Build row per probe row (-1 = none); the last live build row of
    a key is its representative."""
    table = {}
    for i, (k, live) in enumerate(zip(bkeys, blive)):
        if live:
            table[int(k)] = i
    return np.array([table.get(int(k), -1) if live else -1
                     for k, live in zip(pkeys, plive)], dtype=np.int64)


def dict_multimap_join(bkeys, blive, pkeys, plive) -> list[tuple]:
    """Every (probe row, build row) pair with equal live keys."""
    table: dict[int, list[int]] = {}
    for i, (k, live) in enumerate(zip(bkeys, blive)):
        if live:
            table.setdefault(int(k), []).append(i)
    return sorted((p, b) for p, (k, live) in enumerate(zip(pkeys, plive))
                  if live for b in table.get(int(k), ()))


def hashes(keys):
    return H.combine_hashes(
        [H.hash_int_column(jnp.asarray(keys, dtype=jnp.int64))])


def table(live=None, **cols) -> OP.DTable:
    n = len(next(iter(cols.values())))
    return OP.DTable(
        {sym: Val(T.BIGINT, jnp.asarray(v, dtype=jnp.int64))
         for sym, v in cols.items()},
        None if live is None else jnp.asarray(live), n)


def check_lookup(bkeys, blive, pkeys, plive, bh=None, ph=None):
    row, found = H.lookup_join(
        hashes(bkeys) if bh is None else bh, jnp.asarray(blive),
        hashes(pkeys) if ph is None else ph, jnp.asarray(plive))
    want = dict_join(bkeys, blive, pkeys, plive)
    np.testing.assert_array_equal(np.asarray(row), want)
    np.testing.assert_array_equal(np.asarray(found), want >= 0)


# -- (a) the sorted lookup of ops/hash.py -----------------------------------


@pytest.mark.parametrize("nb", [1, 257, 2048])
def test_lookup_join_equals_dict_join(nb):
    rng = np.random.default_rng(nb)
    bkeys = rng.permutation(4 * nb)[:nb]
    pkeys = rng.integers(0, 4 * nb, 1300)
    check_lookup(bkeys, rng.random(nb) > 0.15,
                 pkeys, rng.random(1300) > 0.15)


def test_lookup_join_empty_build():
    rng = np.random.default_rng(1)
    pkeys = rng.integers(0, 64, 300)
    check_lookup(np.arange(64), np.zeros(64, bool),
                 pkeys, np.ones(300, bool))


def test_lookup_join_all_probe_rows_dead():
    check_lookup(np.arange(100), np.ones(100, bool),
                 np.arange(100), np.zeros(100, bool))


def test_lookup_join_hashes_that_alias_in_the_low_word():
    # equal low 32 bits, different high words: a lookup that compared
    # one word, or folded the two, would match every probe row
    m = np.arange(1, 513, dtype=np.uint64)
    bh = (m << np.uint64(32)) | np.uint64(7)
    ph = (np.arange(257, 769, dtype=np.uint64) << np.uint64(32)) \
        | np.uint64(7)
    live = np.ones(512, bool)
    check_lookup(bh, live, ph, live, jnp.asarray(bh), jnp.asarray(ph))


def test_lookup_join_duplicate_build_keys_return_the_last_row():
    bkeys = np.array([5, 9, 5, 9, 5, 3])
    blive = np.array([1, 1, 1, 1, 0, 1], bool)
    row, found = H.lookup_join(hashes(bkeys), jnp.asarray(blive),
                               hashes([5, 9, 3, 4]), jnp.ones(4, bool))
    # the dead duplicate (row 4) is not a candidate
    np.testing.assert_array_equal(np.asarray(row), [2, 3, 5, -1])
    np.testing.assert_array_equal(np.asarray(found), [1, 1, 1, 0])


def test_probe_overflow_counter_and_typed_error():
    from presto_tpu.obs.metrics import REGISTRY
    c = REGISTRY.counter("presto_tpu_hash_probe_overflow_total")
    before = c.value()
    H.note_probe_overflow(2)
    assert c.value() == before + 2
    assert issubclass(H.HashChainOverflow, RuntimeError)


# -- (b) the MultiJoin walk -------------------------------------------------


def chain(nbuilds: int, rng, dead_spine=False, barren: int | None = None):
    """A star chain of ``nbuilds`` dimensions: the spine holds the key
    of build 0, build k holds the key of build k+1 (joins5's shape: a
    probe key of a later step comes out of an earlier gather).
    Returns (spine, builds, node, raw, want): ``raw`` the builds'
    NumPy columns, ``want`` per spine row the tuple of build rows from
    chained dict joins (None = the row dies)."""
    sizes = [97, 61, 43, 29, 11][:nbuilds]
    n = 900
    spine_key = rng.integers(0, int(sizes[0] * 1.2), n)
    spine_live = (rng.random(n) > 0.3 if dead_spine
                  else np.ones(n, bool))
    builds, raw, crit = [], [], []
    for k, size in enumerate(sizes):
        pk = rng.permutation(size)
        nxt = (rng.integers(0, int(sizes[k + 1] * 1.2), size)
               if k + 1 < nbuilds else np.zeros(size, np.int64))
        blive = rng.random(size) > 0.1
        if barren == k:
            pk = pk + 10_000  # no spine or build key reaches it
        raw.append((pk, nxt, blive))
        builds.append(table(blive, **{f"pk{k}": pk, f"fk{k}": nxt}))
        crit.append([("sk" if k == 0 else f"fk{k - 1}", f"pk{k}")])
    spine = table(spine_live, sk=spine_key)
    node = N.MultiJoin(criteria=crit)  # no hints: every leg sorted
    want = []
    for i in range(n):
        key, rows = spine_key[i], []
        alive = bool(spine_live[i])
        for pk, nxt, blive in raw:
            if not alive:
                break
            r = dict_join(pk, blive, [key], [True])[0]
            alive = r >= 0
            rows.append(int(r))
            key = nxt[max(r, 0)]
        want.append(tuple(rows) if alive else None)
    return spine, builds, node, raw, want


def check_multi_join(nbuilds, seed, **kw):
    spine, builds, node, raw, want = chain(
        nbuilds, np.random.default_rng(seed), **kw)
    out, ok = OP.apply_multi_join(spine, builds, node)
    assert bool(np.asarray(ok))
    live = np.asarray(out.live_mask())
    np.testing.assert_array_equal(live, [w is not None for w in want])
    for k, (pk, _nxt, _bl) in enumerate(raw):
        got = np.asarray(out.cols[f"pk{k}"].data)
        for i in np.flatnonzero(live):
            assert got[i] == pk[want[i][k]], (k, i)
    return live


@pytest.mark.parametrize("nbuilds", [1, 2, 5])
def test_multi_join_equals_chained_dict_joins(nbuilds):
    assert check_multi_join(nbuilds, 10 + nbuilds).any()


def test_multi_join_one_build_matches_nothing():
    assert not check_multi_join(3, 21, barren=1).any()


def test_multi_join_dead_spine_rows_stay_dead():
    live = check_multi_join(2, 22, dead_spine=True)
    assert live.any() and not live.all()


def hinted_chain(case: str, rng):
    """spine(sk, sc) -> build0 on sk = pk0 -> build1 on (sc = c1,
    fk0 = pk1), fk0 gathered by build0 and the dense criterion the
    SECOND of the leg -> build2 on fk1 = pk2, fk1 gathered by build1.
    Every build key is unique in [lo, hi] with holes; ``case`` says
    which hazard the data holds ("all": every one)."""
    on = (lambda c: case in (c, "all"))
    n, sizes, lo = 700, (83, 59, 31), (10, -7, 1000)
    hi = tuple(l + 2 * s - 1 for l, s in zip(lo, sizes))

    def keys(k):  # unique, inside [lo, hi], about half the span used
        return lo[k] + rng.permutation(2 * sizes[k])[:sizes[k]]

    def ref(k, m):  # foreign keys into build k
        present = rng.choice(pk[k], m)
        if on("no_match"):  # in range, but no build row holds them
            present = np.where(rng.random(m) < 0.3,
                               rng.integers(lo[k], hi[k] + 1, m), present)
        if on("out_of_range"):
            wild = rng.choice([lo[k] - 1, hi[k] + 1, -2 ** 40, 2 ** 40,
                               lo[k] - 2 ** 32, hi[k] + 2 ** 32], m)
            present = np.where(rng.random(m) < 0.2, wild, present)
        return present

    def val(data, null_share):
        valid = (jnp.asarray(rng.random(len(data)) > null_share)
                 if on("null_keys") else None)
        return Val(T.BIGINT, jnp.asarray(data, dtype=jnp.int64), valid)

    def live(m):
        return (jnp.asarray(rng.random(m) > 0.25) if on("dead_build")
                else None)

    pk = [keys(k) for k in range(3)]
    c1 = rng.integers(0, 3, sizes[1])
    fk0, fk1 = ref(1, sizes[0]), ref(2, sizes[1])
    sk = ref(0, n)
    # the spine's second key agrees with the row sk leads to, unless
    # the case is the one where it must not
    row0 = dict_join(pk[0], np.ones(sizes[0], bool), sk, np.ones(n, bool))
    row1 = dict_join(pk[1], np.ones(sizes[1], bool), fk0[row0],
                     row0 >= 0)
    sc = np.where(row1 >= 0, c1[row1], 0)
    if on("composite_disagrees"):
        sc = np.where(rng.random(n) < 0.4, sc + 1, sc)
    spine = OP.DTable({"sk": val(sk, 0.1), "sc": val(sc, 0.0)}, None, n)
    builds = [
        OP.DTable({"pk0": val(pk[0], 0.1), "fk0": val(fk0, 0.15)},
                  live(sizes[0]), sizes[0]),
        OP.DTable({"pk1": val(pk[1], 0.1), "c1": val(c1, 0.0),
                   "fk1": val(fk1, 0.15)}, live(sizes[1]), sizes[1]),
        OP.DTable({"pk2": val(pk[2], 0.1)}, live(sizes[2]), sizes[2])]
    node = N.MultiJoin(
        criteria=[[("sk", "pk0")], [("sc", "c1"), ("fk0", "pk1")],
                  [("fk1", "pk2")]],
        dense_keys=[(0, lo[0], hi[0]), (1, lo[1], hi[1]),
                    (0, lo[2], hi[2])])
    return spine, builds, node


@pytest.mark.parametrize("case", [
    "plain", "no_match", "null_keys", "dead_build", "out_of_range",
    "composite_disagrees", "all"])
def test_multi_join_direct_legs_equal_the_sorted_walk(case):
    """A MultiJoin run with its dense hints equals the same node with
    the hints cleared, row for row ("plain" also holds the later leg
    keyed on a column an earlier direct leg gathered, as every case
    does); then one leg at a time, so a mixed walk is held too."""
    spine, builds, node = hinted_chain(
        case, np.random.default_rng(sum(map(ord, case))))
    want, _ = OP.apply_multi_join(
        spine, builds, dataclasses.replace(node, dense_keys=[]))
    want_live = np.asarray(want.live_mask())
    if case in ("plain", "no_match", "dead_build"):
        assert want_live.any()
    if case != "plain":
        assert not want_live.all()
    mixes = [node.dense_keys] + [
        [h if i == k else None for i, h in enumerate(node.dense_keys)]
        for k in range(3)]
    for hints in mixes:
        got, ok = OP.apply_multi_join(
            spine, builds, dataclasses.replace(node, dense_keys=hints))
        assert bool(np.asarray(ok))
        np.testing.assert_array_equal(np.asarray(got.live_mask()),
                                      want_live)
        assert list(got.cols) == list(want.cols)
        for sym, w in want.cols.items():
            g = got.cols[sym]
            np.testing.assert_array_equal(
                np.asarray(g.data)[want_live],
                np.asarray(w.data)[want_live], err_msg=sym)
            assert (g.valid is None) == (w.valid is None), sym
            if w.valid is not None:
                np.testing.assert_array_equal(
                    np.asarray(g.valid)[want_live],
                    np.asarray(w.valid)[want_live], err_msg=sym)


def test_multi_join_direct_leg_duplicate_build_key_takes_the_last_row():
    """What the planner promises cannot happen resolves as the sorted
    walk resolves it: the largest live row index of the key."""
    spine = table(sk=[5, 9, 3, 4])
    build = table(live=np.array([1, 1, 1, 1, 0, 1], bool),
                  pk=[5, 9, 5, 9, 5, 3], pay=[10, 11, 12, 13, 14, 15])
    for hints in ([], [(0, 0, 15)]):
        out, _ = OP.apply_multi_join(spine, [build], N.MultiJoin(
            criteria=[[("sk", "pk")]], dense_keys=hints))
        np.testing.assert_array_equal(np.asarray(out.live_mask()),
                                      [1, 1, 1, 0])
        np.testing.assert_array_equal(
            np.asarray(out.cols["pay"].data)[:3], [12, 13, 15])


# -- (c) compaction ---------------------------------------------------------


def check_compact(live, capacity, n=500):
    rng = np.random.default_rng(n)
    wide = rng.integers(-(1 << 62), 1 << 62, n)
    valid = rng.random(n) > 0.25
    limbs = rng.integers(0, 1 << 40, (n, 2))
    dt = OP.DTable({
        "w": Val(T.BIGINT, jnp.asarray(wide), jnp.asarray(valid)),
        "i": Val(T.BIGINT, jnp.arange(n, dtype=jnp.int64)),
        "l": Val(T.BIGINT, jnp.asarray(limbs)),
    }, jnp.asarray(live), n)
    out, ok = OP.compact_dtable(dt, capacity)
    cnt = int(np.sum(live))
    keep = min(cnt, capacity)
    assert bool(np.asarray(ok)) == (cnt <= capacity)
    assert out.n == capacity
    np.testing.assert_array_equal(np.asarray(out.live_mask()),
                                  np.arange(capacity) < cnt)
    for sym, src in (("w", wide), ("i", np.arange(n)), ("l", limbs)):
        np.testing.assert_array_equal(
            np.asarray(out.cols[sym].data)[:keep], src[live][:keep],
            err_msg=sym)
    np.testing.assert_array_equal(
        np.asarray(out.cols["w"].valid)[:keep], valid[live][:keep])
    assert out.cols["i"].valid is None


def test_compact_all_live():
    check_compact(np.ones(500, bool), 512)


def test_compact_none_live():
    check_compact(np.zeros(500, bool), 64)


def test_compact_mixed_keeps_order():
    check_compact(np.random.default_rng(2).random(500) > 0.5, 512)


def test_compact_more_survivors_than_capacity_drops_rows_and_clears_ok():
    check_compact(np.ones(500, bool), 128)


def test_compact_64_bit_and_validity_columns_at_exact_capacity():
    live = np.zeros(500, bool)
    live[np.random.default_rng(3).permutation(500)[:256]] = True
    check_compact(live, 256)


# -- (d) the direct-address probe and the expanding join --------------------


def join_node(dense_key=None, build_unique=True):
    return N.Join(criteria=[("pk", "bk")], build_unique=build_unique,
                  dense_key=dense_key)


def check_direct(bkeys, blive, pkeys, plive, lo, hi):
    left = table(plive, pk=pkeys)
    right = table(blive, bk=bkeys, payload=np.arange(len(bkeys)) * 3)
    row, found = OP._direct_probe(
        left, right, [("pk", "bk")], (0, lo, hi),
        jnp.asarray(plive), jnp.asarray(blive))
    want = dict_join(bkeys, blive, pkeys, plive)
    np.testing.assert_array_equal(np.asarray(row), want)
    np.testing.assert_array_equal(np.asarray(found), want >= 0)
    # and through apply_join, which gathers the payload by that row
    out, ok = OP.apply_join(left, right, join_node((0, lo, hi)), 16)
    assert bool(np.asarray(ok))
    live = np.asarray(out.live_mask())
    np.testing.assert_array_equal(live, want >= 0)
    np.testing.assert_array_equal(
        np.asarray(out.cols["payload"].data)[live], want[live] * 3)


def test_direct_probe_unique_dense_keys():
    rng = np.random.default_rng(4)
    bkeys = rng.permutation(300) + 1000
    check_direct(bkeys, rng.random(300) > 0.2,
                 rng.integers(900, 1400, 2000), rng.random(2000) > 0.2,
                 1000, 1299)


def test_direct_probe_duplicate_build_keys_take_the_last_row():
    bkeys = np.array([3, 4, 3, 7, 4, 3])
    blive = np.array([1, 1, 1, 1, 1, 0], bool)
    check_direct(bkeys, blive, np.arange(10), np.ones(10, bool), 0, 7)


def test_direct_probe_keys_outside_the_hinted_range_match_nothing():
    # the hint is narrower than either side's keys: build rows outside
    # it are dropped, probe rows outside it find nothing
    bkeys = np.arange(0, 200)
    want = dict_join(bkeys, (bkeys >= 50) & (bkeys <= 149),
                     np.arange(-20, 220), np.ones(240, bool))
    row, found = OP._direct_probe(
        table(pk=np.arange(-20, 220)), table(bk=bkeys),
        [("pk", "bk")], (0, 50, 149), jnp.ones(240, bool),
        jnp.ones(200, bool))
    np.testing.assert_array_equal(np.asarray(row), want)
    assert int(np.asarray(found).sum()) == 100


def test_span_past_max_span_takes_the_sorted_lookup():
    n = 64
    assert CM.dense_span_eligible((1, CM.MAX_SPAN), CM.MAX_SPAN)
    assert not CM.dense_span_eligible((0, CM.MAX_SPAN), CM.MAX_SPAN)
    rng = np.random.default_rng(5)
    bkeys = rng.permutation(n) * (CM.MAX_SPAN // 8)
    pkeys = rng.integers(0, n + 8, 400) * (CM.MAX_SPAN // 8)
    assert bkeys.max() - bkeys.min() + 1 > CM.MAX_SPAN
    blive, plive = rng.random(n) > 0.1, rng.random(400) > 0.1
    out, _ok = OP.apply_join(table(plive, pk=pkeys),
                             table(blive, bk=bkeys, payload=np.arange(n)),
                             join_node(), 128)
    want = dict_join(bkeys, blive, pkeys, plive)
    live = np.asarray(out.live_mask())
    np.testing.assert_array_equal(live, want >= 0)
    np.testing.assert_array_equal(
        np.asarray(out.cols["payload"].data)[live], want[live])


def check_expand(bkeys, blive, pkeys, plive, out_capacity):
    out, t_ok, o_ok = OP.apply_expand_join(
        table(plive, pk=pkeys, prow=np.arange(len(pkeys))),
        table(blive, bk=bkeys, brow=np.arange(len(bkeys))),
        join_node(build_unique=False), 16, out_capacity)
    want = dict_multimap_join(bkeys, blive, pkeys, plive)
    assert bool(np.asarray(t_ok))
    assert bool(np.asarray(o_ok)) == (len(want) <= out_capacity)
    live = np.asarray(out.live_mask())
    got = sorted(zip(np.asarray(out.cols["prow"].data)[live].tolist(),
                     np.asarray(out.cols["brow"].data)[live].tolist()))
    return got, want


def test_expand_join_unique_build_keys():
    rng = np.random.default_rng(6)
    got, want = check_expand(
        rng.permutation(400)[:250], rng.random(250) > 0.2,
        rng.integers(0, 400, 700), rng.random(700) > 0.2, 1024)
    assert got == want and want


def test_expand_join_duplicate_build_keys_emit_every_pair():
    rng = np.random.default_rng(7)
    bkeys = rng.integers(0, 40, 300)  # about 7 rows a key
    got, want = check_expand(bkeys, rng.random(300) > 0.2,
                             rng.integers(0, 50, 200),
                             rng.random(200) > 0.2, 2048)
    assert got == want and len(want) > 200
    # one row too few: the flag clears and no pair is invented
    got, _want = check_expand(bkeys[:8] * 0, np.ones(8, bool),
                              np.zeros(4, np.int64), np.ones(4, bool), 31)
    assert len(got) == 31 and set(got) <= {
        (p, b) for p in range(4) for b in range(8)}


@pytest.mark.parametrize("dense", [False, True],
                         ids=["sorted-lookup", "dense-bitmap"])
def test_semijoin_mark_equals_set_membership(dense):
    rng = np.random.default_rng(8)
    fkeys = rng.integers(100, 400, 500)  # duplicates: a set, not a map
    flive = rng.random(500) > 0.3
    skeys = rng.integers(50, 450, 1200)
    slive = rng.random(1200) > 0.2
    node = N.SemiJoin(source_keys=["sk"], filter_keys=["fk"],
                      output="m", dense_key=(100, 399) if dense else None)
    out, ok = OP.apply_semijoin(table(slive, sk=skeys),
                                table(flive, fk=fkeys), node, 16)
    assert bool(np.asarray(ok))
    members = set(fkeys[flive].tolist())
    want = np.array([live and int(k) in members
                     for k, live in zip(skeys, slive)])
    np.testing.assert_array_equal(np.asarray(out.cols["m"].data), want)
    # a semijoin marks rows, it drops none
    np.testing.assert_array_equal(np.asarray(out.live_mask()), slive)


def test_left_join_keeps_unmatched_probe_rows_with_null_build_columns():
    rng = np.random.default_rng(9)
    bkeys = rng.permutation(300)[:120]
    pkeys = rng.integers(0, 300, 500)
    blive, plive = rng.random(120) > 0.2, rng.random(500) > 0.2
    node = N.Join(criteria=[("pk", "bk")], join_type=N.JoinType.LEFT)
    out, _ok = OP.apply_join(table(plive, pk=pkeys),
                             table(blive, bk=bkeys, payload=bkeys * 7),
                             node, 16)
    want = dict_join(bkeys, blive, pkeys, plive)
    np.testing.assert_array_equal(np.asarray(out.live_mask()), plive)
    payload = out.cols["payload"]
    np.testing.assert_array_equal(
        np.asarray(payload.valid)[plive], (want >= 0)[plive])
    hit = plive & (want >= 0)
    np.testing.assert_array_equal(
        np.asarray(payload.data)[hit], pkeys[hit] * 7)


# -- (e) through SQL --------------------------------------------------------


@pytest.mark.parametrize("sql", [
    # empty build: no region matches
    "select count(*), min(n_name) from nation n join region r "
    "on n.n_regionkey = r.r_regionkey where r.r_name = 'NOPE'",
    # every probe row filtered dead before the join
    "select count(*), max(s_name) from supplier s join nation n "
    "on s.s_nationkey = n.n_nationkey where s.s_suppkey < 0",
    # semijoin through the same lookup
    "select count(*), sum(o_totalprice) from orders where o_custkey in "
    "(select c_custkey from customer where c_acctbal > 0)",
], ids=["empty-build", "all-dead-probe", "semijoin"])
def test_join_edges_equal_sqlite(engine, oracle, sql):
    assert_query(engine, oracle, sql)


def test_mesh_join_aggregate_equals_the_local_engine(tpch_tiny):
    from jax.sharding import Mesh
    mesh = Mesh(np.array(jax.devices()[:8]), ("d",))
    sql = ("select n_name, count(*) c, sum(s_acctbal) bal "
           "from supplier s join nation n "
           "on s.s_nationkey = n.n_nationkey "
           "group by n_name order by n_name")
    e = Engine()
    e.register_catalog("tpch", tpch_tiny)
    local = e.execute(sql)
    assert len(local) == 25
    assert e.execute(sql, mesh=mesh) == local


# -- (f) what went with the fork --------------------------------------------


def test_operator_stats_has_no_kernel_column(engine):
    engine.execute("select count(*) from nation")
    cols = list(engine.execute_table(
        "select * from system.operator_stats").columns)
    assert "wall_ms" in cols and "kernel" not in cols
    with pytest.raises(Exception, match="kernel"):
        engine.execute("select kernel from system.operator_stats")


def test_kernel_backend_is_an_unknown_session_property():
    e = Engine()
    with pytest.raises(KeyError, match="unknown session property"):
        e.session.set("kernel_backend", "xla")
    with pytest.raises(Exception, match="kernel_backend"):
        e.execute("set session kernel_backend = 'xla'")
