"""Pallas kernel subsystem (presto_tpu/kernels/): limb-math
bit-exactness, per-kernel pallas-vs-xla parity, chain-overflow
loudness, kernel_backend dispatch through the full SQL path (Q5/Q9
byte-identical under pallas interpret mode vs xla vs the sqlite
oracle), and the per-operator kernel attribution surface."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from presto_tpu import Engine
from presto_tpu import kernels as K
from presto_tpu.kernels import compact as KC
from presto_tpu.kernels import hashjoin as HJ
from presto_tpu.kernels import u64
from presto_tpu.ops import hash as H
from presto_tpu.ops import segred
from presto_tpu.testing.oracle import assert_query

from tpch_queries import QUERIES


# -- 32-bit limb calculus vs the uint64 reference ---------------------------


def test_u64_limb_math_matches_uint64():
    rng = np.random.default_rng(3)
    a = jnp.asarray(rng.integers(0, 1 << 63, 4096, dtype=np.uint64)
                    * np.uint64(2654435761))
    b = jnp.asarray(rng.integers(0, 1 << 63, 4096, dtype=np.uint64))
    hi, lo = u64.split(a)
    np.testing.assert_array_equal(np.asarray(u64.join(hi, lo)),
                                  np.asarray(a))
    # combine step == combine_hashes' accumulator step
    ref = a * jnp.uint64(u64.PHI64) ^ b
    ch, cl = u64.combine_step(hi, lo, *u64.split(b))
    np.testing.assert_array_equal(np.asarray(u64.join(ch, cl)),
                                  np.asarray(ref))


def test_u64_remap_empty_matches_combine_hashes():
    vals = jnp.asarray(np.array(
        [0xFFFFFFFFFFFFFFFF, 0xFFFFFFFFFFFFFFFE, 0, 1],
        dtype=np.uint64))
    ref = H.combine_hashes([vals])
    hi, lo = u64.remap_empty(*u64.split(vals))
    np.testing.assert_array_equal(np.asarray(u64.join(hi, lo)),
                                  np.asarray(ref))


# -- join lookup kernel -----------------------------------------------------


def _lookup_inputs(seed=0, nb=700, npr=1300, key_range=400):
    rng = np.random.default_rng(seed)
    bh = H.combine_hashes([H.hash_int_column(
        jnp.asarray(rng.integers(0, key_range, nb)))])
    ph = H.combine_hashes([H.hash_int_column(
        jnp.asarray(rng.integers(0, 2 * key_range, npr)))])
    bl = jnp.asarray(rng.random(nb) > 0.15)
    pl = jnp.asarray(rng.random(npr) > 0.15)
    return bh, bl, ph, pl


def test_lookup_join_pallas_matches_xla():
    bh, bl, ph, pl = _lookup_inputs()
    want = HJ.lookup_join_xla(bh, bl, ph, pl, 2048)
    got = HJ.lookup_join_pallas(bh, bl, ph, pl, 2048)
    # duplicate build keys: both pick the LARGEST build row index
    np.testing.assert_array_equal(np.asarray(want[0]),
                                  np.asarray(got[0]))
    np.testing.assert_array_equal(np.asarray(want[1]),
                                  np.asarray(got[1]))
    assert bool(np.asarray(got[2]))


def test_lookup_join_empty_build():
    bh, _bl, ph, pl = _lookup_inputs(nb=64)
    dead = jnp.zeros((64,), bool)
    want = HJ.lookup_join_xla(bh, dead, ph, pl, 256)
    got = HJ.lookup_join_pallas(bh, dead, ph, pl, 256)
    assert not np.asarray(got[1]).any()
    np.testing.assert_array_equal(np.asarray(want[1]),
                                  np.asarray(got[1]))


def test_lookup_join_chain_overflow_is_loud():
    # more distinct hashes than max_probes can chain through a tiny
    # table: the kernel must report ok=False (the capacity retry
    # ladder's signal), never silently mis-answer
    h = H.combine_hashes([H.hash_int_column(jnp.arange(40))])
    live = jnp.ones((40,), bool)
    _row, _found, ok = HJ.lookup_join_pallas(h, live, h, live,
                                             8, max_probes=4)
    assert not bool(np.asarray(ok))


def test_lookup_join_word_aliased_keys_resolve():
    # keys of the form (m << 32) | m have equal uint32 words, so a
    # naive mix32(hi ^ lo) slot fold would chain ALL of them into one
    # cluster at EVERY capacity (no retry rung could converge);
    # u64.slot32 avalanches the words independently — the lookup must
    # resolve well past max_probes-many such keys
    n = 2 * HJ.MAX_PROBES
    m = jnp.arange(1, n + 1, dtype=jnp.int64)
    keys = (m << 32) | m
    h = H.combine_hashes([H.hash_int_column(keys)])
    live = jnp.ones((n,), bool)
    row, found, ok = HJ.lookup_join_pallas(h, live, h, live,
                                           2 * H.next_pow2(n))
    assert bool(np.asarray(ok))
    np.testing.assert_array_equal(np.asarray(found),
                                  np.ones(n, bool))
    np.testing.assert_array_equal(np.asarray(row), np.arange(n))


def test_lookup_join_vmem_gate_declines_to_xla(monkeypatch):
    # a table past the VMEM bound must DECLINE to the XLA lookup
    # (identical answer) instead of building an unallocatable block
    bh, bl, ph, pl_ = _lookup_inputs()
    monkeypatch.setattr(HJ, "PALLAS_MAX_TABLE", 64)
    assert not HJ.table_fits_vmem(2048)
    got = HJ.lookup_join_pallas(bh, bl, ph, pl_, 2048)
    want = HJ.lookup_join_xla(bh, bl, ph, pl_, 2048)
    np.testing.assert_array_equal(np.asarray(want[0]),
                                  np.asarray(got[0]))
    np.testing.assert_array_equal(np.asarray(want[1]),
                                  np.asarray(got[1]))


def test_filter_compact_vmem_gate_declines_to_xla(monkeypatch):
    monkeypatch.setattr(KC, "PALLAS_MAX_OUT_BYTES", 64)
    live = jnp.asarray(np.random.default_rng(1).random(512) > 0.5)
    arrays = {"i": jnp.arange(512, dtype=jnp.int64)}
    got = KC.filter_compact_pallas(live, arrays, 256)
    want = KC.filter_compact_xla(live, arrays, 256)
    np.testing.assert_array_equal(np.asarray(want["i"]),
                                  np.asarray(got["i"]))


def test_probe_overflow_counter_and_typed_error():
    from presto_tpu.obs.metrics import REGISTRY
    c = REGISTRY.counter("presto_tpu_hash_probe_overflow_total")
    before = c.value()
    H.note_probe_overflow(2)
    assert c.value() == before + 2
    assert issubclass(H.HashChainOverflow, RuntimeError)


# -- segmented aggregation kernels ------------------------------------------


@pytest.mark.parametrize("dtype", [np.int64, np.int32, np.uint64])
def test_segagg_sum_parity(dtype):
    rng = np.random.default_rng(11)
    if dtype is np.uint64:
        x = rng.integers(0, 1 << 62, 4000).astype(dtype)
    else:
        x = rng.integers(-(1 << 30), 1 << 30, 4000).astype(dtype)
    ids = jnp.asarray(rng.integers(0, 33, 4000).astype(np.int32))
    xj = jnp.asarray(x)
    with K.use_backend("pallas"):
        got = segred.segment_sum(xj, ids, 33)
    want = jax.ops.segment_sum(xj, ids, num_segments=33)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert got.dtype == want.dtype


def test_segagg_sum_wraparound_bit_identical():
    n = 600
    x = np.zeros(n, np.int64)
    x[0] = x[1] = (1 << 62) + 99
    ids = jnp.zeros((n,), jnp.int32)
    with K.use_backend("pallas"):
        got = segred.segment_sum(jnp.asarray(x), ids, 2)
    want = jax.ops.segment_sum(jnp.asarray(x), ids, num_segments=2)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("dtype", [np.int64, np.uint64])
def test_segagg_minmax_parity_and_empty_segments(dtype):
    rng = np.random.default_rng(5)
    if dtype is np.uint64:
        x = rng.integers(0, 1 << 62, 3000).astype(dtype)
    else:
        x = rng.integers(-(1 << 50), 1 << 50, 3000).astype(dtype)
    # segment 7 stays empty: identity fill must match jax.ops
    ids = jnp.asarray((rng.integers(0, 7, 3000)).astype(np.int32))
    xj = jnp.asarray(x)
    with K.use_backend("pallas"):
        gmax = segred.segment_max(xj, ids, 8)
        gmin = segred.segment_min(xj, ids, 8)
    np.testing.assert_array_equal(
        np.asarray(gmax),
        np.asarray(jax.ops.segment_max(xj, ids, num_segments=8)))
    np.testing.assert_array_equal(
        np.asarray(gmin),
        np.asarray(jax.ops.segment_min(xj, ids, num_segments=8)))


def test_segagg_float_falls_back_to_xla():
    # float sums would reassociate under the tile walk: the dispatch
    # must keep them on the XLA path even when pallas is forced
    from presto_tpu.kernels import segagg
    x = jnp.asarray(np.random.default_rng(0).random(512))
    assert not segagg.sum_eligible(x, 8)
    ids = jnp.zeros((512,), jnp.int32)
    with K.use_backend("pallas"):
        got = segred.segment_sum(x, ids, 8)
    np.testing.assert_array_equal(
        np.asarray(got),
        np.asarray(segred.xla_segment_sum(x, ids, 8)))


# -- filter+compact kernel --------------------------------------------------


def test_filter_compact_parity():
    rng = np.random.default_rng(2)
    n, cap = 1000, 600
    live = jnp.asarray(rng.random(n) > 0.5)
    arrays = {
        "i": jnp.arange(n, dtype=jnp.int64),
        "f": jnp.asarray(rng.random(n)),
        "b": jnp.asarray(rng.random(n) > 0.3),
        "limbs": jnp.asarray(
            rng.integers(0, 1 << 40, (n, 2)).astype(np.int64)),
    }
    want = KC.filter_compact_xla(live, arrays, cap)
    got = KC.filter_compact_pallas(live, arrays, cap)
    cnt = int(np.asarray(live).sum())
    assert cnt <= cap
    for k_ in arrays:
        # live rows byte-identical in stable order; pad rows are dead
        np.testing.assert_array_equal(
            np.asarray(want[k_])[:cnt], np.asarray(got[k_])[:cnt],
            err_msg=k_)


def test_filter_compact_overflow_rows_drop():
    live = jnp.ones((500,), bool)
    arrays = {"i": jnp.arange(500, dtype=jnp.int64)}
    got = KC.filter_compact_pallas(live, arrays, 128)
    np.testing.assert_array_equal(np.asarray(got["i"]),
                                  np.arange(128))


# -- backend resolution + dispatch ------------------------------------------


def test_resolve_and_default_backend(monkeypatch):
    from presto_tpu.session import Session
    s = Session()
    # auto stays auto: it resolves per kernel at dispatch
    assert K.resolve(s) == "auto"
    s.set("kernel_backend", "pallas")
    assert K.resolve(s) == "pallas"
    s.set("kernel_backend", "xla")
    assert K.resolve(s) == "xla"
    # the literal set names registered kernels only, and off the TPU
    # auto is the XLA twin for every kernel, listed or not
    assert K.AUTO_PALLAS <= set(K.KERNELS)
    monkeypatch.setattr(K, "AUTO_PALLAS", frozenset({"agg_sum"}))
    with K.use_backend("auto"):
        assert all(K.backend_for(n) == "xla" for n in K.KERNELS)
        assert K.dispatch("agg_sum") is K.KERNELS["agg_sum"]["xla"]
    # on a TPU auto picks Pallas for exactly the listed kernels
    import jax
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with K.use_backend("auto"):
        assert K.backend_for("agg_sum") == "pallas"
        assert K.backend_for("compact") == "xla"
    with K.use_backend("pallas"):
        assert K.backend_for("compact") == "pallas"
    # nothing but the CPU platform interprets
    assert not K.interpret_mode()
    monkeypatch.setattr(jax, "default_backend", lambda: "axelerator")
    assert not K.interpret_mode()
    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    assert K.interpret_mode()


def test_kernel_attribution_reflects_what_ran(monkeypatch):
    # kernels self-note: the recorded tag is the path that EXECUTED
    bh, bl, ph, pl_ = _lookup_inputs(nb=300, npr=300)
    with K.use_backend("pallas"), K.collect() as used:
        HJ.lookup_join_pallas(bh, bl, ph, pl_, 1024)
    assert used == ["pallas:join_lookup"]
    # a VMEM-gate decline must record the XLA lookup, not the kernel
    monkeypatch.setattr(HJ, "PALLAS_MAX_TABLE", 64)
    with K.use_backend("pallas"), K.collect() as used:
        HJ.lookup_join_pallas(bh, bl, ph, pl_, 1024)
    assert used == ["xla:join_lookup"]


def test_aggregate_attribution_on_xla_path():
    # the direct XLA fold path notes too — Aggregate operators must
    # not show empty kernel columns on backend comparisons
    x = jnp.arange(600, dtype=jnp.int64)
    ids = jnp.zeros((600,), jnp.int32)
    with K.use_backend("xla"), K.collect() as used:
        segred.segment_sum(x, ids, 2)
    assert used == ["xla:agg_sum"]


def test_registry_parity_is_total():
    for name, fns in K.KERNELS.items():
        assert set(fns) == {"pallas", "xla"}, name
        assert all(callable(f) for f in fns.values()), name


def test_cache_key_tracks_kernel_backend(tpch_tiny):
    from presto_tpu.exec import executor as ex
    e = Engine()
    e.register_catalog("tpch", tpch_tiny)
    plan, _ = e.plan_sql("select count(*) from lineitem")
    scans = ex.collect_scans(plan, e)
    base = ex._cache_key(e, plan, scans, {})
    e.session.set("kernel_backend", "pallas")
    assert ex._cache_key(e, plan, scans, {}) != base


# -- the acceptance bar: Q5/Q9 byte-identical pallas vs xla vs sqlite -------


def _engine(tpch_tiny, backend: str) -> Engine:
    e = Engine()
    e.register_catalog("tpch", tpch_tiny)
    e.session.set("kernel_backend", backend)
    return e


@pytest.mark.parametrize("qname", ["q05", "q09"])
def test_q5_q9_pallas_oracle_and_xla_parity(qname, tpch_tiny, oracle):
    # against the sqlite oracle under forced pallas (interpret mode
    # on CPU: the kernel bodies execute)
    ep = _engine(tpch_tiny, "pallas")
    assert_query(ep, oracle, QUERIES[qname])
    # and byte-identical to the XLA backend
    ex_ = _engine(tpch_tiny, "xla")
    assert ep.execute(QUERIES[qname]) == ex_.execute(QUERIES[qname])


def test_distributed_mesh_pallas_matches_xla(tpch_tiny):
    # the ShardedInterpreter dispatches the same kernels inside the
    # shard_map trace (per-shard tables, pmin-reduced ok flags): hold
    # an 8-shard join+aggregate byte-identical across backends
    import jax
    from jax.sharding import Mesh
    mesh = Mesh(np.array(jax.devices()[:8]), ("d",))
    sql = ("select n_name, count(*) c from nation n join region r "
           "on n.n_regionkey = r.r_regionkey group by n_name "
           "order by n_name")
    res = {}
    for be in ("xla", "pallas"):
        e = _engine(tpch_tiny, be)
        res[be] = e.execute(sql, mesh=mesh)
    assert res["xla"] == res["pallas"]


def test_join_edge_cases_pallas_vs_xla(tpch_tiny):
    # empty build side + all-dead probe rows through the SQL path
    sqls = [
        # empty build: no region matches
        "select count(*) from nation n join region r "
        "on n.n_regionkey = r.r_regionkey where r.r_name = 'NOPE'",
        # all probe rows filtered dead before the join
        "select count(*) from nation n join region r "
        "on n.n_regionkey = r.r_regionkey where n.n_nationkey < 0",
        # semijoin through the lookup kernel
        "select count(*) from orders where o_custkey in "
        "(select c_custkey from customer where c_acctbal > 0)",
    ]
    ep = _engine(tpch_tiny, "pallas")
    ex_ = _engine(tpch_tiny, "xla")
    for sql in sqls:
        assert ep.execute(sql) == ex_.execute(sql), sql


def test_operator_stats_name_kernels(tpch_tiny):
    from presto_tpu.obs import qstats as QS
    e = _engine(tpch_tiny, "pallas")
    with QS.query("kq1", QUERIES["q05"], "t") as qr:
        e.execute(QUERIES["q05"])
    snap = qr.snapshot()
    ops = [op for st in snap["stages"] for t in st["tasks"]
           for op in t["operators"]]
    kernels_seen = {k for op in ops
                    for k in (op.get("kernel") or "").split(",") if k}
    assert any(k.startswith("pallas:") for k in kernels_seen), \
        kernels_seen
    # execute wall splits across operators and stays attributable
    assert sum(op.get("wallMillis", 0) for op in ops) >= 0
    rows = e.execute("select node_type, kernel, wall_ms from "
                     "system.operator_stats where kernel <> ''")
    assert rows, "no kernel-attributed operators in system.operator_stats"
