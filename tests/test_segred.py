"""segred exactness: the MXU limb path must be bit-identical to the
64-bit scatter-add it replaces (jax.ops.segment_sum), including negative
values, int64 wraparound, and uint64 checksum sums."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from presto_tpu.ops import segred


def _ids(rng, n, k):
    return jnp.asarray(rng.integers(0, k, n).astype(np.int32))


@pytest.mark.parametrize("k", [1, 6, 17, 512])
def test_sum_int64_matches_scatter(k):
    rng = np.random.default_rng(7)
    n = 10_000
    x = rng.integers(-(1 << 40), 1 << 40, n).astype(np.int64)
    ids = _ids(rng, n, k)
    got = np.asarray(segred.segment_sum(jnp.asarray(x), ids, k))
    want = np.zeros(k, np.int64)
    np.add.at(want, np.asarray(ids), x)
    np.testing.assert_array_equal(got, want)


def test_sum_int64_wraparound():
    # two near-max values in one segment: scatter-add wraps mod 2^64
    n = 300  # >= BLOCK so the fast path engages
    x = np.zeros(n, np.int64)
    x[0] = x[1] = (1 << 62) + 12345
    ids = jnp.zeros(n, jnp.int32)
    got = np.asarray(segred.segment_sum(jnp.asarray(x), ids, 2))
    want = np.int64((((1 << 62) + 12345) * 2) % (1 << 64) - (1 << 64))
    assert got[0] == want
    assert got[1] == 0


def test_sum_uint64_checksum_semantics():
    rng = np.random.default_rng(3)
    n = 5_000
    x = rng.integers(0, 1 << 63, n).astype(np.uint64)
    ids = _ids(rng, n, 9)
    got = np.asarray(segred.segment_sum(jnp.asarray(x), ids, 9))
    want = np.zeros(9, np.uint64)
    for i, g in enumerate(np.asarray(ids)):
        want[g] += x[i]
    np.testing.assert_array_equal(got, want)


def test_sum_bool_counts():
    rng = np.random.default_rng(5)
    n = 4_097
    w = rng.integers(0, 2, n).astype(bool)
    ids = _ids(rng, n, 6)
    got = np.asarray(segred.segment_sum(jnp.asarray(w), ids, 6))
    want = np.bincount(np.asarray(ids)[w], minlength=6)
    np.testing.assert_array_equal(got, want)
    assert got.dtype == np.int64


@pytest.mark.parametrize("dtype", [np.int64, np.float64])
def test_min_max_match(dtype):
    rng = np.random.default_rng(11)
    n = 3_000
    if dtype is np.int64:
        x = rng.integers(-(1 << 50), 1 << 50, n).astype(dtype)
    else:
        x = rng.standard_normal(n).astype(dtype) * 1e12
    ids = _ids(rng, n, 13)
    ids_np = np.asarray(ids)
    gmax = np.asarray(segred.segment_max(jnp.asarray(x), ids, 13))
    gmin = np.asarray(segred.segment_min(jnp.asarray(x), ids, 13))
    for g in range(13):
        sel = x[ids_np == g]
        assert gmax[g] == sel.max()
        assert gmin[g] == sel.min()


def test_empty_segment_identities():
    # segment 1 receives no rows: sum=0, max=dtype-min (jax.ops contract)
    n = 300
    x = jnp.arange(n, dtype=jnp.int64)
    ids = jnp.zeros(n, jnp.int32)
    s = np.asarray(segred.segment_sum(x, ids, 2))
    assert s[1] == 0
    mx = np.asarray(segred.segment_max(x, ids, 2))
    assert mx[1] == np.iinfo(np.int64).min


def test_large_k_falls_back():
    # above MAX_MATMUL_K the scatter path must be used and still correct
    rng = np.random.default_rng(2)
    n = 2_000
    k = segred.MAX_MATMUL_K + 1
    x = rng.integers(-100, 100, n).astype(np.int64)
    ids = _ids(rng, n, k)
    got = np.asarray(segred.segment_sum(jnp.asarray(x), ids, k))
    want = np.zeros(k, np.int64)
    np.add.at(want, np.asarray(ids), x)
    np.testing.assert_array_equal(got, want)


# -- fast-path vs slow-path equivalence (the _use_fast_path boundary) -------
# The MXU limb path and the broadcast-compare path must agree with the
# jax.ops scatter path on EXACTLY the inputs where eligibility flips:
# one row below/at the BLOCK floor, one segment count at/above the
# MAX_MATMUL_K / MAX_CMP_K ceilings, empty segments, rows that are all
# dead (out-of-range segment ids drop on both paths), and NaN/NULL
# data through min/max.


def _sum_both_paths(x, ids, k):
    got_fast = np.asarray(segred.segment_sum(jnp.asarray(x),
                                             jnp.asarray(ids), k))
    got_slow = np.asarray(jax.ops.segment_sum(jnp.asarray(x),
                                              jnp.asarray(ids),
                                              num_segments=k))
    return got_fast, got_slow


@pytest.mark.parametrize("n", [segred.BLOCK - 1, segred.BLOCK,
                               segred.BLOCK + 1, 4 * segred.BLOCK])
def test_sum_exact_block_boundary_sizes(n):
    # n < BLOCK takes the scatter path, n >= BLOCK the MXU path:
    # results must be identical either side of the flip
    rng = np.random.default_rng(n)
    x = rng.integers(-(1 << 40), 1 << 40, n).astype(np.int64)
    ids = rng.integers(0, 5, n).astype(np.int32)
    fast, slow = _sum_both_paths(x, ids, 5)
    np.testing.assert_array_equal(fast, slow)


@pytest.mark.parametrize("k", [segred.MAX_MATMUL_K,
                               segred.MAX_MATMUL_K + 1])
def test_sum_exact_segment_count_boundary(k):
    rng = np.random.default_rng(k)
    n = 3 * segred.BLOCK
    x = rng.integers(-(1 << 40), 1 << 40, n).astype(np.int64)
    ids = rng.integers(0, k, n).astype(np.int32)
    fast, slow = _sum_both_paths(x, ids, k)
    np.testing.assert_array_equal(fast, slow)


@pytest.mark.parametrize("k", [segred.MAX_CMP_K, segred.MAX_CMP_K + 1])
def test_minmax_exact_segment_count_boundary(k):
    rng = np.random.default_rng(k)
    n = 3 * segred.BLOCK
    x = rng.integers(-(1 << 50), 1 << 50, n).astype(np.int64)
    ids = jnp.asarray(rng.integers(0, k, n).astype(np.int32))
    xj = jnp.asarray(x)
    np.testing.assert_array_equal(
        np.asarray(segred.segment_max(xj, ids, k)),
        np.asarray(jax.ops.segment_max(xj, ids, num_segments=k)))
    np.testing.assert_array_equal(
        np.asarray(segred.segment_min(xj, ids, k)),
        np.asarray(jax.ops.segment_min(xj, ids, num_segments=k)))


def test_all_dead_rows_match_scatter_path():
    # every row targets the out-of-range pad segment (how the engine
    # masks dead __live__ rows out of a fold): both paths must drop
    # them and report pure identities
    n = 2 * segred.BLOCK
    x = np.full(n, 123456789, np.int64)
    ids = np.full(n, 7, np.int32)  # == num_segments: out of range
    fast, slow = _sum_both_paths(x, ids, 7)
    np.testing.assert_array_equal(fast, slow)
    np.testing.assert_array_equal(fast, np.zeros(7, np.int64))
    xj, idsj = jnp.asarray(x), jnp.asarray(ids)
    np.testing.assert_array_equal(
        np.asarray(segred.segment_max(xj, idsj, 7)),
        np.asarray(jax.ops.segment_max(xj, idsj, num_segments=7)))


def test_minmax_nan_identical_on_both_paths():
    # NaN data rows (live SQL DOUBLE NaNs) must order identically on
    # the broadcast-compare fast path and the scatter slow path (both
    # propagate NaN into the segment's result)
    rng = np.random.default_rng(17)
    n = 3 * segred.BLOCK
    x = rng.standard_normal(n)
    x[:: 7] = np.nan
    xj = jnp.asarray(x)
    ids = jnp.asarray(rng.integers(0, 9, n).astype(np.int32))
    fast_max = np.asarray(segred._cmp_reduce(xj, ids, 9, True))
    slow_max = np.asarray(jax.ops.segment_max(xj, ids, num_segments=9))
    np.testing.assert_array_equal(fast_max, slow_max)
    fast_min = np.asarray(segred._cmp_reduce(xj, ids, 9, False))
    slow_min = np.asarray(jax.ops.segment_min(xj, ids, num_segments=9))
    np.testing.assert_array_equal(fast_min, slow_min)


def test_null_masked_rows_fold_identically():
    # NULL handling upstream masks rows via weight=0 + slot unchanged
    # (expr/aggregates.fold): emulate by zeroing masked data — the
    # fast path must agree with the scatter path on the masked fold
    rng = np.random.default_rng(23)
    n = 4 * segred.BLOCK
    data = rng.integers(-(1 << 40), 1 << 40, n)
    valid = rng.random(n) > 0.4
    masked = np.where(valid, data, 0)
    ids = rng.integers(0, 11, n).astype(np.int32)
    fast, slow = _sum_both_paths(masked, ids, 11)
    np.testing.assert_array_equal(fast, slow)


@pytest.mark.parametrize("blocks,steps", [
    (4096, (4, 1024)),   # equal steps: nothing padded
    (4097, (5, 1024)),   # no power of two divides it: padded steps
    (600, (1, 600)),     # under the cap: one step
])
def test_sum_wide_chunks_equal_default(blocks, steps):
    """A mesh program's wide fold steps (``wide_chunks``) give the
    default's sums bit for bit, and the default's chunking is what it
    was."""
    assert segred._chunking(blocks, 7) == (-(-blocks // 512), 512)
    rng = np.random.default_rng(11)
    n = blocks * segred.BLOCK - 3
    x = jnp.asarray(rng.integers(-(1 << 50), 1 << 50, n).astype(np.int64))
    ids = _ids(rng, n, 6)
    want = np.asarray(segred.segment_sum(x, ids, 6))
    with segred.wide_chunks(1024):
        assert segred._chunking(blocks, 7) == steps
        got = np.asarray(jax.jit(
            lambda a, b: segred.segment_sum(a, b, 6))(x, ids))
    np.testing.assert_array_equal(got, want)
    ref = np.zeros(6, np.int64)
    np.add.at(ref, np.asarray(ids), np.asarray(x))
    np.testing.assert_array_equal(got, ref)


def test_wide_chunks_bound_the_one_hot():
    # many segments: a step's one-hot stays the default's worst case
    with segred.wide_chunks(1 << 15):
        steps, chunk = segred._chunking(1 << 16, segred.MAX_MATMUL_K + 1)
        assert chunk == 512 and steps == 128
        assert segred._chunking(43 << 12, 7) == (8, 43 << 9)
