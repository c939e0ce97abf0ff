"""Segmented reductions that avoid emulated 64-bit scatters on TPU.

TPU v5e has no native 64-bit ALU: under ``jax_enable_x64`` XLA emulates
every int64/float64 scatter-add, making ``jax.ops.segment_sum`` cost
~500ms per 6M-row call — it was >90% of TPC-H Q1's runtime. This module
is the drop-in replacement used by the aggregate fold/merge kernels
(expr/aggregates.py), keeping exact semantics while riding the MXU:

- ``segment_sum`` (integer dtypes, small segment count): values decompose
  into 8-bit limbs — exactly representable in bf16, so the one-hot
  batched matmul per 256-row block is exact at ANY matmul precision
  (TPU truncates f32 matmul operands to bf16 by default); per-block
  per-segment partials (≤ 256·255 < 2^24) accumulate exactly in f32;
  block partials reduce in f64 (< 2^53, exact); limb totals reassemble
  mod 2^64 in int64 — bit-identical to a 64-bit scatter-add (including
  wraparound).
- ``segment_max``/``segment_min`` (small segment count): a chunked
  broadcast compare against all segments — elementwise 64-bit ops are
  vectorizable (cheap) even though 64-bit scatters are not.
- Everything else falls back to ``jax.ops.*``.

The reference engine hits the same wall differently: its per-row Java
group-by loop is why it bytecode-compiles accumulators
(operator/aggregation/AccumulatorCompiler.java); here the fix is mapping
the fold onto the systolic array instead of the (emulated) scatter unit.
"""

from __future__ import annotations

import contextlib
import contextvars

import jax
import jax.numpy as jnp

BLOCK = 256  # rows per exact f32 partial (256 * 255 < 2^24)
NLIMBS = 8  # 8-bit limbs of a 64-bit value (bf16-exact: 255 < 2^8)
MAX_MATMUL_K = 512  # one-hot matmul path bound (flops scale with k)
MAX_CMP_K = 128  # broadcast-compare min/max path bound
_CHUNK_BLOCKS = 512  # lax.map granularity: bounds one-hot memory
# a program traced under ``wide_chunks`` folds in fewer, wider steps
_chunk_cap = contextvars.ContextVar("segred_chunk_cap",
                                    default=_CHUNK_BLOCKS)


@contextlib.contextmanager
def wide_chunks(blocks: int):
    """Segment sums traced inside fold up to ``blocks`` blocks a step
    of the mapped loop. A loop step is a dozen device operations
    whatever its width, so a shard of 45 million rows folded 512 blocks
    at a time is 344 steps a sum and some 20,000 operations a
    statement: a profiler trace of ten seconds of them took ten minutes
    to stop. The programs of one chip keep the default (their text and
    so their cache keys stay what they were)."""
    token = _chunk_cap.set(blocks)
    try:
        yield
    finally:
        _chunk_cap.reset(token)


def _chunking(nb: int, kk: int) -> tuple[int, int]:
    """(steps, blocks a step) of the mapped fold over ``nb`` blocks
    into ``kk`` segments."""
    cap = _chunk_cap.get()
    if cap == _CHUNK_BLOCKS:
        return -(-nb // cap), cap
    # a step's one-hot no larger than the default's at MAX_MATMUL_K
    cap = max(_CHUNK_BLOCKS,
              min(cap, _CHUNK_BLOCKS * (MAX_MATMUL_K + 1) // kk))
    steps = 1 << (-(-nb // cap) - 1).bit_length()
    if nb % steps == 0:  # equal steps: no padded copy of the column
        return steps, nb // steps
    return -(-nb // cap), cap


def _use_fast_path(data, num_segments: int, bound: int) -> bool:
    if getattr(data, "ndim", 1) != 1:
        return False
    if num_segments > bound or data.shape[0] < BLOCK:
        return False
    return True


def _pad_to_blocks(data, segment_ids, num_segments: int, fill):
    n = data.shape[0]
    nb = -(-n // BLOCK)
    pad = nb * BLOCK - n
    if pad:
        data = jnp.concatenate(
            [data, jnp.full((pad,), fill, data.dtype)])
        # padded rows target a dead segment sliced off at the end
        segment_ids = jnp.concatenate(
            [segment_ids,
             jnp.full((pad,), num_segments, segment_ids.dtype)])
    return data, segment_ids, nb


def _blocked_onehot_sums(u, segment_ids, k: int, nb: int):
    """Per-segment f64 totals of each 8-bit limb of ``u`` (uint64
    [nb*BLOCK]) via per-block one-hot matmuls. Limb extraction happens
    inside the mapped chunk so the [n, NLIMBS] f32 tensor is never
    materialized whole. Returns f64 [k+1, NLIMBS] (last row = pad
    segment)."""
    uu = u.reshape(nb, BLOCK)
    sid = segment_ids.reshape(nb, BLOCK)
    kk = k + 1  # pad segment

    def chunk_sum(args):
        sid_c, u_c = args
        limbs = jnp.stack(
            [((u_c >> jnp.uint64(8 * j)) & jnp.uint64(0xFF))
             .astype(jnp.float32) for j in range(NLIMBS)], axis=-1)
        oh = (sid_c[:, :, None]
              == jnp.arange(kk, dtype=sid.dtype)).astype(jnp.float32)
        # contract only the within-block axis: operands are 0..255
        # (bf16-exact) and partials stay < 2^24 (f32-accumulate-exact)
        pb = jnp.einsum("xbk,xbl->xkl", oh, limbs,
                        preferred_element_type=jnp.float32)
        return pb.astype(jnp.float64).sum(axis=0)

    nchunks, chunk = _chunking(nb, kk)
    if nchunks == 1:
        return chunk_sum((sid, uu))
    pad_b = nchunks * chunk - nb
    if pad_b:
        sid = jnp.concatenate(
            [sid, jnp.full((pad_b, BLOCK), kk - 1, sid.dtype)])
        uu = jnp.concatenate(
            [uu, jnp.zeros((pad_b, BLOCK), uu.dtype)])
    sid = sid.reshape(nchunks, chunk, BLOCK)
    uu = uu.reshape(nchunks, chunk, BLOCK)
    per_chunk = jax.lax.map(chunk_sum, (sid, uu))
    return per_chunk.sum(axis=0)


def _sum_int64_like(data, segment_ids, num_segments: int, out_dtype):
    # astype(uint64) sign-extends, so two's-complement arithmetic below
    # reproduces wrapping int64 scatter-add for every integer width
    u = data.astype(jnp.uint64)
    u, segment_ids, nb = _pad_to_blocks(u, segment_ids, num_segments,
                                        jnp.uint64(0))
    totals = _blocked_onehot_sums(u, segment_ids,
                                  num_segments, nb)[:num_segments]
    # limb totals < 6e6 * 255 < 2^53: exact integers in f64; the uint64
    # shift-accumulate reassembles the sum mod 2^64 (= scatter-add wrap)
    acc = jnp.zeros((num_segments,), jnp.uint64)
    for j in range(NLIMBS):
        acc = acc + (totals[:, j].astype(jnp.uint64)
                     << jnp.uint64(8 * j))
    return acc.astype(out_dtype)


def segment_sum(data, segment_ids, num_segments: int, **kwargs):
    dt = data.dtype
    if _use_fast_path(data, num_segments, MAX_MATMUL_K) and (
            jnp.issubdtype(dt, jnp.integer) or dt == jnp.bool_):
        out = jnp.int64 if dt == jnp.bool_ else dt
        return _sum_int64_like(data, segment_ids, num_segments, out)
    return jax.ops.segment_sum(data, segment_ids,
                               num_segments=num_segments, **kwargs)


def _cmp_reduce(data, segment_ids, num_segments: int, is_max: bool):
    """Per-segment min/max via chunked broadcast compare: elementwise
    64-bit select is vector-friendly; only scatters are pathological."""
    if jnp.issubdtype(data.dtype, jnp.floating):
        ident = jnp.array(-jnp.inf if is_max else jnp.inf, data.dtype)
    else:
        info = jnp.iinfo(data.dtype)
        ident = jnp.array(info.min if is_max else info.max, data.dtype)
    data, segment_ids, nb = _pad_to_blocks(
        data, segment_ids, num_segments, ident)
    n = nb * BLOCK
    chunk_rows = _CHUNK_BLOCKS * BLOCK
    nchunks = -(-n // chunk_rows)
    pad = nchunks * chunk_rows - n
    if pad:
        data = jnp.concatenate([data, jnp.full((pad,), ident, data.dtype)])
        segment_ids = jnp.concatenate(
            [segment_ids,
             jnp.full((pad,), num_segments, segment_ids.dtype)])
    data = data.reshape(nchunks, chunk_rows)
    segment_ids = segment_ids.reshape(nchunks, chunk_rows)
    seg_range = jnp.arange(num_segments, dtype=segment_ids.dtype)
    op = jnp.maximum if is_max else jnp.minimum

    def body(carry, args):
        d, s = args
        m = s[None, :] == seg_range[:, None]  # [k, chunk_rows]
        vals = jnp.where(m, d[None, :], ident)
        red = vals.max(axis=1) if is_max else vals.min(axis=1)
        return op(carry, red), None

    init = jnp.full((num_segments,), ident, data.dtype)
    out, _ = jax.lax.scan(body, init, (data, segment_ids))
    return out


def _cmp_eligible(data, num_segments: int) -> bool:
    return (_use_fast_path(data, num_segments, MAX_CMP_K)
            and (jnp.issubdtype(data.dtype, jnp.integer)
                 or jnp.issubdtype(data.dtype, jnp.floating)))


def segment_max(data, segment_ids, num_segments: int, **kwargs):
    if _cmp_eligible(data, num_segments):
        return _cmp_reduce(data, segment_ids, num_segments, True)
    return jax.ops.segment_max(data, segment_ids,
                               num_segments=num_segments, **kwargs)


def segment_min(data, segment_ids, num_segments: int, **kwargs):
    if _cmp_eligible(data, num_segments):
        return _cmp_reduce(data, segment_ids, num_segments, False)
    return jax.ops.segment_min(data, segment_ids,
                               num_segments=num_segments, **kwargs)
