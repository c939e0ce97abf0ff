"""pow2 bucketing of scan shapes for plan-template cache keys.

A compiled XLA executable is pinned to exact input shapes, so a
template over literal variants only pays off while the scanned tables
keep their shapes. Bucketing pads every host scan buffer up to the
next power of two (dead rows masked via the engine's ``__live__``
row-mask convention — the same mechanism block-streamed scans,
exchange pages, and distributed shards already use), which makes the
shape component of the template key a pow2 bucket exactly like the
capacity component (exec/progcache.bucket_capacities): a table growing
within its bucket, or spill/exchange temporaries of nearby sizes,
keep hitting the same executable.

Padded copies of connector-owned arrays are cached per engine (strong
host ref pins the id, the device-pin-cache pattern), so repeat
executions upload the SAME padded object and Engine.device_array keeps
its HBM hit rate; per-execution temporaries pad without caching.
"""

from __future__ import annotations

import dataclasses
import functools
import threading

import numpy as np

from presto_tpu.ops.hash import next_pow2

# engine id -> {id(array): (orig ref, padded)} with a shared mask pool;
# bounded: a full clear is only a lost optimization, never a bug
_PAD_CACHE: dict = {}
_PAD_CACHE_MAX_ARRAYS = 512
_PAD_LOCK = threading.Lock()


def invalidate_pad_cache(engine) -> None:
    """Drop ``engine``'s cached padded copies. MUST be called wherever
    the device-pin cache is invalidated (Engine.invalidate_device_cache
    — DML/DDL statements): connectors may mutate table arrays IN PLACE
    (memory.update_rows), and the id-keyed identity check cannot see a
    same-object content change."""
    eid = id(engine)
    with _PAD_LOCK:
        for key in [k for k in _PAD_CACHE if k[0] == eid]:
            del _PAD_CACHE[key]


def _pad_rows(a: np.ndarray, cap: int,
              stats: dict | None = None) -> np.ndarray:
    padded = np.pad(a, [(0, cap - a.shape[0])] + [(0, 0)] * (a.ndim - 1))
    if stats is not None:
        stats["padded_bytes"] = (stats.get("padded_bytes", 0)
                                 + padded.nbytes)
    return padded


def _cached_pad(engine, a: np.ndarray, cap: int,
                stats: dict | None = None) -> np.ndarray:
    key = (id(engine), id(a), cap)
    with _PAD_LOCK:
        hit = _PAD_CACHE.get(key)
        if hit is not None and hit[0] is a:
            return hit[1]
    padded = _pad_rows(a, cap, stats)
    with _PAD_LOCK:
        if len(_PAD_CACHE) >= _PAD_CACHE_MAX_ARRAYS:
            _PAD_CACHE.clear()
        _PAD_CACHE[key] = (a, padded)
    return padded


def bucket_scan_inputs(engine, scan_inputs: list,
                       stats: dict | None = None) -> list:
    """ScanInputs with every host (numpy) scan padded to a pow2 row
    bucket, dead pad rows masked via ``__live__``. Device-resident
    inputs (segment carriers — already pow2-compacted by
    device_outputs) and empty or already-bucketed scans pass through
    untouched. ``stats["padded_bytes"]`` counts the bytes of the
    copies made by this call (cached pads add nothing)."""
    out = []
    for scan in scan_inputs:
        arrays = scan.arrays
        first = next(iter(arrays.values()), None)
        if (first is None or not isinstance(first, np.ndarray)
                or first.shape[0] == 0):
            out.append(scan)
            continue
        n = int(first.shape[0])
        cap = next_pow2(n)
        if cap <= n:
            out.append(scan)
            continue
        pad = (functools.partial(_cached_pad, engine)
               if getattr(scan, "cache_device", False) else _pad_rows)
        padded: dict = {}
        for sym, a in arrays.items():
            if sym == "__live__":
                continue
            padded[sym] = pad(a, cap, stats)
        base_live = arrays.get("__live__")
        if base_live is not None:
            live = pad(np.asarray(base_live), cap, stats)
        else:
            live = _live_mask(n, cap)
        padded["__live__"] = live
        out.append(dataclasses.replace(scan, arrays=padded, nrows=cap))
    return out


# (rows, cap) -> mask; tiny and shared across engines (masks are
# read-only on both host and device)
_MASK_CACHE: dict = {}


def _live_mask(n: int, cap: int) -> np.ndarray:
    with _PAD_LOCK:
        m = _MASK_CACHE.get((n, cap))
        if m is not None:
            return m
    m = np.arange(cap) < n
    with _PAD_LOCK:
        if len(_MASK_CACHE) >= _PAD_CACHE_MAX_ARRAYS:
            _MASK_CACHE.clear()
        _MASK_CACHE[(n, cap)] = m
    return m
