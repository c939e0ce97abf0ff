"""Scanned columns pinned across a mesh: each host column of a table
version is placed ONCE, rows block-sharded over the mesh axis in table
order, and every later statement of the deployment is handed the same
device array (the mesh twin of ``Engine.device_array``).

Rows are padded at pin time to the devices' bucketed share
(``shard_rows``), in the shards past the last row alone (no copy of the
whole column); the program masks rows past the live count on the chip
(``parallel/executor.py``). The budget is per device. Entries are keyed
by the host array's identity, like the one-chip pins, and dropped with
them when a statement changes table data
(``Engine.invalidate_device_cache``).
"""

from __future__ import annotations

import threading
import time

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from presto_tpu.exec import hostsync as HS
from presto_tpu.obs.metrics import REGISTRY
from presto_tpu.obs.trace import TRACER
from presto_tpu.ops.hash import next_pow2

_PINS = REGISTRY.counter(
    "presto_tpu_shard_pins_total",
    "host columns placed row-sharded on a mesh (a statement over a "
    "table version the mesh already holds places none)")
_PIN_BYTES = REGISTRY.counter(
    "presto_tpu_shard_pin_bytes_total",
    "bytes moved from the host by those placements, padding included")
# the spans' seconds, summed as they close (the benchmark's
# ``mesh.shard_pin_s`` reads this; ``setup.pin_s`` the spans themselves)
_PIN_SECONDS = REGISTRY.histogram(
    "presto_tpu_shard_pin_seconds",
    "seconds of one placement (its shard-pin span: slicing, the "
    "transfers to every device, and the wait for them)")


# pinned bytes a device holds at most: the one-chip pins' budget, a chip
LIMIT_PER_DEVICE = 8 << 30


def shard_rows(nrows: int, nshards: int) -> int:
    """Rows a device holds of a column of ``nrows`` rows: its share,
    rounded up to a 64th of the next power of two (at most 3% more
    rows), so that tables of nearly the same size (another seed's, the
    same table after an INSERT) have shards of one shape and share
    their compiled programs, as ``template_shape_bucketing`` does for
    one chip."""
    per = -(-max(nrows, 1) // nshards)
    step = max(next_pow2(per) >> 6, 1)
    return -(-per // step) * step


def place(a: np.ndarray, mesh: Mesh) -> jax.Array:
    """``a`` as one array sharded over the mesh's (one) axis: device k
    gets rows [k*per, (k+1)*per), zero-padded past the last row."""
    devices = list(mesh.devices.flat)
    per = shard_rows(a.shape[0], len(devices))
    shards = []
    for k, dev in enumerate(devices):
        part = a[k * per:(k + 1) * per]
        if part.shape[0] < per:  # the last rows' shard, or one past them
            part = np.pad(part, [(0, per - part.shape[0])]
                          + [(0, 0)] * (a.ndim - 1))
        shards.append(jax.device_put(part, dev))
    return jax.make_array_from_single_device_arrays(
        (per * len(devices),) + a.shape[1:],
        NamedSharding(mesh, P(mesh.axis_names[0])), shards)


class ShardPins:
    """(id(host array), mesh devices) -> (host ref, sharded array).
    The strong host ref pins the id; FIFO eviction holds each device's
    share of the pinned bytes under ``LIMIT_PER_DEVICE``. Thread-safe
    the way ``Engine.device_array`` is: the transfer runs outside the
    lock, a lost race keeps the first copy."""

    def __init__(self):
        self._lock = threading.Lock()
        self._entries: dict = {}
        self._bytes_per_device = 0

    def get(self, a: np.ndarray, mesh: Mesh, table: str = "",
            column: str = "") -> jax.Array:
        devices = tuple(d.id for d in mesh.devices.flat)
        key = (id(a), devices)
        with self._lock:
            hit = self._entries.get(key)
            if hit is not None and hit[0] is a:
                return hit[1]
        nbytes = (shard_rows(a.shape[0], len(devices)) * len(devices)
                  * a.itemsize * int(np.prod(a.shape[1:])))
        t0 = time.perf_counter()
        with TRACER.span("shard-pin", table=table, column=column,
                         bytes=nbytes, devices=len(devices)) as span:
            dev = place(a, mesh)
            # the span ends when the rows are on the chips, so set-up
            # sees what a placement costs and not what was enqueued
            HS.wait(dev, site="shard-pin")
        # the span's own seconds where there is one, so that the
        # histogram and a sum over the spans are one number
        seconds = (span.t1 - span.t0 if span is not None
                   else time.perf_counter() - t0)
        with self._lock:
            hit = self._entries.get(key)
            if hit is not None and hit[0] is a:
                return hit[1]  # raced: keep the published copy
            share = nbytes // len(devices)
            self._entries[key] = (a, dev, share)
            self._bytes_per_device += share
            while (self._bytes_per_device > LIMIT_PER_DEVICE
                   and len(self._entries) > 1):
                old = self._entries.pop(next(iter(self._entries)))
                self._bytes_per_device -= old[2]
        _PINS.inc()
        _PIN_BYTES.inc(nbytes)
        _PIN_SECONDS.observe(seconds)
        return dev

    def clear(self) -> None:
        with self._lock:
            self._entries.clear()
            self._bytes_per_device = 0
