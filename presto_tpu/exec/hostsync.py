"""The designated host<->device synchronization boundary.

Every deliberate device->host read on the execute path goes through
this module: ``fetch`` (ONE batched ``jax.device_get`` over an
arbitrary pytree — a tuple of separate ``np.asarray`` calls pays a
device round-trip EACH),
``fetch_int`` (a scalar sizing read, e.g. a live-row count), and
``wait`` (``block_until_ready`` so an execute span covers real device
time). Each call increments ``presto_tpu_device_syncs_total`` and
observes ``presto_tpu_device_sync_seconds``, both labeled by call
site, and under a trace opens a ``sync/<site>`` span around the
blocking call: the benchmark reads the counter as
``hostsync.syncs_per_query`` and the spans as ``hostsync.sync_ms``
(how often a statement stops for the device, and for how long).

The ``device-sync`` lint (lint/devicesync.py) enforces the boundary
statically: any host-blocking sync on the execute path OUTSIDE this
module is a finding. Deliberate exceptions are declared in
``DEVICE_SYNC_EXEMPT`` below (id -> justification) and carry the same
staleness discipline as ``TRACE_KEY_EXEMPT``: an entry that matches
no finding is itself a finding.
"""

from __future__ import annotations

import contextlib
import time

import jax

from presto_tpu.obs.metrics import REGISTRY
from presto_tpu.obs.trace import TRACER

SYNCS = REGISTRY.counter(
    "presto_tpu_device_syncs_total",
    "Host-blocking device->host synchronizations through the "
    "exec.hostsync boundary, labeled by call site")
SYNC_SECONDS = REGISTRY.histogram(
    "presto_tpu_device_sync_seconds",
    "Host time blocked in one device->host synchronization (the "
    "device's remaining work plus the transfer), labeled by call site")


@contextlib.contextmanager
def _sync(site: str):
    """Count one sync at ``site`` and time the blocking call."""
    SYNCS.inc(site=site)
    t0 = time.perf_counter()
    with TRACER.span("sync/" + site):
        yield
    SYNC_SECONDS.observe(time.perf_counter() - t0, site=site)


def fetch(tree, site: str):
    """One batched device->host transfer of an arbitrary pytree.
    Returns the same structure with host (numpy) leaves; host leaves
    pass through unchanged, so callers need not split mixed trees."""
    with _sync(site):
        return jax.device_get(tree)


def fetch_int(x, site: str) -> int:
    """Scalar sizing read (live-row count, capacity probe): one
    round-trip, one int."""
    with _sync(site):
        return int(jax.device_get(x))


def wait(x, site: str):
    """Block until ``x`` is computed (measurement sync): the point an
    async dispatch actually finishes, so the enclosing span/timer
    covers device time instead of call overhead. Returns ``x``."""
    with _sync(site):
        return jax.block_until_ready(x)


# Deliberate syncs OUTSIDE the boundary, id -> justification. Id form:
# "<relpath>:<dotted.unit.path>:<kind>" where kind names the sync
# (device_get | block_until_ready | asarray | int | float | bool |
# item | tolist). Stale entries (matching no finding) are findings.
DEVICE_SYNC_EXEMPT = {
    "presto_tpu/exec/profile.py:_profiled_compile_run:block_until_ready":
        "EXPLAIN ANALYZE execute-wall measurement: the sync IS the "
        "measurement, and it stays outside the boundary so profiling "
        "runs do not inflate the hot-path sync counter the benchmark "
        "reads per statement",
    "presto_tpu/exec/profile.py:_profiled_compile_run:asarray":
        "EXPLAIN ANALYZE ok-flag readback inside the measured execute "
        "window: kept raw beside the block_until_ready above so the "
        "profile's run_s includes the same readback the production "
        "ladder pays, without counting profiling syncs as hot-path "
        "syncs",
    "presto_tpu/obs/devprof.py:harvest:float":
        "compile-time cost harvest: the floats come from "
        "compiled.cost_analysis()'s host-side dict (XLA's static "
        "analysis), never from a device array — no transfer happens",
    "presto_tpu/obs/devprof.py:program_bytes:float":
        "arithmetic over the plain-dict cost summary harvest() "
        "produced (host floats persisted in progcache meta); no "
        "device value can reach here",
    "presto_tpu/obs/devprof.py:attribute:float":
        "attribution math over the harvested host-side cost summary "
        "and Python int row counts; no device value can reach here",
}
