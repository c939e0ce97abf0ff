"""The designated host<->device synchronization boundary.

Every deliberate device->host read on the execute path goes through
this module: ``fetch`` (ONE batched ``jax.device_get`` over an
arbitrary pytree — a tuple of separate ``np.asarray`` calls pays a
device round-trip EACH),
``fetch_int`` (a scalar sizing read, e.g. a live-row count), and
``wait`` (``block_until_ready`` so an execute span covers real device
time). Each call increments ``presto_tpu_device_syncs_total`` labeled
by call site, so bench.py can report per-query sync counts
(``qNN_device_syncs``) next to wall time — the first real-TPU run
must show the hot path syncs a bounded, constant number of times per
query.

The ``device-sync`` lint (lint/devicesync.py) enforces the boundary
statically: any host-blocking sync on the execute path OUTSIDE this
module is a finding. Deliberate exceptions are declared in
``DEVICE_SYNC_EXEMPT`` below (id -> justification) and carry the same
staleness discipline as ``TRACE_KEY_EXEMPT``: an entry that matches
no finding is itself a finding.
"""

from __future__ import annotations

import jax

from presto_tpu.obs.metrics import REGISTRY

SYNCS = REGISTRY.counter(
    "presto_tpu_device_syncs_total",
    "Host-blocking device->host synchronizations through the "
    "exec.hostsync boundary, labeled by call site")


def fetch(tree, site: str):
    """One batched device->host transfer of an arbitrary pytree.
    Returns the same structure with host (numpy) leaves; host leaves
    pass through unchanged, so callers need not split mixed trees."""
    SYNCS.inc(site=site)
    return jax.device_get(tree)


def fetch_int(x, site: str) -> int:
    """Scalar sizing read (live-row count, capacity probe): one
    round-trip, one int."""
    SYNCS.inc(site=site)
    return int(jax.device_get(x))


def wait(x, site: str):
    """Block until ``x`` is computed (measurement sync): the point an
    async dispatch actually finishes, so the enclosing span/timer
    covers device time instead of call overhead. Returns ``x``."""
    SYNCS.inc(site=site)
    return jax.block_until_ready(x)


# Deliberate syncs OUTSIDE the boundary, id -> justification. Id form:
# "<relpath>:<dotted.unit.path>:<kind>" where kind names the sync
# (device_get | block_until_ready | asarray | int | float | bool |
# item | tolist). Stale entries (matching no finding) are findings.
DEVICE_SYNC_EXEMPT = {
    "presto_tpu/exec/profile.py:_profiled_compile_run:block_until_ready":
        "EXPLAIN ANALYZE execute-wall measurement: the sync IS the "
        "measurement, and it stays outside the boundary so profiling "
        "runs do not inflate the hot-path sync counter bench.py "
        "reports per query",
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
