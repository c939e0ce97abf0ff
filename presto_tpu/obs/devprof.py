"""Device-cost observatory: XLA cost/memory harvesting, per-node cost
attribution, roofline ratios, and on-demand ``jax.profiler`` capture.

The executors harvest :func:`harvest` output into ``meta["cost"]``
right after AOT compilation, BEFORE the program-cache insert — the
summary is pickled alongside the serialized executable, so a disk-tier
warm hit in a fresh process carries the program's device cost without
recompiling (``cost_analysis`` only exists on a live ``Compiled``).

Attribution splits one program's whole-executable figures across its
plan nodes: XLA fuses the operator chain into one computation, so a
per-operator device counter does not exist — the split is a model
(node-kind FLOP factors x rows-through), not a measurement, but it
makes "which operator dominates" answerable from SQL and it fixes the
rows-proportional wall split that let a cheap-wide scan absorb an
expensive-narrow join's wall.

Roofline ratios compare each node's arithmetic intensity (flops/byte)
against the device balance point ``peak_flops / peak_bw`` of the
device the process runs on (:data:`DEVICE_PEAKS`, keyed by
``device_kind``; ``PRESTO_TPU_DEVICE_PEAK_FLOPS`` /
``PRESTO_TPU_DEVICE_PEAK_BW`` override): ratio >= 1 means
compute-bound at peak, < 1 memory-bound. A device that is not in the
table gets no ratio — never one computed from another device's peaks.
"""

from __future__ import annotations

import contextlib
import os
import tempfile
import threading
import time

from presto_tpu.obs.metrics import REGISTRY

ENV_PEAK_FLOPS = "PRESTO_TPU_DEVICE_PEAK_FLOPS"
ENV_PEAK_BW = "PRESTO_TPU_DEVICE_PEAK_BW"
ENV_PROFILE_DIR = "PRESTO_TPU_PROFILE_DIR"

# (peak FLOP/s, peak bytes/s) of one device, keyed by the
# ``device_kind`` JAX reports.
DEVICE_PEAKS: dict[str, tuple[float, float]] = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16,
    # 819 GB/s HBM per chip
    "TPU v5 lite": (197e12, 819e9),
    # an estimate, not a published figure (one AVX2 core feeding from
    # DRAM: 50 GFLOP/s, 20 GB/s); a ratio against it is a CPU-run
    # curiosity and never a device metric
    "cpu": (5.0e10, 2.0e10),
}

_CAPTURES = REGISTRY.counter(
    "presto_tpu_profile_captures_total",
    "Device profiler capture attempts by result (started/failed).")


# -- compile-time harvest ----------------------------------------------------

def harvest(compiled) -> dict | None:
    """Plain-dict device-cost summary of one AOT-compiled executable,
    or None when the backend exposes neither analysis. Duck-typed and
    swallow-all like progcache's ``_estimate_nbytes``: cost harvesting
    must never fail a compile, and the result must pickle (it rides
    the progcache meta to disk)."""
    out: dict = {}
    try:
        ca = compiled.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0] if ca else {}
        flops = float(ca.get("flops") or 0.0)
        nbytes = float(ca.get("bytes accessed") or 0.0)
        if flops > 0:
            out["flops"] = flops
        if nbytes > 0:
            out["bytes"] = nbytes
    except Exception:  # noqa: BLE001 - backend may not implement it
        pass
    try:
        ma = compiled.memory_analysis()
        for key, attr in (("temp_bytes", "temp_size_in_bytes"),
                          ("arg_bytes", "argument_size_in_bytes"),
                          ("out_bytes", "output_size_in_bytes"),
                          ("code_bytes", "generated_code_size_in_bytes")):
            v = getattr(ma, attr, None)
            if v:
                out[key] = int(v)
    except Exception:  # noqa: BLE001 - backend may not implement it
        pass
    return out or None


def device_peaks() -> tuple[float, float] | None:
    """(peak_flops_per_s, peak_bytes_per_s) of the process' device:
    each env override where it parses to a positive number, else the
    :data:`DEVICE_PEAKS` entry of ``jax.devices()[0].device_kind``.
    None when a figure has neither — callers then report no roofline
    value."""
    import jax
    table = DEVICE_PEAKS.get(jax.devices()[0].device_kind)

    def _peak(name: str, index: int) -> float | None:
        try:
            v = float(os.environ.get(name, "") or 0.0)
        except ValueError:
            v = 0.0
        if v > 0:
            return v
        return table[index] if table else None

    flops, bw = _peak(ENV_PEAK_FLOPS, 0), _peak(ENV_PEAK_BW, 1)
    if flops is None or bw is None:
        return None
    return flops, bw


# -- per-node attribution ----------------------------------------------------

# Relative FLOPs-per-row-through by plan-node kind: a join row costs
# hash+probe work that a scan row does not, which is exactly the skew
# the rows-proportional split got wrong.
_FLOP_FACTOR = {
    "TableScan": 1.0, "Filter": 1.0, "Exchange": 1.0, "Limit": 1.0,
    "Project": 2.0, "Unnest": 2.0, "Values": 1.0,
    "Sort": 4.0, "TopN": 4.0,
    "Aggregate": 6.0, "Distinct": 6.0, "Window": 6.0,
    "Join": 8.0, "SemiJoin": 8.0, "MultiJoin": 12.0,
}
_DEFAULT_FLOP_FACTOR = 2.0


def flop_weight(node_type: str, in_rows: int, out_rows: int) -> float:
    """Wall/flops split weight for one node: kind factor x rows-through
    (+1 keeps zero-row nodes attributable)."""
    factor = _FLOP_FACTOR.get(node_type, _DEFAULT_FLOP_FACTOR)
    return factor * (max(0, in_rows) + max(0, out_rows) + 1)


def program_bytes(cost: dict) -> float:
    """Total bytes moved by the program: XLA's 'bytes accessed' when
    reported, else the memory_analysis arg+out+temp footprint."""
    b = float(cost.get("bytes") or 0.0)
    if b > 0:
        return b
    return float((cost.get("arg_bytes") or 0)
                 + (cost.get("out_bytes") or 0)
                 + (cost.get("temp_bytes") or 0))


def attribute(cost: dict | None,
              nodes: list[tuple[str, int, int, int]]
              ) -> tuple[list[dict], list[float] | None]:
    """Apportion one program's device cost across its plan nodes.

    ``nodes`` is ``[(node_type, in_rows, out_rows, output_bytes)]`` in
    operator order. Returns ``(per_node, weights)``: ``per_node`` is a
    list of ``{"flops", "hbmBytes", "intensity", "roofline"}`` dicts
    (empty dicts when no usable cost; no ``roofline`` key when the
    device's peaks are unknown), ``weights`` the flops-share
    wall-split weights (None when the caller should fall back to the
    rows-proportional split)."""
    if not nodes:
        return [], None
    total_flops = float((cost or {}).get("flops") or 0.0)
    total_bytes = program_bytes(cost or {})
    if total_flops <= 0:
        return [{} for _ in nodes], None
    fw = [flop_weight(nt, i, o) for nt, i, o, _b in nodes]
    fw_sum = sum(fw) or 1.0
    # data movement tracks rows-through, without the kind factor
    bw = [float(max(0, i) + max(0, o) + 1) for _nt, i, o, _b in nodes]
    bw_sum = sum(bw) or 1.0
    peaks = device_peaks()
    per_node: list[dict] = []
    for w, b in zip(fw, bw):
        flops = max(1, round(total_flops * w / fw_sum))
        nbytes = max(1, round(total_bytes * b / bw_sum)) \
            if total_bytes > 0 else 1
        intensity = flops / nbytes
        node = {
            "flops": int(flops),
            "hbmBytes": int(nbytes),
            "intensity": round(float(intensity), 4),
        }
        if peaks is not None:
            node["roofline"] = round(
                float(intensity * peaks[1] / peaks[0]), 4)
        per_node.append(node)
    return per_node, fw


# -- on-demand jax.profiler capture ------------------------------------------

_PROF_LOCK = threading.Lock()
# the jax profiler is process-global: one capture at a time
_PROF: dict = {"active": False, "dir": None}


def profile_base_dir() -> str:
    return (os.environ.get(ENV_PROFILE_DIR)
            or os.path.join(tempfile.gettempdir(),
                            "presto_tpu_profiles"))


def capturing() -> bool:
    with _PROF_LOCK:
        return bool(_PROF["active"])


def start_capture(tag: str = "manual") -> dict:
    """Start a programmatic device trace into a fresh subdirectory of
    ``PRESTO_TPU_PROFILE_DIR``. Idempotent: a second start while one
    is live reports the live capture instead of erroring (the jax
    profiler is a process-global singleton)."""
    with _PROF_LOCK:
        if _PROF["active"]:
            return {"profiling": True, "dir": _PROF["dir"],
                    "started": False}
        safe_tag = "".join(c if c.isalnum() or c in "-_." else "_"
                           for c in str(tag))[:80] or "capture"
        d = os.path.join(
            profile_base_dir(),
            f"{safe_tag}-{int(time.time() * 1000)}-{os.getpid()}")
        try:
            os.makedirs(d, exist_ok=True)
            import jax.profiler
            jax.profiler.start_trace(d)
        except Exception as exc:  # noqa: BLE001 - host may lack profiler
            _CAPTURES.inc(result="failed")
            return {"profiling": False, "started": False,
                    "error": f"{type(exc).__name__}: {exc}"}
        _PROF.update(active=True, dir=d)
        _CAPTURES.inc(result="started")
        return {"profiling": True, "dir": d, "started": True}


def stop_capture() -> dict:
    """Stop the live capture; returns the artifact directory (the
    TensorBoard/Perfetto-loadable trace root) or None when no capture
    was live."""
    with _PROF_LOCK:
        if not _PROF["active"]:
            return {"profiling": False, "artifact": None}
        d = _PROF["dir"]
        _PROF.update(active=False, dir=None)
        try:
            import jax.profiler
            jax.profiler.stop_trace()
        except Exception as exc:  # noqa: BLE001 - stop must not raise
            return {"profiling": False, "artifact": None,
                    "error": f"{type(exc).__name__}: {exc}"}
        return {"profiling": False, "artifact": d}


@contextlib.contextmanager
def maybe_capture(enabled: bool, tag: str = "query"):
    """Wrap one query's execution in a device trace when the
    ``device_profile`` session property asks for it. Yields the
    artifact directory (known up front — callers stamp it into the
    query record before running) or None when disabled, unsupported,
    or another capture already owns the global profiler."""
    if not enabled:
        yield None
        return
    res = start_capture(tag)
    if not res.get("started"):
        yield None
        return
    try:
        yield res["dir"]
    finally:
        stop_capture()
