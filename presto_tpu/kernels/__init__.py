"""Hand-written Pallas/Mosaic kernels for the operator inner loops.

The operator layer (exec/operators.py) lowers everything to
whole-array XLA ops over pow2-padded buffers — hash joins pay
sort/gather cascades, aggregations pay full-width segment ops, and
compaction pays nonzero+gather passes. This package hand-writes the
3-4 inner loops that dominate ``system.operator_stats`` as Pallas
kernels with tiled HBM->VMEM pipelines:

==============  ===================================  ====================
kernel          Pallas implementation                XLA fallback
==============  ===================================  ====================
join_lookup     open-addressing build+probe          sorted-merge lookup
                (kernels/hashjoin.py)                (ops/hash.probe_runs)
multijoin       fused star-chain probe walk          sequential sorted
                (kernels/multijoin.py)               walk (apply_multi_join)
agg_sum/min/max per-tile VMEM accumulate             ops/segred.py
                (kernels/segagg.py)                  (MXU limb matmuls)
compact         one-pass dense survivor write        nonzero+gather
                (kernels/compact.py)                 (compact_dtable)
==============  ===================================  ====================

Every kernel has a NUMERICALLY IDENTICAL fallback — the pre-kernel
XLA path — registered beside it in :data:`KERNELS` (the
``kernel-parity`` lint rule keeps the table total). Selection is the
``kernel_backend`` session property:

- ``auto`` (default): per kernel — Pallas on a TPU for the kernels
  named in :data:`AUTO_PALLAS` (those that passed on the chip), the
  XLA twin for every other kernel and on every other platform;
- ``pallas``: force the kernels. On the CPU platform they run under
  ``pl.pallas_call(interpret=True)`` so the CPU test tier executes
  the real kernel bodies; on any other platform they compile, and a
  kernel the compiler refuses fails the query with the compiler's
  message — there is no trace-time swap to XLA;
- ``xla``: force the fallbacks.

The session's setting is installed as an ambient context for the
duration of one plan trace (both interpreters wrap ``interp.run``),
rides the program-cache key (``kernel_backend`` is in
TRACE_RELEVANT_PROPERTIES and what ``auto`` selects here rides the
platform fingerprint), and every dispatch is noted against the plan
node being traced so ``system.operator_stats`` can name the kernel
and split execute wall per operator.
"""

from __future__ import annotations

import contextlib
import contextvars

from presto_tpu.kernels import compact as _compact
from presto_tpu.kernels import hashjoin as _hashjoin
from presto_tpu.kernels import multijoin as _multijoin
from presto_tpu.kernels import segagg as _segagg

BACKENDS = ("auto", "pallas", "xla")

_ACTIVE: contextvars.ContextVar[str] = contextvars.ContextVar(
    "presto_tpu_kernel_backend", default="xla")
_USED: contextvars.ContextVar[list | None] = contextvars.ContextVar(
    "presto_tpu_kernel_used", default=None)


# kernel name -> backend -> implementation. Both entries of every row
# must exist and be reachable from dispatch() — asserted statically by
# the kernel-parity lint rule (lint/kernels.py).
KERNELS: dict[str, dict[str, object]] = {
    "join_lookup": {"pallas": _hashjoin.lookup_join_pallas,
                    "xla": _hashjoin.lookup_join_xla},
    "agg_sum": {"pallas": _segagg.segment_sum_pallas,
                "xla": _segagg.segment_sum_xla},
    "agg_max": {"pallas": _segagg.segment_max_pallas,
                "xla": _segagg.segment_max_xla},
    "agg_min": {"pallas": _segagg.segment_min_pallas,
                "xla": _segagg.segment_min_xla},
    "compact": {"pallas": _compact.filter_compact_pallas,
                "xla": _compact.filter_compact_xla},
    "multijoin": {"pallas": _multijoin.try_fused,
                  "xla": _multijoin.try_fused_xla},
}


# Kernels ``auto`` runs as Pallas on a TPU. A kernel is listed here only
# after it compiled under Mosaic on the chip (not interpreted), returned
# its XLA twin's answer at the shapes TPC-H SF1 produces, and ran within
# about 2x of the twin (chip_smoke.py's kernel leg prints the table).
# First v5e run (PR 21, one chip, SF1, 6.0M rows, 6 segments; the table
# is in CHANGES.md): agg_sum compiled and matched but took 133.6 ms
# against the twin's 2.4 ms, agg_max/agg_min 141.6 ms against 1.4 ms —
# a per-row loop on the scalar core loses to the MXU/broadcast bodies
# by 55-100x; join_lookup, multijoin and compact do per-row scalar
# read-modify-write on VMEM-resident tables, which Pallas refuses before
# Mosaic sees them ("ValueError: Cannot store scalars to VMEM"). All six
# need a vectorised redesign (ROADMAP S1(b)/D2), so the set is empty and
# ``auto`` is the XLA bodies everywhere — what every chip record ran.
AUTO_PALLAS: frozenset[str] = frozenset()


def auto_backend(name: str) -> str:
    """What ``auto`` resolves kernel ``name`` to on this process'
    platform."""
    import jax
    on_tpu = name in AUTO_PALLAS and jax.default_backend() == "tpu"
    return "pallas" if on_tpu else "xla"


def auto_pallas_here() -> list[str]:
    """The kernels ``auto`` runs as Pallas on this process' platform,
    sorted (the platform fingerprint and reports name this set)."""
    return sorted(k for k in KERNELS if auto_backend(k) == "pallas")


def interpret_mode() -> bool:
    """Pallas kernels run interpreted on the CPU platform only (forced
    ``pallas`` on a CPU container is exactly how tier-1 exercises the
    kernel bodies). On any other platform a Pallas call compiles, or
    the query fails with the compiler's message."""
    import jax
    return jax.default_backend() == "cpu"


def resolve(session) -> str:
    """The session's ``kernel_backend`` setting, normalised to one of
    :data:`BACKENDS`. ``auto`` stays ``auto``: it resolves per kernel
    at dispatch (:func:`backend_for`)."""
    try:
        value = str(session.get("kernel_backend") or "auto").lower()
    except Exception:  # noqa: BLE001 - sessionless callers get auto
        value = "auto"
    return value if value in BACKENDS else "auto"


@contextlib.contextmanager
def use_backend(backend: str):
    """Install the resolved backend for one plan trace (ambient, like
    the trace context — operators and ops/segred read it instead of
    threading a session through every call)."""
    tok = _ACTIVE.set(backend)
    try:
        yield
    finally:
        _ACTIVE.reset(tok)


@contextlib.contextmanager
def collect():
    """Collect the kernel dispatches of one plan node's trace (the
    interpreter wraps each node handler; nested nodes re-enter, so
    notes land on the NEAREST enclosing node)."""
    used: list[str] = []
    tok = _USED.set(used)
    try:
        yield used
    finally:
        _USED.reset(tok)


def backend_for(name: str) -> str:
    """The concrete backend kernel ``name`` runs on under the active
    trace's setting."""
    backend = _ACTIVE.get()
    return auto_backend(name) if backend == "auto" else backend


def dispatch(name: str):
    """The active backend's implementation of kernel ``name``.
    Attribution is SELF-noted by the implementations (each function
    calls :func:`note` for the path that actually executes) — a
    pallas entry may still decline at its eligibility gate and run
    the XLA fallback, and a dispatch-time note would name a kernel
    that never ran."""
    return KERNELS[name][backend_for(name)]


def note(tag: str) -> None:
    """Record one kernel execution (``backend:kernel``) against the
    collecting plan node. No-op outside a collection scope."""
    used = _USED.get()
    if used is not None and tag not in used:
        used.append(tag)
