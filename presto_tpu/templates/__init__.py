"""Parameterized plan templates: shape-polymorphic compile sharing
across literal variants.

The PR 4 program cache only pays off on exact replays; production
traffic is the same query shapes with different literals and dates
(ROADMAP item 2). This subsystem hoists constants out of traced
programs into runtime arguments (analysis.py), keys the program cache
on the parameterized template + pow2-bucketed input shapes (shapes.py,
exec/executor.py / parallel/executor.py integration), and exposes the
Trino PREPARE / EXECUTE ... USING surface (prepared.py) — so
``Q5 WHERE region='ASIA'`` hits the executable compiled for
``region='EUROPE'`` and the 70-152 s XLA compile becomes a
once-per-template cost.

Session properties: ``plan_templates`` (master switch, default on) and
``template_shape_bucketing`` (pad host scans to pow2 row buckets,
default on).
"""

from __future__ import annotations

from presto_tpu.obs.metrics import REGISTRY
from presto_tpu.templates.analysis import (  # noqa: F401
    HOISTABLE_CALL_FNS, STRING_HOISTABLE_FNS, ParamSpec, Template,
    parameterize)
from presto_tpu.templates.shapes import bucket_scan_inputs  # noqa: F401

_TPL_HITS = REGISTRY.counter(
    "presto_tpu_template_cache_hits_total",
    "templated program-cache lookups that found a compiled executable "
    "(a literal variant reused another variant's program)")
_TPL_MISSES = REGISTRY.counter(
    "presto_tpu_template_cache_misses_total",
    "templated program-cache lookups that had to compile")
_TPL_PARAMS = REGISTRY.gauge(
    "presto_tpu_template_params_hoisted",
    "literals hoisted into the parameter vector of the most recent "
    "templated program")


def enabled(session) -> bool:
    try:
        return bool(session.get("plan_templates"))
    except Exception:  # noqa: BLE001 - sessions without the property
        return False


def shape_bucketing(session) -> bool:
    try:
        return bool(session.get("template_shape_bucketing"))
    except Exception:  # noqa: BLE001
        return False


def bucket_scans(engine, scan_inputs: list,
                 stats: dict | None = None) -> list:
    """Apply pow2 shape bucketing when the session asks for it.
    ``stats["padded_bytes"]`` grows by the bytes of every padded copy
    made now (a pad found in the cache adds nothing)."""
    if not shape_bucketing(engine.session):
        return scan_inputs
    return bucket_scan_inputs(engine, scan_inputs, stats)


def note_lookup(hit: bool, params: int) -> None:
    """Record one templated program-cache lookup."""
    _TPL_PARAMS.set(params)
    (_TPL_HITS if hit else _TPL_MISSES).inc()
