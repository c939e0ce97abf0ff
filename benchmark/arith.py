"""Metric arithmetic of the benchmark, on plain statement records.

A record is what ``loadgen.py`` writes: ``cls``, ``due``, ``sent``,
``done`` (seconds on one monotonic clock) and ``rows`` or ``error``. A
record counts as good when it has no ``error`` and ``verify.py`` did not
mark it ``wrong``; only good records enter a latency or a rate.
"""

from __future__ import annotations

import math


def good(records: list[dict]) -> list[dict]:
    return [r for r in records if "error" not in r and not r.get("wrong")]


def median(xs: list[float]) -> float:
    s = sorted(xs)
    n = len(s)
    if n == 0:
        raise ValueError("median of nothing")
    return s[n // 2] if n % 2 else 0.5 * (s[n // 2 - 1] + s[n // 2])


def geomean(xs: list[float]) -> float:
    if not xs or min(xs) <= 0:
        raise ValueError("geometric mean needs positive values")
    return math.exp(sum(math.log(x) for x in xs) / len(xs))


def wall_ms(rec: dict) -> float:
    """Client-side wall of one statement: from its due time (open loop;
    in a closed loop ``due`` is the send) to its last page."""
    return (rec["done"] - rec["due"]) * 1e3


def by_class(records: list[dict]) -> dict[str, list[dict]]:
    out: dict[str, list[dict]] = {}
    for r in records:
        out.setdefault(r["cls"], []).append(r)
    return out


def geomean_of_class_medians(records: list[dict],
                             value=wall_ms) -> float | None:
    """Geometric mean over classes of the median of ``value(record)``
    over the class's good records. TPC-H's power metric has this form: a
    change in any class shows, and a slow class does not drown a fast
    one. Records where ``value`` is None are left out, classes with none
    too; None when nothing is left or a median is not positive."""
    meds = []
    for _cls, rs in sorted(by_class(good(records)).items()):
        xs = [v for v in (value(r) for r in rs) if v is not None]
        if xs:
            meds.append(median(xs))
    if not meds or min(meds) <= 0:
        return None
    return geomean(meds)


def qph(records: list[dict], t0: float) -> float:
    """Good statements per hour, over the time from the window's start
    to the last completion (not over the nominal length, so a 14 s
    statement does not quantise it)."""
    ok = good(records)
    last = max(r["done"] for r in ok)
    return len(ok) * 3600.0 / (last - t0)


def percentile(xs: list[float], p: float, beyond: int = 10) -> float | None:
    """Nearest-rank percentile, or None unless ``beyond`` samples lie
    above it (a tail read from fewer is one reading, not a tail)."""
    s = sorted(xs)
    rank = math.ceil(p / 100.0 * len(s))
    if rank < 1 or len(s) - rank < beyond:
        return None
    return s[rank - 1]
