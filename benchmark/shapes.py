"""Shape functions of the yardstick: the bytes a query class must read.

The least a class can move is every column its text touches, once, at
the width the data has: ``reads`` of the class file (table -> columns)
times the generated column's item size times its rows. No operation
count: these queries are scans, joins and sorts over int64 with a few
operations per byte, far under the chip's ridge, so memory bounds them.
"""

from __future__ import annotations

import arith


def must_read_bytes(cls: dict, data) -> int:
    total = 0
    for table, columns in cls.get("reads", {}).items():
        for name in columns:
            total += data.col(table, name).nbytes
    return total


def inside(records: list[dict], lo: float, hi: float) -> list[dict]:
    """Good statements sent and answered inside [lo, hi]."""
    return [r for r in arith.good(records)
            if r["sent"] >= lo and r["done"] <= hi]


def busy_ms(ctx, cls: str) -> float | None:
    """Median device-busy milliseconds inside a statement of ``cls``,
    over the statements wholly inside the traced sub-window; None in an
    open loop, where statements overlap and busy time has no owner."""
    if ctx.trace is None or ctx.mix["loop"] != "closed":
        return None
    rs = [r for r in inside(ctx.records, ctx.trace.lo, ctx.trace.hi)
          if r["cls"] == cls]
    if not rs:
        return None
    return arith.median([ctx.trace.busy_between(r["sent"], r["done"]) * 1e3
                         for r in rs])


def roofline_pct(ctx, cls: str) -> float | None:
    """Least time for the class (bytes it must read over the peak
    bytes/s) as a percentage of the device-busy time of one statement."""
    if ctx.peaks is None or cls not in ctx.classes:
        return None
    busy = busy_ms(ctx, cls)
    if not busy:
        return None
    least_ms = (must_read_bytes(ctx.classes[cls], ctx.data)
                / ctx.peaks["hbm_bytes_per_s"] * 1e3)
    return 100.0 * least_ms / busy


def span_ms(ctx, rec: dict, names: tuple[str, ...]) -> float | None:
    """Milliseconds the statement's spans of these names took together;
    None where the program kept no trace of it (a result-cache hit on
    the fast path, or one past the 256 traces it retains)."""
    spans = ctx.spans.get(rec.get("qid", ""))
    if not spans:
        return None
    return sum(s["t1"] - s["t0"] for s in spans if s["name"] in names) * 1e3


def class_ms(ctx, cls: str) -> float | None:
    """Median client wall of the window's good statements of ``cls``."""
    rs = [r for r in arith.good(ctx.records) if r["cls"] == cls]
    return arith.median([arith.wall_ms(r) for r in rs]) if rs else None
