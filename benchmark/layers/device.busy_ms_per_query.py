"""Milliseconds an operation ran on the device per statement, from the
profiler trace. Closed loop: the busy time inside each statement wholly
in the traced sub-window, median per class, geometric mean. Open loop:
the sub-window's busy time over the statements answered in it."""

import arith
import shapes


def read(ctx):
    if ctx.trace is None:
        return None
    if ctx.mix["loop"] == "closed":
        meds = [b for b in (shapes.busy_ms(ctx, c) for c in ctx.classes)
                if b]
        return arith.geomean(meds) if meds else None
    n = sum(1 for r in arith.good(ctx.records)
            if ctx.trace.lo <= r["done"] <= ctx.trace.hi)
    return ctx.trace.busy_s * 1e3 / n if n else None
