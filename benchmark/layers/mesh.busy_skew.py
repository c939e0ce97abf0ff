"""How evenly a statement's device work falls on the chips: per
statement wholly inside the traced sub-window, the busiest device
plane's busy time (union of its ``XLA Ops`` intervals inside the
statement) over the least busy plane's; median over the statements; a
ratio, 1.0 is even. Closed loops on more than one device plane only; a
statement in which some plane did nothing is left out."""

import arith
import shapes
import tracered


def read(ctx):
    if ctx.trace is None or ctx.mix["loop"] != "closed" \
            or len(ctx.trace.busy) < 2:
        return None
    ratios = []
    for r in shapes.inside(ctx.records, ctx.trace.lo, ctx.trace.hi):
        busy = [tracered.covered(plane, r["sent"], r["done"])
                for plane in ctx.trace.busy]
        if min(busy) > 0:
            ratios.append(max(busy) / min(busy))
    return arith.median(ratios) if ratios else None
