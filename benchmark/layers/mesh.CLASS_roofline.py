"""A query class's share of the mesh's memory roofline: the bytes the
class must read (``shapes.must_read_bytes``) over the cell's ``chips``
times one chip's peak bytes/s, as a percentage of the BUSIEST device
plane's busy time inside one statement (the statement waits for its
slowest chip); median over the class's statements wholly inside the
traced sub-window; closed loops only. One reader for every
``mesh.<class>_roofline``: the harness hands it the class that the
metric's name holds."""

import arith
import shapes
import tracered


def read(ctx, cls):
    if ctx.trace is None or ctx.peaks is None or cls not in ctx.classes \
            or ctx.mix["loop"] != "closed":
        return None
    busy = [max(tracered.covered(plane, r["sent"], r["done"])
                for plane in ctx.trace.busy)
            for r in shapes.inside(ctx.records, ctx.trace.lo, ctx.trace.hi)
            if r["cls"] == cls]
    busy = [b for b in busy if b > 0]
    if not busy:
        return None
    least_s = (shapes.must_read_bytes(ctx.classes[cls], ctx.data)
               / (ctx.cell["chips"] * ctx.peaks["hbm_bytes_per_s"]))
    return 100.0 * least_s / arith.median(busy)
