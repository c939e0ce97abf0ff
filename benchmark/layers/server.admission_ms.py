"""The statement's ``admission`` span: from the POST that created it
(``QueryInfo.created_wall``) to the start of its ``query`` span on a pool
thread, so resource-group queueing and the pool's hand-over; median per
class, geometric mean over classes; ms. Statements with a trace only."""

import arith
import shapes

SPANS = ("admission",)


def read(ctx):
    return arith.geomean_of_class_medians(
        ctx.records, lambda r: shapes.span_ms(ctx, r, SPANS) or None)
