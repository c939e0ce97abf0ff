"""Sum of the statement's ``execute`` spans (dispatch to the fetched
result, so device time is inside): median per class, geometric mean over
the classes that have such spans; ms. The streamed scan opens none."""

import arith
import shapes


def read(ctx):
    return arith.geomean_of_class_medians(
        ctx.records, lambda r: shapes.span_ms(ctx, r, ("execute",)) or None)
