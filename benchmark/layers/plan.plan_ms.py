"""The statement's ``plan`` spans (parse, analyse, plan or template
lookup): median per class, geometric mean over classes; ms."""

import arith
import shapes


def read(ctx):
    return arith.geomean_of_class_medians(
        ctx.records, lambda r: shapes.span_ms(ctx, r, ("plan",)) or None)
