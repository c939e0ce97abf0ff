"""The statement's ``dedup-wait`` span (``server/serving._dedup_execute``:
a follower waiting for the in-flight execution of the same plan, as the
``sel``s behind an INSERT do): median per class, geometric mean over the
classes that have such spans; ms."""

import arith
import shapes

SPANS = ("dedup-wait",)


def read(ctx):
    return arith.geomean_of_class_medians(
        ctx.records, lambda r: shapes.span_ms(ctx, r, SPANS) or None)
