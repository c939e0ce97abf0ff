"""The statement's ``block-input`` spans (``exec/streaming.py``: slicing
and padding one block of the scanned columns on the host): sum per
statement, median per class, geometric mean over the classes that have
such spans; ms."""

import arith
import shapes

SPANS = ("block-input",)


def read(ctx):
    return arith.geomean_of_class_medians(
        ctx.records, lambda r: shapes.span_ms(ctx, r, SPANS) or None)
