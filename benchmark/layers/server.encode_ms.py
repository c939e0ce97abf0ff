"""The statement's ``encode`` spans (``server/server._stream_result``:
slicing a page and encoding it as JSON rows or Arrow bytes): sum per
statement, median per class, geometric mean over classes; ms."""

import arith
import shapes

SPANS = ("encode",)


def read(ctx):
    return arith.geomean_of_class_medians(
        ctx.records, lambda r: shapes.span_ms(ctx, r, SPANS) or None)
