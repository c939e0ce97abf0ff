"""The statement's ``pin`` spans (``engine.device_array``: the upload of a
host array the device-pin cache does not hold, so a new version of a
table): sum per statement, median per class, geometric mean over the
classes that have such spans; ms."""

import arith
import shapes

SPANS = ("pin",)


def read(ctx):
    return arith.geomean_of_class_medians(
        ctx.records, lambda r: shapes.span_ms(ctx, r, SPANS) or None)
