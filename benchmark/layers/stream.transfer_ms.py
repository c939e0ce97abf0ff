"""The statement's ``transfer`` spans (``exec/streaming.py``: the host's
share of one block's ``jax.device_put``; what is still in flight when
the call returns falls into ``execute``): sum per statement, median per
class, geometric mean over the classes that have such spans; ms."""

import arith
import shapes

SPANS = ("transfer",)


def read(ctx):
    return arith.geomean_of_class_medians(
        ctx.records, lambda r: shapes.span_ms(ctx, r, SPANS) or None)
