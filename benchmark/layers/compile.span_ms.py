"""The statement's ``compile`` spans (trace, lower and XLA compile of a
program the caches did not hold, ``exec/executor.prepare_plan``,
``exec/streaming.py``, ``exec/batch.py``): sum per statement, median per
class, geometric mean over the classes that have such spans; ms. The
inside measure of what ``compile.traced_compile_share`` reads from the
profiler's host plane."""

import arith
import shapes

SPANS = ("compile",)


def read(ctx):
    return arith.geomean_of_class_medians(
        ctx.records, lambda r: shapes.span_ms(ctx, r, SPANS) or None)
