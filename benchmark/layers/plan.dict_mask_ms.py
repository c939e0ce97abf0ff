"""The statement's ``dict-mask`` spans (``templates/runtime.py``: a LIKE
pattern run over every entry of its column's dictionary on the host, to
bind the mask the program indexes by code): sum per statement, median
per class, geometric mean over the classes that have such spans; ms.
A program that opens no such span (it bakes the pattern into the
program instead) leaves the metric out."""

import arith
import shapes


def read(ctx):
    return arith.geomean_of_class_medians(
        ctx.records, lambda r: shapes.span_ms(ctx, r, ("dict-mask",)) or None)
