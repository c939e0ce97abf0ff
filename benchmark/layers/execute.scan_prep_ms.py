"""The statement's ``scan-collect`` and ``bucket-pad`` spans together
(``exec/executor.collect_scans``: the connector's columns as host arrays;
``prepare_plan`` around ``templates.bucket_scans``: padding every column to
its power-of-two bucket, a copy of the table when the pad cache misses,
as it does for a table an INSERT just changed): sum per statement,
median per class, geometric mean over classes; ms."""

import arith
import shapes

SPANS = ("scan-collect", "bucket-pad")


def read(ctx):
    return arith.geomean_of_class_medians(
        ctx.records, lambda r: shapes.span_ms(ctx, r, SPANS) or None)
