"""Mean handler-thread time of a result-cache fast hit
(``ServingLayer.try_fast_hit`` calls that ended in a hit: memo lookup,
access check, table versions, cache lookup), window delta of
``presto_tpu_fast_hit_seconds``; ms. A fast hit has no trace, so this
is all the program records of 99.6% of ``dash``'s statements."""


def read(ctx):
    n = ctx.counters.get("presto_tpu_fast_hit_seconds_count", 0.0)
    total = ctx.counters.get("presto_tpu_fast_hit_seconds_sum", 0.0)
    return total / n * 1e3 if n else None
