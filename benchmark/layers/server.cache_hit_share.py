"""Result-cache hits over hits plus misses, window delta of the
program's counters; left out where the cache saw no lookup."""


def read(ctx):
    hits = ctx.counters.get("presto_tpu_result_cache_hits_total", 0.0)
    misses = ctx.counters.get("presto_tpu_result_cache_misses_total", 0.0)
    return hits / (hits + misses) if hits + misses else None
