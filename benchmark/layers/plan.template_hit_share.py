"""Plan-template hits over hits plus misses, window delta; 1.0 where
every statement of the window reused a warmed template."""


def read(ctx):
    hits = ctx.counters.get("presto_tpu_template_cache_hits_total", 0.0)
    misses = ctx.counters.get("presto_tpu_template_cache_misses_total", 0.0)
    return hits / (hits + misses) if hits + misses else None
