"""Device-to-host syncs per statement that executed: window delta of
``device_syncs_total`` over the good statements answered in the window
that were not answered from the result cache (a count). The counters
run from the window's start, so statements of an open loop's ramp that
were answered after it are counted with the window's."""

import arith


def read(ctx):
    syncs = ctx.counters.get("presto_tpu_device_syncs_total", 0.0)
    hits = ctx.counters.get("presto_tpu_result_cache_hits_total", 0.0)
    late = [r for r in arith.good(ctx.ramp) if r["done"] >= ctx.t0]
    ran = len(arith.good(ctx.records)) + len(late) - hits
    return syncs / ran if ran > 0 else None
