"""What one write costs the HTTP handler threads in re-planning: window
delta of ``presto_tpu_fast_path_plan_seconds_sum`` (parse and plan of a
text ``try_fast_hit`` found no memo for; a write clears the memo, so
every distinct text plans again) per good statement of a writing class
answered in the window; ms."""

import arith


def read(ctx):
    if "presto_tpu_fast_path_plan_seconds_sum" not in ctx.counters:
        return None
    writes = sum(1 for r in arith.good(ctx.records)
                 if ctx.classes[r["cls"]].get("writes"))
    if not writes:
        return None
    return (ctx.counters["presto_tpu_fast_path_plan_seconds_sum"]
            / writes * 1e3)
