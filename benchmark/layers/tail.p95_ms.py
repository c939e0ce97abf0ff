"""95th percentile of client wall from due time, pooled over the window's
good statements, only with ten samples beyond it; ms. Below the knee of an
open loop this is what the slowest twentieth of a dashboard's refreshes
waits. It is a per-layer metric and not an end-to-end one because in
``tpch_sf1.dash`` it reads the stall after each INSERT (PERF.md section 5)
and spread by 22% between runs of the same code."""

import arith


def read(ctx):
    return arith.percentile(
        [arith.wall_ms(r) for r in arith.good(ctx.records)], 95.0)
