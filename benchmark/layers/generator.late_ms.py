"""Median of sent minus due in an open loop: how late the load
generator ran. Tells a starved generator from a fast server; ms."""

import arith


def read(ctx):
    if ctx.mix["loop"] != "open" or not ctx.records:
        return None
    return arith.median([(r["sent"] - r["due"]) * 1e3
                         for r in ctx.records])
