"""Seconds of the warm-up statements together (one per class): compile
or compile-cache load, pinning the columns, first execution."""


def read(ctx):
    first = ctx.setup.get("first_exec_s")
    return sum(first.values()) if first else None
