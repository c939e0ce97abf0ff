"""Median client wall of the window's good statements of one query
class; ms. One reader for every ``class.<class>_ms``: the harness hands
it the class that the metric's name holds."""

import shapes


def read(ctx, cls):
    return shapes.class_ms(ctx, cls)
