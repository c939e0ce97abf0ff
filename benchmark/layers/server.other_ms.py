"""Client wall minus the statement's plan, compile and execute spans:
protocol, admission, result encoding, paging and polling. Statements
with a trace only (a fast-path cache hit has none); median per class,
geometric mean over classes; ms."""

import arith
import shapes


def read(ctx):
    def other(rec):
        inside = shapes.span_ms(ctx, rec, ("plan", "compile", "execute"))
        return None if inside is None else max(
            arith.wall_ms(rec) - inside, 1e-6)
    return arith.geomean_of_class_medians(ctx.records, other)
