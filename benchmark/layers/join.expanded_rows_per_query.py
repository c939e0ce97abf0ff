"""Rows of a statement's largest many-to-many join: the ``expanded``
attribute of the statement's ``segment`` spans (``exec/executor.
note_expansions``: the realised output rows of the largest expanding
join the segment's program ran, from the per-node counts that ride the
hand-over's one fetch), the largest a statement, median over the
window's statements that have one (a count). A program whose
``segment`` spans carry no such attribute leaves the metric out."""

import arith


def read(ctx):
    per_statement = []
    for r in arith.good(ctx.records):
        rows = [s["attrs"]["expanded"]
                for s in ctx.spans.get(r.get("qid", ""), [])
                if s["name"] == "segment" and "expanded" in s["attrs"]]
        if rows:
            per_statement.append(float(max(rows)))
    return arith.median(per_statement) if per_statement else None
