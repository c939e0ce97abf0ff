"""Groups of a statement's largest grouped aggregate: the ``groups``
attribute of the statement's ``segment`` spans (``exec/executor.
device_outputs``: the occupied slots of the largest grouped Aggregate
of the segment's program, from the per-node counts that ride the
hand-over's one fetch), the largest a statement, median over the
window's statements that have one (a count). 15,000,000 for TPC-H Q18
at SF10: its IN sums lineitem into every order. A program whose
``segment`` spans have no such attribute (the parent of PR 32), or a
mix whose aggregates run in no segment, leaves the metric out."""

import arith


def read(ctx):
    per_statement = []
    for r in arith.good(ctx.records):
        groups = [s["attrs"]["groups"]
                  for s in ctx.spans.get(r.get("qid", ""), [])
                  if s["name"] == "segment" and "groups" in s["attrs"]]
        if groups:
            per_statement.append(float(max(groups)))
    return arith.median(per_statement) if per_statement else None
