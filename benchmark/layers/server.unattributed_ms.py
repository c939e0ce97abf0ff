"""What no span names: the statement's root ``query`` span minus the
union of its descendants, clipped to the root; median per class,
geometric mean over classes; ms. A span named ``query`` (the root, and
the engine's own statement span under it) names the whole statement and
no part of it, so it covers nothing here. A root wholly covered reads
0 for the statement (1e-6 in the mean, which needs positive values, as
in ``server.other_ms``)."""

import arith
import tracered


def unattributed_ms(spans: list[dict]) -> float | None:
    roots = [s for s in spans if s["parent"] is None
             and s["name"] == "query" and not s["attrs"].get("instant")]
    if not roots:
        return None
    lo, hi = roots[0]["t0"], roots[0]["t1"]
    named = tracered.merge([(s["t0"], s["t1"]) for s in spans
                            if s["name"] != "query"
                            and not s["attrs"].get("instant")])
    return (hi - lo - tracered.covered(named, lo, hi)) * 1e3


def read(ctx):
    def gap(rec):
        spans = ctx.spans.get(rec.get("qid", ""))
        gap_ms = unattributed_ms(spans) if spans else None
        return None if gap_ms is None else max(gap_ms, 1e-6)
    return arith.geomean_of_class_medians(ctx.records, gap)
