"""The statement's ``sync/<site>`` spans (``exec/hostsync.py``: the host
blocked in ``fetch``, ``fetch_int`` or ``wait``, so the device's
remaining work plus the transfer): sum per statement, median per class,
geometric mean over classes; ms. ``hostsync.syncs_per_query`` counts the
same calls."""

import arith


def read(ctx):
    def syncs(rec):
        spans = ctx.spans.get(rec.get("qid", ""))
        if not spans:
            return None
        return sum(s["t1"] - s["t0"] for s in spans
                   if s["name"].startswith("sync/")) * 1e3 or None
    return arith.geomean_of_class_medians(ctx.records, syncs)
