"""A query class's many-to-many joins against their memory roofline: the
least bytes the expansions must move (``least_bytes``) over the chip's
peak bytes/s, as a percentage of their device time (``<class>_expand_ms``);
median least bytes over the class's statements in the window; closed
loops only. One reader for every ``<class>_expand_roofline``."""

from pathlib import Path

import arith
import verify

EXPAND_MS = Path(__file__).resolve().parent / "CLASS_expand_ms.py"


def least_bytes(spans) -> int:
    """What a statement's expansions cannot do without, from its
    ``segment`` spans' ``expand_probe_rows`` and ``expanded``: each probe
    row's run bounds read (8 bytes: start and count), and each pair's
    probe and build row indices written (8 bytes)."""
    return sum(8 * s["attrs"]["expand_probe_rows"]
               + 8 * s["attrs"]["expanded"]
               for s in spans if s["name"] == "segment"
               and "expanded" in s["attrs"])


def read(ctx, cls):
    if ctx.peaks is None:
        return None
    ms = verify.load_attr(EXPAND_MS, "read")(ctx, cls)
    least = [least_bytes(ctx.spans.get(r.get("qid", ""), []))
             for r in arith.good(ctx.records) if r["cls"] == cls]
    least = [b for b in least if b]
    if not ms or not least:
        return None
    least_ms = arith.median(least) / ctx.peaks["hbm_bytes_per_s"] * 1e3
    return 100.0 * least_ms / ms
