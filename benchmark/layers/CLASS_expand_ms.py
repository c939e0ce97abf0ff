"""Device self time of the operations under ``Join#<n>/expand`` scopes
(``exec/operators.apply_expand_join``: a many-to-many join's hashing,
sort, probe runs, expansion and gathers, not the join's inputs) inside
one statement of the class, all such scopes of the statement together:
median over the class's statements wholly inside the traced sub-window;
ms; closed loops only. One reader for every ``<class>_expand_ms``. A
program without such scopes leaves the metric out."""

import re

import arith
import opnames
import shapes
import tracered

EXPAND = re.compile(r"(?:^|/)Join#\d+/expand(?:/|$)")


def per_statement(ctx, cls):
    """[ms under an expand scope] of each statement of ``cls`` wholly
    inside the traced sub-window, or None."""
    if ctx.trace is None or ctx.mix["loop"] != "closed":
        return None
    files = sorted((opnames.HERE / ".cache" / "trace" / ctx.cell["name"])
                   .glob("plugins/profile/*/*.xplane.pb"))
    rs = [r for r in shapes.inside(ctx.records, ctx.trace.lo, ctx.trace.hi)
          if r["cls"] == cls]
    if not files or not rs:
        return None
    planes = opnames.load(files[-1])
    host = {line: [(p.names.get(meta, ""), start, dur)
                   for meta, start, dur in events]
            for p in planes if p.name == tracered.HOST_PLANE
            for line, events in p.lines.items()}
    devices = [p for p in planes if tracered.DEVICE_PLANE.match(p.name)]
    try:
        shift = tracered.clock_shift_s({tracered.HOST_PLANE: host})
    except ValueError:
        return None
    seen = False
    out = [0.0] * len(rs)
    for p in devices:
        events = p.lines.get(tracered.OPS_LINE, [])
        own = tracered.self_times(
            [(i, start, dur) for i, (_m, start, dur) in enumerate(events)])
        for i, (meta, start, _dur) in enumerate(events):
            if not EXPAND.search(p.op_names.get(meta, "")):
                continue
            seen = True
            t = start * 1e-9 + shift
            for k, r in enumerate(rs):
                if r["sent"] <= t <= r["done"]:
                    out[k] += own[i] / len(devices) * 1e3
    return out if seen else None


def read(ctx, cls):
    ms = per_statement(ctx, cls)
    return arith.median(ms) if ms else None
