"""Device self time of the dearest ``MultiJoin#<n>/build<k>`` scope
(``exec/operators.apply_multi_join``: one scope per build, in plan
order) inside one statement of the class: per scope the median over
the class's statements wholly inside the traced sub-window, then the
largest; ms; closed loops only. One reader for every
``<class>_top_build_ms``; ``by_build`` gives every scope's median
(stderr of a traced run lists them). A program without such scopes
leaves the metric out."""

import re
import sys

import arith
import opnames
import shapes
import tracered

BUILD = re.compile(r"(?:^|/)(MultiJoin#\d+/build\d+)(?:/|$)")


def by_build(ctx, cls):
    """{scope: median ms per statement of ``cls``}, or {}."""
    if ctx.trace is None or ctx.mix["loop"] != "closed":
        return {}
    files = sorted((opnames.HERE / ".cache" / "trace" / ctx.cell["name"])
                   .glob("plugins/profile/*/*.xplane.pb"))
    rs = [r for r in shapes.inside(ctx.records, ctx.trace.lo, ctx.trace.hi)
          if r["cls"] == cls]
    if not files or not rs:
        return {}
    planes = opnames.load(files[-1])
    host = {line: [(p.names.get(meta, ""), start, dur)
                   for meta, start, dur in events]
            for p in planes if p.name == tracered.HOST_PLANE
            for line, events in p.lines.items()}
    devices = [p for p in planes if tracered.DEVICE_PLANE.match(p.name)]
    try:
        shift = tracered.clock_shift_s({tracered.HOST_PLANE: host})
    except ValueError:
        return {}
    if not devices:
        return {}
    per = [{} for _ in rs]  # statement -> scope -> seconds
    for p in devices:
        events = p.lines.get(tracered.OPS_LINE, [])
        own = tracered.self_times(
            [(i, start, dur) for i, (_m, start, dur) in enumerate(events)])
        for i, (meta, start, _dur) in enumerate(events):
            m = BUILD.search(p.op_names.get(meta, ""))
            if m is None:
                continue
            t = start * 1e-9 + shift
            for k, r in enumerate(rs):
                if r["sent"] <= t <= r["done"]:
                    per[k][m.group(1)] = (per[k].get(m.group(1), 0.0)
                                          + own[i] / len(devices))
    scopes = sorted({s for d in per for s in d})
    return {s: arith.median([d.get(s, 0.0) * 1e3 for d in per])
            for s in scopes}


def read(ctx, cls):
    builds = by_build(ctx, cls)
    for scope, ms in builds.items():
        print(f"{cls}: {scope} {ms:.1f} ms", file=sys.stderr)
    return max(builds.values()) if builds else None
