"""Device time of a statement's collectives: the time covered by the
all-gather, all-reduce, all-to-all, collective-permute and
reduce-scatter operations (their ``-start`` and ``-done`` halves too)
that started inside the statement, per device plane and averaged over
the planes; median per class over the statements wholly inside the
traced sub-window, geometric mean over the classes; ms; closed loops
only. An operation counts from the ``XLA Ops`` line and, where the
runtime draws an asynchronous one as a span from its start to its done
on the ``Async XLA Ops`` line, from there too: a plane's intervals are
united, so a span and the two halves under it count once. The
operations are told by the HLO instruction's name and opcode as the
trace gives them (``opnames.load`` reads the ``XLA Ops`` line; the
clock shift is ``tracered``'s sync event). None where the trace holds
no collective (one chip)."""

import re

import arith
import opnames
import shapes
import tracered

COLLECTIVE = re.compile(
    r"(all-gather|all-reduce|all-to-all|collective-permute|reduce-scatter)")
ASYNC_LINE = "Async XLA Ops"


def async_collectives(path) -> dict[str, list[tuple[float, float]]]:
    """Device plane -> [(start ns, duration ns)] of the collective
    spans on its ``Async XLA Ops`` line, on the trace's clock."""
    from jax.profiler import ProfileData
    out = {}
    for plane in ProfileData.from_file(str(path)).planes:
        if not tracered.DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            if line.name == ASYNC_LINE:
                out.setdefault(plane.name, []).extend(
                    (float(ev.start_ns), float(ev.duration_ns))
                    for ev in line.events
                    if COLLECTIVE.search(tracered.short_name(ev.name)))
    return out


def collectives(path):
    """Per device plane, [(start, monotonic s; duration s)] of the
    disjoint intervals its collective operations cover, sorted."""
    planes = opnames.load(path)
    spans = async_collectives(path)
    shift = None
    for p in planes:
        if p.name != tracered.HOST_PLANE:
            continue
        for events in p.lines.values():
            for meta, start, _dur in events:
                name = p.names.get(meta, "")
                if name.startswith(tracered.SYNC):
                    shift = (int(name[len(tracered.SYNC):]) - start) * 1e-9
    if shift is None:
        raise ValueError("the trace holds no bench_clock_sync event")
    out = []
    for p in planes:
        if not tracered.DEVICE_PLANE.match(p.name):
            continue
        which = {m for m, n in p.names.items()
                 if COLLECTIVE.search(tracered.short_name(n))}
        events = [(start, dur)
                  for meta, start, dur in p.lines.get(tracered.OPS_LINE, [])
                  if meta in which] + spans.get(p.name, [])
        out.append([(a * 1e-9 + shift, (b - a) * 1e-9) for a, b in
                    tracered.merge([(s, s + d) for s, d in events])])
    return out


def read(ctx):
    if ctx.trace is None or ctx.mix["loop"] != "closed":
        return None
    files = sorted((opnames.HERE / ".cache" / "trace"
                    / ctx.cell["name"]).glob("plugins/profile/*/*.xplane.pb"))
    if not files:
        return None
    planes = collectives(files[-1])
    if not any(planes):
        return None

    def ms(r):
        return sum(dur for plane in planes for start, dur in plane
                   if r["sent"] <= start <= r["done"]) / len(planes) * 1e3

    return arith.geomean_of_class_medians(
        shapes.inside(ctx.records, ctx.trace.lo, ctx.trace.hi), ms)
