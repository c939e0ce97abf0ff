"""Device self time by the plan operator that asked for it.

    python benchmark/opnames.py <file.xplane.pb>      # what a trace names

The program runs each plan node under ``jax.named_scope("<Kind>#<n>")``
(``exec/executor.PlanInterpreter.run``; ``<n>`` is the node's preorder
position), so an operation's HLO ``op_name`` is a path like
``jit(output_e840251b)/Output#0/TopN#1/Aggregate#5/Join#6/gather``. The
profiler keeps that path in the ``tf_op`` stat of the *event metadata* of
the ``XLA Ops`` line (jax 0.9.0, libtpu 0.0.34), which
``jax.profiler.ProfileData`` does not hand out: it gives an event's own
stats only, and the event's name is the HLO line without its metadata.
So this module reads the ``.xplane.pb`` itself, with a few lines of
protobuf wire format (the ``XSpace`` schema of tsl's ``xplane.proto``;
only the fields numbered below), and skips everything but the device
planes' ``XLA Ops`` and ``XLA Modules`` lines and the host's
``bench_clock_sync`` event.

An operation belongs to the innermost ``Kind#n`` of its path. Self time
is ``tracered.self_times``: an event that encloses others (a loop
around its body) keeps what they leave. One trace is read once per run
(``of(ctx)``) and shared by the readers under ``layers/``.
"""

from __future__ import annotations

import dataclasses
import re
import sys
from pathlib import Path

import arith
import shapes
import tracered

HERE = Path(__file__).resolve().parent

# a path component the program gave: a plan node's class name (they are
# CamelCase; JAX's own components, ``while``, ``body``, ``jit(f)``, are
# not), with its preorder position where the node has one
SCOPE = re.compile(r"^[A-Z][A-Za-z]*(#\d+)?$")
UNNAMED = "(no scope)"


# -- protobuf wire format -----------------------------------------------------

def _varint(buf, i: int) -> tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf):
    """(field number, value) of one message: an int for a varint, a
    memoryview for a length-delimited field; fixed-width fields (the
    doubles of a stat) are skipped."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(buf, i)
            yield key >> 3, v
        elif wire == 2:
            size, i = _varint(buf, i)
            yield key >> 3, buf[i:i + size]
            i += size
        elif wire == 1:
            i += 8
        elif wire == 5:
            i += 4
        else:
            raise ValueError(f"wire type {wire} in an xplane file")


def _text(view) -> str:
    return bytes(view).decode("utf-8", "replace")


def _map_entry(buf) -> tuple[int, memoryview]:
    key, value = 0, memoryview(b"")
    for f, v in _fields(buf):
        if f == 1:
            key = v
        elif f == 2:
            value = v
    return key, value


@dataclasses.dataclass
class Plane:
    name: str
    # line name -> [(metadata id, start ns, duration ns)]
    lines: dict[str, list[tuple[int, float, float]]]
    names: dict[int, str]            # event metadata id -> name
    op_names: dict[int, str]         # event metadata id -> tf_op path
    programs: dict[int, int]         # event metadata id -> program id


MODULES_LINE = "XLA Modules"


def _wanted(plane: str, line: str | None = None) -> bool:
    """The host plane whole (it holds the sync event); of a device
    plane the operations and the modules they belong to."""
    if plane == tracered.HOST_PLANE:
        return True
    return bool(tracered.DEVICE_PLANE.match(plane)) and (
        line is None or line in (tracered.OPS_LINE, MODULES_LINE))


def _plane(buf) -> Plane:
    name, lines, metas, stat_names = "", [], [], {}
    for f, v in _fields(buf):
        if f == 2:
            name = _text(v)
        elif f == 3:
            lines.append(v)
        elif f == 4:
            metas.append(v)
        elif f == 5:
            key, value = _map_entry(v)
            for f2, v2 in _fields(value):
                if f2 == 2:
                    stat_names[key] = _text(v2)
    plane = Plane(name, {}, {}, {}, {})
    if not _wanted(name):
        return plane
    for entry in metas:
        key, value = _map_entry(entry)
        for f, v in _fields(value):
            if f == 2:
                plane.names[key] = _text(v)
            elif f == 5:  # XStat: metadata_id 1, int64 4 / uint64 3,
                stat = dict(_fields(v))  # str 5, ref (a stat name) 7
                what = stat_names.get(stat.get(1))
                if what == "tf_op":
                    plane.op_names[key] = (
                        _text(stat[5]) if 5 in stat
                        else stat_names.get(stat.get(7), ""))
                elif what == "program_id":
                    plane.programs[key] = stat.get(3, stat.get(4, 0))
    for line in lines:
        lname, t0_ns, events = "", 0, []
        for f, v in _fields(line):
            if f == 2:
                lname = _text(v)
            elif f == 3:
                t0_ns = v
            elif f == 4:
                events.append(v)
        if not _wanted(name, lname):
            continue
        out = plane.lines.setdefault(lname, [])
        for ev in events:
            meta = offset_ps = dur_ps = 0
            for f, v in _fields(ev):
                if f == 1:
                    meta = v
                elif f == 2:
                    offset_ps = v
                elif f == 3:
                    dur_ps = v
            out.append((meta, t0_ns + offset_ps * 1e-3, dur_ps * 1e-3))
    return plane


def load(path: Path | str) -> list[Plane]:
    """The device planes (their ``XLA Ops`` and ``XLA Modules`` lines)
    and the host plane (every line; it holds the sync event)."""
    buf = memoryview(Path(path).read_bytes())
    return [p for p in (_plane(v) for f, v in _fields(buf) if f == 1)
            if p.lines]


# -- the reduction ------------------------------------------------------------

def scope_of(op_name: str) -> str:
    """The program's scopes of an ``op_name`` path, outermost first and
    joined by ``/`` (``Output#0/TopN#1``); empty where it has none."""
    return "/".join(c for c in op_name.rstrip(":").split("/")
                    if SCOPE.match(c))


def kind_of(scope: str) -> str:
    """The plan-operator kind an operation belongs to: the innermost
    ``Kind#n`` of its scopes, without the position."""
    return scope.rsplit("/", 1)[-1].split("#", 1)[0] if scope else UNNAMED


@dataclasses.dataclass
class ByOperator:
    """Self seconds of the traced sub-window's device operations, mean
    over the device planes."""
    kinds: dict[str, float]              # plan-operator kind -> s
    scopes: dict[tuple[str, str], float]  # (module, scopes) -> s
    ops: dict[tuple[str, str, str], float]  # (module, scopes, op) -> s
    named_s: float
    total_s: float
    # (start, monotonic s; self s; kind), sorted by start, all planes
    events: list[tuple[float, float, str]]
    devices: int

    def kind_s_between(self, a: float, b: float,
                       kinds: tuple[str, ...]) -> float:
        return sum(own for start, own, kind in self.events
                   if a <= start <= b and kind in kinds) / self.devices


def reduce(path: Path | str) -> ByOperator:
    planes = load(path)
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
    devices = [p for p in planes if tracered.DEVICE_PLANE.match(p.name)]
    if not devices:
        raise ValueError("no device plane in the trace")
    out = ByOperator({}, {}, {}, 0.0, 0.0, [], len(devices))
    for p in devices:
        modules = {}  # program id -> module name, "jit_f(123)" -> "jit_f"
        for meta, _s, _d in p.lines.get(MODULES_LINE, []):
            name = p.names.get(meta, "")
            m = re.match(r"^(.*)\((\d+)\)$", name)
            if m:
                modules[int(m.group(2))] = m.group(1)
        events = p.lines.get(tracered.OPS_LINE, [])
        # self time per event: tracered.self_times sums by name, so
        # give it each event's index for a name
        own_s = tracered.self_times(
            [(i, start, dur) for i, (_m, start, dur) in enumerate(events)])
        for i, (meta, start, _dur) in enumerate(events):
            own = own_s[i]
            sec = own / len(devices)
            scope = scope_of(p.op_names.get(meta, ""))
            kind = kind_of(scope)
            module = modules.get(
                p.programs.get(meta, 0) & 0xFFFFFFFFFFFFFFFF, "?")
            out.total_s += sec
            if scope:
                out.named_s += sec
            out.kinds[kind] = out.kinds.get(kind, 0.0) + sec
            key = (module, scope or UNNAMED)
            out.scopes[key] = out.scopes.get(key, 0.0) + sec
            op = key + (tracered.short_name(p.names.get(meta, "")),)
            out.ops[op] = out.ops.get(op, 0.0) + sec
            out.events.append((start * 1e-9 + shift, own, kind))
    out.events.sort()
    return out


_CACHE: dict[str, ByOperator | None] = {}


def of(ctx) -> ByOperator | None:
    """The reduction of the cell's newest trace, made once per run;
    None where the run traced no device (the CPU rehearsal) or the
    trace cannot be read."""
    if ctx.trace is None:
        return None
    files = sorted((HERE / ".cache" / "trace" / ctx.cell["name"]).glob(
        "plugins/profile/*/*.xplane.pb"))
    if not files:
        return None
    key = str(files[-1])
    if key not in _CACHE:
        try:
            _CACHE[key] = reduce(files[-1])
        except (ValueError, IndexError) as exc:
            print(f"opnames: {files[-1]}: {exc}", file=sys.stderr)
            _CACHE[key] = None
    return _CACHE[key]


def named_share(ctx) -> float | None:
    """Device self time under a scope the program gave, over all device
    self time; None where the program gave none (no scope to read)."""
    red = of(ctx)
    if red is None or not red.named_s or not red.total_s:
        return None
    return red.named_s / red.total_s


def class_kind_ms(ctx, cls: str, kinds: tuple[str, ...]) -> float | None:
    """Median over the statements of ``cls`` wholly inside the traced
    sub-window of the device self time, in ms, of the operations that
    started inside the statement under a plan operator of ``kinds``;
    closed loops only (``shapes.busy_ms``'s rule). None where no
    operation carries such a scope."""
    red = of(ctx)
    if red is None or ctx.mix["loop"] != "closed":
        return None
    if not any(red.kinds.get(k) for k in kinds):
        return None
    rs = [r for r in shapes.inside(ctx.records, ctx.trace.lo, ctx.trace.hi)
          if r["cls"] == cls]
    if not rs:
        return None
    return arith.median([red.kind_s_between(r["sent"], r["done"], kinds)
                         * 1e3 for r in rs])


def main(argv: list[str]) -> int:
    red = reduce(argv[1])
    print(f"{red.total_s:.6f} s of device self time, "
          f"{red.named_s:.6f} s under a scope of the program's")
    for kind, sec in sorted(red.kinds.items(), key=lambda kv: -kv[1]):
        print(f"  {sec:10.6f} s  {kind}")
    print("by module and scope:")
    for (module, scope), sec in sorted(red.scopes.items(),
                                       key=lambda kv: -kv[1])[:25]:
        print(f"  {sec:10.6f} s  {module}  {scope}")
    print("by operation:")
    for (module, scope, op), sec in sorted(red.ops.items(),
                                           key=lambda kv: -kv[1])[:25]:
        print(f"  {sec:10.6f} s  {module}  {scope}  {op}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
