"""Reduction of a profiler trace (``.xplane.pb``) to the benchmark's numbers.

    python benchmark/tracered.py <file.xplane.pb>     # what is in a trace

``reduce`` gives, for the traced sub-window: the seconds in which an
operation ran on the device (the union of the device-op intervals, mean
over the device planes), the operations that took most self time under
the names the trace gives them, and the idle gaps labelled by what the
host was doing: the statement in flight (from the load generator's
records) and the program's innermost span over the gap.

The trace has a clock of its own (nanoseconds from the profiler's
start). ``run.py`` writes one host event named
``bench_clock_sync:<time.monotonic_ns()>`` right after the start; its
position in the trace ties that clock to the records' monotonic one.
Read with ``jax.profiler.ProfileData`` only.
"""

from __future__ import annotations

import dataclasses
import re
import sys
from pathlib import Path

SYNC = "bench_clock_sync:"
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
TOP = 10
MIN_IDLE_S = 1e-3  # an idle label with less than this is not listed
SHORT_GAP_S = 1e-4  # shorter gaps (between a program's operations) are
SHORT_GAPS = "gaps under 0.1 ms"  # summed under one label, not looked up

# What the host's own events say it was doing, by the names the runtime
# gives them (jax 0.9.0, libtpu 0.0.34); first match wins.
HOST_WORK = (
    ("XLA compile", ("backend_compile_and_load",)),
    ("host-to-device", ("TransferToDevice", "Linearize", "DevicePut")),
    ("device-to-host", ("TransferFromDevice", "Delinearize", "X64FromTuple",
                        "ToLiteral", "copy_to_host")),
)
HOST_WORK_SHARE = 0.2  # of a gap, before the gap is named after it

Interval = tuple[float, float]


def load(path: Path | str) -> dict[str, dict[str, list[tuple]]]:
    """plane -> line -> [(name, start_ns, duration_ns)], all of it.
    Host threads share a line name (the process's): their events are
    kept together under it."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(str(path))
    out: dict[str, dict[str, list[tuple]]] = {}
    for plane in data.planes:
        lines = out.setdefault(plane.name, {})
        for line in plane.lines:
            lines.setdefault(line.name, []).extend(
                (ev.name, float(ev.start_ns), float(ev.duration_ns))
                for ev in line.events)
    return out


def clock_shift_s(planes: dict) -> float:
    """Seconds to add to a trace time to get ``time.monotonic()``."""
    for lines in planes.values():
        for events in lines.values():
            for name, start, _dur in events:
                if name.startswith(SYNC):
                    return (int(name[len(SYNC):]) - start) * 1e-9
    raise ValueError("the trace holds no bench_clock_sync event")


def merge(intervals: list[Interval]) -> list[Interval]:
    """Union of intervals as a sorted list of disjoint ones."""
    out: list[Interval] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def covered(merged: list[Interval], lo: float, hi: float) -> float:
    """Seconds of [lo, hi] that the disjoint intervals cover."""
    return sum(max(0.0, min(b, hi) - max(a, lo)) for a, b in merged)


def gaps(merged: list[Interval], lo: float, hi: float) -> list[Interval]:
    out, at = [], lo
    for a, b in merged:
        if b <= lo or a >= hi:
            continue
        if a > at:
            out.append((at, a))
        at = max(at, b)
    if at < hi:
        out.append((at, hi))
    return out


HLO = re.compile(r"^(%[\w.\-]+) = \(?(\w+\[[\d,]*\]).*?\s([\w\-]+)\(")


def short_name(name: str) -> str:
    """An operation's name as the trace gives it is its whole HLO line;
    keep the instruction, its (first) result shape and its opcode."""
    m = HLO.match(name)
    return f"{m.group(1)} {m.group(2)} {m.group(3)}" if m else name[:80]


def self_times(events: list[tuple]) -> dict[str, float]:
    """name -> seconds of self time on one line: an event that encloses
    others (a loop around its body) keeps only what they leave."""
    out: dict[str, float] = {}
    stack: list[list] = []  # [name, end, self]

    def close(upto: float) -> None:
        while stack and stack[-1][1] <= upto:
            name, _end, own = stack.pop()
            out[name] = out.get(name, 0.0) + own

    for name, start, dur in sorted(events, key=lambda e: (e[1], -e[2])):
        close(start)
        if stack:
            stack[-1][2] -= min(dur, stack[-1][1] - start)
        stack.append([name, start + dur, dur])
    close(float("inf"))
    return {k: v * 1e-9 for k, v in out.items()}


def host_work(planes: dict, shift: float) -> dict[str, list[Interval]]:
    """kind of host work -> disjoint intervals (monotonic seconds) in
    which some host thread was at it, from the trace's host plane."""
    found: dict[str, list[Interval]] = {kind: [] for kind, _ in HOST_WORK}
    for events in planes.get(HOST_PLANE, {}).values():
        for name, start, dur in events:
            for kind, needles in HOST_WORK:
                if any(n in name for n in needles):
                    found[kind].append((start * 1e-9 + shift,
                                        (start + dur) * 1e-9 + shift))
                    break
    return {kind: merge(iv) for kind, iv in found.items()}


def label(a: float, b: float, records: list[dict],
          spans: dict[str, list[dict]],
          work: dict[str, list[Interval]] | None = None) -> str:
    """What the host was doing over [a, b]: the class of the statement
    in flight over most of it, the program's innermost span there, and
    the kind of host work the trace shows over the largest part of it."""
    doing = ""
    if work:
        kind, cover = max(((k, covered(iv, a, b)) for k, iv in work.items()),
                          key=lambda kc: kc[1])
        if cover >= HOST_WORK_SHARE * (b - a):
            doing = f" / {kind}"
    best, best_cover = None, 0.0
    for r in records:
        c = min(b, r["done"]) - max(a, r["sent"])
        if c > best_cover:
            best, best_cover = r, c
    if best is None:
        return "no statement in flight" + doing
    inner, inner_key = None, (0.0, 0.0)
    for s in spans.get(best.get("qid", ""), []):
        c = min(b, s["t1"]) - max(a, s["t0"])
        # most of the gap first, then the span that started last
        if c > 0 and (c >= 0.5 * (b - a), s["t0"]) > inner_key:
            inner, inner_key = s, (c >= 0.5 * (b - a), s["t0"])
    return (f"{best['cls']}: " + (inner["name"] if inner else "no span")
            + doing)


@dataclasses.dataclass
class Reduced:
    lo: float                       # traced window, monotonic seconds
    hi: float
    busy: list[list[Interval]]      # per device plane, disjoint, monotonic
    ops: list[tuple[str, float]]    # self seconds, mean over devices
    idle: list[tuple[str, float]]   # idle seconds by label
    work: dict[str, list[Interval]]  # kind of host work -> intervals

    @property
    def window_s(self) -> float:
        return self.hi - self.lo

    @property
    def busy_s(self) -> float:
        return self.busy_between(self.lo, self.hi)

    def busy_between(self, a: float, b: float) -> float:
        return sum(covered(m, a, b) for m in self.busy) / len(self.busy)

    def host_work_s(self, kind: str) -> float:
        """Seconds of the window in which a host thread did ``kind``."""
        return covered(self.work.get(kind, []), self.lo, self.hi)

    def breakdown(self) -> dict:
        return {"device_ops": [[n, s] for n, s in self.ops[:TOP]],
                "idle_gaps": [[n, s] for n, s in self.idle[:TOP]
                              if s >= MIN_IDLE_S]}


def reduce(path: Path | str, lo: float, hi: float, records: list[dict],
           spans: dict[str, list[dict]]) -> Reduced:
    planes = load(path)
    shift = clock_shift_s(planes)
    devices = sorted(p for p in planes if DEVICE_PLANE.match(p))
    if not devices:
        raise ValueError(f"no device plane among {sorted(planes)}")
    busy, ops = [], {}
    for p in devices:
        events = planes[p].get(OPS_LINE, [])
        busy.append(merge([(s * 1e-9 + shift, (s + d) * 1e-9 + shift)
                           for _n, s, d in events if d > 0]))
        for name, sec in self_times(events).items():
            name = short_name(name)
            ops[name] = ops.get(name, 0.0) + sec / len(devices)
    work = host_work(planes, shift)
    around = [r for r in records if r["done"] > lo and r["sent"] < hi]
    idle: dict[str, float] = {}
    for a, b in gaps(busy[0], lo, hi):
        key = (label(a, b, around, spans, work) if b - a >= SHORT_GAP_S
               else SHORT_GAPS)
        idle[key] = idle.get(key, 0.0) + (b - a)
    by_time = lambda kv: -kv[1]  # noqa: E731
    return Reduced(lo, hi, busy, sorted(ops.items(), key=by_time),
                   sorted(idle.items(), key=by_time), work)


def main(argv: list[str]) -> int:
    planes = load(argv[1])
    for pname, lines in planes.items():
        print(f"plane {pname!r}")
        for lname, events in lines.items():
            total = sum(d for _n, _s, d in events) * 1e-9
            print(f"  line {lname!r}: {len(events)} events, "
                  f"{total:.6f} s summed")
            top = sorted(self_times(events).items(), key=lambda kv: -kv[1])
            for name, sec in top[:5]:
                print(f"    {sec:10.6f} s self  {name[:90]}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
