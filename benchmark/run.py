"""Run one cell of the benchmark once, in this process.

    python benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

This process holds the chip: it makes the data from ``--seed``, builds
the ``Engine``, starts ``CoordinatorServer`` in-process, warms one
statement per class, and owns the profiler. The load comes from
``loadgen.py`` in a child process that imports no JAX. After the window
the answers are held to the NumPy references, and the last line of
standard output is one JSON object (``correct``, ``attempted``,
``failed``, ``metrics``, ``device``; with ``--trace 1`` the per-layer
metrics and ``breakdown``). README.md says how cells, mixes, classes and
per-layer metrics are added as files.

There is no CPU fallback: without a TPU whose ``device_kind`` is in
``peaks.json`` the exit code is 2 and nothing is printed. For rehearsal
and tests only, ``BENCH_ALLOW_CPU=1`` lets it run on the CPU; every
device metric is then left out (the tests rehearse that way, through a
tiny configuration in a temporary copy).
"""

from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402
import types  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
sys.path.insert(0, str(ROOT))  # the program under test

import arith  # noqa: E402
import traffic  # noqa: E402

CHILD_START_S = 1.0   # the child's start-up, before the window opens
DRAIN_S = 200.0       # longest wait for the child after the window


def fail(msg: str) -> "NoReturn":  # noqa: F821
    print(f"benchmark: {msg}", file=sys.stderr)
    sys.exit(2)


def say(msg: str) -> None:
    print(f"[bench +{time.monotonic() - T_START:7.1f}s] {msg}",
          file=sys.stderr, flush=True)


# -- manifest -----------------------------------------------------------------

def load_cell(name: str) -> tuple[dict, dict]:
    path = ROOT / "BENCHMARK.json"
    if not path.is_file():
        fail(f"{path} is missing")
    manifest = json.loads(path.read_text(encoding="utf-8"))
    for cell in manifest["workloads"]:
        if cell["name"] == name:
            return manifest, cell
    fail(f"no workload {name!r} in BENCHMARK.json")


def metrics_of(manifest: dict, kind: str, cell: str) -> list[dict]:
    return [m for m in manifest[kind]
            if "workloads" not in m or cell in m["workloads"]]


# -- device -------------------------------------------------------------------

def device_stamp(chips: int) -> tuple[dict, dict | None]:
    """(device as JAX reports it, its row of peaks.json or None on the
    rehearsal CPU). Exits 2 without the chips the cell asks for."""
    import jax
    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    peaks = json.loads((HERE / "peaks.json").read_text(encoding="utf-8"))
    if dev["platform"] == "tpu":
        if dev["kind"] not in peaks:
            fail(f"device kind {dev['kind']!r} is not in peaks.json")
        if len(devs) < chips:
            fail(f"the cell needs {chips} chip(s), JAX sees {len(devs)}")
        return dev, peaks[dev["kind"]]
    if dev["platform"] == "cpu" and rehearsal():
        return dev, None
    fail(f"platform is {dev['platform']!r}, not 'tpu'; there is no CPU "
         f"fallback")


def rehearsal() -> bool:
    return os.environ.get("BENCH_ALLOW_CPU") == "1"


def memory_peak_bytes() -> int:
    import jax
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.devices()]
    return int(max(peaks))


# -- the program's counters and spans -----------------------------------------

def counters() -> dict[str, float]:
    """Every sample of the program's registry, summed over labels (the
    Prometheus text it serves at /metrics)."""
    from presto_tpu.obs.metrics import REGISTRY
    out: dict[str, float] = {}
    for line in REGISTRY.render().splitlines():
        if not line or line.startswith("#"):
            continue
        name, _, value = line.rpartition(" ")
        name = name.split("{", 1)[0]
        try:
            out[name] = out.get(name, 0.0) + float(value)
        except ValueError:
            continue
    return out


def span_trees(records: list[dict]) -> dict[str, list[dict]]:
    """The program's spans of each statement that still has them (it
    keeps the last 256 traces), on the records' monotonic clock."""
    from presto_tpu.obs.trace import TRACER
    shift = time.time() - time.monotonic()
    out = {}
    for r in records:
        spans = TRACER.spans(r.get("qid", "")) if r.get("qid") else []
        if spans:
            out[r["qid"]] = [
                {"name": s.name, "id": s.span_id, "parent": s.parent_id,
                 "t0": s.t0 - shift,
                 "t1": (s.t1 if s.t1 is not None else s.t0) - shift,
                 "attrs": dict(s.attrs)} for s in spans]
    return out


# -- set-up -------------------------------------------------------------------

def prepare(workload: str):
    """The cell's files and the device: (manifest, cell, config, mix,
    classes, device, peaks). Imports the program, so JAX starts here."""
    manifest, cell = load_cell(workload)
    try:
        config = traffic.load_config(cell["config"])
        mix = traffic.load_mix(cell["traffic"])
    except ValueError as exc:
        fail(str(exc))
    classes = {c["name"]: traffic.load_class(c["name"])
               for c in mix["classes"]}
    if config["chips"] != cell["chips"]:
        fail(f"the cell asks for {cell['chips']} chip(s), its "
             f"configuration is laid out on {config['chips']}")
    if config.get("mesh") is not None:
        fail("the served path takes no mesh yet (CoordinatorServer calls "
             "the engine without one): 'mesh' has to be null")
    if config["compile_cache_in_window"] not in ("off", "on"):
        fail("compile_cache_in_window is 'off' or 'on'")
    # every statement carries the configuration's session properties
    # and, over them, the mix's; the rest is the program's default
    mix["session"] = {**config.get("session", {}), **mix.get("session", {})}
    if not (ROOT / "presto_tpu").is_dir():
        fail(f"the program (presto_tpu/) is not in {ROOT}")
    import presto_tpu  # noqa: F401 - x64 and the compile cache, first
    dev, peaks = device_stamp(int(cell["chips"]))
    return manifest, cell, config, mix, classes, dev, peaks


def make_catalogs(config: dict, seed: int) -> dict:
    """Catalog name -> connector, as the configuration lists them:
    ``connector`` is ``module:Class`` and ``args`` its keyword arguments,
    where "$seed" stands for ``--seed`` and any other "$key" for the
    configuration's top-level ``key``."""
    out = {}
    for name, spec in config["catalogs"].items():
        module, _, attr = spec["connector"].partition(":")
        args = {k: ((seed if v == "$seed" else config[v[1:]])
                    if isinstance(v, str) and v.startswith("$") else v)
                for k, v in spec.get("args", {}).items()}
        out[name] = getattr(importlib.import_module(module), attr)(**args)
    return out


def build(config: dict, classes: dict[str, dict], seed: int, setup: dict):
    """(engine, connector of the data catalog, server) with the cell's
    tables generated."""
    from presto_tpu import Engine
    from presto_tpu.server.server import CoordinatorServer
    t = time.monotonic()
    catalogs = make_catalogs(config, seed)
    conn = catalogs[config["data_catalog"]]
    tables = sorted({name for c in classes.values() for name in c["reads"]})
    rows = {name: conn.table(name).nrows for name in tables}
    setup["datagen_s"] = time.monotonic() - t
    say(f"datagen {config['catalogs'][config['data_catalog']]} seed={seed} "
        f"rows={rows} {setup['datagen_s']:.1f}s")
    engine = Engine()
    for name, connector in catalogs.items():
        engine.register_catalog(name, connector)
    return engine, conn, CoordinatorServer(engine).start()


def warm(uri: str, mix: dict, classes: dict[str, dict], seed: int,
         setup: dict) -> None:
    """The mix's set-up statements, then one statement per class."""
    from protocol import Connection
    conn = Connection(uri, mix.get("session"), timeout_s=1500.0)
    try:
        for sql in mix.get("setup", []):
            t = time.monotonic()
            conn.execute(sql)
            say(f"set-up statement {time.monotonic() - t:.2f}s: {sql[:60]}")
        setup["first_exec_s"] = {}
        for st in traffic.warmup(mix, classes, seed):
            t = time.monotonic()
            conn.execute(st["sql"])
            setup["first_exec_s"][st["cls"]] = time.monotonic() - t
            say(f"warm-up {st['cls']} "
                f"{setup['first_exec_s'][st['cls']]:.2f}s")
    finally:
        conn.close()


def persistent_cache_off() -> None:
    """``compile_cache_in_window: "off"`` of a configuration: from here
    on nothing is read from or written to JAX's persistent compile
    cache. Set-up ends with every shape of the warm-up in it; a program
    the window still compiles (a shape or a literal the warm-up did not
    cover) then costs what it costs a server that sees it for the first
    time, in every run, whatever earlier runs left in the directory, and
    the directory stops growing. This is the one place where the harness
    changes a setting of the program's process, so the configuration
    has to ask for it and says so under ``assumed``."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()


# -- the window ---------------------------------------------------------------

class Tracing:
    """A profiler trace of a sub-window, taken by a thread of this
    process (only the process that holds the chip can trace it)."""

    SYNC = "bench_clock_sync"

    def __init__(self, cell: str, t0: float, span: tuple[float, float]):
        self.dir = HERE / ".cache" / "trace" / cell
        shutil.rmtree(self.dir, ignore_errors=True)
        self.lo, self.hi = t0 + span[0], t0 + span[1]
        self.error: BaseException | None = None
        self.thread = threading.Thread(target=self._run, daemon=True)
        self.thread.start()

    def _run(self) -> None:
        import jax
        try:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0  # the spans come from records
            time.sleep(max(0.0, self.lo - time.monotonic()))
            jax.profiler.start_trace(str(self.dir), profiler_options=opts)
            try:
                # the trace has a clock of its own: an event named by
                # the monotonic time it was made at ties the two
                self.lo = time.monotonic()
                with jax.profiler.TraceAnnotation(
                        f"{self.SYNC}:{time.monotonic_ns()}"):
                    pass
                time.sleep(max(0.0, self.hi - time.monotonic()))
                self.hi = time.monotonic()
            finally:
                jax.profiler.stop_trace()
        except BaseException as exc:  # noqa: BLE001 - reported by join()
            self.error = exc

    def join(self) -> Path:
        self.thread.join()
        if self.error is not None:
            raise self.error
        files = sorted(self.dir.glob("plugins/profile/*/*.xplane.pb"))
        if not files:
            raise RuntimeError(f"the profiler wrote no trace in {self.dir}")
        return files[-1]


def make_plan(uri: str, mix: dict, statements: list[dict],
              seconds: float) -> dict:
    """What ``loadgen.py`` is handed; the window opens at ``t0``, after
    the child's start-up and the mix's ramp."""
    clients = int(mix.get("clients", 1))
    return {
        "uri": uri, "session": mix.get("session", {}), "loop": mix["loop"],
        "t0": (time.monotonic() + CHILD_START_S
               + float(mix.get("ramp_s", 0.0))),
        "seconds": seconds,
        "connections": int(mix.get("connections", clients)),
        "round": len(mix["classes"]) if clients == 1 else 1,
        "min_per_class": int(mix.get("min_per_class", 1)),
        "timeout_s": float(mix.get("statement_timeout_s", 120)),
        "serial": [c["name"] for c in mix["classes"] if c.get("serial")],
        "statements": [{k: st[k] for k in ("i", "cls", "sql", "due")}
                       for st in statements]}


def run_window(plan: dict, at_t0=lambda: None) -> list[dict]:
    """Hand the plan to the load generator and collect its records;
    ``at_t0`` is called when the window opens (after an open loop's
    ramp, which the generator runs before ``t0``)."""
    child = subprocess.Popen(
        [sys.executable, str(HERE / "loadgen.py")],
        stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True)
    try:
        child.stdin.write(json.dumps(plan))
        child.stdin.close()
        killer = threading.Timer(
            plan["t0"] - time.monotonic() + plan["seconds"] + DRAIN_S,
            child.kill)
        opener = threading.Timer(plan["t0"] - time.monotonic(), at_t0)
        killer.start()
        opener.start()
        try:
            records = [json.loads(line) for line in child.stdout]
        finally:
            killer.cancel()
            opener.cancel()
            opener.join()
        if child.wait() != 0:
            raise RuntimeError(f"load generator exited {child.returncode}")
        return records
    finally:
        if child.poll() is None:
            child.kill()
        child.wait()


# -- metrics ------------------------------------------------------------------

def end_to_end(name: str, records: list[dict], t0: float,
               setup_s: float, mix: dict) -> float | None:
    if name == "setup_s":
        return setup_s
    if name == "geomean_ms":
        # a class the mix marks "in_geomean": false (a write offered a
        # few times per window) has its median in class.<name>_ms only
        counted = {c["name"] for c in mix["classes"]
                   if c.get("in_geomean", True)}
        return arith.geomean_of_class_medians(
            [r for r in records if r["cls"] in counted])
    if name == "qph":
        return arith.qph(records, t0)
    raise KeyError(f"no end-to-end metric {name!r}")


def reader_of(name: str, classes: dict[str, dict]):
    """The reader of a per-layer metric: ``layers/<name>.py`` with
    ``read(ctx)``, or, for a metric that exists once per query class, the
    file that has ``CLASS`` where the metric's name has a class of the
    cell's mix, with ``read(ctx, cls)``. None where neither is there (a
    class the mix does not hold): the metric is then left out."""
    import verify
    path = HERE / "layers" / f"{name}.py"
    if path.is_file():
        return verify.load_attr(path, "read")
    for cls in sorted(classes, key=len, reverse=True):
        path = HERE / "layers" / f"{name.replace(cls, 'CLASS')}.py"
        if cls in name and path.is_file():
            read = verify.load_attr(path, "read")
            return lambda ctx: read(ctx, cls)
    return None


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    manifest, cell, config, mix, classes, dev, peaks = prepare(
        args.workload)
    setup: dict = {"import_s": time.monotonic() - T_START}
    say(f"device {dev}")

    engine, conn, server = build(config, classes, args.seed, setup)
    try:
        warm(server.uri, mix, classes, args.seed, setup)
        if config["compile_cache_in_window"] == "off":
            persistent_cache_off()
        statements = traffic.schedule(mix, classes, args.seed, args.seconds)
        plan = make_plan(server.uri, mix, statements, args.seconds)
        t0 = plan["t0"]
        setup_s = t0 - T_START
        tracing = (Tracing(cell["name"], t0, traffic.traced_span(mix))
                   if args.trace and peaks is not None else None)
        before: dict[str, float] = {}
        say(f"window opens at +{t0 - T_START:.1f}s (set-up), "
            f"{len(statements)} statements planned")
        records = run_window(plan, lambda: before.update(counters()))
        after = counters()
        window = [r for r in records if r["due"] >= t0]
        say(f"window closed: {len(window)} records, "
            f"{len(records) - len(window)} in the ramp")
        for cls, rs in sorted(arith.by_class(arith.good(window)).items()):
            walls = sorted(arith.wall_ms(r) for r in rs)
            say(f"  {cls}: {len(rs)} answered, wall ms median "
                f"{arith.median(walls):.1f} p95 "
                f"{walls[int(0.95 * (len(walls) - 1))]:.1f} "
                f"max {walls[-1]:.1f}")
        for r in sorted(arith.good(window), key=arith.wall_ms)[-3:]:
            say(f"  slowest: {r['cls']} {statements[r['i']]['params']} sent "
                f"at +{r['sent'] - t0:.2f}s took {arith.wall_ms(r):.1f} ms")
        trace_file = tracing.join() if tracing else None
        for r in records:
            r["params"] = statements[r["i"]]["params"]
        spans = span_trees(records) if args.trace else {}
    finally:
        server.stop()

    import refdata
    import verify
    data = refdata.Columns(conn)
    t = time.monotonic()
    compared = verify.check(records, classes, data, config, args.seed)
    failed = sum(1 for r in records if "error" in r or "wrong" in r)
    say(f"verified {compared} answers in {time.monotonic() - t:.1f}s; "
        f"{failed} of {len(records)} failed")
    for r in records:
        if "error" in r or "wrong" in r:
            say(f"  failed {r['cls']} {r['params']}: "
                f"{r.get('error') or r['wrong']}")
            break

    dev["memory_peak_bytes"] = memory_peak_bytes()
    out: dict = {"correct": failed == 0 and bool(records),
                 "attempted": len(records), "failed": failed,
                 "metrics": {}, "device": dev}
    if not args.trace:
        for m in metrics_of(manifest, "end_to_end", cell["name"]):
            v = end_to_end(m["name"], window, t0, setup_s, mix)
            if v is not None:
                out["metrics"][m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        reduced = None
        if trace_file is not None:
            import tracered
            # the reduction's other inputs stay beside the trace, so a
            # number can be worked out again from what is on disk
            (tracing.dir / "reduce_inputs.json").write_text(json.dumps({
                "lo": tracing.lo, "hi": tracing.hi, "spans": spans,
                "records": [{k: v for k, v in r.items() if k != "rows"}
                            for r in records]}, default=str))
            reduced = tracered.reduce(trace_file, tracing.lo, tracing.hi,
                                      records, spans)
            dev["busy_s"] = reduced.busy_s
            dev["window_s"] = reduced.window_s
            out["breakdown"] = reduced.breakdown()
        ctx = types.SimpleNamespace(
            cell=cell, config=config, mix=mix, classes=classes,
            records=window, ramp=[r for r in records if r["due"] < t0],
            t0=t0, setup=setup, setup_s=setup_s,
            counters={k: after[k] - before.get(k, 0.0) for k in after},
            spans=spans, trace=reduced, peaks=peaks, data=data,
            device=dev)
        for m in metrics_of(manifest, "per_layer", cell["name"]):
            read = reader_of(m["name"], classes)
            # a reader with nothing to read returns None, and a value
            # that is no finite number would not be JSON: the metric is
            # left out, as it is when its reader fails (one reader
            # costs one metric, not the run's line)
            try:
                v = read(ctx) if read else None
                if v is not None and not math.isfinite(v):
                    raise ValueError(f"{v} is no finite number")
            except Exception as exc:  # noqa: BLE001
                say(f"{m['name']}: its reader failed: {exc!r}")
                v = None
            if v is not None:
                out["metrics"][m["name"]] = {"value": v, "unit": m["unit"]}
            else:
                say(f"{m['name']}: left out of the line")
    # a NumPy scalar a reader hands back is a number, not a reason to
    # lose the run's one line
    print(json.dumps(out, default=float), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
