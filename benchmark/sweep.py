"""Find an open-loop mix's knee: one set-up, then a window per rate.

    python benchmark/sweep.py --workload tpch_sf1.dash --seed 1 --seconds 20 --rates 50,100,150,200,300

Not part of a check: the builder runs it once on the chip, writes the
table into PERF.md and the fixed rate (0.8 of the knee) into the mix
file. A rate is sustained when the backlog does not grow over the
window: the generator's lateness and the walls of the window's second
half are no worse than those of its first. One JSON line per rate; the
sweep stops after the first rate that is plainly overloaded (the
generator's lateness in the second half has a 95th percentile over a
second), since a higher one only costs its drain.
"""

from __future__ import annotations

import argparse
import json
import sys

import arith
import run
import traffic


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    _manifest, _cell, config, mix, classes, dev, _peaks = run.prepare(
        args.workload)
    if mix["loop"] != "open":
        run.fail("only an open loop has a rate to sweep")
    setup: dict = {}
    _engine, _conn, server = run.build(config, classes, args.seed, setup)
    try:
        run.warm(server.uri, mix, classes, args.seed, setup)
        if config["compile_cache_in_window"] == "off":
            run.persistent_cache_off()
        seen = 0  # statements of a sequence class the table already has
        for k, rate in enumerate(float(r) for r in args.rates.split(",")):
            mix["rate"] = rate
            statements = traffic.schedule(mix, classes, args.seed + k,
                                          args.seconds, seen)
            seen += sum(1 for st in statements
                        if classes[st["cls"]].get("sequence"))
            plan = run.make_plan(server.uri, mix, statements, args.seconds)
            t0 = plan["t0"]
            records = [r for r in run.run_window(plan) if r["due"] >= t0]
            ok = arith.good(records)
            half = t0 + args.seconds / 2
            walls = [[arith.wall_ms(r) for r in ok
                      if (r["due"] < half) == first] for first in (True,
                                                                    False)]
            late = [[(r["sent"] - r["due"]) * 1e3 for r in records
                     if (r["due"] < half) == first] for first in (True,
                                                                  False)]
            late_p95 = [arith.percentile(x, 95.0) for x in late]
            print(json.dumps({
                "rate": rate, "attempted": len(records),
                "per_class": {c: len(rs) for c, rs in
                              sorted(arith.by_class(records).items())},
                "failed": len(records) - len(ok),
                "completed_per_s": len(ok) / (max(r["done"] for r in ok)
                                              - t0),
                "median_ms_halves": [arith.median(w) for w in walls],
                "p95_ms_halves": [arith.percentile(w, 95.0) for w in walls],
                "late_p95_ms_halves": late_p95,
                "late_median_ms": arith.median(late[0] + late[1]),
                "drain_s": max(r["done"] for r in ok) - t0 - args.seconds,
                "device": dev}), flush=True)
            if (late_p95[1] or 0.0) > 1000.0:
                break
    finally:
        server.stop()
    return 0


if __name__ == "__main__":
    sys.exit(main())
