"""Load generator of the benchmark: a process of its own.

    python benchmark/loadgen.py < plan.json > records.jsonl

It imports the standard library and ``protocol.py`` only (no JAX, no
``presto_tpu``), so it shares neither the chip nor the server's
interpreter lock. The plan, one JSON object on standard input, is made
by ``traffic.py`` in the parent; this file draws nothing:

    {"uri": ..., "session": {...}, "loop": "closed" | "open",
     "connections": n, "t0": <time.monotonic() of the window's start>,
     "seconds": s, "round": k, "min_per_class": m, "timeout_s": t,
     "serial": [<class>, ...],
     "statements": [{"i": 0, "cls": "q06", "sql": ..., "due": 0.0}, ...]}

``closed``: each connection sends the next statement of the list as
soon as its last one is answered; the window ends at the first
completed round (``round`` statements) after ``seconds`` in which every
class has ``min_per_class`` completions. ``open``: a statement is sent
at ``t0 + due`` whatever the server does, by the first connection that
is free (a late send is the generator's lateness, and latency counts
from ``due``; a negative ``due`` is the ramp before the window). A class
in ``serial`` has one statement in flight at a time (one writer): the
next waits for the last one's answer, and its wait counts in its wall. One record per statement goes to standard output as a
JSON line, times on ``time.monotonic()`` (one clock for every process
of the machine): ``i``, ``cls``, ``due``, ``sent``, ``done``, ``qid``
and ``rows`` or ``error``.
"""

from __future__ import annotations

import contextlib
import json
import os
import sys
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from protocol import Connection, StatementError  # noqa: E402


NO_LOCK = contextlib.nullcontext()


class Window:
    """What the connections share: the list, the cursor, the stop rule."""

    def __init__(self, plan: dict, out):
        self.plan = plan
        self.t0 = float(plan["t0"])
        self.open_loop = plan["loop"] == "open"
        self.statements = plan["statements"]
        self.lock = threading.Lock()
        self.next = 0
        self.done = 0
        self.per_class = {s["cls"]: 0 for s in self.statements}
        self.stop = False
        self.out = out
        self.serial = {c: threading.Lock() for c in plan.get("serial", [])}

    def take(self) -> dict | None:
        with self.lock:
            if self.stop or self.next >= len(self.statements):
                return None
            st = self.statements[self.next]
            self.next += 1
            return st

    def record(self, rec: dict) -> None:
        line = json.dumps(rec, separators=(",", ":"))
        with self.lock:
            self.out.write(line + "\n")
            self.done += 1
            if "error" not in rec:
                self.per_class[rec["cls"]] += 1
            if (not self.open_loop
                    and self.done % int(self.plan["round"]) == 0
                    and rec["done"] - self.t0 >= self.plan["seconds"]
                    and min(self.per_class.values())
                    >= int(self.plan["min_per_class"])):
                self.stop = True


def serve(window: Window) -> None:
    plan = window.plan
    conn = Connection(plan["uri"], plan.get("session"),
                      timeout_s=float(plan["timeout_s"]))
    try:
        while (st := window.take()) is not None:
            due = window.t0 + float(st["due"])
            wait = due - time.monotonic()
            if wait > 0:  # open loop; in a closed one only before t0
                time.sleep(wait)
            with window.serial.get(st["cls"], NO_LOCK):
                sent = time.monotonic()
                rec = {"i": st["i"], "cls": st["cls"],
                       "due": due if window.open_loop else sent,
                       "sent": sent}
                try:
                    rec["qid"], rec["rows"] = conn.execute(st["sql"])
                except StatementError as exc:
                    rec["error"] = str(exc)
                rec["done"] = time.monotonic()
            window.record(rec)
    finally:
        conn.close()


def main() -> int:
    plan = json.load(sys.stdin)
    window = Window(plan, sys.stdout)
    threads = [threading.Thread(target=serve, args=(window,), daemon=True)
               for _ in range(int(plan["connections"]))]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    sys.stdout.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
