"""The comparison that decides ``correct``: records against references.

A reference is ``reference/<class>.py`` with ``answer(data, params,
state)``: plain NumPy over the generated columns (``refdata.Columns``),
independent of the code under test. A record whose rows differ from the
reference gets ``wrong`` set and counts as failed; ``check`` returns the
number of comparisons made.

The guarantees held here are the configuration's: answers exact (row for
row, in order where the class says ``ordered``), and an acknowledged
write visible to every statement sent after its acknowledgement.
"""

from __future__ import annotations

import importlib.util
import itertools
import json
import re
from pathlib import Path

import numpy as np

HERE = Path(__file__).resolve().parent
MAX_IN_FLIGHT = 10  # 2**10 table states is the most one answer is held to

# The guarantees this comparison knows how to hold a system to, by the
# ``rule`` a configuration gives each; another rule is refused, since
# ``correct`` would then promise what nothing checked.
RULES = {"answers": "exact", "read_your_writes": "acknowledged_visible"}


def load_attr(path: Path, attr: str):
    """``attr`` of the Python file at ``path`` (references and per-layer
    readers are found by file name, not imported by package)."""
    spec = importlib.util.spec_from_file_location(
        "bench_" + re.sub(r"\W", "_", path.stem), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return getattr(mod, attr)


def load_reference(name: str, root: Path = HERE):
    return load_attr(root / "reference" / f"{name}.py", "answer")


def _key(params: dict) -> str:
    return json.dumps(params, sort_keys=True)


def _same(got: list, want: list, ordered: bool) -> bool:
    if ordered:
        return got == want
    return sorted(map(json.dumps, got)) == sorted(map(json.dumps, want))


def _states(rec: dict, writes: list[dict]):
    """Every table state a statement sent at ``rec['sent']`` and answered
    at ``rec['done']`` may show: all writes acknowledged before it was
    sent, plus any subset of those in flight while it ran (a write that
    failed may or may not have landed, so it stays in flight for ever)."""
    acked, flying = [], []
    for w in writes:
        if "error" not in w and w["done"] <= rec["sent"]:
            acked.append(w["params"])
        elif w["sent"] < rec["done"]:
            flying.append(w["params"])
    if len(flying) > MAX_IN_FLIGHT:
        raise ValueError(f"{len(flying)} writes in flight over one read")
    for k in range(len(flying) + 1):
        for extra in itertools.combinations(flying, k):
            yield acked + list(extra)


def check(records: list[dict], classes: dict[str, dict], data,
          config: dict, seed: int, root: Path = HERE) -> int:
    rules = {k: g["rule"] for k, g in config["guarantees"].items()}
    if rules != RULES:
        raise ValueError(f"guarantees {rules} are not the ones held here: "
                         f"{RULES}")
    sample_per_class = config["verify"]["sample_per_class"]
    good = [r for r in records if "error" not in r]
    by_cls: dict[str, list[dict]] = {}
    for r in good:
        by_cls.setdefault(r["cls"], []).append(r)
    compared = 0
    for name, recs in sorted(by_cls.items()):
        cls = classes[name]
        answer = load_reference(name, root)
        ordered = bool(cls.get("ordered", True))
        if cls.get("reads_writes_of"):
            writes = [r for r in records
                      if r["cls"] in cls["reads_writes_of"]]
            for r in recs:
                compared += 1
                if not any(_same(r["rows"], answer(data, r["params"], s),
                                 ordered) for s in _states(r, writes)):
                    r["wrong"] = "equals no table state it may show"
            continue
        groups: dict[str, list[dict]] = {}
        for r in recs:
            groups.setdefault(_key(r["params"]), []).append(r)
        # the same parameters over the same tables give the same rows
        for rs in groups.values():
            for r in rs[1:]:
                if r["rows"] != rs[0]["rows"]:
                    r["wrong"] = "differs from an answer to the same text"
        keys = sorted(groups)
        if (sample_per_class is not None and len(keys) > sample_per_class
                and not cls.get("writes")):
            rng = np.random.default_rng([int(seed), 7])
            keys = [keys[i] for i in rng.choice(
                len(keys), size=sample_per_class, replace=False)]
        for k in keys:
            want = answer(data, groups[k][0]["params"], None)
            compared += 1
            if not _same(groups[k][0]["rows"], want, ordered):
                for r in groups[k]:
                    r.setdefault("wrong", "differs from the reference")
    return compared
