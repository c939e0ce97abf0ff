"""The one traffic generator: mix file + class files + seed -> statements.

A mix (``traffic/<name>.json``) and a class (``queries/<name>.json``
beside ``queries/<name>.sql``) are data; README.md has their schemas.
Everything random here comes from ``--seed`` through NumPy's PCG64, one
stream per purpose, so a seed repeats its statements exactly.
"""

from __future__ import annotations

import datetime
import json
import zlib
from pathlib import Path

import numpy as np

HERE = Path(__file__).resolve().parent

# statements a closed loop is handed; it stops by the clock, not here
CLOSED_LIST = 20000


def _rng(seed: int, purpose: str) -> np.random.Generator:
    return np.random.default_rng([int(seed), zlib.crc32(purpose.encode())])


def load_json(kind: str, name: str, root: Path = HERE) -> dict:
    path = root / kind / f"{name}.json"
    with open(path, encoding="utf-8") as f:
        return json.load(f)


# What the harness reads from a file, and nothing else may be in it: a
# key that looks like an option and does nothing misleads whoever adds
# the next file. A key that ends in ``_why`` is a note and is free.
MIX_KEYS = {"loop", "clients", "rate", "connections", "ramp_s", "session",
            "classes", "draw", "setup", "min_per_class",
            "statement_timeout_s", "trace"}
MIX_CLASS_KEYS = {"name", "weight", "rate", "serial", "in_geomean"}
CONFIG_KEYS = {"source", "chips", "catalogs", "data_catalog", "session",
               "mesh", "compile_cache_in_window", "guarantees", "assumed",
               "reduced", "reduced_why", "verify"}


def _known(what: str, body: dict, keys: set[str]) -> None:
    unknown = sorted(k for k in body
                     if k not in keys and not k.endswith("_why"))
    if unknown:
        raise ValueError(f"{what}: nothing reads the key(s) {unknown}")


def load_mix(name: str, root: Path = HERE) -> dict:
    mix = load_json("traffic", name, root)
    _known(f"traffic/{name}.json", mix, MIX_KEYS)
    for c in mix["classes"]:
        _known(f"traffic/{name}.json, class {c.get('name')}", c,
               MIX_CLASS_KEYS)
    return mix


def load_config(name: str, root: Path = HERE) -> dict:
    """A configuration; besides CONFIG_KEYS it may hold the top-level
    keys its catalogs' ``args`` name as "$key" (``scale_factor``)."""
    config = load_json("configs", name, root)
    named = {v[1:] for spec in config["catalogs"].values()
             for v in spec.get("args", {}).values()
             if isinstance(v, str) and v.startswith("$")}
    _known(f"configs/{name}.json", config, CONFIG_KEYS | named - {"seed"})
    return config


def load_class(name: str, root: Path = HERE) -> dict:
    """A query class: its JSON (domain, tables, kind) plus ``sql``."""
    cls = load_json("queries", name, root)
    cls["name"] = name
    cls["sql"] = (root / "queries" / f"{name}.sql").read_text(
        encoding="utf-8").strip()
    return cls


def domain_size(cls: dict) -> int:
    n = 1
    for axis in cls.get("axes", []):
        n *= len(axis)
    return n


def params_at(cls: dict, index: int, occurrence: int) -> dict:
    """The parameters of domain point ``index`` (mixed radix over the
    axes, last axis fastest); a ``sequence`` gives the class's
    ``occurrence``-th statement the next value instead of a draw."""
    out: dict = {}
    for axis in reversed(cls.get("axes", [])):
        index, k = divmod(index, len(axis))
        out.update(axis[k])
    seq = cls.get("sequence")
    if seq:
        day = (datetime.date.fromisoformat(seq["start"])
               + datetime.timedelta(days=occurrence))
        out[seq["placeholder"]] = day.isoformat()
    return out


class Drawer:
    """Draws domain points of one class: uniform, or Zipf over a seeded
    permutation of the domain (rank r with weight r**-s; 0.99 is YCSB's
    constant)."""

    def __init__(self, cls: dict, draw: dict, seed: int, purpose: str,
                 first_occurrence: int = 0):
        self.cls = cls
        self.n = domain_size(cls)
        self.rng = _rng(seed, f"{purpose}:{cls['name']}")
        kind = draw.get("kind", "uniform")
        if kind == "uniform":
            self.cdf = None
        elif kind == "zipf":
            w = np.arange(1, self.n + 1, dtype=np.float64) ** -float(
                draw["s"])
            self.cdf = np.cumsum(w / w.sum())
            self.perm = _rng(seed, f"perm:{cls['name']}").permutation(
                self.n)
        else:
            raise ValueError(f"unknown draw kind {kind!r}")
        self.count = first_occurrence

    def next(self) -> dict:
        if self.cdf is None:
            index = int(self.rng.integers(self.n))
        else:
            rank = int(np.searchsorted(self.cdf, self.rng.random()))
            index = int(self.perm[min(rank, self.n - 1)])
        params = params_at(self.cls, index, self.count)
        self.count += 1
        return params


def statement(cls: dict, params: dict) -> str:
    return cls["sql"].format(**params)


def schedule(mix: dict, classes: dict[str, dict], seed: int,
             seconds: float, first_occurrence: int = 0) -> list[dict]:
    """The window's statements in order: ``i``, ``cls``, ``params``,
    ``sql`` and, in an open loop, ``due`` (seconds after the window's
    start, negative in the ramp; Poisson arrivals at the mix's fixed
    ``rate``). ``first_occurrence`` is where a ``sequence`` class goes
    on from when a server has already seen that many of its statements."""
    names = [c["name"] for c in mix["classes"]]
    weights = np.array([float(c.get("weight", 1)) for c in mix["classes"]])
    drawers = {n: Drawer(classes[n], mix.get("draw", {}), seed, "window",
                         first_occurrence) for n in names}
    out: list[dict] = []
    if mix["loop"] == "closed":
        # round-robin in the order the mix lists its classes
        for i in range(CLOSED_LIST):
            out.append({"i": i, "cls": names[i % len(names)], "due": 0.0})
    elif mix["loop"] == "open":
        # A Poisson process given its count: n = rate x length arrival
        # times, uniform over the window and sorted, and each class's
        # share of n exactly, shuffled. A class with a ``rate`` of its
        # own takes rate x length of the n whatever the mix's rate is (a
        # writer's pace does not follow the readers'); the others share
        # the rest by weight. The amount of work is then the same for
        # every seed; only its order and spacing are drawn. The ramp is
        # the same traffic for ``ramp_s`` before the window (negative
        # ``due``), so that the window sees caches as full as a server
        # that has been up for a while has them.
        rng = _rng(seed, "arrivals")
        rate, ramp = float(mix["rate"]), float(mix.get("ramp_s", 0.0))
        own = np.array([float(c.get("rate", 0.0)) for c in mix["classes"]])
        weights = np.where(own > 0, 0.0, weights)
        i = 0
        for lo, hi in ((-ramp, 0.0), (0.0, float(seconds))):
            n = int(round(rate * (hi - lo)))
            fixed = np.round(own * (hi - lo)).astype(int)
            exact = weights / weights.sum() * (n - fixed.sum())
            counts = np.floor(exact).astype(int)
            for k in np.argsort(-(exact - counts))[:n - fixed.sum()
                                                   - counts.sum()]:
                counts[k] += 1
            counts += fixed
            which = rng.permutation(np.repeat(np.arange(len(names)), counts))
            for t, k in zip(np.sort(rng.uniform(lo, hi, n)), which):
                out.append({"i": i, "cls": names[int(k)], "due": float(t)})
                i += 1
    else:
        raise ValueError(f"unknown loop {mix['loop']!r}")
    for st in out:
        st["params"] = drawers[st["cls"]].next()
        st["sql"] = statement(classes[st["cls"]], st["params"])
    return out


def traced_span(mix: dict) -> tuple[float, float]:
    """(start, end) of a ``--trace 1`` run's traced sub-window, seconds
    after the window's start, from the mix's ``trace`` (``start_s``,
    ``seconds``). A mix whose device work comes at moments drawn from
    the seed (a write that invalidates cached answers) traces its whole
    window: a few seconds of it may hold no device work at all, the
    profiler then writes no device plane, and a trace in which no
    operation ran on the device says nothing."""
    spec = mix["trace"]
    start = float(spec.get("start_s", 0.0))
    return start, start + float(spec["seconds"])


def warmup(mix: dict, classes: dict[str, dict], seed: int) -> list[dict]:
    """One statement per class for set-up, from a stream of its own so
    the window's draws do not depend on it. A ``sequence`` class is left
    out: its statements change state, and the window owns the sequence
    (a mix warms such a class through its ``setup`` statements)."""
    out = []
    for c in mix["classes"]:
        cls = classes[c["name"]]
        if cls.get("sequence"):
            continue
        params = Drawer(cls, {}, seed, "warmup").next()
        out.append({"cls": cls["name"], "params": params,
                    "sql": statement(cls, params)})
    return out
