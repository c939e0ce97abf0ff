"""Compile-latency subsystem: bounded LRU program cache + persistent
AOT disk store + parallel segment compilation.

XLA compilation dominates cold-query latency by 20-40x (BENCH r05: Q5
compiles 152 s against ~4 s of execution). The reference amortizes
codegen with compiled-artifact caches shared across queries
(gen/PageFunctionCompiler.java:101); the JAX analog treats compiled
executables as reusable artifacts keyed on canonical structure
("Fine-Tuning Data Structures for Analytical Query Processing",
PAPERS.md). Three legs:

1. **In-memory LRU** (:class:`ProgramCache`): replaces the unbounded
   ``engine._program_cache`` dict with a size-bounded (entries AND
   bytes) LRU reporting hits/misses/evictions/resident-bytes through
   the obs registry.

2. **Persistent AOT store**: entries serialize through
   ``jax.experimental.serialize_executable`` into a content-addressed
   directory (``PRESTO_TPU_PROGRAM_CACHE_DIR``), keyed by the
   canonical cache key PLUS a platform fingerprint (jax/jaxlib
   version, backend, device kind/count, mesh shape, x64 flag) so a
   warm process — or a freshly-POSTed worker task on another node
   sharing the directory — skips lower+compile entirely.  Any
   serialize/deserialize failure falls back to a live compile (miss
   counted, error counted, never a crash).  A tiny ``.caps.json``
   sidecar persists the successful hash-table capacity vector per
   plan, so a warm process goes straight to the right program instead
   of replaying the overflow-retry ladder.

3. **Parallel compilation** (:func:`map_parallel`): independent
   segments/programs compile concurrently on a bounded thread pool —
   XLA compilation releases the GIL — with the segment dependency
   order respected by the caller (exec/executor._segment_carriers
   compiles wave-by-wave).

Key canonicalization: capacities route through the same pow2
bucketing the cost-based reorderer uses (ops/hash.next_pow2), and the
session component of the key is restricted to the properties the
trace actually reads (:data:`TRACE_RELEVANT_PROPERTIES`) — resolved
through ``Session.get`` so per-thread overrides participate — so
structurally-identical replans hit the same entry.

Dictionary contents participate in the key: string-dictionary arrays
get a content digest (:func:`dictionary_token`, memoized by array
identity so the hash is paid once per process per dictionary) because
traced programs embed dictionary codes as constants and ``meta``
carries the decode dictionary — a disk entry surviving a data rewrite
at constant shape must miss, not silently decode against stale
strings.

Locking: all mutable cache state (``_entries``, ``_bytes``,
``max_entries``, ``max_bytes``) is guarded by ``self._lock``; disk IO
runs outside the lock (atomic tmp+rename writes), so a slow
serialization never blocks concurrent lookups.
"""

from __future__ import annotations

import functools
import hashlib
import json
import os
import pickle
import threading
import time

from presto_tpu.obs.metrics import REGISTRY
from presto_tpu.ops.hash import next_pow2

ENV_DIR = "PRESTO_TPU_PROGRAM_CACHE_DIR"

_HITS = REGISTRY.counter(
    "presto_tpu_program_cache_hits_total",
    "compiled-program cache hits, labeled tier=memory|disk")
_MISSES = REGISTRY.counter(
    "presto_tpu_program_cache_misses_total",
    "compiled-program cache misses (a live XLA compile follows)")
_EVICTIONS = REGISTRY.counter(
    "presto_tpu_program_cache_evictions_total",
    "LRU evictions from the in-memory program cache")
_DISK_ERRORS = REGISTRY.counter(
    "presto_tpu_program_cache_disk_errors_total",
    "disk-store serialize/deserialize failures (fallback to live "
    "compile), labeled op=load|store")
_RESIDENT = REGISTRY.gauge(
    "presto_tpu_program_cache_resident_bytes",
    "estimated bytes of compiled programs resident across every "
    "live in-process LRU (delta-accounted process total)")
_ENTRIES_G = REGISTRY.gauge(
    "presto_tpu_program_cache_entries",
    "compiled programs resident across every live in-process LRU "
    "(delta-accounted process total)")
_LOAD_SECONDS = REGISTRY.histogram(
    "presto_tpu_program_cache_load_seconds",
    "wall time to deserialize one AOT program from the disk store")

# Session properties the trace-time interpreters actually read
# (PlanInterpreter / ShardedInterpreter): the canonical session
# component of a cache key. Everything else either acts at plan time
# (captured by the plan fingerprint) or host-side before/after the
# compiled program runs. The adaptive-execution properties
# (adaptive_replanning, speculative_execution, speculation_*) are
# deliberately NOT listed: they steer the coordinator's HTTP stage
# walk only, and re-keying compiled programs on them would evict warm
# entries for a knob the trace never sees. (A replan changes plan
# ANNOTATIONS — capacities, distributions — which already participate
# via the plan fingerprint and capacity buckets.)
#
# This tuple is machine-checked both ways by the ``tracekey`` lint
# rule (lint/tracekey.py): a trace-reachable session read missing
# here is an ``unsound-read`` finding (stale-executable wrong
# results), and an entry no trace-reachable code reads is a
# ``stale-key-entry`` finding (spurious recompiles). PR 15 pruned
# ``use_connector_partitioning`` on that analysis: it is read only
# host-side by execute_plan_distributed, and the bucketing decision
# it drives already rides the distributed cache key as the explicit
# per-scan ``(part_cols, bucketed)`` component.
TRACE_RELEVANT_PROPERTIES = (
    "broadcast_join_threshold_rows",
    "distributed_sort",
    "enable_dynamic_filtering",
    "groupby_table_size",
    "join_distribution_type",
    "join_salting",
    "partial_aggregation",
    "partitioned_agg_min_groups",
    "skew_hot_key_threshold",
)

# Ambient reads the tracekey provenance analysis sees inside trace
# scope that are DELIBERATELY not part of the canonical key, each with
# the soundness argument. Ids are the rule's finding ids
# (``session:<prop>``, ``env:<NAME>``, ``global:<relpath>:<NAME>``,
# ``key:<prop>``); an entry that stops matching a finding becomes a
# ``stale-exemption`` finding itself, so this registry cannot rot into
# a blanket waiver.
TRACE_KEY_EXEMPT = {
    "global:presto_tpu/ops/hash.py:_DICT_HASH_CACHE":
        "pure memoization: the cached hashes are a content-only "
        "function of the dictionary array (identity-checked strong "
        "ref), and dictionary CONTENT already rides every cache key "
        "via scan_dictionary_key — a rebuilt cache yields bit-equal "
        "values",
    "global:presto_tpu/expr/compile.py:_DATE_FORMAT_CACHE":
        "pure memoization keyed by the date_format literal: the LUT "
        "is a content-only function of the format string, which is "
        "structural (never hoisted by templates/analysis.py) and so "
        "participates in the plan fingerprint",
}

# retrace-hazard exemptions (lint/retrace.py): deliberate
# data-dependent control flow / shape construction in trace scope,
# id ("<relpath>:<dotted.unit.path>:<kind>", kind in branch | shape |
# key) -> justification. Stale entries are findings, like
# TRACE_KEY_EXEMPT above.
RETRACE_EXEMPT = {
    "presto_tpu/exec/executor.py:carrier_width:branch":
        "the branch on the live count IS the bucketing helper: every "
        "arm produces a bucketed carrier width (the remembered "
        "template width when the count fits, the planner's pow2 "
        "width when the count is of its order, pow2-with-margin of "
        "the count otherwise), so the data dependence is confined to "
        "choosing between cache-stable shapes",
}

DEFAULT_MAX_ENTRIES = 64
DEFAULT_MAX_BYTES = int(os.environ.get(
    "PRESTO_TPU_PROGRAM_CACHE_MEM_BYTES", 2 << 30))
# disk-store budget: oldest entries are pruned (best effort, after
# each store) once the directory exceeds this — the store accumulates
# across schema/scale/session/platform variations forever otherwise
DISK_BYTES_LIMIT = int(os.environ.get(
    "PRESTO_TPU_PROGRAM_CACHE_DISK_BYTES", 32 << 30))
# conservative stand-in when the backend cannot report code size
_DEFAULT_ENTRY_BYTES = 1 << 22


def trace_session_key(session) -> tuple:
    """Canonical session component of a cache key: only the properties
    the trace reads, resolved through Session.get so per-thread query
    overrides (server dispatch) participate."""
    return tuple((name, repr(session.get(name)))
                 for name in TRACE_RELEVANT_PROPERTIES)


def bucket_capacities(capacities: dict) -> tuple:
    """Capacity-override vector canonicalized to pow2 buckets (the
    bucketing cost/reorder.py already applies to its hints), sorted
    for key stability."""
    return tuple(sorted(
        (k, next_pow2(v)) for k, v in capacities.items()))


# dictionary content digests memoized by array identity (strong ref
# pins the id, the engine's device-pin cache uses the same pattern);
# bounded so per-execution temporary dictionaries cannot leak
_DICT_TOKENS: dict[int, tuple] = {}
_DICT_TOKENS_MAX = 256
_DICT_LOCK = threading.Lock()


def dictionary_token(arr) -> str | None:
    """Content digest of one dictionary array, or None. Traced
    programs embed dictionary codes as constants and cached meta
    carries the decode dictionary, so dictionary CONTENT — not just
    shape — must participate in cache keys."""
    import numpy as np
    if arr is None:
        return None
    key = id(arr)
    with _DICT_LOCK:
        hit = _DICT_TOKENS.get(key)
        if hit is not None and hit[0] is arr:
            return hit[1]
    data = np.asarray(arr)
    h = hashlib.blake2b(digest_size=8)
    h.update(str(len(data)).encode())
    if data.dtype == object:
        for s in data.tolist():
            h.update(str(s).encode())
            h.update(b"\0")
    else:
        h.update(np.ascontiguousarray(data).tobytes())
    digest = h.hexdigest()
    with _DICT_LOCK:
        if len(_DICT_TOKENS) >= _DICT_TOKENS_MAX:
            _DICT_TOKENS.clear()
        _DICT_TOKENS[key] = (arr, digest)
    return digest


def scan_dictionary_key(scan_inputs) -> tuple:
    """Key component covering every scanned dictionary's content."""
    return tuple(
        (i, sym, dictionary_token(d))
        for i, scan in enumerate(scan_inputs)
        for sym, d in scan.dictionaries.items() if d is not None)


# traced-program output-format version: participates in the platform
# fingerprint so persisted entries from an engine with a different
# output contract (e.g. before the always-on per-node row counts
# became a fourth program output, or before the distributed path
# stacked its ok flags into one (k,) array) miss instead of
# mis-unpacking. "cost1": meta carries the compile-time device-cost
# summary (obs/devprof.harvest) — pre-cost entries would report zero
# flops forever on warm hits, so they miss and recompile once.
# "dynf1": which join legs register a dynamic filter changed under an
# unchanged plan fingerprint (PlanInterpreter._collect_dyn_filters),
# and meta carries the legs' counts — an older entry would go on
# testing a direct-address leg's key twice and report no counts
PROGRAM_FORMAT = "dynf1"


@functools.lru_cache(maxsize=32)
def mesh_key(mesh) -> tuple:
    """What tells one mesh's programs from another's: shape, axis
    names and the ids of its devices in mesh order (an executable is
    bound to the devices it was compiled for, not to the first N)."""
    return (tuple(mesh.devices.shape), tuple(mesh.axis_names),
            tuple(int(d.id) for d in mesh.devices.flat))


@functools.lru_cache(maxsize=32)
def platform_fingerprint(mesh_shape: tuple | None = None) -> tuple:
    """What a serialized executable is only valid for: jax/jaxlib
    versions, backend kind, device kind and count, x64 mode, the
    engine's traced-program output format, and (for shard_map
    programs) the mesh (:func:`mesh_key`)."""
    import jax
    import jaxlib

    devs = jax.devices()
    return (jax.__version__, jaxlib.__version__,
            jax.default_backend(), len(devs),
            getattr(devs[0], "device_kind", "?"),
            bool(jax.config.jax_enable_x64), PROGRAM_FORMAT,
            mesh_shape)


def entry_digest(key, fingerprint) -> str:
    """Content address of one (canonical key, platform fingerprint)
    pair in the disk store."""
    h = hashlib.blake2b(digest_size=16)
    h.update(repr((key, fingerprint)).encode())
    return h.hexdigest()


def map_parallel(fn, items: list, width: int) -> list:
    """Run ``fn`` over ``items`` on a bounded thread pool, preserving
    order (XLA compilation releases the GIL, so concurrent
    lower+compile calls genuinely overlap). width<=1 or a single item
    runs inline; exceptions propagate like the serial loop."""
    if width <= 1 or len(items) <= 1:
        return [fn(it) for it in items]
    from concurrent.futures import ThreadPoolExecutor
    with ThreadPoolExecutor(
            max_workers=min(width, len(items))) as pool:
        return list(pool.map(fn, items))


def _estimate_nbytes(compiled, payload_len: int | None = None) -> int:
    """Resident-size estimate for LRU accounting: serialized payload
    length when known, else the backend's generated-code size, else a
    flat default."""
    if payload_len:
        return int(payload_len)
    try:
        ma = compiled.memory_analysis()
        size = int(getattr(ma, "generated_code_size_in_bytes", 0))
        if size > 0:
            return size
    except Exception:  # noqa: BLE001 - backend may not implement it
        pass
    return _DEFAULT_ENTRY_BYTES


class ProgramCache:
    """Two-tier compiled-program cache: a bounded in-memory LRU over an
    optional shared on-disk AOT store."""

    def __init__(self, max_entries: int = DEFAULT_MAX_ENTRIES,
                 max_bytes: int = DEFAULT_MAX_BYTES,
                 disk_dir: str | None = None):
        self._lock = threading.Lock()
        # key -> (compiled, meta, nbytes); insertion order = LRU order
        self._entries: dict = {}
        self._bytes = 0
        self.max_entries = max(1, int(max_entries))
        self.max_bytes = max(1, int(max_bytes))
        if disk_dir is None:
            disk_dir = os.environ.get(ENV_DIR) or None
        self.disk_dir = disk_dir

    def __len__(self) -> int:
        with self._lock:
            return len(self._entries)

    def stats(self) -> dict:
        with self._lock:
            return {"entries": len(self._entries),
                    "bytes": self._bytes,
                    "max_entries": self.max_entries,
                    "max_bytes": self.max_bytes,
                    "disk_dir": self.disk_dir}

    def configure(self, session) -> None:
        """Refresh the entry bound from the session knob (SET SESSION
        program_cache_entries takes effect on the next query)."""
        try:
            limit = int(session.get("program_cache_entries") or 0)
        except KeyError:
            return
        if limit <= 0:
            return
        with self._lock:
            self.max_entries = max(1, limit)
            self._trim()

    # -- lookups ------------------------------------------------------------

    def lookup(self, key, fingerprint: tuple | None = None,
               devices=None):
        """(compiled, meta) for ``key`` or None. Memory tier first,
        then the disk store (deserialized entries are promoted into
        memory). Counts one hit (labeled by tier) or one miss.
        ``devices`` are the ones a disk entry is loaded onto: the
        mesh's for a shard_map program, None for the default device."""
        with self._lock:
            ent = self._entries.pop(key, None)
            if ent is not None:
                self._entries[key] = ent  # re-append: most recent
        if ent is not None:
            _HITS.inc(tier="memory")
            return ent[0], ent[1]
        loaded = self._disk_load(key, fingerprint, devices)
        if loaded is not None:
            compiled, meta, nbytes = loaded
            self._remember(key, compiled, meta, nbytes)
            _HITS.inc(tier="disk")
            return compiled, meta
        _MISSES.inc()
        return None

    def insert(self, key, compiled, meta,
               fingerprint: tuple | None = None,
               persist: bool = True) -> None:
        """Add a freshly compiled program; serialize to the disk store
        when enabled (best-effort — a backend that cannot serialize
        just keeps the memory tier)."""
        payload_len = None
        if persist and self.disk_dir:
            payload_len = self._disk_store(key, compiled, meta,
                                           fingerprint)
        self._remember(key, compiled, meta,
                       _estimate_nbytes(compiled, payload_len))

    def discard(self, key) -> None:
        """Drop one entry without counting an eviction: programs
        compiled on failed capacity-retry rungs are never looked up
        again (the capacity memory jumps straight to the successful
        vector), and keeping them would squeeze live programs out of
        the bounded LRU."""
        with self._lock:
            ent = self._entries.pop(key, None)
            if ent is not None:
                self._bytes -= ent[2]
                _RESIDENT.dec(ent[2])
                _ENTRIES_G.dec()

    def _remember(self, key, compiled, meta, nbytes: int) -> None:
        with self._lock:
            old = self._entries.pop(key, None)
            if old is not None:
                self._bytes -= old[2]
                _RESIDENT.dec(old[2])
                _ENTRIES_G.dec()
            self._entries[key] = (compiled, meta, nbytes)
            self._bytes += nbytes
            _RESIDENT.inc(nbytes)
            _ENTRIES_G.inc()
            self._trim()

    def _trim(self) -> None:
        """Evict LRU entries beyond the entry/byte bounds (gauges track
        the process-wide total by delta, so several live caches — a
        worker holds one engine per split view — sum instead of
        clobbering each other). Caller must hold the lock."""
        while self._entries and (
                len(self._entries) > self.max_entries
                or self._bytes > self.max_bytes):
            if len(self._entries) == 1:
                # only the byte bound can be violated here
                # (max_entries >= 1): keep the single oversized entry
                break
            oldest = next(iter(self._entries))
            _, _, nb = self._entries.pop(oldest)
            self._bytes -= nb
            _RESIDENT.dec(nb)
            _ENTRIES_G.dec()
            _EVICTIONS.inc()

    # -- disk store ---------------------------------------------------------

    def _path(self, digest: str, suffix: str) -> str:
        return os.path.join(self.disk_dir, digest + suffix)

    def _disk_load(self, key, fingerprint, devices=None):
        """(compiled, meta, nbytes) deserialized from the store, or
        None on any failure (missing file, corrupt pickle, backend
        refusal) — the caller falls back to a live compile. A failing
        entry is unlinked: some program classes cannot be relinked by
        the XLA CPU runtime at all ('Symbols not found'), and keeping
        the file would re-pay the failed deserialize on every warm
        start (the next process re-stores a fresh payload)."""
        if not self.disk_dir:
            return None
        path = self._path(entry_digest(key, fingerprint), ".prog")
        if not os.path.exists(path):
            return None
        t0 = time.perf_counter()
        try:
            with open(path, "rb") as f:
                blob = pickle.load(f)
            if blob.get("key") != repr(key):
                raise ValueError("digest collision / stale entry")
            import jax
            from jax.experimental import serialize_executable as _se
            # jax 0.9.0 loads onto EVERY device of the backend unless
            # told otherwise, and a one-device program then "expects
            # N shards": name the devices the caller runs on
            if devices is None:
                devices = jax.devices()[:1]
            compiled = _se.deserialize_and_load(
                blob["payload"], blob["in_tree"], blob["out_tree"],
                execution_devices=list(devices))
            _LOAD_SECONDS.observe(time.perf_counter() - t0)
            return compiled, blob["meta"], len(blob["payload"])
        except Exception:  # noqa: BLE001 - corrupt/incompatible entry
            _DISK_ERRORS.inc(op="load")
            try:
                os.unlink(path)
            except OSError:
                pass
            return None

    def _disk_store(self, key, compiled, meta,
                    fingerprint) -> int | None:
        """Serialize one executable into the store (atomic tmp+rename,
        so concurrent writers across processes can only race to the
        same content). Returns the payload length, or None when the
        backend cannot serialize."""
        digest = entry_digest(key, fingerprint)
        try:
            from jax.experimental import serialize_executable as _se
            payload, in_tree, out_tree = _se.serialize(compiled)
            blob = pickle.dumps({
                "key": repr(key), "payload": payload,
                "in_tree": in_tree, "out_tree": out_tree,
                "meta": meta})
            os.makedirs(self.disk_dir, exist_ok=True)
            tmp = self._path(digest, f".tmp.{os.getpid()}")
            with open(tmp, "wb") as f:
                f.write(blob)
            os.replace(tmp, self._path(digest, ".prog"))
            self._prune_disk()
            return len(payload)
        except Exception:  # noqa: BLE001 - never fail the query
            _DISK_ERRORS.inc(op="store")
            return None

    def _prune_disk(self) -> None:
        """Best-effort disk budget: drop oldest-mtime entries beyond
        DISK_BYTES_LIMIT (superseded capacity rungs, dead schema/scale
        variants, stale platform fingerprints). Runs after each store
        — once per NEW program, never on the lookup path. Concurrent
        processes may race to unlink the same file; losing is fine."""
        try:
            entries = []
            total = 0
            with os.scandir(self.disk_dir) as it:
                for de in it:
                    if not de.name.endswith((".prog", ".caps.json")):
                        continue
                    st = de.stat()
                    entries.append((st.st_mtime, st.st_size, de.path))
                    total += st.st_size
            if total <= DISK_BYTES_LIMIT:
                return
            for _mtime, size, path in sorted(entries):
                try:
                    os.unlink(path)
                    total -= size
                except OSError:
                    pass
                if total <= DISK_BYTES_LIMIT:
                    break
        except Exception:  # noqa: BLE001 - pruning is best-effort
            pass

    # -- capacity sidecar ---------------------------------------------------

    def load_caps(self, base_key,
                  fingerprint: tuple | None = None) -> dict:
        """Persisted successful capacity vector for a plan, so a warm
        process skips the overflow-retry ladder. {} when absent."""
        if not self.disk_dir:
            return {}
        path = self._path(entry_digest(base_key, fingerprint),
                          ".caps.json")
        try:
            with open(path, encoding="utf-8") as f:
                rows = json.load(f)
            return {(int(pos), str(kind)): int(cap)
                    for pos, kind, cap in rows}
        except FileNotFoundError:
            return {}
        except Exception:  # noqa: BLE001 - corrupt sidecar = no caps
            _DISK_ERRORS.inc(op="load")
            return {}

    def store_caps(self, base_key, caps: dict,
                   fingerprint: tuple | None = None) -> None:
        if not self.disk_dir or not caps:
            return
        digest = entry_digest(base_key, fingerprint)
        try:
            rows = [[int(pos), str(kind), int(cap)]
                    for (pos, kind), cap in sorted(caps.items())]
            os.makedirs(self.disk_dir, exist_ok=True)
            tmp = self._path(digest, f".capstmp.{os.getpid()}")
            with open(tmp, "w", encoding="utf-8") as f:
                json.dump(rows, f)
            os.replace(tmp, self._path(digest, ".caps.json"))
        except Exception:  # noqa: BLE001 - sidecar is best-effort
            _DISK_ERRORS.inc(op="store")
