"""Hashing and open-addressing hash tables as XLA-friendly kernels.

Design notes (vs the reference's Java hash machinery):

- The reference inserts rows into `MultiChannelGroupByHash` one at a time,
  rehashing on load (MultiChannelGroupByHash.java:140-149). A TPU kernel
  cannot grow tables or loop per row, so `group_by_slots` assigns dense
  slots by **sorting**: rows sort by 64-bit key hash (one O(N log N)
  device sort — a few fused HBM passes), run boundaries become dense
  group ids, and the table stores each group's hash at its dense slot in
  ascending order. Probes are vectorized binary searches over that
  ascending table — log2(capacity) gather rounds with no data-dependent
  probe chains. (An earlier open-addressing design with parallel claim
  rounds cost O(rounds x N) scatter passes and was 50x+ slower on TPU.)
- Capacity is static and chosen by the planner from connector stats
  (reference sizes from `expectedGroups`); on overflow (more groups than
  slots) the kernel reports failure and the host retries with a doubled
  capacity — the analog of the reference's host-side rehash.
- Group identity is the full 64-bit mixed hash (splitmix64 finaliser over
  all key columns). Two distinct key tuples merging requires a 64-bit
  collision *within one query's keys* (~N^2 / 2^64).
- NULL group keys hash to a fixed sentinel so all-NULL keys form one group
  (SQL semantics); NULL join keys are masked out before probing (SQL joins
  never match NULLs).
"""

from __future__ import annotations

import hashlib

import jax
import jax.numpy as jnp
import numpy as np

# Sentinel for an empty slot: max uint64. Real hashes are remapped off it.
# NumPy scalars, not jnp: a module-level jnp scalar initialises the JAX
# backend at import, and a process that imports this module would then
# hold the chip (one process per TPU).
_EMPTY = np.uint64(0xFFFFFFFFFFFFFFFF)
_NULL_KEY_HASH = np.uint64(0x9E3779B97F4A7C15)

# The TPU's compiler takes far longer over a sort the more operands and
# keys it carries (a v5e described to the compiler, 2^16 rows: 1.4 s for
# one 64-bit operand, 9 s for a 64-bit key and a row index, 70 s for 8
# operands, 110 s for 8 stable; six keys at 2^17 rows 139 s), while a
# gather compiles in a fraction of a second. Up to this many rows a
# grouping, compaction or ORDER BY sort of integer keys is therefore made
# of one-operand sorts (lexsort_perm) and gathers: on a v5e they run a
# few milliseconds longer than the one wide sort at 2^15-2^16 rows, and
# save minutes of compile. Above it the payloads ride one wide sort,
# where a gather over the whole width is a random-access pass of its
# own. The order is the stable sort's either way.
NARROW_SORT_MAX_ROWS = 1 << 17


def _order_words(key) -> list:
    """A 1-D integer or bool key as uint32 words, most significant
    first, whose lexicographic unsigned order is the key's order."""
    if key.dtype == jnp.bool_ or key.dtype.itemsize < 4:
        key = key.astype(jnp.int32)
    signed = jnp.issubdtype(key.dtype, jnp.signedinteger)
    if key.dtype.itemsize == 4:
        w = key.astype(jnp.uint32)
        return [w ^ jnp.uint32(1 << 31) if signed else w]
    u = key.astype(jnp.uint64)
    hi = (u >> jnp.uint64(32)).astype(jnp.uint32)
    lo = u.astype(jnp.uint32)
    return [hi ^ jnp.uint32(1 << 31) if signed else hi, lo]


def narrow_sortable(keys) -> bool:
    """True when ``lexsort_perm`` may order these keys: at most
    NARROW_SORT_MAX_ROWS rows of 1-D integer or bool keys."""
    return (keys[0].shape[0] <= NARROW_SORT_MAX_ROWS
            and all(k.ndim == 1 and (k.dtype == jnp.bool_ or
                                     jnp.issubdtype(k.dtype, jnp.integer))
                    for k in keys))


def lexsort_perm(keys):
    """Permutation ordering rows by ``keys`` lexicographically, ties by
    row index (a stable sort's order), as one one-operand sort per 32-bit
    word of the keys, least significant word first: each sorts the word
    (gathered into the current order) above the row's current position,
    so equal words keep their order and the passes compose (LSD radix
    order). ``narrow_sortable(keys)`` must hold."""
    n = keys[0].shape[0]
    pos = jnp.arange(n, dtype=jnp.uint64)
    perm = jnp.arange(n, dtype=jnp.int32)
    words = [w for k in keys for w in _order_words(k)]
    for w in reversed(words):
        packed = (w[perm].astype(jnp.uint64) << jnp.uint64(32)) | pos
        order = jax.lax.sort(packed).astype(jnp.uint32).astype(jnp.int32)
        perm = perm[order]
    return perm


class HashChainOverflow(RuntimeError):
    """A hash table gave up LOUDLY: a group or build count exceeded
    every capacity the retry ladder was willing to try (``max_rounds``
    analog). Raised by the executor when the capacity retry ladder
    exhausts — the in-program bound itself surfaces as a failed ``ok``
    flag that the ladder catches and retries at a larger capacity,
    counted per occurrence in
    ``presto_tpu_hash_probe_overflow_total``. Subclasses RuntimeError
    so callers matching the ladder's historical exception keep
    working."""


def grow_overflowed(capacities: dict, ok_keys, oks,
                    used_capacity: dict, growth: int = 4) -> int:
    """Host-side body of one capacity-retry rung, shared by every
    retry ladder (prepare_plan, the distributed executor, block
    streaming, EXPLAIN ANALYZE): grow each failed key's capacity by
    ``growth`` and count hash-TABLE overflows (kinds table/final) in
    ``presto_tpu_hash_probe_overflow_total`` — output/compaction
    capacity kinds are sizing misses, not hash-chain give-ups, and
    stay out of the metric. EVERY failed key additionally counts one
    ``presto_tpu_capacity_overflow_retries_total{operator=<kind>}``:
    each rung is a full recompile on the hot path, so the "overflow
    retries go to ~zero" claim of adaptive capacity re-bucketing
    (parallel/adaptive.py) is measurable from /metrics rather than
    inferred from logs. The kind label names the operator role the
    capacity sizes (table/final = hash build or aggregation table,
    out/pout = expanding-join output, probe_exch/build_exch/agg_exch =
    exchange buckets, hot/htab = hybrid-join hot set, ...). A failed
    ``pack`` key is no capacity but a packed expansion's range check:
    it is set to 0, which runs that expansion unpacked, and counts
    under ``presto_tpu_expand_pack_total{outcome=range_retry}``
    (exec/executor.note_expand_pack) and not here.
    Returns the counted hash-table overflow total."""
    import numpy as np
    overflowed = 0
    for key, okv in zip(ok_keys, oks):
        if not bool(np.asarray(okv)):
            if key[1] == "pack":
                capacities[key] = 0
                continue
            if key[1] in ("table", "final"):
                overflowed += 1
            note_capacity_retry(str(key[1]))
            capacities[key] = growth * used_capacity[key]
    if overflowed:
        note_probe_overflow(overflowed)
    return overflowed


def note_capacity_retry(kind: str) -> None:
    """Count one capacity-overflow retry rung (a recompile) for the
    capacity kind that overflowed."""
    from presto_tpu.obs.metrics import REGISTRY
    REGISTRY.counter(
        "presto_tpu_capacity_overflow_retries_total",
        "capacity-overflow retry-ladder rungs (each one is a "
        "recompile), by the operator-role capacity kind that "
        "overflowed").inc(operator=kind)


def note_probe_overflow(count: int = 1) -> None:
    """Count a program-reported hash-TABLE overflow — a group/build
    count exceeding its table capacity (the max_rounds analog). The
    loud path of what used to be a silent give-up; output/compaction
    capacity retries are deliberately NOT counted here."""
    from presto_tpu.obs.metrics import REGISTRY
    REGISTRY.counter(
        "presto_tpu_hash_probe_overflow_total",
        "hash-table probe-chain/capacity overflows caught by the "
        "capacity retry ladder").inc(count)


def _splitmix64(x):
    x = x.astype(jnp.uint64)
    x = (x + jnp.uint64(0x9E3779B97F4A7C15))
    x = (x ^ (x >> jnp.uint64(30))) * jnp.uint64(0xBF58476D1CE4E5B9)
    x = (x ^ (x >> jnp.uint64(27))) * jnp.uint64(0x94D049BB133111EB)
    x = x ^ (x >> jnp.uint64(31))
    return x


def hash_int_column(data, valid=None):
    """Order-preserving identity key of an integer-like column
    (int64/int32/date/decimal/bool physical): the value with its sign
    bit flipped into uint64. NULLs map to a fixed sentinel.

    Deliberately NO mixing: TPU v5e has no native 64-bit ALU, so
    splitmix64's two 64-bit multiplies cost ~40ms per million rows
    (measured; they dominated every join/group-by). The sort-based
    kernels only need equal keys to compare equal and the dead-row
    sentinel to stay unreachable; exactness against residual collisions
    comes from value verification (joins, _verify_keys) and key-payload
    secondary sort keys (grouping, SortedGroups)."""
    u = data.astype(jnp.int64).astype(jnp.uint64) ^ jnp.uint64(1 << 63)
    if valid is not None:
        u = jnp.where(valid, u, _NULL_KEY_HASH)
    return u


# id(dictionary) -> (strong ref to the dictionary, hashes). Holding the
# reference keeps the id stable, so a recycled address cannot alias.
_DICT_HASH_CACHE: dict[int, tuple[np.ndarray, np.ndarray]] = {}


def hash_string_dictionary(dictionary: np.ndarray) -> np.ndarray:
    """Stable 64-bit hash per dictionary entry, content-based so string
    joins/groupings agree across tables with different dictionaries."""
    cached = _DICT_HASH_CACHE.get(id(dictionary))
    if cached is not None and cached[0] is dictionary:
        return cached[1]
    out = np.empty(len(dictionary), dtype=np.uint64)
    for i, s in enumerate(dictionary):
        d = hashlib.blake2b(str(s).encode(), digest_size=8).digest()
        out[i] = np.frombuffer(d, dtype=np.uint64)[0]
    if len(_DICT_HASH_CACHE) > 256:
        _DICT_HASH_CACHE.clear()
    _DICT_HASH_CACHE[id(dictionary)] = (dictionary, out)
    return out


def hash_string_column(codes, dictionary: np.ndarray, valid=None):
    lut = jnp.asarray(hash_string_dictionary(dictionary))
    if len(dictionary) == 0:
        h = jnp.zeros(codes.shape, dtype=jnp.uint64)
    else:
        h = lut[jnp.clip(codes, 0, len(dictionary) - 1)]
    if valid is not None:
        h = jnp.where(valid, h, _NULL_KEY_HASH)
    return h


def combine_hashes(hashes: list):
    """Combine per-column keys into one row key. Order-dependent: the
    accumulator multiplies by an odd constant (a bijection of Z/2^64)
    before xoring the next column, so (a, b) and (b, a) tuples don't
    systematically collide. ONE emulated 64-bit multiply per extra
    column (vs splitmix64's two plus shifts) — single-key rows (the
    common case) pay nothing, and residual collisions are exact-checked
    downstream (see hash_int_column)."""
    out = hashes[0]
    for h in hashes[1:]:
        out = out * jnp.uint64(0x9E3779B97F4A7C15) ^ h
    # keep the EMPTY sentinel unreachable
    return jnp.where(out == _EMPTY, out - jnp.uint64(1), out)


def _sorted_group_ids(row_hash, live):
    """Sort rows by hash and assign dense group ids in hash order.

    Returns (sh sorted hashes [N], sidx source row per sorted position
    [N], gid_sorted dense group id per sorted position [N] (-1 before
    the first live group), ngroups scalar). Dead rows sort last (hash
    forced to the EMPTY sentinel, which real hashes never take)."""
    n = row_hash.shape[0]
    h = jnp.where(live, row_hash, _EMPTY)
    sh, sidx = jax.lax.sort(
        (h, jnp.arange(n, dtype=jnp.int32)), num_keys=1, is_stable=True)
    first = jnp.concatenate(
        [jnp.ones((1,), bool), sh[1:] != sh[:-1]])
    is_new = first & (sh != _EMPTY)
    gid_sorted = jnp.cumsum(is_new.astype(jnp.int32)) - 1
    return sh, sidx, gid_sorted, jnp.sum(is_new.astype(jnp.int32))


class SortedGroups:
    """Row grouping derived from one hash sort (the core of every
    grouping/join kernel; see module docstring).

    Extra per-row arrays ride the sort as PAYLOADS — on TPU additional
    sort operands are nearly free at run time, while gathering a column
    into sorted order afterwards costs a full random-access pass.
    Aggregation therefore sorts (hash, key cols..., idx, agg inputs...)
    in ONE sort; up to NARROW_SORT_MAX_ROWS rows, where the compile of a
    wide sort costs more than the gathers, the keys are ordered by
    lexsort_perm and the payloads gathered.

    sh:       sorted hashes [N] (dead rows forced to EMPTY, so they sort
              last and form no group)
    sidx:     source row index per sorted position [N]
    payloads: the extra arrays, in sorted order
    live:     live mask in sorted order [N] (== sh != EMPTY)
    is_new:   first sorted row of each live group [N]
    is_last:  last sorted row of each live group [N]
    start:    per sorted row, position of its run's first row [N]
    gidc:     ascending dense group id per sorted row; dead rows get N
    ngroups:  live group count (scalar)
    """

    __slots__ = ("sh", "sidx", "payloads", "live", "is_new", "is_last",
                 "start", "gidc", "ngroups")

    def __init__(self, row_hash, live, payloads=(), num_key_payloads=0):
        """``num_key_payloads``: the first K payload arrays are the
        group-key columns themselves (normalized data + validity). They
        participate as SECONDARY SORT KEYS, and group boundaries come
        from hash-or-key changes — group identity is the actual key
        tuple, not the 64-bit hash, so two distinct keys colliding in
        64 bits still form two groups (the reference always
        value-compares after a hash hit, MultiChannelGroupByHash;
        a probabilistic group identity has no place in a SQL engine)."""
        n = row_hash.shape[0]
        h = jnp.where(live, row_hash, _EMPTY)
        keys = (h,) + tuple(payloads[:num_key_payloads])
        rest = tuple(payloads[num_key_payloads:])
        if narrow_sortable(keys):
            sidx = lexsort_perm(keys)
            out = (tuple(k[sidx] for k in keys) + (sidx,)
                   + tuple(p[sidx] for p in rest))
        else:
            out = jax.lax.sort(
                keys + (jnp.arange(n, dtype=jnp.int32),) + rest,
                num_keys=len(keys), is_stable=True)
        sh = out[0]
        sidx = out[1 + num_key_payloads]
        self.payloads = (out[1:1 + num_key_payloads]
                         + out[2 + num_key_payloads:])
        self.sh, self.sidx = sh, sidx
        self.live = sh != _EMPTY
        i = jnp.arange(n, dtype=jnp.int32)
        differs = sh[1:] != sh[:-1]
        for kp in out[1:1 + num_key_payloads]:
            differs = differs | (kp[1:] != kp[:-1])
        self.is_new = (jnp.concatenate(
            [jnp.ones((1,), bool), differs]) & self.live)
        self.is_last = (jnp.concatenate(
            [differs, jnp.ones((1,), bool)]) & self.live)
        self.start = jnp.clip(
            jax.lax.cummax(jnp.where(self.is_new, i, -1)), 0, None)
        gid = jnp.cumsum(self.is_new.astype(jnp.int32)) - 1
        self.ngroups = jnp.sum(self.is_new.astype(jnp.int32))
        self.gidc = jnp.where(self.live, jnp.clip(gid, 0, None), n)

    def _compact(self, keep, columns, capacity: int):
        n = self.sh.shape[0]
        key = jnp.where(keep, self.gidc, n)
        if narrow_sortable([key]):
            perm = lexsort_perm([key])
            columns = [col[perm] for col in columns]
        else:
            columns = jax.lax.sort((key,) + tuple(columns), num_keys=1,
                                   is_stable=True)[1:]
        res = []
        for col in columns:
            if capacity <= n:
                res.append(col[:capacity])
            else:
                pad = [(0, capacity - n)] + [(0, 0)] * (col.ndim - 1)
                res.append(jnp.pad(col, pad))
        occupied = (jnp.arange(capacity) <
                    jnp.minimum(self.ngroups, capacity))
        return res, occupied

    def compact(self, columns, capacity: int):
        """Compact per-sorted-row arrays to [capacity], keeping each
        group's LAST row at its dense group id — one multi-payload sort
        keyed by (is_last ? gid : N), no scatter, no binary search.
        Returns (compacted columns, occupied mask [capacity])."""
        return self._compact(self.is_last, columns, capacity)

    def compact_first(self, columns, capacity: int):
        """Like compact but keeps each group's FIRST row (distinct)."""
        return self._compact(self.is_new, columns, capacity)

    def slots(self):
        """Dense group id per ORIGINAL row (inverse permutation via an
        n->n unique scatter) — only needed by segment-op fallbacks."""
        n = self.sh.shape[0]
        safe = jnp.clip(self.gidc, 0, n - 1).astype(jnp.int32)
        return jnp.zeros((n,), jnp.int32).at[self.sidx].set(
            safe, unique_indices=True)


def group_by_slots(row_hash, live, capacity: int, max_rounds: int = 64):
    """Assign each live row a slot in a capacity-sized table such that
    rows with equal hashes share a slot.

    Sort-based dense grouping (no open addressing): rows sort by hash,
    run boundaries become dense group ids 0..G-1, and the table stores
    each group's hash at its dense slot — the slot array ``table`` stays
    ascending (EMPTY = max uint64 pads the tail), which probe kernels
    exploit with binary search. One O(N log N) device sort replaces the
    reference's per-row open-addressed insertion loop
    (MultiChannelGroupByHash.java:140) — a claim-round loop over
    scattered tables costs O(rounds * N) on a TPU, the sort runs in a
    handful of fused HBM passes.

    Returns (slot int32 [N], table_hash uint64 [capacity], ok bool
    scalar). ``ok`` is False when the group count exceeds capacity
    (host retries with a doubled capacity)."""
    n = row_hash.shape[0]
    sh, sidx, gid_sorted, ngroups = _sorted_group_ids(row_hash, live)
    ok = ngroups <= capacity
    safe_gid = jnp.clip(gid_sorted, 0, capacity - 1)
    slot = jnp.zeros((n,), jnp.int32).at[sidx].set(safe_gid)
    return slot, _dense_table(sh, gid_sorted, capacity), ok


def _dense_table(sh, gid_sorted, capacity: int):
    """Scatter each group's hash to its dense slot, leaving the EMPTY
    sentinel past ngroups so the table stays ascending. Dead rows sort
    last with the EMPTY hash but inherit the previous group's id —
    exclude them (and overflowed ids) from the scatter."""
    safe_gid = jnp.clip(gid_sorted, 0, capacity - 1)
    table = jnp.full((capacity,), _EMPTY, dtype=jnp.uint64)
    return table.at[jnp.where(
        (gid_sorted >= 0) & (sh != _EMPTY) & (gid_sorted < capacity),
        safe_gid, capacity)].set(sh, mode="drop")


def build_join_table(row_hash, live, capacity: int, max_rounds: int = 64):
    """Build-side of a hash join: returns (table_hash uint64 [capacity],
    table_row int32 [capacity] (source row index per slot, -1 empty), ok).

    Duplicate build keys share one slot; the representative row is the one
    with the largest row index (callers needing many-to-many semantics use
    the expanding join path instead)."""
    n = row_hash.shape[0]
    slot, table, ok = group_by_slots(row_hash, live, capacity, max_rounds)
    rows = jnp.arange(n, dtype=jnp.int32)
    table_row = jnp.full((capacity,), -1, dtype=jnp.int32)
    table_row = table_row.at[slot].max(jnp.where(live, rows, -1))
    return table, table_row, ok


def sort_build_side(row_hash, live):
    """Build side of a join as a sorted run structure: returns (sh
    sorted hashes [N] with dead rows at the EMPTY tail, sidx source row
    per sorted position [N]). No table, no capacity, no overflow — the
    probe is a binary search over ``sh`` directly."""
    n = row_hash.shape[0]
    h = jnp.where(live, row_hash, _EMPTY)
    return jax.lax.sort(
        (h, jnp.arange(n, dtype=jnp.int32)), num_keys=1, is_stable=True)


def probe_runs(build_hash, build_live, probe_hash, probe_live):
    """Join probe by co-sorted merge: returns (lo, count, found) per
    PROBE row (original order) where matching build rows occupy
    BUILD-SORTED positions [lo[i], lo[i]+count[i]) — the contiguous-run
    analog of the reference's PositionLinks chain walk
    (operator/join/JoinHash.java:28).

    Build and probe hashes sort TOGETHER keyed by (hash, position in
    the concatenation) with builds first, so within a key run every
    build precedes every probe; a probe row's run bounds then come from
    running build counts — one combined sort, two scans, one monotone
    gather and one un-sort, with NO random-access binary search
    (vectorized searchsorted costs log2(N) random-gather passes; this is
    ~5x cheaper at 6M probes). The position is the tie-break key of the
    first sort and the only key of the second, so neither carries a
    side column or an index added for stability (the TPU's compile of a
    sort grows fast with its operands: NARROW_SORT_MAX_ROWS)."""
    nb = build_hash.shape[0]
    npr = probe_hash.shape[0]
    n = nb + npr
    allh = jnp.concatenate([
        jnp.where(build_live, build_hash, _EMPTY),
        jnp.where(probe_live, probe_hash, _EMPTY)])
    sh, spos = jax.lax.sort((allh, jnp.arange(n, dtype=jnp.int32)),
                            num_keys=2, is_stable=False)
    i = jnp.arange(n, dtype=jnp.int32)
    is_new = jnp.concatenate(
        [jnp.ones((1,), bool), sh[1:] != sh[:-1]])
    start = jnp.clip(jax.lax.cummax(jnp.where(is_new, i, -1)), 0, None)
    is_build = (spos < nb) & (sh != _EMPTY)
    builds_before = (jnp.cumsum(is_build.astype(jnp.int32))
                     - is_build)  # exclusive running build count
    lo = builds_before[start]  # build rank of each run's first build
    count = builds_before - lo  # for a probe row: all builds in its run
    # restore probe order: one sort keyed by the (unique) position
    _, lo_o, cnt_o = jax.lax.sort(
        (spos, lo.astype(jnp.int32), count.astype(jnp.int32)),
        num_keys=1, is_stable=False)
    lo_p, cnt_p = lo_o[nb:], cnt_o[nb:]
    found = probe_live & (cnt_p > 0)
    return lo_p, jnp.where(found, cnt_p, 0), found


def lookup_join(build_hash, build_live, probe_hash, probe_live):
    """FK->PK join lookup by sorted merge: returns (build_row int32
    [n_probe] (-1 = none), found bool [n_probe]). ``found`` = live
    probe row whose 64-bit combined hash equals a live build row's;
    on duplicate build keys the representative is the LARGEST build
    row index (the last row of the run). Residual 64-bit collisions
    are the caller's to verify by value
    (exec/operators._verify_keys). No table, so nothing to size and
    nothing that can overflow."""
    nb = build_hash.shape[0]
    _bsh, bsidx = sort_build_side(build_hash, build_live)
    lo, count, found = probe_runs(build_hash, build_live,
                                  probe_hash, probe_live)
    build_row = jnp.where(
        found, bsidx[jnp.clip(lo + count - 1, 0, nb - 1)], -1)
    return build_row, found


def _probe_sorted(table_hash, row_hash, live):
    """Binary-search each row's hash in the ascending table (dense
    group prefix + EMPTY tail). Returns (pos int32 [N], found bool)."""
    capacity = table_hash.shape[0]
    pos = jnp.clip(jnp.searchsorted(table_hash, row_hash),
                   0, capacity - 1).astype(jnp.int32)
    found = live & (table_hash[pos] == row_hash)
    return pos, found


def probe_join_table(table_hash, table_row, row_hash, live,
                     max_probes: int = 256):
    """Probe: find the slot whose stored hash equals the row hash via
    vectorized binary search (the table is ascending by construction —
    see group_by_slots; the reference's PagesHash.getAddressIndex
    linear-probe equivalent, log2(capacity) gather rounds instead of a
    data-dependent probe chain). Returns (build_row int32 [N]
    (-1 = no match), found bool [N], ok bool scalar, always True)."""
    pos, found = _probe_sorted(table_hash, row_hash, live)
    build_row = jnp.where(found, table_row[pos], -1)
    return build_row, found, jnp.asarray(True)


def build_join_multimap(row_hash, live, capacity: int, max_rounds: int = 64):
    """Build-side of an expanding (many-to-many) hash join.

    The analog of the reference's PagesHash + PositionLinks chains
    (operator/join/PagesHash.java:35, JoinHash.java:28): instead of linked
    row chains, build rows are bucketed contiguously — ``build_order``
    lists build row indices grouped by slot, ``offsets[slot]`` is the
    group start and ``counts[slot]`` the group size. The hash sort that
    assigns dense slots already groups rows contiguously, so
    ``build_order`` is the sort permutation itself (dead rows last).

    Returns (table_hash [capacity], counts [capacity], offsets [capacity],
    build_order [n], ok).
    """
    n = row_hash.shape[0]
    sh, sidx, gid_sorted, ngroups = _sorted_group_ids(row_hash, live)
    ok = ngroups <= capacity
    safe_gid = jnp.clip(gid_sorted, 0, capacity - 1)
    table = _dense_table(sh, gid_sorted, capacity)
    live_sorted = sh != _EMPTY
    counts = jax.ops.segment_sum(
        live_sorted.astype(jnp.int32),
        jnp.where(live_sorted, safe_gid, capacity),
        num_segments=capacity + 1)[:capacity]
    offsets = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32),
         jnp.cumsum(counts)[:-1].astype(jnp.int32)])
    return table, counts, offsets, sidx, ok


def probe_join_slot(table_hash, row_hash, live, max_probes: int = 256):
    """Find each probe row's matching table slot via binary search over
    the ascending table. Returns (slot int32 [N] (-1 = none), found
    bool [N], ok — always True)."""
    pos, found = _probe_sorted(table_hash, row_hash, live)
    return jnp.where(found, pos, -1), found, jnp.asarray(True)


def expand_matches(lo, counts, build_rows: int, probe_found,
                   probe_live, out_capacity: int, left_join: bool):
    """Expand probe rows into one output row per (probe, build) match.

    ``lo``/``counts`` are per-PROBE-row run bounds from probe_runs, in
    SORTED build positions of a build ``build_rows`` wide. Each probe
    row that emits marks its first output slot with its index and a
    running max carries it over the rest of its slots: one scatter of
    the probe's width and one scan of the output's, where a binary
    search for every output slot costs log2(probe rows) random gathers
    of the output's width (23 of 2^27 for TPC-DS q95's self-join at
    SF10). Then a slot's sorted build position is its own offset plus
    its probe row's ``lo - first slot``: one gather. No data-dependent
    shapes.

    Returns (probe_idx int32 [out_capacity], build_pos [out_capacity]
    (a sorted build position: sort_build_side's order maps it to a
    build row), matched bool [out_capacity] (False on a LEFT row's one
    slot of no match), out_live bool [out_capacity], ok).
    """
    matches = jnp.where(probe_found & probe_live, counts, 0)
    if left_join:
        per_probe = jnp.where(probe_live,
                              jnp.maximum(matches, 1), 0)
    else:
        per_probe = matches
    prefix = jnp.cumsum(per_probe) - per_probe
    total = prefix[-1] + per_probe[-1]
    ok = total <= out_capacity
    n = per_probe.shape[0]
    first = jnp.where(per_probe > 0, prefix, out_capacity)
    marks = jnp.full((out_capacity,), -1, jnp.int32).at[first].max(
        jnp.arange(n, dtype=jnp.int32), mode="drop")
    probe_idx = jnp.clip(jax.lax.cummax(marks), 0, n - 1)
    k = jnp.arange(out_capacity, dtype=prefix.dtype)
    out_live = k < total
    # an emitting slot is a match, but a LEFT row's one slot of no match
    matched = out_live & (matches[probe_idx] > 0) if left_join \
        else out_live
    build_pos = jnp.clip(k + (lo - prefix)[probe_idx], 0, build_rows - 1)
    return probe_idx, build_pos, matched, out_live, ok


def next_pow2(x: int) -> int:
    return 1 << max(int(x) - 1, 1).bit_length()


def partition_id(h, nparts: int):
    """Destination partition of a 64-bit row key: fold to 32 bits and
    golden-ratio multiply, then mod. 32-bit multiplies are native on
    TPU (64-bit are emulated), and the multiply spreads the identity
    keys produced by hash_int_column evenly across partitions even when
    they are dense or strided. Must stay bit-identical to
    np_partition_id (host-side scan bucketing)."""
    x = (h ^ (h >> jnp.uint64(32))).astype(jnp.uint32)
    x = x * jnp.uint32(0x9E3779B1)
    return (x % jnp.uint32(nparts)).astype(jnp.int32)


# --- numpy twins (host-side, exact same bit pattern) -----------------------
# Scan bucketing for connector-defined partitioning happens on host
# before shard placement; it must land rows on the SAME shard as the
# device repartition kernel would, so co-partitioned scans and
# FIXED_HASH exchange outputs are mutually co-located. Tested equal in
# tests/test_connector_partitioning.py.


def np_splitmix64(x: np.ndarray) -> np.ndarray:
    x = x.astype(np.uint64, copy=True)
    with np.errstate(over="ignore"):
        x += np.uint64(0x9E3779B97F4A7C15)
        x = (x ^ (x >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
        x = (x ^ (x >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
        x = x ^ (x >> np.uint64(31))
    return x


def np_hash_int_column(data: np.ndarray, valid=None) -> np.ndarray:
    h = (np.asarray(data).astype(np.int64).view(np.uint64)
         ^ np.uint64(1 << 63))
    if valid is not None:
        h = np.where(valid, h, np.uint64(0x9E3779B97F4A7C15))
    return h


def np_hash_string_column(codes, dictionary, valid=None) -> np.ndarray:
    lut = hash_string_dictionary(dictionary)
    codes = np.asarray(codes)
    if len(dictionary) == 0:
        h = np.zeros(codes.shape, dtype=np.uint64)
    else:
        h = lut[np.clip(codes, 0, len(dictionary) - 1)]
    if valid is not None:
        h = np.where(valid, h, np.uint64(0x9E3779B97F4A7C15))
    return h


def np_partition_id(h: np.ndarray, nparts: int) -> np.ndarray:
    x = (h ^ (h >> np.uint64(32))).astype(np.uint32)
    with np.errstate(over="ignore"):
        x = x * np.uint32(0x9E3779B1)
    return (x % np.uint32(nparts)).astype(np.int64)


def np_combine_hashes(hashes: list) -> np.ndarray:
    out = hashes[0]
    with np.errstate(over="ignore"):
        for h in hashes[1:]:
            out = out * np.uint64(0x9E3779B97F4A7C15) ^ h
    return np.where(out == np.uint64(0xFFFFFFFFFFFFFFFF),
                    out - np.uint64(1), out)
