"""Trace-time physical operators over masked columnar tables.

Each function takes/returns a DTable (dict of symbol -> Val plus a live
mask) during jit tracing. Static shapes: filters only update the live
mask; aggregation/join outputs have planner-chosen static capacities.

Operator parity map (reference core/trino-main/.../operator/):
- apply_filter/apply_project  <- FilterAndProjectOperator, PageProcessor
- apply_aggregate             <- HashAggregationOperator + GroupByHash
- apply_join                  <- HashBuilderOperator + LookupJoinOperator
- apply_semijoin              <- SetBuilderOperator + HashSemiJoinOperator
- apply_sort/topn/limit       <- OrderByOperator, TopNOperator, LimitOperator
- apply_distinct              <- DistinctLimitOperator/MarkDistinct family
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from presto_tpu import types as T
from presto_tpu.expr import aggregates as A
from presto_tpu.expr import ir
from presto_tpu.expr.compile import ExprCompiler, Val, and_valid, cast_val
from presto_tpu.ops import hash as H
from presto_tpu.ops import segred
from presto_tpu.plan import nodes as N


@dataclasses.dataclass
class DTable:
    cols: dict[str, Val]
    live: object | None  # bool [n] or None (all live)
    n: int

    def live_mask(self):
        if self.live is None:
            return jnp.ones((self.n,), dtype=bool)
        return self.live


def _compiler(dt: DTable) -> ExprCompiler:
    return ExprCompiler(dt.cols)


def apply_filter(dt: DTable, predicate: ir.Expr) -> DTable:
    v = _compiler(dt).compile(predicate)
    keep = v.data if v.valid is None else (v.data & v.valid)  # null -> false
    live = keep if dt.live is None else (dt.live & keep)
    return DTable(dt.cols, live, dt.n)


def apply_project(dt: DTable, assignments: dict[str, ir.Expr]) -> DTable:
    c = _compiler(dt)
    out = {}
    for sym, expr in assignments.items():
        v = c.compile(expr)
        data = v.data
        if v.is_array:
            # literal arrays built from scalars have one row: broadcast
            # to the table's row count
            if data.shape[0] == 1 and dt.n != 1:
                data = jnp.broadcast_to(data, (dt.n,) + data.shape[1:])
                lengths = jnp.broadcast_to(v.lengths, (dt.n,))
                ev = (jnp.broadcast_to(
                    v.elem_valid, (dt.n,) + v.elem_valid.shape[1:])
                    if v.elem_valid is not None else None)
                valid = v.valid
                if valid is not None and valid.shape[0] == 1:
                    valid = jnp.broadcast_to(valid, (dt.n,))
                v = Val(v.dtype, data, valid, v.dictionary, lengths,
                        ev, v.map_keys)
        elif getattr(data, "ndim", 1) == 0:  # broadcast scalar literal
            data = jnp.broadcast_to(data, (dt.n,))
            valid = v.valid
            if valid is not None and getattr(valid, "ndim", 1) == 0:
                valid = jnp.broadcast_to(valid, (dt.n,))
            v = Val(v.dtype, data, valid, v.dictionary)
        elif (isinstance(v.dtype, T.DecimalType) and v.dtype.is_long
              and data.ndim == 1):  # scalar LONG decimal: [2] limbs
            data = jnp.broadcast_to(data, (dt.n, 2))
            valid = v.valid
            if valid is not None and getattr(valid, "ndim", 1) == 0:
                valid = jnp.broadcast_to(valid, (dt.n,))
            v = Val(v.dtype, data, valid, v.dictionary)
        out[sym] = v
    return DTable(out, dt.live, dt.n)


def _row_hash(dt: DTable, keys: list[str]):
    hs = []
    for k in keys:
        v = dt.cols[k]
        if v.is_string:
            hs.append(H.hash_string_column(v.data, v.dictionary, v.valid))
        elif getattr(v.data, "ndim", 1) == 2:
            # LONG decimal: both int64 limbs feed the row key (exactness
            # still comes from the limb secondary sort keys downstream)
            hs.append(H.hash_int_column(v.data[:, 0], v.valid))
            hs.append(H.hash_int_column(v.data[:, 1], v.valid))
        else:
            hs.append(H.hash_int_column(v.data, v.valid))
    return H.combine_hashes(hs)


# Max code-product capacity for the direct dictionary-code group-by path.
_DIRECT_GROUP_MAX = 1 << 16


def _long_key_operands(v: Val):
    """LONG decimal grouping identity as two u64 sort operands
    (order-preserving: sign-flipped high limb, then the low limb);
    NULL rows collapse to zeros (validity rides separately)."""
    from presto_tpu.ops import int128 as I
    khi, klo = I.sort_keys(v.data)
    if v.valid is not None:
        khi = jnp.where(v.valid, khi, jnp.uint64(0))
        klo = jnp.where(v.valid, klo, jnp.uint64(0))
    return khi, klo


def _unpack_long_key(khi, klo):
    """Inverse of _long_key_operands (modulo NULL collapsing): [n, 2]
    limbs."""
    from presto_tpu.ops import int128 as I
    return I.pack(klo, (khi ^ jnp.uint64(1 << 63)).astype(jnp.int64))


def _group_key_operand(v: Val):
    """Normalize a group-key column for exact key-identity sorting:
    NULL rows collapse to one value, NaNs to one bit pattern, and
    +-0.0 unify (SQL grouping equality), so equal keys are equal
    operands."""
    data = v.data
    if jnp.issubdtype(data.dtype, jnp.floating):
        bits = jnp.where(data == 0, jnp.zeros_like(data), data)
        bits = bits.view(jnp.int64 if data.dtype == jnp.float64
                         else jnp.int32)
        data = jnp.where(jnp.isnan(v.data),
                         jnp.full_like(bits, -1), bits)
    if v.valid is not None:
        data = jnp.where(v.valid, data, jnp.zeros_like(data))
    return data


def _direct_group_ids(dt: DTable, keys: list[str]):
    """Low-cardinality fast path: when every group key is a non-null
    dictionary-encoded column with a small code product, the group id is
    the mixed-radix code product — no hash table, no probe loop, no
    overflow retry (the analog of MultiChannelGroupByHash's dictionary /
    low-cardinality fast paths, MultiChannelGroupByHash.java:55).

    Returns (gid int32 [n], capacity, sizes) or None if inapplicable."""
    sizes = []
    for k in keys:
        v = dt.cols[k]
        if not v.is_string or v.valid is not None or v.dictionary is None:
            return None
        sizes.append(max(len(v.dictionary), 1))
    capacity = 1
    for s in sizes:
        capacity *= s
        if capacity > _DIRECT_GROUP_MAX:
            return None
    gid = jnp.zeros((dt.n,), dtype=jnp.int32)
    for k, size in zip(keys, sizes):
        code = jnp.clip(dt.cols[k].data.astype(jnp.int32), 0, size - 1)
        gid = gid * size + code
    return gid, capacity, sizes


def _agg_call_inputs(c: ExprCompiler, dt: DTable, call, live):
    """Prepared (data, weight, data2, data_valid, arg_type) for one
    aggregate call over the rows of ``dt`` (shared by the segment-op
    and sorted-scan fold paths)."""
    data2 = None
    data_valid = None
    if call.arg is not None:
        av = c.compile(call.arg)
        if call.fn == "checksum":
            # NULL rows contribute a fixed hash constant instead
            # of being excluded (checksums must see null counts)
            weight = live
        elif call.fn in A.BY_FNS:
            # min_by/max_by: a NULL x is a legal result; only
            # NULL comparison keys (arg2) exclude rows
            weight = live
            data_valid = av.valid
        else:
            weight = live if av.valid is None else (live & av.valid)
        data = A.prepare_arg(call.fn, av.data, av.dtype)
        if A.is_long_decimal(av.dtype) and getattr(
                data, "ndim", 1) == 1:
            # scalar long-decimal literal: [2] limbs -> [n, 2]
            data = jnp.broadcast_to(data, (dt.n, 2))
        if A.is_long_decimal(av.dtype) and getattr(
                data, "ndim", 1) == 2:
            if call.fn in ("sum", "avg", "min", "max",
                           "arbitrary", "count"):
                # int128 [n, 2] -> separate low/high limb columns so the
                # existing (data, data2) plumbing (sort payloads, state
                # columns) stays 1D throughout
                data, data2 = data[:, 0], data[:, 1]
            else:
                raise NotImplementedError(
                    f"{call.fn} over long decimals (precision > 18) "
                    "is not supported yet")
        if call.fn == "checksum" and av.valid is not None:
            data = jnp.where(av.valid, data,
                             jnp.uint64(0x2545F4914F6CDD1D))
        if getattr(data, "ndim", 1) == 0:
            data = jnp.broadcast_to(data, (dt.n,))
        arg_type = av.dtype
    else:
        weight = live
        data = jnp.ones((dt.n,), dtype=jnp.int64)
        arg_type = None
    if call.arg2 is not None:
        av2 = c.compile(call.arg2)
        if av2.valid is not None:
            weight = weight & av2.valid
        data2 = A.prepare_arg2(call.fn, av2.data, av2.dtype)
        if getattr(data2, "ndim", 1) == 0:
            data2 = jnp.broadcast_to(data2, (dt.n,))
    if call.mask is not None:
        mv = dt.cols[call.mask]
        weight = weight & mv.data
        if mv.valid is not None:
            weight = weight & mv.valid
    return data, weight, data2, data_valid, arg_type


def _apply_aggregate_sorted(dt: DTable, node: N.Aggregate, capacity: int,
                            c: ExprCompiler, live) -> tuple:
    """Grouped aggregation via one hash sort + segmented scans + one
    compaction sort (no group-table scatters, no random gathers: every
    per-row array rides the grouping sort as a payload, and the
    capacity-sized output is produced by a second multi-payload sort —
    see ops/segscan.py and SortedGroups.compact). Output contract
    matches the segment-op path: [capacity] rows, ok=False when the
    group count exceeds capacity."""
    # FD-reduced identity (plan/dense.py): when a subset of the group
    # keys determines the rest, only that subset hashes and sorts as
    # group identity; dependent keys (constant within each group) ride
    # as plain payloads
    id_keys = (node.fd_keys if node.fd_keys
               and set(node.fd_keys) <= set(node.group_keys)
               else node.group_keys)
    rh = _row_hash(dt, id_keys)
    is_final = node.step == N.AggStep.FINAL

    # assemble sort payloads: identity key columns first (they double
    # as SECONDARY SORT KEYS so group identity is the exact key tuple,
    # not the 64-bit hash — see SortedGroups), then per-call agg inputs
    payloads: list = []

    def _add(arr) -> int:
        payloads.append(arr)
        return len(payloads) - 1

    key_refs = []  # (sym, Val, data_idx, valid_idx)
    plain_keys = []  # float originals / FD-dependent keys ride outside
    for k in node.group_keys:
        v = dt.cols[k]
        if k not in id_keys:
            plain_keys.append((k, v, None if v.valid is None else v.valid))
            continue
        if getattr(v.data, "ndim", 1) == 2:  # LONG decimal key
            khi, klo = _long_key_operands(v)
            hi_idx, lo_idx = _add(khi), _add(klo)
            valid_idx = None if v.valid is None else _add(v.valid)
            key_refs.append((k, v, ("long", hi_idx, lo_idx), valid_idx))
            continue
        norm_idx = _add(_group_key_operand(v))
        valid_idx = None if v.valid is None else _add(v.valid)
        if jnp.issubdtype(v.data.dtype, jnp.floating):
            # the normalized operand is a bit view; keep the original
            # float data as a plain payload for output
            plain_keys.append((k, v, valid_idx))
        else:
            key_refs.append((k, v, norm_idx, valid_idx))
    num_key_payloads = len(payloads)
    for k, v, valid_ref in plain_keys:
        if isinstance(valid_ref, int) or valid_ref is None:
            valid_idx = valid_ref
        else:
            valid_idx = _add(valid_ref)
        if getattr(v.data, "ndim", 1) == 2:  # LONG decimal payload
            khi, klo = _long_key_operands(v)
            key_refs.append((k, v, ("long", _add(khi), _add(klo)),
                             valid_idx))
            continue
        key_refs.append((k, v, _add(v.data), valid_idx))

    call_refs: dict[str, tuple] = {}
    for sym, call in node.aggs.items():
        scan = call.fn in A.SCAN_FNS
        if is_final:
            sum_state = dt.cols.get(f"{sym}$sum")
            arg_type = sum_state.dtype if sum_state is not None else None
            if scan:
                idxs = {f: _add(dt.cols[f"{sym}${f}"].data)
                        for f in A.state_fields(call)}
                call_refs[sym] = ("merge", idxs, arg_type)
            else:
                call_refs[sym] = ("seg", None, arg_type)
        else:
            data, weight, data2, data_valid, arg_type = \
                _agg_call_inputs(c, dt, call, live)
            if scan:
                idxs = (_add(data), _add(weight),
                        None if data2 is None else _add(data2),
                        None if data_valid is None else _add(data_valid))
                call_refs[sym] = ("fold", idxs, arg_type)
            else:
                call_refs[sym] = ("seg", (data, weight, data2,
                                          data_valid), arg_type)

    sg = H.SortedGroups(rh, live, payloads, num_key_payloads)
    ok = sg.ngroups <= capacity
    sp = sg.payloads
    slots = None  # lazily built for segment-op fallbacks (sketches)

    # per-sorted-row arrays destined for the compaction sort
    compact_in: list = []

    def _adc(arr) -> int:
        compact_in.append(arr)
        return len(compact_in) - 1

    key_out = [(sym, v,
                ("long", _adc(sp[di[1]]), _adc(sp[di[2]]))
                if isinstance(di, tuple) else _adc(sp[di]),
                None if vi is None else _adc(sp[vi]))
               for sym, v, di, vi in key_refs]

    state_out: dict[str, dict] = {}
    seg_states: dict[str, dict] = {}
    arg_types: dict[str, object] = {}
    for sym, call in node.aggs.items():
        kind, refs, arg_type = call_refs[sym]
        arg_types[sym] = arg_type
        if kind == "fold":
            di, wi, d2i, dvi = refs
            st = A.scan_fold(
                call.fn, sp[di], sp[wi], sg,
                data2=None if d2i is None else sp[d2i],
                data_valid=None if dvi is None else sp[dvi],
                param=call.param)
            state_out[sym] = {f: _adc(arr) for f, arr in st.items()}
        elif kind == "merge":
            st = A.scan_merge(
                call.fn, {f: sp[i] for f, i in refs.items()},
                sg.live, sg)
            state_out[sym] = {f: _adc(arr) for f, arr in st.items()}
        else:  # segment-op fallback (2D sketch states can't ride sorts)
            if slots is None:
                slots = sg.slots()
            if is_final:
                fields = A.state_fields(call)
                seg_states[sym] = A.merge(
                    call.fn,
                    {f: dt.cols[f"{sym}${f}"].data for f in fields},
                    slots, capacity, live)
            else:
                data, weight, data2, data_valid = refs
                seg_states[sym] = A.fold(
                    call.fn, data, weight, slots, capacity,
                    data2=data2, data_valid=data_valid,
                    param=call.param)

    compacted, occupied = sg.compact(compact_in, capacity)

    out: dict[str, Val] = {}
    for sym, v, di, vi in key_out:
        valid = None if vi is None else compacted[vi]
        if isinstance(di, tuple):  # LONG decimal limbs
            data = _unpack_long_key(compacted[di[1]], compacted[di[2]])
            out[sym] = Val(v.dtype, data, valid, v.dictionary)
            continue
        out[sym] = Val(v.dtype, compacted[di], valid, v.dictionary)

    for sym, call in node.aggs.items():
        states = (seg_states[sym] if sym in seg_states else
                  {f: compacted[i] for f, i in state_out[sym].items()})
        out_dictionary = None
        if is_final:
            val_state = dt.cols.get(
                f"{sym}$xval" if call.fn in A.BY_FNS else f"{sym}$val")
            if val_state is not None:
                out_dictionary = val_state.dictionary
        if node.step == N.AggStep.PARTIAL:
            for f, arr in states.items():
                dictionary = None
                if f == "val" and call.arg is not None:
                    dictionary = _arg_dictionary(
                        c, call.arg2 if call.fn in A.BY_FNS
                        else call.arg)
                elif f == "xval":
                    dictionary = _arg_dictionary(c, call.arg)
                out[f"{sym}${f}"] = Val(
                    A.state_type(call, f), arr, None, dictionary)
        else:
            fdata, fvalid = A.finalize(call.fn, states, call.dtype,
                                       arg_types[sym], param=call.param)
            if out_dictionary is None and call.arg is not None:
                out_dictionary = _arg_dictionary(c, call.arg)
            out[sym] = Val(call.dtype, fdata, fvalid, out_dictionary)

    return DTable(out, occupied, capacity), ok


def apply_aggregate(dt: DTable, node: N.Aggregate, capacity: int) -> tuple:
    """Returns (DTable of [capacity] rows, ok flag)."""
    live = dt.live_mask()
    c = _compiler(dt)
    # FD-reduced keys carry dependent output columns the arithmetic
    # slot decode can't reproduce: those plans take the sorted path
    fd_reduced = (node.fd_keys
                  and set(node.fd_keys) < set(node.group_keys))
    direct = _direct_group_ids(dt, node.group_keys) \
        if node.group_keys and not fd_reduced else None

    if direct is not None:
        slots, capacity, sizes = direct
        occupancy = segred.segment_sum(
            live.astype(jnp.int32), slots, num_segments=capacity) > 0
        ok = jnp.asarray(True)
    elif node.group_keys:
        # hash-grouped path: sort-and-scan, no group-table scatters
        return _apply_aggregate_sorted(dt, node, capacity, c, live)
    else:
        # global aggregation: one group in slot 0
        slots = jnp.zeros((dt.n,), dtype=jnp.int32)
        occupancy = jnp.ones((capacity,), dtype=bool)  # capacity == 1
        ok = jnp.asarray(True)

    safe_slots = slots  # masked rows fold with weight 0, slot harmless
    out: dict[str, Val] = {}

    if direct is not None:
        out.update(_decode_direct_keys(dt, node.group_keys, sizes,
                                       capacity))

    is_final = node.step == N.AggStep.FINAL
    for sym, call in node.aggs.items():
        out_dictionary = None
        if is_final:
            states = {f: dt.cols[f"{sym}${f}"].data
                      for f in A.state_fields(call)}
            val_state = dt.cols.get(
                f"{sym}$xval" if call.fn in A.BY_FNS else f"{sym}$val")
            if val_state is not None:
                out_dictionary = val_state.dictionary
            states = A.merge(call.fn, states, safe_slots, capacity, live)
            sum_state = dt.cols.get(f"{sym}$sum")
            arg_type = sum_state.dtype if sum_state is not None else None
        else:
            data, weight, data2, data_valid, arg_type = \
                _agg_call_inputs(c, dt, call, live)
            states = A.fold(call.fn, data, weight, safe_slots, capacity,
                            data2=data2, data_valid=data_valid,
                            param=call.param)

        if node.step == N.AggStep.PARTIAL:
            for f, arr in states.items():
                dictionary = None
                if f == "val" and call.arg is not None:
                    dictionary = _arg_dictionary(
                        c, call.arg2 if call.fn in A.BY_FNS
                        else call.arg)
                elif f == "xval":
                    dictionary = _arg_dictionary(c, call.arg)
                out[f"{sym}${f}"] = Val(
                    A.state_type(call, f), arr, None, dictionary)
        else:
            fdata, fvalid = A.finalize(call.fn, states, call.dtype,
                                       arg_type, param=call.param)
            if out_dictionary is None and call.arg is not None:
                out_dictionary = _arg_dictionary(c, call.arg)
            out[sym] = Val(call.dtype, fdata, fvalid, out_dictionary)

    return DTable(out, occupancy, capacity), ok


def _decode_direct_keys(dt: DTable, keys: list[str], sizes: list[int],
                        capacity: int) -> dict[str, Val]:
    """Key columns of the direct group-by path, decoded arithmetically
    from the slot index (inverse of the mixed-radix code product)."""
    gid_range = jnp.arange(capacity, dtype=jnp.int32)
    rev: list = []
    for k, size in zip(reversed(keys), reversed(sizes)):
        rev.append((k, gid_range % size))
        gid_range = gid_range // size
    out: dict[str, Val] = {}
    for k, codes in reversed(rev):
        v = dt.cols[k]
        out[k] = Val(v.dtype, codes.astype(v.data.dtype), None,
                     v.dictionary)
    return out


def _arg_dictionary(c: ExprCompiler, arg: ir.Expr):
    """min/max over a string column keep its dictionary."""
    if isinstance(arg, ir.ColumnRef):
        v = c.columns.get(arg.name)
        if v is not None and v.is_string:
            return v.dictionary
    return None


def _verify_keys(left: DTable, right: DTable,
                 criteria: list[tuple[str, str]], probe_idx, gather):
    """Value-compare matched non-string join keys (64-bit row-hash
    collision defence — the analog of the reference's
    PagesHash.positionEqualsRow after the hash hit). String keys rely on
    content-based per-dictionary hashes (ops/hash.py blake2b), which a
    row-hash collision does not weaken."""
    eq = None
    for lk, rk in criteria:
        lv, rv = left.cols[lk], right.cols[rk]
        if lv.is_string or rv.is_string:
            continue
        ld = lv.data if probe_idx is None else lv.data[probe_idx]
        e = ld == rv.data[gather]
        eq = e if eq is None else (eq & e)
    return eq if eq is not None else True


def _and_key_valid(dt: DTable, keys: list[str], live):
    for k in keys:
        v = dt.cols[k]
        if v.valid is not None:
            live = live & v.valid
    return live


def _direct_probe(left: DTable, right: DTable,
                  criteria: list[tuple[str, str]], dense_key: tuple,
                  probe_live, build_live):
    """Direct-address probe for a dense unique build key (plan/dense.py
    hint ``dense_key`` = (index into ``criteria``, lo, hi); a Join's or
    a MultiJoin leg's): scatter build row indices into a span-sized
    table, gather at probe key offsets — no hashing, no sorts (one
    scatter + one gather vs sort-merge's two full-width sorts; TPU
    sorts cost ~6ns/row/pass).
    Returns (build_row int32 [left.n] (-1 = none), found bool)."""
    ci, lo, hi = dense_key
    span = hi - lo + 1
    lk, rk = criteria[ci]
    bkey = right.cols[rk].data.astype(jnp.int64)
    slot = (bkey - lo).astype(jnp.int32)
    table = jnp.full((span,), -1, dtype=jnp.int32)
    # last-wins on (planner-promised-impossible) duplicates, matching
    # the sort path's largest-source-index representative
    table = table.at[jnp.where(
        build_live & (bkey >= lo) & (bkey <= hi), slot, span)].max(
        jnp.arange(right.n, dtype=jnp.int32), mode="drop")
    pkey = left.cols[lk].data.astype(jnp.int64)
    in_range = (pkey >= lo) & (pkey <= hi)
    build_row = table[jnp.clip(pkey - lo, 0, span - 1).astype(jnp.int32)]
    found = probe_live & in_range & (build_row >= 0)
    return jnp.where(found, build_row, -1), found


def _verify_rest(left: DTable, right: DTable,
                 criteria: list[tuple[str, str]], dense_key: tuple,
                 probe_idx, gather):
    """Value-verify the non-dense criteria (the dense key matched by
    construction; remaining equalities are exact compares against the
    unique candidate row)."""
    ci = dense_key[0]
    rest = [c for i, c in enumerate(criteria) if i != ci]
    if not rest:
        return True
    return _verify_keys(left, right, rest, probe_idx, gather)


def apply_join(left: DTable, right: DTable, node: N.Join,
               capacity: int) -> tuple:
    """Hash join, probe side preserved (each probe row matches <= 1 build
    row — FK->PK). Returns (DTable, ok). Neither lookup below builds a
    table, so ``capacity`` sizes nothing and ``ok`` is constant: both
    stay because the interpreters' stacked ok flags are an output of
    every compiled join program (ROADMAP D13)."""
    lkeys = [lk for lk, _ in node.criteria]
    rkeys = [rk for _, rk in node.criteria]
    # SQL joins never match NULL keys: mask key-invalid rows out of both sides
    build_live = _and_key_valid(right, rkeys, right.live_mask())
    probe_live = _and_key_valid(left, lkeys, left.live_mask())

    if node.dense_key is not None:
        build_row, found = _direct_probe(left, right, node.criteria,
                                         node.dense_key, probe_live,
                                         build_live)
        gather = jnp.clip(build_row, 0, right.n - 1)
        verify = _verify_rest(left, right, node.criteria,
                              node.dense_key, None, gather)
        if verify is not True:
            found = found & verify
    else:
        rh = _row_hash(right, rkeys)
        ph = _row_hash(left, lkeys)
        build_row, found = H.lookup_join(rh, build_live, ph, probe_live)
        gather = jnp.clip(build_row, 0, right.n - 1)
        found = found & _verify_keys(left, right, node.criteria, None,
                                     gather)
    out = dict(left.cols)
    inner = node.join_type == N.JoinType.INNER
    for sym, v in right.cols.items():
        data = v.data[gather]
        if inner:
            # unmatched rows die via the live mask below, so the found
            # mask is redundant as per-column validity — omitting it
            # keeps build-side dictionary keys eligible for the direct
            # group-by fast path downstream
            valid = None if v.valid is None else v.valid[gather]
        else:
            valid = found if v.valid is None else (found & v.valid[gather])
        out[sym] = Val(v.dtype, data, valid, v.dictionary)

    if node.filter is not None:
        fv = ExprCompiler(out).compile(node.filter)
        match_ok = fv.data if fv.valid is None else (fv.data & fv.valid)
        found = found & match_ok

    if node.join_type == N.JoinType.INNER:
        live = probe_live & found
    elif node.join_type == N.JoinType.LEFT:
        # probe rows with NULL keys survive a LEFT join (they match
        # nothing): use the full live mask, not the key-valid one
        live = left.live_mask()
        # un-matched rows: right columns become NULL
        for sym in right.cols:
            v = out[sym]
            out[sym] = Val(v.dtype, v.data,
                           found if v.valid is None else (found & v.valid),
                           v.dictionary)
    else:
        raise NotImplementedError(f"join type {node.join_type}")
    return DTable(out, live, left.n), jnp.asarray(True)


def apply_multi_join(spine: DTable, builds: list[DTable],
                     node: "N.MultiJoin") -> tuple:
    """Fused multi-way INNER equi-join (plan/nodes.MultiJoin): one
    sequential probe walk over the spine's static width. Every build
    is unique (FK->PK) and residual-free by construction, so each step
    is one lookup whose gathered columns immediately become probe
    keys for later builds; a single live mask accumulates the
    conjunction of all matches. The cascade of binary joins this
    replaces materialized (and in segmented execution, compacted and
    re-uploaded) an intermediate DTable per join.

    A leg with a dense-key hint (``node.dense_keys``, plan/dense.py)
    takes apply_join's direct-address body: one scatter, one gather,
    no hash, no sort, the leg's other criteria verified by value. A
    leg without one is the sorted lookup of ops/hash.lookup_join,
    written out here because the probe keys of step k are hashed after
    step k-1's gather. Nothing can overflow: returns (DTable, ok) with
    ok always True (kept for the same reason as apply_join's)."""
    # one scope per build under the node's own (MultiJoin#n/build<k>,
    # in plan order), so a device trace splits the probes
    out = dict(spine.cols)
    live = spine.live_mask()
    width = spine.n
    for k, (bdt, crit) in enumerate(zip(builds, node.criteria)):
        with jax.named_scope(f"build{k}"):
            lkeys = [lk for lk, _ in crit]
            rkeys = [rk for _, rk in crit]
            acc = DTable(out, live, width)
            build_live = _and_key_valid(bdt, rkeys, bdt.live_mask())
            probe_live = _and_key_valid(acc, lkeys, live)
            dense_key = node.leg_dense_key(k)
            if dense_key is not None:
                build_row, found = _direct_probe(
                    acc, bdt, crit, dense_key, probe_live, build_live)
                gather = jnp.clip(build_row, 0, bdt.n - 1)
                verify = _verify_rest(acc, bdt, crit, dense_key, None,
                                      gather)
            else:
                rh = _row_hash(bdt, rkeys)
                _bsh, bsidx = H.sort_build_side(rh, build_live)
                ph = _row_hash(acc, lkeys)
                lo, count, found = H.probe_runs(rh, build_live, ph,
                                                probe_live)
                build_row = jnp.where(
                    found,
                    bsidx[jnp.clip(lo + count - 1, 0, bdt.n - 1)], -1)
                gather = jnp.clip(build_row, 0, bdt.n - 1)
                verify = _verify_keys(acc, bdt, crit, None, gather)
            if verify is not True:
                found = found & verify
            for sym, v in bdt.cols.items():
                # INNER: unmatched rows die via the live mask, so the
                # found mask is redundant as per-column validity (see
                # apply_join)
                out[sym] = Val(
                    v.dtype, v.data[gather],
                    None if v.valid is None else v.valid[gather],
                    v.dictionary)
            live = probe_live & found
    return DTable(out, live, width), jnp.asarray(True)


def concat_dtables(parts: list[DTable]) -> DTable:
    """Row-concatenate DTables with identical column sets (the hybrid
    join's hot + cold result union). Validity masks materialize where
    any part carries one; array columns keep their length/element-mask
    companions."""
    first = parts[0]
    cols: dict[str, Val] = {}
    total = sum(p.n for p in parts)
    for sym, v0 in first.cols.items():
        vs = [p.cols[sym] for p in parts]
        data = jnp.concatenate([v.data for v in vs])
        if any(v.valid is not None for v in vs):
            valid = jnp.concatenate([
                v.valid if v.valid is not None
                else jnp.ones((p.n,), dtype=bool)
                for v, p in zip(vs, parts)])
        else:
            valid = None
        lengths = ev = None
        if v0.is_array:
            lengths = jnp.concatenate([v.lengths for v in vs])
            if any(v.elem_valid is not None for v in vs):
                ev = jnp.concatenate([
                    v.elem_valid if v.elem_valid is not None
                    else jnp.ones(v.data.shape, dtype=bool)
                    for v in vs])
        cols[sym] = Val(v0.dtype, data, valid, v0.dictionary,
                        lengths, ev)
    live = jnp.concatenate([p.live_mask() for p in parts])
    return DTable(cols, live, total)


def apply_expand_join(left: DTable, right: DTable, node: N.Join,
                      capacity: int, out_capacity: int) -> tuple:
    """Expanding (many-to-many) hash join: every (probe, build) match
    becomes one output row (reference LookupJoinOperator + PositionLinks
    chains, operator/join/JoinProbe.java). Output has static capacity
    ``out_capacity``; overflow reported for host retry.

    Returns (DTable [out_capacity], table_ok, out_ok)."""
    lkeys = [lk for lk, _ in node.criteria]
    rkeys = [rk for _, rk in node.criteria]
    build_live = _and_key_valid(right, rkeys, right.live_mask())
    probe_live = _and_key_valid(left, lkeys, left.live_mask())
    full_join = node.join_type == N.JoinType.FULL
    left_join = node.join_type == N.JoinType.LEFT or full_join
    if left_join:
        # left-join preserves probe rows with NULL keys (they just match
        # nothing); only the probe lookup masks them out
        probe_rows_live = left.live_mask()
    else:
        probe_rows_live = probe_live

    rh = _row_hash(right, rkeys)
    _bsh, bsidx = H.sort_build_side(rh, build_live)
    ph = _row_hash(left, lkeys)
    lo, count, found = H.probe_runs(rh, build_live, ph, probe_live)
    t_ok = jnp.asarray(True)  # sorted build: no table, no overflow
    probe_idx, build_row, out_live, o_ok = H.expand_matches(
        lo, count, bsidx, found & probe_live,
        probe_rows_live, out_capacity, left_join)

    out: dict[str, Val] = {}
    for sym, v in left.cols.items():
        data = v.data[probe_idx]
        valid = None if v.valid is None else v.valid[probe_idx]
        out[sym] = Val(v.dtype, data, valid, v.dictionary)
    matched = build_row >= 0
    gather = jnp.clip(build_row, 0, right.n - 1)
    verify = _verify_keys(left, right, node.criteria, probe_idx, gather)
    if verify is not True and not left_join:
        out_live = out_live & (verify | ~matched)
    for sym, v in right.cols.items():
        data = v.data[gather]
        if left_join:
            valid = matched if v.valid is None \
                else (matched & v.valid[gather])
        else:
            # inner expansion emits matched rows only: matched is
            # redundant with out_live (see apply_join)
            valid = None if v.valid is None else v.valid[gather]
        out[sym] = Val(v.dtype, data, valid, v.dictionary)

    keep = matched
    f_ok = None
    if node.filter is not None:
        fv = ExprCompiler(out).compile(node.filter)
        f_ok = fv.data if fv.valid is None else (fv.data & fv.valid)
        if not left_join:
            out_live = out_live & f_ok
    if left_join and (f_ok is not None or verify is not True):
        # outer-join keep/revert pass: a match failing the residual
        # filter or the key value-verify is NOT a match (identity int
        # keys make the EMPTY-remap collision of combine_hashes
        # deterministic for INT64_MAX neighbours, so verify demotion is
        # a correctness path). A probe row whose slots ALL fail must
        # still emit exactly once, unmatched; its surviving collision
        # slots must die (reference JoinFilterFunction handling in
        # LookupJoinOperator — outer rows emit after filtering). Slots
        # of one probe row are contiguous, so "first slot" is where
        # probe_idx changes; revive it when no sibling slot survives.
        keep = matched & out_live
        if f_ok is not None:
            keep = keep & f_ok
        if verify is not True:
            keep = keep & verify
        surv = jax.ops.segment_max(
            keep.astype(jnp.int32), probe_idx,
            num_segments=left.n, indices_are_sorted=True)
        first = jnp.concatenate(
            [jnp.ones((1,), bool), probe_idx[1:] != probe_idx[:-1]])
        revert = (first & (surv[probe_idx] == 0)
                  & probe_rows_live[probe_idx] & out_live)
        out_live = keep | revert
        # right columns of reverted slots are NULL
        for sym, v in right.cols.items():
            data = out[sym].data
            valid = keep if v.valid is None \
                else (keep & v.valid[gather])
            out[sym] = Val(v.dtype, data, valid, v.dictionary)

    if full_join:
        # FULL = LEFT + the build rows no probe row matched, appended as
        # a build-sized tail region with NULL probe columns (reference
        # JoinNode.Type.FULL + LookupOuterOperator's unvisited-positions
        # pass, operator/join/LookupJoinOperator.java)
        nb = right.n
        matched_build = jnp.zeros((nb,), bool).at[jnp.where(
            keep & out_live, build_row, nb)].set(True, mode="drop")
        tail_live = right.live_mask() & ~matched_build
        zero = jnp.zeros((nb,), jnp.int32)
        out2: dict[str, Val] = {}
        for sym, v in out.items():
            if sym in left.cols:
                lv = left.cols[sym]
                tdata = lv.data[zero]  # values dead: all-NULL via valid
                tvalid = jnp.zeros((nb,), bool)
            else:
                rv = right.cols[sym]
                tdata = rv.data
                tvalid = rv.valid
            if v.valid is None and tvalid is None:
                valid = None
            else:
                va = (v.valid if v.valid is not None
                      else jnp.ones((out_capacity,), bool))
                vb = (tvalid if tvalid is not None
                      else jnp.ones((nb,), bool))
                valid = jnp.concatenate([va, vb])
            out2[sym] = Val(v.dtype, jnp.concatenate([v.data, tdata]),
                            valid, v.dictionary)
        live2 = jnp.concatenate([out_live, tail_live])
        return DTable(out2, live2, out_capacity + nb), t_ok, o_ok

    return DTable(out, out_live, out_capacity), t_ok, o_ok


def apply_semijoin(dt: DTable, filt: DTable, node: N.SemiJoin,
                   capacity: int) -> tuple:
    build_live = _and_key_valid(filt, node.filter_keys, filt.live_mask())
    probe_live = _and_key_valid(dt, node.source_keys, dt.live_mask())
    if node.dense_key is not None:
        # dense membership bitmap: one scatter + one gather, exact by
        # construction (value addressing); duplicates just re-set a bit
        lo, hi = node.dense_key
        span = hi - lo + 1
        bkey = filt.cols[node.filter_key].data.astype(jnp.int64)
        bits = jnp.zeros((span,), dtype=bool).at[jnp.where(
            build_live & (bkey >= lo) & (bkey <= hi),
            (bkey - lo).astype(jnp.int32), span)].set(True, mode="drop")
        pkey = dt.cols[node.source_key].data.astype(jnp.int64)
        in_range = (pkey >= lo) & (pkey <= hi)
        found = probe_live & in_range & bits[
            jnp.clip(pkey - lo, 0, span - 1).astype(jnp.int32)]
    else:
        fh = _row_hash(filt, node.filter_keys)
        sh = _row_hash(dt, node.source_keys)
        build_row, found = H.lookup_join(fh, build_live, sh, probe_live)
        found = found & _verify_keys(
            dt, filt, list(zip(node.source_keys, node.filter_keys)),
            None, jnp.clip(build_row, 0, filt.n - 1))
    out = dict(dt.cols)
    mark_valid = None
    if node.null_aware:
        # x IN (S) is NULL (not FALSE) when unmatched and either x is
        # NULL or S contains a NULL — three-valued logic that matters
        # under negation (NOT IN): such rows must NOT pass the filter
        bk = filt.cols[node.filter_keys[0]]
        build_has_null = (jnp.any(filt.live_mask() & ~bk.valid)
                          if bk.valid is not None else jnp.asarray(False))
        pk = dt.cols[node.source_keys[0]]
        probe_null = (~pk.valid if pk.valid is not None
                      else jnp.zeros((dt.n,), bool))
        # x IN (empty set) is definitively FALSE even for NULL x
        set_empty = ~jnp.any(filt.live_mask())
        mark_valid = found | set_empty | (~probe_null & ~build_has_null)
    out[node.output] = Val(T.BOOLEAN, found, mark_valid)
    # ok is constant, like apply_join's
    return DTable(out, dt.live, dt.n), jnp.asarray(True)


def compact_dtable(dt: DTable, capacity: int) -> tuple:
    """Gather live rows to the front of a ``capacity``-row DTable (the
    page-compaction analog inside a traced program). Returns
    (DTable [capacity], ok); ok is False when live rows overflow the
    capacity (host retries with a grown capacity). Survivors keep
    their order; rows past the live count replicate the last input row
    and are dead."""
    live = dt.live_mask()
    cnt = jnp.sum(live.astype(jnp.int32))
    ok = cnt <= capacity
    idx = jnp.nonzero(live, size=int(capacity), fill_value=dt.n - 1)[0]
    cols = {
        sym: Val(v.dtype, v.data[idx],
                 None if v.valid is None else v.valid[idx],
                 v.dictionary)
        for sym, v in dt.cols.items()}
    return DTable(cols, jnp.arange(capacity) < cnt, capacity), ok


def apply_cross_general(left: DTable, right: DTable) -> DTable:
    """General nested-loop cross join: the full static product
    left.n x right.n (reference NestedLoopJoinOperator.java:46).
    Callers compact both sides first so the product is sized by live
    estimates, not input capacities."""
    nl, nr = left.n, right.n
    i = jnp.repeat(jnp.arange(nl, dtype=jnp.int32), nr)
    j = jnp.tile(jnp.arange(nr, dtype=jnp.int32), nl)
    out: dict[str, Val] = {}
    for sym, v in left.cols.items():
        out[sym] = Val(v.dtype, v.data[i],
                       None if v.valid is None else v.valid[i],
                       v.dictionary)
    for sym, v in right.cols.items():
        out[sym] = Val(v.dtype, v.data[j],
                       None if v.valid is None else v.valid[j],
                       v.dictionary)
    live = left.live_mask()[i] & right.live_mask()[j]
    return DTable(out, live, nl * nr)


def apply_cross_scalar(left: DTable, right: DTable) -> DTable:
    """Cross join against a single-row relation (uncorrelated scalar
    subquery; reference EnforceSingleRowNode + JoinNode w/o criteria):
    broadcast the scalar row's columns over the probe side."""
    rlive = right.live_mask()
    # index of the single live row (0 if none; validity handles empties)
    idx = jnp.argmax(rlive.astype(jnp.int32))
    any_live = jnp.any(rlive)
    out = dict(left.cols)
    for sym, v in right.cols.items():
        data = jnp.broadcast_to(v.data[idx],
                                (left.n,) + v.data.shape[1:])
        rv = any_live if v.valid is None else (any_live & v.valid[idx])
        valid = jnp.broadcast_to(rv, (left.n,))
        out[sym] = Val(v.dtype, data, valid, v.dictionary)
    return DTable(out, left.live, left.n)


def _unify_string_vals(vals: list[Val]) -> list[Val]:
    """Remap string Vals onto one shared sorted union dictionary."""
    dicts = [v.dictionary for v in vals]
    if all(d is dicts[0] for d in dicts):
        return vals
    union = np.unique(np.concatenate([d.astype("U") for d in dicts]))
    uobj = union.astype(object)
    out = []
    for v in vals:
        remap = jnp.asarray(
            np.searchsorted(union, v.dictionary.astype("U"))
            .astype(np.int32))
        out.append(Val(v.dtype, remap[v.data], v.valid, uobj))
    return out


def apply_union(parts: list[DTable], node: N.Union) -> DTable:
    """UNION ALL: concatenate columns (static total capacity = sum of
    input capacities), remapping each input's symbols per node.mappings
    and merging string dictionaries (reference plan/UnionNode.java)."""
    n = sum(p.n for p in parts)
    out: dict[str, Val] = {}
    for sym in node.symbols:
        dtype = node.types[sym]
        vals = []
        for p, mapping in zip(parts, node.mappings):
            v = p.cols[mapping[sym]]
            vals.append(v if v.is_string else cast_val(v, dtype))
        if isinstance(dtype, T.VarcharType):
            vals = _unify_string_vals(vals)
        long_dec = isinstance(dtype, T.DecimalType) and dtype.is_long

        def part_data(v, p):
            if long_dec:  # [n,2] / scalar [2] limbs -> [p.n, 2]
                return jnp.broadcast_to(
                    v.data if v.data.ndim == 2 else v.data[None, :],
                    (p.n, 2))
            return jnp.broadcast_to(v.data, (p.n,))

        data = jnp.concatenate([part_data(v, p)
                                for v, p in zip(vals, parts)])
        if any(v.valid is not None for v in vals):
            valid = jnp.concatenate([
                v.valid if v.valid is not None
                else jnp.ones((p.n,), dtype=bool)
                for v, p in zip(vals, parts)])
        else:
            valid = None
        out[sym] = Val(dtype, data, valid,
                       vals[0].dictionary if vals[0].is_string else None)
    live = jnp.concatenate([p.live_mask() for p in parts])
    return DTable(out, live, n)


def _sort_keys(dt: DTable, orderings: list[N.Ordering]) -> list:
    """Per-row sort key arrays: ascending lexicographic order over the
    returned list == the requested ordering (dead rows last, null
    placement per SQL semantics folded into the key values)."""
    live = dt.live_mask()
    keys = [(~live).astype(jnp.int32)]  # dead rows last
    for o in orderings:
        v = dt.cols[o.symbol]
        data = v.data
        if getattr(data, "ndim", 1) == 2:
            # LONG decimal: int128 limbs -> two u64 key levels
            # (sign-flipped high word, then the unsigned low word);
            # descending order complements both levels
            from presto_tpu.ops import int128 as I
            khi, klo = I.sort_keys(data)
            if not o.ascending:
                khi, klo = ~khi, ~klo
            if v.valid is not None:
                cls = jnp.where(v.valid, 0, 2 if _nulls_last(o) else -2
                                ).astype(jnp.int32)
                khi = jnp.where(v.valid, khi, jnp.uint64(0))
                klo = jnp.where(v.valid, klo, jnp.uint64(0))
                keys.append(cls)
            keys.append(khi)
            keys.append(klo)
            continue
        if data.dtype == jnp.bool_:
            data = data.astype(jnp.int32)
        is_float = jnp.issubdtype(data.dtype, jnp.floating)
        if not o.ascending:
            # ints reverse via bitwise NOT (~x = -x-1): monotone
            # decreasing with no INT_MIN negation wrap
            data = -data if is_float else ~data
        # Nulls and NaNs order via a separate class-key level rather
        # than folding into extreme data values: value < NaN < NULL
        # (reference NaN-is-largest + null-is-largest semantics, null
        # placement per _nulls_last). Folding would collide NULL/NaN
        # with genuine +-inf / INT_MAX data, and NaN would break
        # merge_runs_perm's rank counting (needs a total comparator) —
        # dead lanes can carry NaN from computed expressions even when
        # live rows never do.
        cls = None
        if is_float:
            nan = jnp.isnan(data)
            cls = jnp.where(nan, 1 if o.ascending else -1, 0
                            ).astype(jnp.int32)
            data = jnp.where(nan, jnp.zeros_like(data), data)
        if v.valid is not None:
            if cls is None:
                cls = jnp.zeros(data.shape, jnp.int32)
            cls = jnp.where(v.valid, cls, 2 if _nulls_last(o) else -2)
            data = jnp.where(v.valid, data, jnp.zeros_like(data))
        if cls is not None:
            keys.append(cls)
        keys.append(data)
    return keys


def _sort_perm(dt: DTable, orderings: list[N.Ordering]):
    keys = _sort_keys(dt, orderings)
    operands = tuple(keys) + (jnp.arange(dt.n, dtype=jnp.int32),)
    sorted_ops = jax.lax.sort(operands, num_keys=len(keys), is_stable=True)
    return sorted_ops[-1]


def merge_runs_perm(keys: list, k: int, m: int):
    """Permutation merging ``k`` presorted runs of ``m`` rows each
    (stored concatenated) into one sorted order — the kernel behind
    merge exchange / distributed sort (reference MergeOperator.java:44,
    docs/admin/dist-sort.rst).

    Each row's output position is its local rank plus, for every other
    run, the count of rows ordered before it — found by a vectorised
    binary search with the full lexicographic comparator, O(N·k·log m)
    elementwise work instead of re-sorting N rows (O(N·log^2 N)
    compare-exchange stages), with the expensive per-shard sorts running
    in parallel on their own devices. Ties break by (run, local rank),
    matching a stable sort of the concatenation. Key arrays must be
    NaN-free so the comparator is total — _sort_keys guarantees this by
    encoding NULL/NaN in a separate int32 class-key level and zeroing
    the data lanes underneath.
    """
    n = k * m
    run_of = jnp.arange(n, dtype=jnp.int32) // m
    local_rank = jnp.arange(n, dtype=jnp.int32) % m
    rank = local_rank
    # lower-bound binary search over [0, m] needs floor(log2 m)+1 halvings
    steps = m.bit_length()
    for j in range(k):
        run_keys = [kk[j * m:(j + 1) * m] for kk in keys]
        # ties in run j precede rows of later runs (stability)
        tie_after = run_of > j
        lo = jnp.zeros((n,), jnp.int32)
        hi = jnp.full((n,), m, jnp.int32)
        for _ in range(steps):
            mid = (lo + hi) >> 1
            lt = jnp.zeros((n,), bool)
            eq = jnp.ones((n,), bool)
            for rk, qk in zip(run_keys, keys):
                c = rk[mid]
                lt = lt | (eq & (c < qk))
                eq = eq & (c == qk)
            before = lt | (eq & tie_after)  # run[mid] orders before query
            open_ = lo < hi  # converged lanes must not move past hi
            lo = jnp.where(open_ & before, mid + 1, lo)
            hi = jnp.where(open_ & ~before, mid, hi)
        rank = rank + jnp.where(run_of == j, 0, lo)
    # rank is a permutation of 0..n-1; invert to a gather index
    return jnp.zeros((n,), jnp.int32).at[rank].set(
        jnp.arange(n, dtype=jnp.int32))


def merge_sorted_runs(dt: DTable, orderings: list[N.Ordering],
                      k: int) -> DTable:
    """Merge a table holding ``k`` concatenated presorted runs."""
    assert dt.n % k == 0
    perm = merge_runs_perm(_sort_keys(dt, orderings), k, dt.n // k)
    return _gather_table(dt, perm)


def head(dt: DTable, count: int) -> DTable:
    """Static slice of the first ``count`` rows (compaction after sort —
    the analog of a bounded PageBuilder flush before an exchange)."""
    c = min(count, dt.n)
    cols = {sym: Val(v.dtype, v.data[:c],
                     None if v.valid is None else v.valid[:c],
                     v.dictionary)
            for sym, v in dt.cols.items()}
    live = None if dt.live is None else dt.live[:c]
    return DTable(cols, live, c)


def _nulls_last(o: N.Ordering) -> bool:
    if o.nulls_first is None:
        # Trino default: nulls last in ASC, first in DESC (null = largest)
        return o.ascending
    return not o.nulls_first


def _gather_table(dt: DTable, perm) -> DTable:
    out = {}
    for sym, v in dt.cols.items():
        out[sym] = Val(v.dtype, v.data[perm],
                       None if v.valid is None else v.valid[perm],
                       v.dictionary)
    live = None if dt.live is None else dt.live[perm]
    return DTable(out, live, dt.n)


def apply_sort(dt: DTable, orderings: list[N.Ordering]) -> DTable:
    perm = _sort_perm(dt, orderings)
    return _gather_table(dt, perm)


def apply_topn(dt: DTable, count: int, orderings: list[N.Ordering]) -> DTable:
    out = apply_sort(dt, orderings)
    live = out.live_mask() & (jnp.arange(dt.n) < count)
    return DTable(out.cols, live, dt.n)


def apply_limit(dt: DTable, count: int, offset: int = 0) -> DTable:
    live = dt.live_mask()
    pos = jnp.cumsum(live.astype(jnp.int64))
    keep = (pos > offset) & (pos <= offset + count)
    return DTable(dt.cols, live & keep, dt.n)


def _keys_equal_prev(vals: list[Val], sorted_perm) -> object:
    """bool[n]: row i's key tuple equals row i-1's (in sorted order).
    Exact value comparison (not hashes). Row 0 is always False."""
    n = sorted_perm.shape[0]
    eq = jnp.ones((n,), dtype=bool)
    for v in vals:
        d = v.data[sorted_perm]
        pair_eq = d[1:] == d[:-1]
        if pair_eq.ndim == 2:  # LONG decimal limbs: equal iff both are
            pair_eq = pair_eq.all(axis=-1)
        same = jnp.concatenate(
            [jnp.zeros((1,), bool), pair_eq])
        if v.valid is not None:
            vv = v.valid[sorted_perm]
            both_null = jnp.concatenate(
                [jnp.zeros((1,), bool), ~vv[1:] & ~vv[:-1]])
            same_valid = jnp.concatenate(
                [jnp.zeros((1,), bool), vv[1:] == vv[:-1]])
            same = (same | both_null) & same_valid
        eq = eq & same
    if not vals:
        return jnp.ones((n,), dtype=bool).at[0].set(False)
    return eq.at[0].set(False)


def apply_window(dt: DTable, node: N.Window) -> DTable:
    """Window functions: sort by (partition, order) keys, compute ranks /
    running & full-partition aggregates with scans over the sorted
    layout, scatter results back to the original row order.

    TPU-native reformulation of the reference's WindowOperator +
    PagesIndex (operator/WindowOperator.java:70, PagesIndex.java:79):
    where the reference walks partitions row-by-row, every function here
    is a vectorised prefix-scan/segment reduction over the sorted array.
    """
    n = dt.n
    live = dt.live_mask()
    part_orderings = [N.Ordering(s) for s in node.partition_by]
    perm = _sort_perm(dt, part_orderings + list(node.orderings))
    inv = jnp.zeros((n,), jnp.int32).at[perm].set(
        jnp.arange(n, dtype=jnp.int32))

    pvals = [dt.cols[s] for s in node.partition_by]
    ovals = [dt.cols[o.symbol] for o in node.orderings]
    slive = live[perm]
    same_part = _keys_equal_prev(pvals, perm) & slive \
        & jnp.concatenate([jnp.zeros((1,), bool), slive[:-1]])
    same_peer = same_part & _keys_equal_prev(pvals + ovals, perm)

    idx = jnp.arange(n, dtype=jnp.int64)
    # index of this row's partition start / peer-group start: running max
    # over boundary markers
    part_start = jax.lax.associative_scan(
        jnp.maximum, jnp.where(same_part, jnp.int64(-1), idx))
    peer_start = jax.lax.associative_scan(
        jnp.maximum, jnp.where(same_peer, jnp.int64(-1), idx))

    # partition / peer-group END positions (reverse running min over
    # boundary markers) — frames and value functions need both ends
    is_last_of_part = jnp.concatenate(
        [part_start[1:] != part_start[:-1], jnp.ones((1,), bool)])
    part_end = jax.lax.associative_scan(
        jnp.minimum, jnp.where(is_last_of_part, idx, jnp.int64(n)),
        reverse=True)
    is_last_of_peer = jnp.concatenate(
        [peer_start[1:] != peer_start[:-1], jnp.ones((1,), bool)])
    peer_end = jax.lax.associative_scan(
        jnp.minimum, jnp.where(is_last_of_peer, idx, jnp.int64(n)),
        reverse=True)

    out = dict(dt.cols)
    c = ExprCompiler({s: Val(v.dtype, v.data[perm],
                             None if v.valid is None else v.valid[perm],
                             v.dictionary)
                      for s, v in dt.cols.items()})

    key_val = (c.columns.get(node.orderings[0].symbol)
               if len(node.orderings) == 1 else None)
    fctx = {"orderings": node.orderings, "same_peer": same_peer,
            "same_part": same_part, "peer_start": peer_start,
            "peer_end": peer_end, "key": key_val}
    for sym, call in node.functions.items():
        data, valid, dictionary = _window_fn(
            call, c, idx, part_start, peer_start, part_end, peer_end,
            same_part, slive, n, fctx)
        # scatter back to original order
        data = data[inv]
        valid = None if valid is None else valid[inv]
        out[sym] = Val(call.dtype, data, valid, dictionary)
    return DTable(out, dt.live, n)


def _window_fn(call: N.WindowCall, c: ExprCompiler, idx, part_start,
               peer_start, part_end, peer_end, same_part, slive, n,
               fctx=None):
    fn = call.fn
    if fn == "row_number":
        return (idx - part_start + 1), None, None
    if fn == "rank":
        return (peer_start - part_start + 1), None, None
    if fn == "dense_rank":
        new_peer = ~jnp.concatenate(
            [jnp.zeros((1,), bool), peer_start[1:] == peer_start[:-1]])
        peer_ord = jnp.cumsum(new_peer.astype(jnp.int64))
        at_start = peer_ord[jnp.clip(part_start, 0, n - 1)]
        return peer_ord - at_start + 1, None, None
    if fn == "percent_rank":
        rank = (peer_start - part_start).astype(jnp.float64)
        rows = (part_end - part_start).astype(jnp.float64)
        return jnp.where(rows > 0, rank / jnp.maximum(rows, 1), 0.0), \
            None, None
    if fn == "cume_dist":
        rows = (part_end - part_start + 1).astype(jnp.float64)
        return (peer_end - part_start + 1).astype(jnp.float64) / rows, \
            None, None
    if fn == "ntile":
        buckets = int(call.args[0].value)
        pos = idx - part_start
        rows = part_end - part_start + 1
        q, r = rows // buckets, rows % buckets
        # the first r buckets get q+1 rows (SQL ntile split)
        big_span = (q + 1) * r
        in_big = pos < big_span
        bucket = jnp.where(
            in_big, pos // jnp.maximum(q + 1, 1),
            r + (pos - big_span) // jnp.maximum(q, 1))
        return jnp.clip(bucket, 0, buckets - 1) + 1, None, None
    if fn in ("first_value", "last_value", "nth_value"):
        v = c.compile(call.args[0])
        lo, hi = _frame_bounds(call, idx, part_start, part_end,
                               peer_end, fctx)
        if fn == "first_value":
            at = lo
        elif fn == "last_value":
            at = hi
        else:
            k = int(call.args[1].value)
            at = lo + (k - 1)
        in_frame = (at >= lo) & (at <= hi) & (hi >= lo)
        src = jnp.clip(at, 0, n - 1).astype(jnp.int32)
        data = v.data[src]
        valid = in_frame if v.valid is None else (in_frame
                                                  & v.valid[src])
        return data, valid, v.dictionary
    if fn in ("lag", "lead"):
        v = c.compile(call.args[0])
        offset = 1
        if len(call.args) > 1:
            offset = int(call.args[1].value)  # planner enforces literal
        shift = -offset if fn == "lag" else offset
        src = jnp.clip(idx + shift, 0, n - 1).astype(jnp.int32)
        in_part = (part_start[src] == part_start) & \
            (src == idx + shift)
        data = v.data[src]
        valid = in_part if v.valid is None else (in_part & v.valid[src])
        return data, valid, v.dictionary
    if fn in ("sum", "count", "avg", "min", "max"):
        if call.args:
            v = c.compile(call.args[0])
            if getattr(v.data, "ndim", 1) == 2:
                raise NotImplementedError(
                    "window aggregates over long decimals "
                    "(precision > 18) are not supported yet")
            w = slive if v.valid is None else (slive & v.valid)
            vals = v.data
        else:
            v = None
            w = slive
            vals = jnp.ones((n,), jnp.int64)
        restart = ~same_part  # new partition begins (row 0 included)
        if fn == "count":
            vals = jnp.ones((n,), jnp.int64)
        if jnp.issubdtype(vals.dtype, jnp.integer):
            vals = vals.astype(jnp.int64)

        if (call.range_frame is not None
                or call.groups_frame is not None
                or (call.rows_frame is not None and (
                    call.rows_frame[0] is not None
                    or call.rows_frame[1] is not None))):
            return _frame_agg(call, fn, v, vals, w, idx, part_start,
                              part_end, restart, n, fctx)

        if call.rows_frame == (None, None) \
                or call.frame == "full_partition":
            # ROWS UNBOUNDED..UNBOUNDED == the whole partition
            frame_at = None
        elif call.frame == "rows_unbounded_current":
            # ROWS frame: ends exactly at the current row (peers excluded)
            frame_at = jnp.clip(idx, 0, n - 1)
        elif call.frame != "full_partition":
            # RANGE default includes the whole peer group — the running
            # value is the segmented scan taken at the END of this
            # row's peer group
            frame_at = jnp.clip(peer_end, 0, n - 1)
        else:
            frame_at = None

        def run_scan(masked, op):
            scanned = _segmented_scan(masked, restart, op)
            if frame_at is not None:
                return scanned[frame_at]
            # full partition: value at partition's last row
            return scanned[jnp.clip(part_end, 0, n - 1)]

        cnt = run_scan(w.astype(jnp.int64), jnp.add)
        if fn == "count":
            return cnt, None, None
        if fn in ("sum", "avg"):
            masked = jnp.where(w, vals, jnp.zeros((), vals.dtype))
            total = run_scan(masked, jnp.add)
            if fn == "avg":
                sf = total.astype(jnp.float64)
                if v is not None and isinstance(v.dtype, T.DecimalType):
                    sf = sf / v.dtype.unscale_factor
                return sf / jnp.maximum(cnt, 1), cnt > 0, None
            return total, cnt > 0, None
        if fn == "max":
            sentinel = jnp.asarray(
                jnp.iinfo(vals.dtype).min if jnp.issubdtype(
                    vals.dtype, jnp.integer) else -jnp.inf, vals.dtype)
            run = run_scan(jnp.where(w, vals, sentinel), jnp.maximum)
        else:
            sentinel = jnp.asarray(
                jnp.iinfo(vals.dtype).max if jnp.issubdtype(
                    vals.dtype, jnp.integer) else jnp.inf, vals.dtype)
            run = run_scan(jnp.where(w, vals, sentinel), jnp.minimum)
        return run, cnt > 0, (v.dictionary if v is not None else None)
    raise NotImplementedError(f"window function {fn}")


def _frame_bounds(call: N.WindowCall, idx, part_start, part_end,
                  peer_end, fctx=None):
    """Inclusive sorted-position frame [lo, hi] for value functions and
    framed aggregates. Default (no explicit frame): RANGE UNBOUNDED
    PRECEDING..CURRENT ROW = partition start .. peer-group end."""
    if call.range_frame is not None or call.groups_frame is not None:
        return _dynamic_frame_bounds(call, fctx, idx, part_start,
                                     part_end)
    rf = call.rows_frame
    if rf is not None:
        p, f = rf
        lo = part_start if p is None else jnp.maximum(idx - p,
                                                      part_start)
        hi = part_end if f is None else jnp.minimum(idx + f, part_end)
        return lo, hi
    if call.frame == "full_partition":
        return part_start, part_end
    if call.frame == "rows_unbounded_current":
        return part_start, idx
    return part_start, peer_end


def _bounded_bsearch(vals, targets, lo0, hi0, left: bool, n: int):
    """Per-row binary search: the insertion position of ``targets[i]``
    in ascending ``vals`` restricted to [lo0[i], hi0[i]) — the
    partition-respecting vectorized searchsorted behind RANGE frames
    (log2(n) gather rounds; reference window/RangeFraming.java walks
    row-at-a-time from the previous frame instead)."""

    def body(_k, lh):
        lo, hi = lh
        mid = (lo + hi) >> 1
        v = vals[jnp.clip(mid, 0, n - 1).astype(jnp.int32)]
        go = (v < targets) if left else (v <= targets)
        active = lo < hi
        return (jnp.where(active & go, mid + 1, lo),
                jnp.where(active & ~go, mid, hi))

    iters = max(int(n - 1).bit_length(), 1) + 1
    lo, hi = jax.lax.fori_loop(
        0, iters, body,
        (lo0.astype(jnp.int64), hi0.astype(jnp.int64)))
    return lo


def _dynamic_frame_bounds(call: N.WindowCall, fctx, idx, part_start,
                          part_end):
    """Inclusive [lo, hi] sorted positions of a value-based RANGE or a
    GROUPS frame (reference window/RangeFraming.java,
    GroupsFraming.java).

    GROUPS: peer groups carry a GLOBALLY ascending dense id (cumsum of
    group starts), so both bounds are one vectorized searchsorted each,
    clamped into the partition. RANGE: the sort key is ascending within
    each partition's non-null span, so bounds come from a
    partition-bounded binary search over [key - preceding,
    key + following]; null-key rows frame over their peer group (all
    nulls), and UNBOUNDED sides keep whole-partition bounds (nulls
    included), matching the reference's null handling."""
    n = idx.shape[0]
    peer_start, peer_end = fctx["peer_start"], fctx["peer_end"]
    if call.groups_frame is not None:
        p, f = call.groups_frame
        gg = jnp.cumsum((~fctx["same_peer"]).astype(jnp.int64))
        lo = part_start if p is None else jnp.maximum(
            jnp.searchsorted(gg, gg - jnp.int64(p), side="left"),
            part_start)
        hi = part_end if f is None else jnp.minimum(
            jnp.searchsorted(gg, gg + jnp.int64(f), side="right") - 1,
            part_end)
        return lo, hi

    p, f = call.range_frame
    o = fctx["orderings"][0]
    kv = fctx["key"]
    if jnp.issubdtype(kv.data.dtype, jnp.floating):
        key = kv.data.astype(jnp.float64)
        pv = jnp.float64(0 if p is None else p)
        fv = jnp.float64(0 if f is None else f)
    else:
        key = kv.data.astype(jnp.int64)
        pv = jnp.int64(0 if p is None else p)
        fv = jnp.int64(0 if f is None else f)
    if not o.ascending:
        # descending keys negate into an ascending search; PRECEDING
        # still points at the partition start side
        key = -key
    valid = kv.valid
    if valid is None:
        nn_start, nn_end = part_start, part_end
        isnull = None
    else:
        isnull = ~valid
        restart = ~fctx["same_part"]
        npref = _segmented_scan(isnull.astype(jnp.int64), restart,
                                jnp.add)
        tot = npref[jnp.clip(part_end, 0, n - 1)]
        if _nulls_last(o):
            nn_start, nn_end = part_start, part_end - tot
        else:
            nn_start, nn_end = part_start + tot, part_end
    lo = part_start if p is None else jnp.maximum(
        _bounded_bsearch(key, key - pv, nn_start, nn_end + 1, True, n),
        part_start)
    hi = part_end if f is None else jnp.minimum(
        _bounded_bsearch(key, key + fv, nn_start, nn_end + 1, False,
                         n) - 1,
        part_end)
    if isnull is not None:
        # a null-key row's offset frame is its peer group (all nulls)
        if p is not None:
            lo = jnp.where(isnull, peer_start, lo)
        if f is not None:
            hi = jnp.where(isnull, peer_end, hi)
    return lo, hi


def _sparse_minmax(masked, lo, hi, op, ident, n: int):
    """min/max over arbitrary inclusive [lo, hi] spans via a doubling
    sparse table: tables[k][i] covers [i, i + 2^k), a query is
    op(T[k][lo], T[k][hi-2^k+1]) with k = floor(log2(width)) — log2(n)
    elementwise passes to build, two 2D gathers to query (the
    RMQ-sparse-table classic; the reference's per-row accumulator loop
    has no vectorized analog)."""
    if n > (1 << 23):
        raise NotImplementedError(
            "doubly-bounded RANGE/GROUPS min/max frames over >8M "
            "sorted rows")
    levels = [masked]
    t = masked
    k = 1
    while (1 << k) <= n:
        sh = 1 << (k - 1)
        shifted = jnp.concatenate(
            [t[sh:], jnp.full((sh,), ident, t.dtype)])
        t = op(t, shifted)
        levels.append(t)
        k += 1
    table = jnp.stack(levels)  # [K, n]
    width = jnp.maximum(hi - lo + 1, 1)
    kq = jnp.floor(jnp.log2(width.astype(jnp.float64))).astype(
        jnp.int32)
    kq = jnp.clip(kq, 0, len(levels) - 1)
    span = jnp.left_shift(jnp.int64(1), kq.astype(jnp.int64))
    a = table[kq, jnp.clip(lo, 0, n - 1).astype(jnp.int32)]
    b = table[kq, jnp.clip(hi - span + 1, 0, n - 1).astype(jnp.int32)]
    return op(a, b)


def _frame_agg(call: N.WindowCall, fn: str, v, vals, w, idx,
               part_start, part_end, restart, n, fctx=None):
    """Aggregate over a general ROWS/RANGE/GROUPS frame (reference
    window/RowsFraming.java, RangeFraming.java, GroupsFraming.java).
    sum/count/avg difference two points of the segmented prefix scan;
    one-sided-unbounded min/max take a (possibly reversed) running
    scan; doubly-bounded min/max unroll one static shift+select pass
    per frame offset for ROWS (frames in practice are narrow) and use
    a doubling sparse table for value/group frames whose width is
    data-dependent."""
    if call.rows_frame is not None:
        p, f = call.rows_frame
        lo = part_start if p is None else jnp.maximum(idx - p,
                                                      part_start)
        hi = part_end if f is None else jnp.minimum(idx + f, part_end)
        rows_static = True
    else:
        p, f = (call.range_frame if call.range_frame is not None
                else call.groups_frame)
        lo, hi = _dynamic_frame_bounds(call, fctx, idx, part_start,
                                       part_end)
        rows_static = False
    empty = hi < lo
    hi_c = jnp.clip(hi, 0, n - 1).astype(jnp.int32)
    lo_c = jnp.clip(lo, 0, n - 1).astype(jnp.int32)

    def span_sum(masked):
        s = _segmented_scan(masked, restart, jnp.add)
        at_hi = s[hi_c]
        prev = s[jnp.clip(lo_c - 1, 0, n - 1)]
        has_prev = lo > part_start
        return jnp.where(empty, 0, at_hi - jnp.where(has_prev, prev, 0))

    cnt = span_sum(w.astype(jnp.int64))
    if fn == "count":
        return cnt, None, None
    if fn in ("sum", "avg"):
        total = span_sum(jnp.where(w, vals, jnp.zeros((), vals.dtype)))
        if fn == "avg":
            sf = total.astype(jnp.float64)
            if v is not None and isinstance(v.dtype, T.DecimalType):
                sf = sf / v.dtype.unscale_factor
            return sf / jnp.maximum(cnt, 1), cnt > 0, None
        return total, cnt > 0, None

    # min/max: sparse table over masked values
    is_max = fn == "max"
    if jnp.issubdtype(vals.dtype, jnp.integer):
        ident = jnp.asarray(jnp.iinfo(vals.dtype).min if is_max
                            else jnp.iinfo(vals.dtype).max, vals.dtype)
    else:
        ident = jnp.asarray(-jnp.inf if is_max else jnp.inf,
                            vals.dtype)
    op = jnp.maximum if is_max else jnp.minimum
    masked = jnp.where(w, vals, ident)
    if p is None or f is None:
        # one-sided unbounded: running scan (possibly reversed) taken
        # at the bounded end
        if p is None:
            s = _segmented_scan(masked, restart, op)
            run = s[hi_c]
        else:
            rrestart = jnp.concatenate(
                [restart[1:], jnp.ones((1,), bool)])
            s = _rsegmented_scan(masked, rrestart, op)
            run = s[lo_c]
        return jnp.where(empty, ident, run), cnt > 0, \
            (v.dictionary if v is not None else None)
    if not rows_static:
        res = _sparse_minmax(masked, lo, hi, op, ident, n)
        return jnp.where(empty, ident, res), cnt > 0, \
            (v.dictionary if v is not None else None)
    # bounded frame: one static shift + select per offset (width total
    # elementwise passes, no gathers; frames in practice are narrow —
    # moving averages of a few rows)
    width = int(p) + int(f) + 1
    if width > 1024:
        raise NotImplementedError(
            f"ROWS frame of width {width} (bounded min/max frames "
            "support width <= 1024)")
    res = jnp.full((n,), ident, masked.dtype)
    for d in range(-int(p), int(f) + 1):
        if d < 0:
            shifted = jnp.concatenate(
                [jnp.full((-d,), ident, masked.dtype), masked[:d]])
        elif d > 0:
            shifted = jnp.concatenate(
                [masked[d:], jnp.full((d,), ident, masked.dtype)])
        else:
            shifted = masked
        pos = idx + d
        inside = (pos >= lo) & (pos <= hi)
        res = op(res, jnp.where(inside, shifted, ident))
    return jnp.where(empty, ident, res), cnt > 0, \
        (v.dictionary if v is not None else None)


def _rsegmented_scan(vals, restart_rev, op):
    """Reverse segmented inclusive scan (restart flags mark segment
    ENDS)."""

    def combine(a, b):
        av, af = a
        bv, bf = b
        return jnp.where(bf, bv, op(av, bv)), af | bf

    out, _ = jax.lax.associative_scan(combine, (vals, restart_rev),
                                      reverse=True)
    return out


def _segmented_scan(vals, restart, op):
    """Inclusive scan that restarts wherever ``restart`` is True."""

    def combine(a, b):
        av, af = a
        bv, bf = b
        return jnp.where(bf, bv, op(av, bv)), af | bf

    out, _ = jax.lax.associative_scan(combine, (vals, restart))
    return out


def apply_unnest(dt: DTable, node: N.Unnest) -> DTable:
    """Expand array elements into rows (reference UnnestOperator over
    UnnestNode): output row (i, j) carries source row i's columns and
    each array's j-th element; static output size n * max_capacity.
    Multiple arrays zip to the longest length (NULL-padding shorter
    ones); NULL arrays produce no rows."""
    arrays = [dt.cols[s] for s in node.array_syms]
    cap = max(a.data.shape[1] for a in arrays)
    n = dt.n
    live = dt.live_mask()

    # per-row zip length: max of array lengths (NULL array counts 0)
    zlen = None
    for a in arrays:
        ln = a.lengths
        if a.valid is not None:
            ln = jnp.where(a.valid, ln, 0)
        zlen = ln if zlen is None else jnp.maximum(zlen, ln)

    out: dict[str, Val] = {}
    for sym, v in dt.cols.items():
        if sym in node.array_syms and sym not in node.out_syms:
            continue  # consumed arrays drop from the output
        data = jnp.repeat(v.data, cap, axis=0)
        valid = (None if v.valid is None
                 else jnp.repeat(v.valid, cap, axis=0))
        out[sym] = Val(v.dtype, data, valid, v.dictionary,
                       None if v.lengths is None
                       else jnp.repeat(v.lengths, cap, axis=0),
                       None if v.elem_valid is None
                       else jnp.repeat(v.elem_valid, cap, axis=0))
    j = jnp.tile(jnp.arange(cap, dtype=jnp.int32), n)
    for osym, asym in zip(node.out_syms, node.array_syms):
        a = dt.cols[asym]
        acap = a.data.shape[1]
        data2, em2 = a.data, a.elem_valid
        if acap != cap:  # re-pad to the common capacity
            data2 = jnp.pad(data2, [(0, 0), (0, cap - acap)])
            if em2 is not None:
                em2 = jnp.pad(em2, [(0, 0), (0, cap - acap)])
        flat = data2.reshape(n * cap)
        em = em2.reshape(n * cap) if em2 is not None else None
        within = j < jnp.repeat(a.lengths, cap)
        if a.valid is not None:
            within = within & jnp.repeat(a.valid, cap)
        valid = within if em is None else (within & em)
        out[osym] = Val(node.out_types[osym], flat, valid,
                        a.dictionary)
    if node.ordinality_sym:
        out[node.ordinality_sym] = Val(
            T.BIGINT, (j + 1).astype(jnp.int64), None)
    out_live = jnp.repeat(live, cap) & (j < jnp.repeat(zlen, cap))
    return DTable(out, out_live, n * cap)


def apply_mark_distinct(dt: DTable, node: N.MarkDistinct,
                        capacity: int) -> tuple:
    """Adds node.mark_symbol: true on the first live row of each
    distinct key tuple (reference MarkDistinctOperator.java; here one
    hash-slot assignment + a segment-min race for the first row)."""
    live = dt.live_mask()
    rh = _row_hash(dt, node.keys)
    key_ops = []
    for k in node.keys:
        v = dt.cols[k]
        if getattr(v.data, "ndim", 1) == 2:  # LONG decimal key
            khi, klo = _long_key_operands(v)
            key_ops.extend([khi, klo])
        else:
            key_ops.append(_group_key_operand(v))
        if v.valid is not None:
            key_ops.append(v.valid)
    sg = H.SortedGroups(rh, live, key_ops, len(key_ops))
    # is_new flags the first sorted row of each key run (stable sort ->
    # the smallest source index); a second sort keyed by the source row
    # index inverts the permutation without a scatter
    _, mark = jax.lax.sort((sg.sidx, sg.is_new), num_keys=1)
    cols = dict(dt.cols)
    cols[node.mark_symbol] = Val(T.BOOLEAN, mark, None, None)
    return DTable(cols, dt.live, dt.n), jnp.asarray(True)


def apply_distinct(dt: DTable, capacity: int) -> tuple:
    live = dt.live_mask()
    direct = _direct_group_ids(dt, list(dt.cols))
    if direct is not None:
        slots, capacity, sizes = direct
        occupancy = segred.segment_sum(
            live.astype(jnp.int32), slots, num_segments=capacity) > 0
        out = _decode_direct_keys(dt, list(dt.cols), sizes, capacity)
        return DTable(out, occupancy, capacity), jnp.asarray(True)
    rh = _row_hash(dt, list(dt.cols))
    payloads = []
    refs = []
    float_cols = []
    for sym, v in dt.cols.items():
        di = len(payloads)
        if getattr(v.data, "ndim", 1) == 2:  # LONG decimal key
            khi, klo = _long_key_operands(v)
            payloads.append(khi)
            payloads.append(klo)
            vi = None
            if v.valid is not None:
                vi = len(payloads)
                payloads.append(v.valid)
            refs.append((sym, v, ("long", di, di + 1), vi))
            continue
        payloads.append(_group_key_operand(v))
        vi = None
        if v.valid is not None:
            vi = len(payloads)
            payloads.append(v.valid)
        if jnp.issubdtype(v.data.dtype, jnp.floating):
            float_cols.append((sym, v, vi))
        else:
            refs.append((sym, v, di, vi))
    num_key_payloads = len(payloads)
    for sym, v, vi in float_cols:
        refs.append((sym, v, len(payloads), vi))
        payloads.append(v.data)
    sg = H.SortedGroups(rh, live, payloads, num_key_payloads)
    ok = sg.ngroups <= capacity
    compacted, occupied = sg.compact_first(sg.payloads, capacity)
    out = {}
    for sym, v, di, vi in refs:
        valid = None if vi is None else compacted[vi]
        if isinstance(di, tuple):  # LONG decimal limbs
            data = _unpack_long_key(compacted[di[1]], compacted[di[2]])
            out[sym] = Val(v.dtype, data, valid, v.dictionary)
            continue
        out[sym] = Val(v.dtype, compacted[di], valid, v.dictionary)
    return DTable(out, occupied, capacity), ok
