"""Chip smoke: the served path, end to end, on one TPU chip.

    python chip_smoke.py [--sf 1] [--seed 19920101]

One process, no child that needs the device. It builds TPC-H at
``--sf`` from ``--seed`` (the connector's own generator), starts a
``CoordinatorServer`` over an ``Engine`` and drives it through
``/v1/statement`` with the protocol client: Q6, Q1 and Q3, a literal
variant of each (which must compile nothing), a CTAS into the memory
catalog read back, and an INSERT after which the same SELECT must
answer differently. Every answer is compared EXACTLY with plain NumPy
over the same generated columns (int64 scaled decimals, written here,
independent of presto_tpu/exec). With four or more devices Q1 and the
engine's all_to_all exchange step then run over a four-device mesh (Q3
over the mesh is owed: its one program compiles for longer than this
script may run).

There is no CPU fallback: the script exits non-zero unless
``jax.default_backend()`` is ``tpu``, and any step that fails raises.
Walls it prints are observations from one run, not metrics. The last
stdout line of a passing run is one JSON object
``{"ok": true, "device": {...}}``. tests/test_chip_smoke.py runs the
same steps on the CPU at SF 0.01.
"""

from __future__ import annotations

import argparse
import json
import sys
import time

import numpy as np

# literal variants ("old" -> "new" textual swap): same query shape,
# different constants — the plan-template path must reuse the program
Q1_CUTOFF = ("interval '90' day", "interval '60' day")
Q6_YEAR = ("date '1994-01-01'", "date '1995-01-01'")
Q3_DATE = ("date '1995-03-15'", "date '1995-03-22'")

SMOKE_TABLE = "memory.default.smoke_orders"
SMOKE_COLS = "o_orderpriority, o_totalprice, o_orderkey"
CTAS_BEFORE, INSERT_FROM = "1993-01-01", "1998-01-01"
CTAS_PRED = f"o_orderdate < date '{CTAS_BEFORE}'"
INSERT_PRED = f"o_orderdate >= date '{INSERT_FROM}'"
SMOKE_SELECT = (
    "select o_orderpriority, count(*) as n, sum(o_totalprice) as total "
    f"from {SMOKE_TABLE} group by o_orderpriority "
    "order by o_orderpriority")

# lineitem columns Q1, Q6 and Q3 pin on the device between them
PINNED_LINEITEM = ("l_shipdate", "l_returnflag", "l_linestatus",
                   "l_quantity", "l_extendedprice", "l_discount",
                   "l_tax", "l_orderkey")


class SmokeFailure(RuntimeError):
    """A smoke step's check did not hold."""


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def say(msg: str) -> None:
    print(msg, flush=True)


# -- device -----------------------------------------------------------------

def device_stamp() -> dict:
    """Print what JAX runs on; return the device as JAX reports it."""
    import jax
    import jaxlib
    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    from importlib import metadata
    try:
        libtpu = metadata.version("libtpu")
    except metadata.PackageNotFoundError:
        libtpu = "absent"
    say(f"[device] backend={jax.default_backend()} "
        f"kind={dev['kind']!r} count={dev['count']} "
        f"jax={jax.__version__} jaxlib={jaxlib.__version__} "
        f"libtpu={libtpu}")
    say(f"[device] compile cache dir="
        f"{jax.config.jax_compilation_cache_dir}")
    return dev


class CacheEvents:
    """Counts JAX's persistent compilation-cache hits and misses."""

    HIT = "/jax/compilation_cache/cache_hits"
    MISS = "/jax/compilation_cache/cache_misses"

    def __init__(self):
        import jax.monitoring
        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event: str, **_kw) -> None:
        if event == self.HIT:
            self.hits += 1
        elif event == self.MISS:
            self.misses += 1


# -- plain NumPy reference ----------------------------------------------------
# Exact integer arithmetic over the connector's generated columns:
# decimals are scaled int64 (cents), dates are days since 1970-01-01,
# strings are (codes, sorted dictionary). Nothing here touches
# presto_tpu/exec or JAX.

def _days(date: str) -> int:
    return int((np.datetime64(date) - np.datetime64("1970-01-01"))
               .astype(int))


def _date_str(days: int) -> str:
    return str(np.datetime64("1970-01-01") + int(days))


def _isum(x: np.ndarray) -> int:
    """Exact sum of an int64 array as a Python int (two 32-bit limbs,
    so no partial sum can wrap)."""
    x = np.asarray(x, dtype=np.int64)
    return ((int(np.sum(x >> 32, dtype=np.int64)) << 32)
            + int(np.sum(x & 0xFFFFFFFF, dtype=np.int64)))


def _dec(v: int, scale: int) -> str:
    """Scaled integer -> the decimal text the protocol returns."""
    sign = "-" if v < 0 else ""
    q, r = divmod(abs(int(v)), 10 ** scale)
    return f"{sign}{q}.{r:0{scale}d}"


def _avg_half_up(total: int, count: int) -> int:
    """SQL decimal avg: HALF_UP division in the scaled domain."""
    sign = -1 if total < 0 else 1
    return sign * ((2 * abs(total) + count) // (2 * count))


def _col(conn, table: str, name: str) -> np.ndarray:
    return np.asarray(conn.table(table).columns[name].data)


def _dictionary(conn, table: str, name: str) -> np.ndarray:
    return conn.table(table).columns[name].dictionary


def ref_q1(conn, cutoff: str) -> list[list]:
    ship = _col(conn, "lineitem", "l_shipdate")
    rf = _col(conn, "lineitem", "l_returnflag").astype(np.int64)
    ls = _col(conn, "lineitem", "l_linestatus").astype(np.int64)
    rf_d = _dictionary(conn, "lineitem", "l_returnflag")
    ls_d = _dictionary(conn, "lineitem", "l_linestatus")
    qty = _col(conn, "lineitem", "l_quantity")
    price = _col(conn, "lineitem", "l_extendedprice")
    disc = _col(conn, "lineitem", "l_discount")
    tax = _col(conn, "lineitem", "l_tax")
    keep = ship <= _days(cutoff)
    gid = rf * len(ls_d) + ls  # code order == collation order
    rows = []
    for g in np.unique(gid[keep]):
        sel = keep & (gid == g)
        n = int(sel.sum())
        p, d = price[sel], disc[sel]
        disc_price = p * (100 - d)
        s_qty, s_base = _isum(qty[sel]), _isum(p)
        rows.append([
            str(rf_d[g // len(ls_d)]), str(ls_d[g % len(ls_d)]),
            _dec(s_qty, 2), _dec(s_base, 2),
            _dec(_isum(disc_price), 4),
            _dec(_isum(disc_price * (100 + tax[sel])), 6),
            _dec(_avg_half_up(s_qty, n), 2),
            _dec(_avg_half_up(s_base, n), 2),
            _dec(_avg_half_up(_isum(d), n), 2), n])
    return rows


def ref_q6(conn, year_start: str) -> list[list]:
    ship = _col(conn, "lineitem", "l_shipdate")
    qty = _col(conn, "lineitem", "l_quantity")
    price = _col(conn, "lineitem", "l_extendedprice")
    disc = _col(conn, "lineitem", "l_discount")
    lo = np.datetime64(year_start)
    hi = (lo.astype("datetime64[Y]") + 1).astype("datetime64[D]")
    keep = ((ship >= _days(str(lo))) & (ship < _days(str(hi)))
            & (disc >= 5) & (disc <= 7) & (qty < 2400))
    return [[_dec(_isum(price[keep] * disc[keep]), 4)]]


def ref_q3(conn, date: str) -> list[list]:
    day = _days(date)
    seg = _col(conn, "customer", "c_mktsegment")
    seg_d = _dictionary(conn, "customer", "c_mktsegment")
    building = int(np.flatnonzero(seg_d == "BUILDING")[0])
    custkeys = _col(conn, "customer", "c_custkey")[seg == building]
    okey = _col(conn, "orders", "o_orderkey")
    odate = _col(conn, "orders", "o_orderdate")
    oprio = _col(conn, "orders", "o_shippriority")
    okeep = (odate < day) & np.isin(_col(conn, "orders", "o_custkey"),
                                    custkeys)
    lkey = _col(conn, "lineitem", "l_orderkey")
    lkeep = ((_col(conn, "lineitem", "l_shipdate") > day)
             & np.isin(lkey, okey[okeep]))
    keys = lkey[lkeep]
    rev = (_col(conn, "lineitem", "l_extendedprice")[lkeep]
           * (100 - _col(conn, "lineitem", "l_discount")[lkeep]))
    order = np.argsort(keys, kind="stable")
    keys, rev = keys[order], rev[order]
    starts = np.flatnonzero(np.r_[True, keys[1:] != keys[:-1]])
    # an order has at most 7 lines: group sums stay far inside int64
    sums = np.add.reduceat(rev, starts) if len(starts) else rev[:0]
    gkeys = keys[starts]
    by_key = np.argsort(okey, kind="stable")
    oidx = by_key[np.searchsorted(okey[by_key], gkeys)]
    top = np.lexsort((odate[oidx], -sums))[:10]
    return [[int(gkeys[i]), _dec(int(sums[i]), 4),
             _date_str(odate[oidx[i]]), int(oprio[oidx[i]])]
            for i in top]


def ref_smoke_select(conn, keep: np.ndarray) -> list[list]:
    prio = _col(conn, "orders", "o_orderpriority")
    prio_d = _dictionary(conn, "orders", "o_orderpriority")
    total = _col(conn, "orders", "o_totalprice")
    rows = []
    for code in np.unique(prio[keep]):
        sel = keep & (prio == code)
        rows.append([str(prio_d[code]), int(sel.sum()),
                     _dec(_isum(total[sel]), 2)])
    return rows


def pinned_lineitem_bytes(conn) -> int:
    return sum(_col(conn, "lineitem", c).nbytes for c in PINNED_LINEITEM)


# -- served leg ---------------------------------------------------------------

def build_engine(sf: float, seed: int):
    """(engine, tpch connector) with the data generated on the host."""
    from presto_tpu import Engine
    from presto_tpu.connectors.memory import MemoryConnector
    from presto_tpu.connectors.tpch import TpchConnector
    t0 = time.perf_counter()
    conn = TpchConnector(scale=sf, seed=seed)
    rows = {t: conn.table(t).nrows
            for t in ("customer", "orders", "lineitem")}
    say(f"[datagen] sf={sf:g} seed={seed} rows={rows} "
        f"wall={time.perf_counter() - t0:.1f}s")
    engine = Engine()
    engine.register_catalog("tpch", conn)
    engine.register_catalog("memory", MemoryConnector())
    return engine, conn


def served_leg(engine, conn) -> None:
    """Drive the coordinator over HTTP and hold every answer to the
    NumPy reference. Session properties stay at their defaults."""
    from presto_tpu.client import Client
    from presto_tpu.obs.metrics import REGISTRY
    from presto_tpu.server.server import CoordinatorServer
    from tests.tpch_queries import QUERIES

    compiled = REGISTRY.counter("presto_tpu_programs_compiled_total")
    # trace, lowering, XLA compile and cache load together
    compile_s = REGISTRY.counter("presto_tpu_jax_compile_seconds_total")

    # references first, outside any timed region
    t0 = time.perf_counter()
    want = {
        "q06": ref_q6(conn, "1994-01-01"),
        "q06 variant": ref_q6(conn, "1995-01-01"),
        "q01": ref_q1(conn, "1998-09-02"),   # 1998-12-01 - 90 days
        "q01 variant": ref_q1(conn, "1998-10-02"),  # - 60 days
        "q03": ref_q3(conn, "1995-03-15"),
        "q03 variant": ref_q3(conn, "1995-03-22"),
    }
    odate = _col(conn, "orders", "o_orderdate")
    ctas_keep = odate < _days(CTAS_BEFORE)
    both_keep = ctas_keep | (odate >= _days(INSERT_FROM))
    want["select after ctas"] = ref_smoke_select(conn, ctas_keep)
    want["select after insert"] = ref_smoke_select(conn, both_keep)
    say(f"[reference] numpy answers wall={time.perf_counter() - t0:.1f}s")

    server = CoordinatorServer(engine).start()
    try:
        client = Client(server.uri)

        def run(label: str, sql: str, expect_compiles: str) -> list:
            c0, s0 = compiled.value(), compile_s.total()
            t = time.perf_counter()
            _cols, rows = client.execute(sql)
            wall = time.perf_counter() - t
            n = int(compiled.value() - c0)
            say(f"[served] {label}: rows={len(rows)} wall={wall:.3f}s "
                f"programs_compiled={n} "
                f"compile={compile_s.total() - s0:.1f}s")
            if expect_compiles == "some":
                check(n >= 1, f"{label}: first execution compiled no "
                              f"program")
            elif expect_compiles == "none":
                check(n == 0, f"{label}: compiled {n} programs, a "
                              f"literal variant must compile none")
            if label in want:
                check(rows == want[label],
                      f"{label}: answer differs from the NumPy "
                      f"reference\n  got  {rows[:3]}\n  want "
                      f"{want[label][:3]}")
                say(f"[served] {label}: equals the NumPy reference "
                    f"exactly")
            return rows

        for name, (old, new) in (("q06", Q6_YEAR), ("q01", Q1_CUTOFF),
                                 ("q03", Q3_DATE)):
            sql = QUERIES[name]
            check(old in sql, f"{name}: literal {old!r} not in query")
            run(name, sql, "some")
            # the identical text again: answered by the result cache
            check(run(f"{name} repeat", sql, "any") == want[name],
                  f"{name} repeat: answer changed")
            run(f"{name} variant", sql.replace(old, new), "none")

        n_ctas = int(ctas_keep.sum())
        rows = run("ctas", f"create table {SMOKE_TABLE} as select "
                   f"{SMOKE_COLS} from orders where {CTAS_PRED}", "any")
        check(rows == [[n_ctas]], f"ctas wrote {rows}, want {n_ctas}")
        run("select after ctas", SMOKE_SELECT, "some")
        n_ins = int(both_keep.sum()) - n_ctas
        rows = run("insert", f"insert into {SMOKE_TABLE} select "
                   f"{SMOKE_COLS} from orders where {INSERT_PRED}",
                   "any")
        check(rows == [[n_ins]], f"insert wrote {rows}, want {n_ins}")
        # same text, new table version: the result cache must miss
        check(want["select after insert"] != want["select after ctas"],
              "insert does not change the reference answer")
        run("select after insert", SMOKE_SELECT, "any")
    finally:
        server.stop()


def check_device_memory(conn) -> None:
    """The device held at least the lineitem columns the queries pin."""
    import jax
    stats = jax.devices()[0].memory_stats()
    check(bool(stats) and "peak_bytes_in_use" in stats,
          "the device reports no peak_bytes_in_use")
    peak, pinned = stats["peak_bytes_in_use"], pinned_lineitem_bytes(conn)
    say(f"[memory] peak_bytes_in_use={peak} pinned lineitem columns="
        f"{pinned}")
    check(peak >= pinned, f"peak device memory {peak} is below the "
                          f"{pinned} bytes of pinned lineitem columns")


# -- mesh leg -----------------------------------------------------------------

COLLECTIVES = ("all_to_all", "all_gather", "all_reduce",
               "all-to-all", "all-gather", "all-reduce")


def mesh_leg(engine, conn, devices) -> None:
    """Q1 over a four-device mesh at the smoke's scale: the one-device
    answer, a four-partition program with collectives in it, bytes
    resident on every device. Then the engine's FIXED_HASH exchange
    alone over the same lineitem (``__graft_entry__.exchange_step``:
    partial aggregate -> all_to_all -> final aggregate, plus a psum),
    held to the host's counts — Q1's plan gathers, so this is the
    leg's all_to_all. Q3 over the mesh is NOT run; the leg says so."""
    from jax.sharding import Mesh

    import __graft_entry__
    from tests.tpch_queries import QUERIES
    check(len(devices) >= 4, "mesh leg needs four devices")
    devices = list(devices[:4])
    mesh = Mesh(np.array(devices), ("d",))
    local = engine.execute(QUERIES["q01"])
    t0 = time.perf_counter()
    dist = engine.execute(QUERIES["q01"], mesh=mesh)
    wall = time.perf_counter() - t0
    check(dist == local, "mesh q01: answer differs from the one-device "
                         "answer")
    hlo = engine.last_dist_hlo
    check("num_partitions = 4" in hlo,
          "mesh q01: program is not four-partition")
    found = sorted({c for c in COLLECTIVES if c in hlo})
    check(bool(found), "mesh q01: no collective in the program")
    say(f"[mesh] q01: equals one-device answer; first wall={wall:.1f}s "
        f"collectives={found}")
    t0 = time.perf_counter()
    text = __graft_entry__.exchange_step(4, conn)  # raises on a mismatch
    found = sorted({c for c in COLLECTIVES if c in text})
    check("all_to_all" in found,
          "mesh exchange step: no all_to_all in the program")
    say(f"[mesh] exchange step: row count, group count and psum total "
        f"equal the host's; wall={time.perf_counter() - t0:.1f}s "
        f"collectives={found}")
    stats = [d.memory_stats() for d in devices]
    if all(s and "peak_bytes_in_use" in s for s in stats):
        peaks = [s["peak_bytes_in_use"] for s in stats]
        say(f"[mesh] peak_bytes_in_use per device={peaks}")
        check(all(p > 0 for p in peaks),
              "a mesh device held no bytes: inputs were not spread "
              "over four devices")
    else:
        say("[mesh] backend reports no per-device memory stats")
    say("[mesh] q03 over the mesh: NOT RUN, owed. Its one shard_map "
        "program holds all of Q3's sorts; libtpu took 1436 s to compile "
        "it for four v5e chips off the chip (PERF.md section 7), which "
        "with the rest of this script is past its 1200 s limit")


# -- entry --------------------------------------------------------------------

def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--sf", type=float, default=1.0,
                    help="TPC-H scale factor (default 1)")
    ap.add_argument("--seed", type=int, default=19920101,
                    help="datagen seed (the connector's default)")
    args = ap.parse_args(argv)

    import jax

    import presto_tpu  # noqa: F401 - x64 + compile cache before arrays
    dev = device_stamp()
    if jax.default_backend() != "tpu":
        print(f"chip_smoke: backend is {jax.default_backend()!r}, not "
              f"'tpu'; there is no CPU fallback", file=sys.stderr)
        return 1
    events = CacheEvents()
    t_start = time.perf_counter()
    engine, conn = build_engine(args.sf, args.seed)
    served_leg(engine, conn)
    check_device_memory(conn)
    if len(jax.devices()) >= 4:
        mesh_leg(engine, conn, jax.devices())
    else:
        say(f"[mesh] skipped: {len(jax.devices())} device(s) visible, "
            f"the leg needs 4")
    say(f"[cache] persistent compile cache hits={events.hits} "
        f"misses={events.misses}")
    say(f"[done] total wall={time.perf_counter() - t_start:.1f}s")
    print(json.dumps({"ok": True, "device": dev}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
