"""Table bytes moved from the host per statement of the window: window
delta of ``presto_tpu_shard_pin_bytes_total`` (``parallel/pins.py``: the
bytes of every column placed on the mesh, padding included) over the
good statements answered in it. 0 while the table lives on the chips:
set-up placed every column the classes read, and a statement over a
table version the mesh holds places nothing. Anything else says the
table is being moved again. None where the program has no such counter
(no sharded pins, or nothing was ever placed)."""

import arith


def read(ctx):
    moved = ctx.counters.get("presto_tpu_shard_pin_bytes_total")
    ran = len(arith.good(ctx.records))
    return moved / ran if moved is not None and ran else None
