"""Seconds set-up spent placing scanned columns on the mesh. A counter
of the program (``source: program_counter``), not its spans: the
program's span store keeps the last 256 statements and the placements
are made by the warm-up's first statement, hundreds of statements
before a reader runs, so ``parallel/pins.ShardPins.get`` adds each
``shard-pin`` span's seconds (a column's slicing, its transfers to
every device and the wait for them) to the histogram
``presto_tpu_shard_pin_seconds`` as the span closes, and this reads
what of its ``_sum`` was there when the window opened. None where the
program has no such histogram (no mesh path, or nothing was placed)."""


def read(ctx):
    from presto_tpu.obs.metrics import REGISTRY
    name = "presto_tpu_shard_pin_seconds_sum"
    total = None
    for line in REGISTRY.render().splitlines():
        if line.split("{", 1)[0].split(" ", 1)[0] == name:
            total = (total or 0.0) + float(line.rpartition(" ")[2])
    if total is None:
        return None
    return total - ctx.counters.get(name, 0.0)
