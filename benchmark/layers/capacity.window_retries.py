"""Capacity rungs climbed inside the window (a count): window delta of
``presto_tpu_capacity_overflow_retries_total`` summed over its
``operator`` label (``ops/hash.note_capacity_retry``: a group table,
join table or join output that overflowed and was grown, so its program
ran again at the larger size; since PR 32 also ``segment``, a hand-over
whose rows outgrew the width the programs downstream were compiled
for). 0 where set-up left every capacity the traffic needs in the
program's memory; each one is a compile where the persistent cache is
off. A counter without a sample has climbed nothing."""


def read(ctx):
    return ctx.counters.get(
        "presto_tpu_capacity_overflow_retries_total", 0.0)
