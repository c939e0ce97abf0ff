"""A query class's share of its memory roofline: the bytes the class
must read (shapes.must_read_bytes) over the chip's peak bytes/s, as a
percentage of the device-busy time of one statement; closed loops only.
One reader for every ``<class>_roofline``: the harness hands it the
class that the metric's name holds."""

import shapes


def read(ctx, cls):
    return shapes.roofline_pct(ctx, cls)
