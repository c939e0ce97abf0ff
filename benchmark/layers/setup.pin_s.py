"""Seconds of set-up spent placing columns on the device: the ``pin``
spans (``engine.device_array``, one chip) and the ``shard-pin`` spans
(``parallel/pins.py``, a mesh) that closed before the window opened. A
``pin`` times the host's side of ``jax.device_put``, which returns
before the copy has landed: the wait for it shows in the first
``execute``. A ``shard-pin`` waits for its transfers, so on a mesh this
reads ``mesh.shard_pin_s`` plus the replicated pins."""

from pathlib import Path

import verify

closed_before_t0 = verify.load_attr(
    Path(__file__).with_name("setup.unattributed_s.py"), "closed_before_t0")


def read(ctx):
    pins = closed_before_t0(ctx, "setup.pin_s", ("pin", "shard-pin"))
    return None if pins is None else sum(s["t1"] - s["t0"] for s in pins)
