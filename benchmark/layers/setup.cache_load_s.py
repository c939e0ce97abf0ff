"""Seconds of set-up spent loading executables from JAX's persistent cache
(key, read, deserialisation): ``cache_load_s`` over the ``compile``
spans that closed before the window opened."""

from pathlib import Path

import verify

phase_seconds = verify.load_attr(
    Path(__file__).with_name("setup.unattributed_s.py"), "phase_seconds")


def read(ctx):
    return phase_seconds(ctx, "setup.cache_load_s", "cache_load_s")
