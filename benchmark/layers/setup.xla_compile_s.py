"""Seconds of set-up inside XLA's compiler: ``xla_s`` over the ``compile``
spans that closed before the window opened. 0 when every program came
out of JAX's persistent cache, the hundreds of seconds of a cold run."""

from pathlib import Path

import verify

phase_seconds = verify.load_attr(
    Path(__file__).with_name("setup.unattributed_s.py"), "phase_seconds")


def read(ctx):
    return phase_seconds(ctx, "setup.xla_compile_s", "xla_s")
