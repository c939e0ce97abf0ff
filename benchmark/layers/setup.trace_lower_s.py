"""Seconds of set-up spent walking plans in Python and lowering them:
``trace_s`` + ``lower_s`` over the ``compile`` spans that closed before
the window opened. Paid warm and cold alike: a program that comes out of
JAX's persistent cache is traced and lowered first, to make its key."""

from pathlib import Path

import verify

phase_seconds = verify.load_attr(
    Path(__file__).with_name("setup.unattributed_s.py"), "phase_seconds")


def read(ctx):
    return phase_seconds(ctx, "setup.trace_lower_s", "trace_s", "lower_s")
