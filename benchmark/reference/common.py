"""Exact integer arithmetic shared by the NumPy references.

Copied from ``chip_smoke.py`` (PR 21): decimals are scaled int64 (cents),
dates are days since 1970-01-01, strings are (codes, sorted dictionary).
Nothing under ``reference/`` touches ``presto_tpu/exec`` or JAX.
"""

from __future__ import annotations

import numpy as np


def days(date: str) -> int:
    return int((np.datetime64(date) - np.datetime64("1970-01-01"))
               .astype(int))


def date_str(d: int) -> str:
    return str(np.datetime64("1970-01-01") + int(d))


def isum(x: np.ndarray) -> int:
    """Exact sum of an int64 array as a Python int (two 32-bit limbs, so
    no partial sum can wrap)."""
    x = np.asarray(x, dtype=np.int64)
    return ((int(np.sum(x >> 32, dtype=np.int64)) << 32)
            + int(np.sum(x & 0xFFFFFFFF, dtype=np.int64)))


def dec(v: int, scale: int) -> str:
    """Scaled integer -> the decimal text the protocol returns."""
    sign = "-" if v < 0 else ""
    q, r = divmod(abs(int(v)), 10 ** scale)
    return f"{sign}{q}.{r:0{scale}d}"


def avg_half_up(total: int, count: int) -> int:
    """SQL decimal avg: HALF_UP division in the scaled domain."""
    sign = -1 if total < 0 else 1
    return sign * ((2 * abs(total) + count) // (2 * count))


def cents(text: str) -> int:
    """'0.05' -> 5, '24' -> 2400: a decimal literal at scale 2."""
    whole, _, frac = text.partition(".")
    return int(whole) * 100 + int((frac + "00")[:2])
