"""What a NumPy reference may read: the generated host columns."""

from __future__ import annotations

import numpy as np


class Columns:
    """The connector's generated columns as NumPy arrays (the data the
    engine was given), with a ``memo`` a reference may keep sums in."""

    def __init__(self, conn):
        self._conn = conn
        self.memo: dict = {}

    def col(self, table: str, name: str) -> np.ndarray:
        return np.asarray(self._conn.table(table).columns[name].data)

    def dictionary(self, table: str, name: str) -> np.ndarray:
        return self._conn.table(table).columns[name].dictionary
