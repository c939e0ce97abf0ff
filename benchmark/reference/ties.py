"""An ordered answer whose ORDER BY leaves some rows' order open.

TPC-H Q10 orders by revenue alone and Q18 by ``o_totalprice`` and
``o_orderdate``: rows that tie on those keys may come back in any order,
and where a tie straddles the LIMIT any of its rows may fill the last
places. ``verify.check`` compares an ordered class with ``got == want``;
``want`` here is a list that compares each run of tied rows as a set, so
the reference and the comparison agree on ties without either guessing
the engine's order. (A subclass's ``__eq__`` is asked first, whichever
side of ``==`` it stands on.) An answer without ties is a plain list.
"""

from __future__ import annotations

import json


def _key(row) -> str:
    return json.dumps(row)


class TiedRows(list):
    """``rows`` in one admissible order, and ``runs``: (start, stop,
    candidates) for every run of tied places; the rows a correct answer
    has at ``[start, stop)`` are distinct members of ``candidates``."""

    def __init__(self, rows, runs):
        super().__init__(rows)
        self.runs = runs

    def __eq__(self, other):
        if not isinstance(other, list) or len(other) != len(self):
            return False
        open_places = set()
        for start, stop, candidates in self.runs:
            got = [_key(r) for r in other[start:stop]]
            allowed = {_key(r) for r in candidates}
            if len(set(got)) != len(got) or not set(got) <= allowed:
                return False
            open_places.update(range(start, stop))
        return all(a == b for i, (a, b) in enumerate(zip(self, other))
                   if i not in open_places)

    def __ne__(self, other):
        return not self.__eq__(other)

    __hash__ = None


def first(keys: list, limit: int, row) -> list:
    """The first ``limit`` of ``len(keys)`` rows already in their final
    order: ``keys[i]`` is row i's ORDER BY key, ``row(i)`` builds it.
    Rows of one key form a run whose order is open."""
    n = min(limit, len(keys))
    rows = [row(i) for i in range(n)]
    runs = []
    i = 0
    while i < n:
        j = i + 1
        while j < len(keys) and keys[j] == keys[i]:
            j += 1
        if j - i > 1:
            stop = min(j, n)
            runs.append((i, stop, rows[i:stop]
                         + [row(k) for k in range(stop, j)]))
        i = j
    return TiedRows(rows, runs) if runs else rows
