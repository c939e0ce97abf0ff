"""Columns bit-packed into 32-bit words for output-width gathers.

A gather at a many-to-many join's output width costs one random-access
pass for each 32-bit word it moves, whatever the word holds: a BIGINT
column is two passes, its validity mask one more. A column whose live
values lie in a known range [lo, hi] needs only ceil(log2(hi - lo + 1))
bits, its offset from lo, and one more for its validity where it is
nullable; so several columns share one word. They are packed at the
input's width, the words are gathered, and the columns are rebuilt at
the output's width with the dtypes they had. The ranges are estimates
(plan/dense.expand_layout), so :func:`pack` also says whether every
live value lies in its field's range; where one does not, the caller
moves the columns unpacked (exec/operators.apply_expand_join).
"""

from __future__ import annotations

import dataclasses

import jax.numpy as jnp
import numpy as np

WORD_BITS = 32


@dataclasses.dataclass(frozen=True)
class Field:
    """One column's place in the words: ``bits`` of offset from ``lo``
    at ``shift`` of word ``word``, and above them its validity bit
    where it is ``nullable``. A field of no width (one value, never
    NULL) takes no word (``word`` -1)."""

    sym: str
    lo: int
    hi: int
    bits: int
    nullable: bool
    word: int = -1
    shift: int = 0

    @property
    def width(self) -> int:
        return self.bits + int(self.nullable)


def fields_of(cols: dict, layout) -> list[Field]:
    """The packable columns of ``layout``'s (symbol, lo, hi) among
    ``cols`` (symbol -> expr.compile.Val): an integer column with a
    stated range, or a dictionary-coded string (codes 0 .. size - 1;
    the dictionary is a constant of the program), that fits one word
    with its validity bit. A float, a LONG decimal, an array, a
    column with no range or a wider one is not."""
    out = []
    for sym, lo, hi in layout:
        v = cols.get(sym)
        if v is None or v.data.ndim != 1:
            continue
        if lo is None:
            if not v.is_string or v.dictionary is None:
                continue
            lo, hi = 0, max(len(v.dictionary) - 1, 0)
        f = Field(sym, lo, hi, (hi - lo).bit_length(), v.valid is not None)
        if f.width <= WORD_BITS:
            out.append(f)
    return out


def assign_words(fields: list[Field]) -> tuple[list[Field], int]:
    """Place the fields in words, first fit, widest first (ties in
    ``fields``' order). Returns (the placed fields, the word count)."""
    room: list[int] = []
    placed = []
    for f in sorted(fields, key=lambda f: -f.width):
        if not f.width:
            placed.append(f)
            continue
        w = next((i for i, r in enumerate(room) if r >= f.width), None)
        if w is None:
            w = len(room)
            room.append(WORD_BITS)
        placed.append(dataclasses.replace(
            f, word=w, shift=WORD_BITS - room[w]))
        room[w] -= f.width
    return placed, len(room)


def _mask(bits: int):
    return np.uint32((1 << bits) - 1)


def pack(cols: dict, fields: list[Field], nwords: int, live):
    """The words (uint32 [n] each) of the placed ``fields`` of ``cols``,
    and a flag that is False where a ``live``, non-NULL value lies
    outside its field's [lo, hi]. A NULL packs as offset 0."""
    words = [jnp.zeros(live.shape, jnp.uint32) for _ in range(nwords)]
    ok = jnp.asarray(True)
    for f in fields:
        v = cols[f.sym]
        data = v.data.astype(jnp.int64)
        checked = live if v.valid is None else (live & v.valid)
        ok = ok & jnp.all(~checked | ((data >= f.lo) & (data <= f.hi)))
        if f.word < 0:
            continue
        part = jnp.zeros(live.shape, jnp.uint32)
        if f.bits:
            off = data - f.lo
            if v.valid is not None:
                off = jnp.where(v.valid, off, 0)
            part = (off.astype(jnp.uint32) & _mask(f.bits)) << f.shift
        if f.nullable:
            part = part | (v.valid.astype(jnp.uint32)
                           << (f.shift + f.bits))
        words[f.word] = words[f.word] | part
    return words, ok


def unpack(words: list, f: Field, dtype, n: int):
    """(data of ``dtype`` [n], validity or None) of field ``f`` from
    its gathered words."""
    if f.word < 0:
        return jnp.full((n,), f.lo, dtype), None
    w = words[f.word]
    off = (w >> f.shift) & _mask(f.bits) if f.bits \
        else jnp.zeros((n,), jnp.uint32)
    data = (off.astype(jnp.int64) + f.lo).astype(dtype)
    valid = (((w >> (f.shift + f.bits)) & np.uint32(1)).astype(bool)
             if f.nullable else None)
    return data, valid


def column_words(v) -> int:
    """Words a gather of column ``v`` moves a row unpacked: its data's
    32-bit words (at least one: a bool mask is a pass of its own) and
    one for its validity mask."""
    per_row = v.data.dtype.itemsize * int(np.prod(v.data.shape[1:]))
    return max(1, per_row // 4) + int(v.valid is not None)
