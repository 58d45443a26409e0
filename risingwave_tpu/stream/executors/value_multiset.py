"""The value multiset of one aggregate call, in the executor's memory.

A retractable MIN/MAX, a ``string_agg`` / ``array_agg`` and a DISTINCT
column each keep, per group, how often each input value is present
(aggregation/minput.rs and distinct.rs analog, value-multiset form).
The durable copy is a state table of ``(group keys..., value, _cnt)``
rows (``hash_agg.minput_state_schema``); its only writer is the
executor, so the executor keeps what it wrote: ``ValueMultiset`` is
that copy, ``group → {value → count}``. The table is written through
once a barrier and read only to fill this (recovery, cold-tier reload).
A DISTINCT column's table may hold a count per call
(``hash_agg.distinct_state_schema``); ``DistinctCounts`` is its copy,
``group → {value → counts}``.

Groups and values are the Python objects of the table's rows. A float
NaN is one SQL value and one key of the table, but no two NaN objects
are equal: ``pylist`` and ``load`` map every NaN to one object.
"""

from __future__ import annotations

from typing import Dict, Iterable, Iterator, List, Mapping

import numpy as np

_NAN = float("nan")
_NO_VALUES: Mapping = {}


def pylist(arr: np.ndarray) -> List:
    """A column's values as the objects the multiset keys on."""
    out = arr.tolist()
    if arr.dtype == object:
        return [x.item() if hasattr(x, "item") else x for x in out]
    if arr.dtype.kind == "f":
        for i in np.flatnonzero(np.isnan(arr)).tolist():
            out[i] = _NAN
    return out


def value_order(v):
    """Sort key of a group's values in the table's pk order: NULL
    first, then ascending (``state/keycodec.py``)."""
    return (v is not None, v)


class ValueMultiset:
    """``group → {value → count}``; a pair is present while its count
    is not zero."""

    __slots__ = ("_groups", "_pairs")
    zero = 0        # what an absent pair counts

    def __init__(self) -> None:
        self._groups: Dict[tuple, Dict[object, int]] = {}
        self._pairs = 0

    def __len__(self) -> int:
        """(group, value) pairs held: what the memory manager sizes."""
        return self._pairs

    def count(self, group: tuple, value):
        vals = self._groups.get(group)
        return self.zero if vals is None \
            else vals.get(value, self.zero)

    def put(self, group: tuple, value, cnt) -> None:
        vals = self._groups.get(group)
        if cnt == self.zero:
            if vals is not None and vals.pop(value, None) is not None:
                self._pairs -= 1
                if not vals:
                    del self._groups[group]
            return
        if vals is None:
            vals = self._groups[group] = {}
        if value not in vals:
            self._pairs += 1
        vals[value] = cnt

    def values(self, group: tuple) -> Mapping:
        """One group's ``value → count``, touching no other group."""
        return self._groups.get(group, _NO_VALUES)

    def load(self, rows: Iterable[tuple]) -> None:
        """Fill from the table's rows ``(group keys..., value, _cnt)``."""
        for row in rows:
            key = tuple(_NAN if v != v else v for v in row[:-1])
            self.put(key[:-1], key[-1], int(row[-1]))

    def drop_groups(self, groups: Iterable[tuple]) -> None:
        for g in groups:
            vals = self._groups.pop(g, None)
            if vals is not None:
                self._pairs -= len(vals)

    def cut_below(self, pos: int, phys) -> None:
        """Drop the groups a watermark on group column ``pos`` retired
        (NULL sorts outside every watermark and stays)."""
        self.drop_groups([g for g in self._groups
                          if g[pos] is not None and g[pos] < phys])

    def rows(self) -> Iterator[tuple]:
        """The rows the table holds for the groups in memory."""
        for g, vals in self._groups.items():
            for v, cnt in vals.items():
                yield g + (v, cnt)


class DistinctCounts(ValueMultiset):
    """The dedup state of one DISTINCT input column
    (aggregation/distinct.rs): ``group → {value → counts}``, one count
    per count column of the column's dedup table
    (``hash_agg.distinct_state_schema``), as a tuple. A pair is present
    while any of its counts is not zero."""

    __slots__ = ("width", "zero")

    def __init__(self, width: int = 1) -> None:
        super().__init__()
        self.width = width
        self.zero = (0,) * width

    def load(self, rows: Iterable[tuple]) -> None:
        """Fill from the table's rows ``(group keys..., value,
        counts...)``."""
        w = self.width
        for row in rows:
            key = tuple(_NAN if v != v else v for v in row[:-w])
            self.put(key[:-1], key[-1], tuple(int(c) for c in row[-w:]))

    def rows(self) -> Iterator[tuple]:
        for g, vals in self._groups.items():
            for v, counts in vals.items():
                yield g + (v,) + counts
