"""Row tags and the filters that restrict a search to them.

A filtered search (the big-ann-benchmarks NeurIPS'23 filtered track)
admits a row when the row carries every tag of the request's filter: one
tag, or the AND of two.  An index keeps its rows' tags twice:

* on the device, a (capacity, T) int32 tag table, each row's tags sorted
  and INVALID-padded, which the beam reads to admit its candidates
  (``core/beam.py::admitted``);
* on the host, a :class:`TagIndex`: each tag's posting list (the rows that
  carry it) and the rows each filter admits, which serving counts to
  refuse a filter that admits fewer than k rows and to choose a request's
  route (``serving/buckets.py::route``).

Tags enter as a CSR list per row, ``(indptr, indices)``: the tags of row
``i`` are ``indices[indptr[i]:indptr[i + 1]]``.
"""
from __future__ import annotations

import threading

import numpy as np

from .graph import INVALID

#: the most tags a filter may AND; filter operands are this wide, so one
#: compiled program serves one- and two-tag filters alike
MAX_FILTER_TAGS = 2


def tag_rows(indptr, indices, slots: int) -> np.ndarray:
    """A CSR tag list -> (rows, slots) int32, each row's distinct tags
    sorted ascending and INVALID-padded."""
    indptr = np.asarray(indptr, np.int64)
    indices = np.asarray(indices, np.int64)
    counts = np.diff(indptr)
    if len(indices) != (indptr[-1] if len(indptr) else 0) or (counts < 0
                                                              ).any():
        raise ValueError("tags must be a CSR list (indptr, indices)")
    if len(indices) and (indices.min() < 0
                         or indices.max() >= np.iinfo(np.int32).max):
        raise ValueError("tag ids must be non-negative int32 values")
    if counts.max(initial=0) > slots:
        raise ValueError(f"a row carries {counts.max()} tags; the tag "
                         f"table holds {slots} per row")
    n = len(counts)
    top = np.iinfo(np.int32).max
    key = np.full((n, slots), top, np.int64)
    row = np.repeat(np.arange(n), counts)
    key[row, np.arange(len(indices)) - np.repeat(indptr[:-1], counts)] = \
        indices
    key.sort(axis=1)
    key[:, 1:][key[:, 1:] == key[:, :-1]] = top      # a repeated tag
    key.sort(axis=1)
    return np.where(key == top, INVALID, key).astype(np.int32)


def normalize_filter(filt) -> tuple[int, ...]:
    """A filter as a sorted tuple of 1 to :data:`MAX_FILTER_TAGS`
    distinct non-negative tag ids; ``ValueError`` otherwise."""
    try:
        tags = tuple(sorted({int(t) for t in np.atleast_1d(filt)}))
    except (TypeError, ValueError):
        raise ValueError(f"a filter is a tuple of tag ids, got {filt!r}"
                         ) from None
    if not 1 <= len(tags) <= MAX_FILTER_TAGS or tags[0] < 0:
        raise ValueError(f"a filter ANDs 1 to {MAX_FILTER_TAGS} "
                         f"non-negative tag ids, got {filt!r}")
    return tags


class TagIndex:
    """The host side of a tag table's first ``n`` rows: the posting list
    of each tag and the rows each filter admits.  Immutable: a change of
    the tags makes a new one, so a published epoch may hold it."""

    #: a two-tag filter's count of admitted rows is kept, and its rows too
    #: where it admits at most CACHED_FILTER_ROWS (the few the scan route
    #: reads), up to CACHED_ROWS rows in all; a one-tag filter's rows are a
    #: slice of the posting lists
    CACHED_FILTER_ROWS = 1 << 12
    CACHED_ROWS = 1 << 22
    #: a tag whose posting is longer than this checks a row against a
    #: mask of its posting (one byte a row, made once), not the row's tags
    MASKED_POSTING = 256

    def __init__(self, table: np.ndarray):
        self.table = table                      # (n, T) int32
        n, t = table.shape
        flat = table.ravel()
        ok = flat != INVALID
        tags = flat[ok]
        order = np.argsort(tags, kind="stable")  # rows ascend in a tag
        self._rows = np.repeat(np.arange(n, dtype=np.int32), t)[ok][order]
        self._tags, self._start, counts = np.unique(
            tags[order], return_index=True, return_counts=True)
        self._end = self._start + counts
        self._cache: dict = {}
        self._cached = 0
        self._counts: dict = {}
        self._masks: dict = {}
        self._lock = threading.Lock()       # submit and dispatch threads

    def posting(self, tag: int) -> np.ndarray:
        """The rows that carry ``tag``, ascending."""
        i = int(np.searchsorted(self._tags, tag))
        if i < len(self._tags) and self._tags[i] == tag:
            return self._rows[self._start[i]: self._end[i]]
        return self._rows[:0]

    def count(self, filt) -> int:
        """How many rows ``filt`` admits."""
        filt = normalize_filter(filt)
        if len(filt) == 1:
            return len(self.posting(filt[0]))
        got = self._counts.get(filt)
        return len(self.rows(filt)) if got is None else got

    def rows(self, filt) -> np.ndarray:
        """The rows ``filt`` admits (those carrying each of its tags),
        ascending int32."""
        filt = normalize_filter(filt)
        if len(filt) == 1:
            return self.posting(filt[0])
        got = self._cache.get(filt)
        if got is not None:
            return got
        first, *rest = sorted(filt, key=lambda t: len(self.posting(t)))
        rows = self.posting(first)
        for t in rest:      # the shortest posting, checked against the others
            if len(self.posting(t)) > self.MASKED_POSTING:
                rows = rows[self._mask(t)[rows]]
            else:
                rows = rows[(self.table[rows] == t).any(axis=1)]
        with self._lock:
            self._counts[filt] = len(rows)
            if (len(rows) <= self.CACHED_FILTER_ROWS
                    and self._cached + len(rows) <= self.CACHED_ROWS):
                self._cache[filt] = rows
                self._cached += len(rows)
        return rows

    def _mask(self, tag: int) -> np.ndarray:
        """(n,) bool: the rows carrying ``tag``."""
        mask = self._masks.get(tag)
        if mask is None:
            mask = np.zeros(len(self.table), bool)
            mask[self.posting(tag)] = True
            with self._lock:
                self._masks[tag] = mask
        return mask
