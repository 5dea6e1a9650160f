"""Reference folds of per-row results: what a sweep's reducers must return.

Computed over all rows at once, with no chunking, so a sweep's folded
answer is checked against the plain per-row results it summarises.
``tol`` is the relative tolerance of a time: a row whose value lies within
``tol`` of a bin edge may fall on either side of it.
"""
from __future__ import annotations

import numpy as np


def bin_index(v, lo: float, hi: float, bins: int):
    """The bin of each value, out-of-range values clipped into the end bins."""
    width = (hi - lo) / bins
    return np.clip(np.floor((np.asarray(v, np.float64) - lo) / width),
                   0, bins - 1).astype(np.int64)


def histogram_range(v, lo: float, hi: float, bins: int, tol: float):
    """``(certain, possible)`` counts per bin: rows that lie in the bin for
    every value within ``tol`` of theirs, and rows that may."""
    v = np.asarray(v, np.float64)
    a = bin_index(v - tol * np.abs(v), lo, hi, bins)
    b = bin_index(v + tol * np.abs(v), lo, hi, bins)
    certain = np.bincount(a[a == b], minlength=bins)
    step = np.zeros(bins + 1, np.int64)
    np.add.at(step, a, 1)
    np.add.at(step, b + 1, -1)
    return certain, np.cumsum(step)[:bins]


def histogram_excess(counts, v, lo: float, hi: float, bins: int, tol: float) -> int:
    """Rows by which ``counts`` leaves the range that ``v`` allows, summed
    over bins (0 when the program binned every row where it may lie)."""
    counts = np.asarray(counts, np.int64)
    certain, possible = histogram_range(v, lo, hi, bins, tol)
    return int(np.maximum(certain - counts, 0).sum()
               + np.maximum(counts - possible, 0).sum())
