"""Smallest text positions over suffix-array row ranges, on the host.

A base-tier match set is one contiguous row range ``[lo, lo + count)``
of the suffix array (or of the FM-index's ``SA$``), and a read reports
the smallest text positions in it.  Gathering the whole range costs
O(count): at genome scale a one- or two-symbol pattern matches tens of
millions of rows.  A block-minimum array over the rows bounds it:

* the minimum over a range is the minimum of at most ``2 * block``
  gathered rows at its ragged ends and the block minima in between;
* the ``k`` smallest positions lie in the ragged ends and the ``k``
  blocks with the smallest minima (each of the ``k`` smallest values
  sits in a block whose minimum is no larger than it, and positions are
  distinct), so at most ``(k + 2) * block`` rows are gathered.

``get(rows)`` maps row indices to text positions: a gather from the
host SA mirror, or LF walks on a frozen table.
"""
from __future__ import annotations

from typing import Callable

import numpy as np

BLOCK = 1024            # rows per block-minimum entry

Getter = Callable[[np.ndarray], np.ndarray]


def block_minima(values: np.ndarray, block: int = BLOCK) -> np.ndarray:
    """Minimum of every ``block`` consecutive entries (last one ragged)."""
    values = np.asarray(values)
    if values.size == 0:
        return np.zeros((0,), np.int64)
    return np.minimum.reduceat(values, np.arange(0, values.size, block))


def _split(lo: int, hi: int, block: int):
    """Whole blocks ``[b0, b1)`` inside ``[lo, hi)`` and the ragged rows
    outside them (all of ``[lo, hi)`` when no whole block fits)."""
    b0 = -(-lo // block)
    b1 = hi // block
    if b0 >= b1:
        return b0, b0, np.arange(lo, hi)
    return b0, b1, np.concatenate([np.arange(lo, b0 * block),
                                   np.arange(b1 * block, hi)])


def range_min(get: Getter, bmin: np.ndarray, lo: np.ndarray,
              count: np.ndarray, block: int = BLOCK) -> np.ndarray:
    """Per range ``[lo[i], lo[i] + count[i])``, the smallest position
    (int64; -1 for an empty range).  One ``get`` call for the whole
    batch."""
    lo = np.asarray(lo, np.int64)
    count = np.asarray(count, np.int64)
    out = np.full(lo.shape, -1, np.int64)
    mid = np.full(lo.shape, np.iinfo(np.int64).max, np.int64)
    rows, seg = [], []
    for i in np.flatnonzero(count > 0):
        b0, b1, r = _split(int(lo[i]), int(lo[i] + count[i]), block)
        if b1 > b0:
            mid[i] = int(bmin[b0:b1].min())
        rows.append(r)
        seg.append(np.full(r.size, i, np.int64))
    if not rows:
        return out
    rows = np.concatenate(rows)
    seg = np.concatenate(seg)
    best = mid.copy()
    if rows.size:
        np.minimum.at(best, seg, np.asarray(get(rows), np.int64))
    hit = count > 0
    out[hit] = best[hit]
    return out


def range_smallest(get: Getter, bmin: np.ndarray, lo: int, count: int,
                   k: int, block: int = BLOCK) -> np.ndarray:
    """The ``k`` smallest positions in ``[lo, lo + count)``, ascending
    (fewer when the range is shorter)."""
    if count <= 0 or k <= 0:
        return np.zeros((0,), np.int64)
    b0, b1, rows = _split(int(lo), int(lo + count), block)
    if b1 > b0:
        nb = b1 - b0
        pick = (np.arange(nb) if nb <= k
                else np.argpartition(bmin[b0:b1], k - 1)[:k])
        blocks = (b0 + np.sort(pick))[:, None] * block
        rows = np.concatenate([rows,
                               (blocks + np.arange(block)).reshape(-1)])
    vals = np.asarray(get(rows), np.int64)
    if vals.size > k:
        vals = np.partition(vals, k - 1)[:k]
    return np.sort(vals)
