"""Plain reference answers over one DNA text: numpy only, independent of
the store under test (it imports nothing of ``repro``).

Derived from the smoke run's reference: every text position carries the
code of the ``KMER`` bases that start there, a pattern of at most
``KMER`` bases matches where that code's leading bases equal it, and a
longer pattern's candidates (positions whose code equals its first
``KMER`` bases) are verified base by base.  Here the work is vectorised
over a whole sample of patterns, so thousands are checked in seconds at
genome scale:

* counts of short patterns come from one histogram of all ``KMER``-mers
  (a range sum per pattern) plus the last few positions checked
  directly;
* the smallest positions of short patterns come from scanning the text
  from its start in doubling prefixes, one pass per pattern length,
  stopping once every pattern of that length has ``k`` hits;
* long patterns gather their candidates with one ``isin`` pass and
  verify them in blocks of rows.

``sa_in_order`` checks a suffix array's order on sampled adjacent rows.
"""
from __future__ import annotations

import numpy as np

KMER = 12               # prefix length (24 bits of uint32)
_PAIRS = 1 << 18        # candidate rows verified per block


def _codes(patterns: list[np.ndarray], m: int) -> np.ndarray:
    """The code of each pattern's first ``m`` bases, uint32."""
    out = np.zeros(len(patterns), np.uint32)
    for i, p in enumerate(patterns):
        for c in p[:m]:
            out[i] = (out[i] << np.uint32(2)) | np.uint32(c)
    return out


def _member(values: np.ndarray, codes: np.ndarray, m: int) -> np.ndarray:
    """``values`` in ``codes``, for codes of ``m`` bases: one gather
    from a table of all ``4**m`` codes."""
    table = np.zeros(1 << (2 * m), bool)
    table[codes] = True
    return table[values]


class Reference:
    """Exact counts and smallest positions over one text."""

    def __init__(self, text: np.ndarray):
        self.text = np.asarray(text, np.uint8)
        n = self.text.size
        # km[i] = codes i..i+KMER-1, by doubling: codes of 2, 4, 8 bases
        t = np.concatenate([self.text, np.zeros(KMER, np.uint8)]
                           ).astype(np.uint32)
        k2 = (t[:-1] << np.uint32(2)) | t[1:]
        k4 = (k2[:-2] << np.uint32(4)) | k2[2:]
        k8 = (k4[:-4] << np.uint32(8)) | k4[4:]
        self.kmer = ((k8[:-4] << np.uint32(8)) | k4[8:])[:n]
        self._cum = None

    def _full(self) -> np.ndarray:
        """Positions whose whole ``KMER``-mer lies in the text."""
        return self.kmer[:max(0, self.text.size - KMER + 1)]

    def _cumulative(self) -> np.ndarray:
        if self._cum is None:
            hist = np.bincount(self._full(), minlength=1 << (2 * KMER))
            self._cum = np.concatenate([[0], np.cumsum(hist)])
        return self._cum

    def answers(self, patterns: list[np.ndarray], k: int):
        """(count (P,), smallest ``k`` positions (P, k), -1 padded)."""
        k = max(int(k), 1)
        P = len(patterns)
        count = np.zeros(P, np.int64)
        first = np.full((P, k), -1, np.int64)
        lens = np.array([p.size for p in patterns], np.int64)
        for m in np.unique(lens[lens <= KMER]):
            idx = np.flatnonzero(lens == m)
            self._short(patterns, idx, int(m), k, count, first)
        idx = np.flatnonzero(lens > KMER)
        if idx.size:
            self._long(patterns, idx, k, count, first)
        return count, first

    def _tail_hits(self, p: np.ndarray) -> list[int]:
        """Positions past the last whole ``KMER``-mer where ``p`` occurs."""
        n, m = self.text.size, p.size
        return [s for s in range(max(0, n - KMER + 1), n - m + 1)
                if np.array_equal(self.text[s:s + m], p)]

    def _short(self, patterns, idx, m, k, count, first) -> None:
        shift = np.uint32(2 * (KMER - m))
        codes = _codes([patterns[i] for i in idx], m)
        cum = self._cumulative()
        lo = codes.astype(np.int64) << int(shift)
        count[idx] = cum[lo + (1 << int(shift))] - cum[lo]
        full = self._full()
        got: dict[int, list[int]] = {int(c): [] for c in codes}
        pending = set(got)
        end = 0
        size = 1 << 16
        while pending and end < full.size:
            stop = min(full.size, end + size)
            block = full[end:stop] >> shift
            want = np.array(sorted(pending), np.uint32)
            pos = np.flatnonzero(_member(block, want, m))
            if pos.size:
                hit_codes = block[pos]
                order = np.argsort(hit_codes, kind="stable")
                hc, hp = hit_codes[order], pos[order] + end
                for c in want:
                    a, b = np.searchsorted(hc, [c, c + 1])
                    lst = got[int(c)]
                    lst.extend(int(v) for v in hp[a:b][:k - len(lst)])
                    if len(lst) >= k:
                        pending.discard(int(c))
            end = stop
            size *= 2
        for j, i in enumerate(idx):
            tail = self._tail_hits(patterns[i])
            count[i] += len(tail)
            pos = (got[int(codes[j])] + tail)[:k]
            first[i, :len(pos)] = pos

    def _long(self, patterns, idx, k, count, first) -> None:
        n = self.text.size
        codes = _codes([patterns[i] for i in idx], KMER)
        full = self._full()
        cand = np.flatnonzero(_member(full, codes, KMER))
        ckm = full[cand]
        order = np.argsort(ckm, kind="stable")     # positions stay ascending
        cand, ckm = cand[order], ckm[order]
        lo = np.searchsorted(ckm, codes, "left")
        hi = np.searchsorted(ckm, codes, "right")
        width = max(patterns[i].size for i in idx)
        pat = np.full((idx.size, width), 255, np.uint8)
        plen = np.zeros(idx.size, np.int64)
        for j, i in enumerate(idx):
            pat[j, :patterns[i].size] = patterns[i]
            plen[j] = patterns[i].size
        ext = np.concatenate([self.text, np.full(width, 254, np.uint8)])
        cols = np.arange(width)
        row = np.repeat(np.arange(idx.size), hi - lo)
        at = np.concatenate([cand[a:b] for a, b in zip(lo, hi)]) \
            if row.size else np.zeros(0, np.int64)
        ok = np.zeros(row.size, bool)
        for s in range(0, row.size, _PAIRS):
            r, a = row[s:s + _PAIRS], at[s:s + _PAIRS]
            win = ext[a[:, None] + cols[None, :]]
            want = pat[r]
            match = (win == want) | (cols[None, :] >= plen[r][:, None])
            ok[s:s + _PAIRS] = match.all(axis=1) & (a + plen[r] <= n)
        hits_row, hits_at = row[ok], at[ok]
        count[idx] = np.bincount(hits_row, minlength=idx.size)
        starts = np.searchsorted(hits_row, np.arange(idx.size))
        for j, i in enumerate(idx):
            pos = hits_at[starts[j]:starts[j] + min(k, int(count[i]))]
            first[i, :pos.size] = pos


def brute_force(text: np.ndarray, pattern: np.ndarray, k: int):
    """(count, smallest ``k`` positions) by trying every start."""
    text = np.asarray(text, np.uint8)
    m = pattern.size
    hits = [s for s in range(text.size - m + 1)
            if np.array_equal(text[s:s + m], pattern)]
    return len(hits), hits[:k]


def sa_in_order(text: np.ndarray, sa: np.ndarray, rng, samples: int = 4096
                ) -> bool:
    """Every sampled adjacent pair ``sa[i] < sa[i+1]`` compares strictly
    increasing as suffixes (a shorter suffix sorts first on a tie), and
    ``sa`` is a permutation of the text positions."""
    n = text.size
    if sa.size != n:
        return False
    seen = np.zeros(n, bool)
    seen[sa] = True
    if not seen.all():
        return False
    if n < 2:
        return True
    i = rng.integers(0, n - 1, size=min(samples, n - 1))
    a = sa[i].astype(np.int64)
    b = sa[i + 1].astype(np.int64)
    ext = np.concatenate([text.astype(np.int16), np.full(64, -1, np.int16)])
    undecided = np.ones(i.size, bool)
    ok = np.ones(i.size, bool)
    off = 0
    while undecided.any() and off <= n:
        u = np.flatnonzero(undecided)
        cols = off + np.arange(64)
        wa = ext[np.minimum(a[u, None] + cols, n)]
        wb = ext[np.minimum(b[u, None] + cols, n)]
        diff = wa != wb
        has = diff.any(axis=1)
        j = np.argmax(diff, axis=1)
        r = np.arange(u.size)
        ok[u[has]] = wa[r, j][has] < wb[r, j][has]
        undecided[u[has]] = False
        off += 64
    return bool(ok.all() and not undecided.any())
