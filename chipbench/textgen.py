"""Seeded DNA text with genome-like repeats, as 2-bit codes (A, C, G, T).

A genome's repeats set its match counts: a pattern cut from an Alu copy
occurs thousands of times, one cut from unique sequence once.  Uniform
random DNA has almost none of that, so the text is a background of
independent bases at a stated GC share, overwritten by copies of a few
repeat families and by segmental duplications (Lander et al. 2001):

* ``families`` elements (Alu-like, L1-like): each family has one random
  consensus of ``consensus`` bases; a copy takes a stretch of it, ending
  at the consensus's 3' end (L1 copies are 5'-truncated), of a length
  drawn uniformly from ``length``, on either strand, with each base
  substituted at rate ``divergence``;
* ``segdup`` elements: copies of stretches of the text itself (repeats
  included), placed elsewhere, substituted at rate ``divergence``.

Copies of each element are placed at uniform starts until they cover
``share`` of the text in bases (overlaps overwrite, so the shares are
approximate).  Everything is drawn from one generator seeded by ``seed``.
"""
from __future__ import annotations

import numpy as np

CHUNK = 1 << 23          # bases of copies written per vectorised step


def _bases(rng, n: int, gc: float) -> np.ndarray:
    """``n`` independent bases with P(C) = P(G) = gc / 2."""
    table = np.empty(1 << 16, np.uint8)
    edges = np.round(np.cumsum([(1 - gc) / 2, gc / 2, gc / 2])
                     * (1 << 16)).astype(np.int64)
    lo = 0
    for code, hi in enumerate(list(edges) + [1 << 16]):
        table[lo:hi] = code
        lo = hi
    return table[rng.integers(0, 1 << 16, size=n, dtype=np.uint16)]


def _lengths(rng, total: int, lo: int, hi: int) -> np.ndarray:
    """Copy lengths uniform in ``[lo, hi]`` summing to about ``total``."""
    count = max(1, int(round(total / ((lo + hi) / 2))))
    return rng.integers(lo, hi + 1, size=count)


def _write_copies(rng, text: np.ndarray, src: np.ndarray, src_start,
                  lens: np.ndarray, divergence: float,
                  reverse: np.ndarray) -> None:
    """Copy ``src[src_start[i] : src_start[i] + lens[i]]`` (reverse-
    complemented where ``reverse[i]``), mutated, to uniform starts of
    ``text``.  ``src`` may be ``text`` itself: each chunk reads before
    it writes."""
    n = text.size
    dst_start = rng.integers(0, n - lens + 1)
    i = 0
    while i < lens.size:
        j = i + max(1, int(np.searchsorted(np.cumsum(lens[i:]), CHUNK)))
        ln = lens[i:j]
        seg = np.repeat(np.arange(ln.size), ln)
        within = np.arange(seg.size) - np.repeat(np.cumsum(ln) - ln, ln)
        rev = reverse[i:j][seg]
        off = np.where(rev, ln[seg] - 1 - within, within)
        vals = src[np.asarray(src_start)[i:j][seg] + off]
        vals = np.where(rev, 3 - vals, vals).astype(np.uint8)
        hit = rng.random(vals.size, dtype=np.float32) < divergence
        vals[hit] = (vals[hit] + rng.integers(
            1, 4, size=int(hit.sum()), dtype=np.uint8)) % 4
        text[dst_start[i:j][seg] + within] = vals
        i = j


def generate(params: dict, seed: int) -> np.ndarray:
    """The text a configuration's ``text`` block describes, uint8 codes."""
    rng = np.random.default_rng(int(seed))
    n = int(params["length"])
    gc = float(params.get("gc_fraction", 0.5))
    text = _bases(rng, n, gc)
    for el in params.get("repeats", ()):
        lo, hi = (int(v) for v in el["length"])
        hi = min(hi, n)
        lo = min(lo, hi)
        lens = _lengths(rng, int(el["share"] * n), lo, hi)
        reverse = rng.random(lens.size) < float(el.get("reverse_share", 0.5))
        if el["kind"] == "family":
            fams = int(el["families"])
            cons_len = max(int(el["consensus"]), hi)
            cons = _bases(rng, fams * cons_len, gc)
            fam = rng.integers(0, fams, size=lens.size)
            # a copy is the 3' end of its family's consensus
            start = fam * cons_len + cons_len - lens
            _write_copies(rng, text, cons, start, lens,
                          float(el["divergence"]), reverse)
        elif el["kind"] == "segdup":
            start = rng.integers(0, n - lens + 1)
            _write_copies(rng, text, text, start, lens,
                          float(el["divergence"]), reverse)
        else:
            raise ValueError(f"unknown repeat kind {el['kind']!r}")
    return text
