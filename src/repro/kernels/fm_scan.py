"""FM-index backward-search kernels: rank/select over a compressed BWT.

The frozen storage tier (``repro.api.fm``) replaces the base suffix
array with a Burrows-Wheeler index — the move both follow-up papers
(arXiv 2007.10095, 2107.03341) make at genome scale.  ``count()``
becomes O(pattern_len) independent of text size: one backward-search
step per pattern symbol, each step two rank queries over the packed BWT.

Index layout (built host-side by ``repro.api.fm.FMIndex``):

* the BWT is taken over ``T$`` (virtual sentinel, ``$`` < all symbols),
  so its ``n + 1`` rows are the real suffix array plus one sentinel
  row.  Row ``i >= 1`` of ``SA$`` is row ``i - 1`` of the real SA, and
  the backward-search lower bound ``lo`` maps to ``first_rank = lo - 1``
  — bit-identical to the binary-search path, including ties (the base
  builder's shorter-suffix-first convention IS the sentinel order);
* DNA: 2-bit-packed words (``pack2bit`` layout), rank = blocked Occ
  checkpoint (every ``SB`` symbols) + an in-block popcount bit trick;
  the sentinel row stores dummy symbol 0 and rank subtracts it;
* tokens: uint8 BWT, per-symbol Occ checkpoints, compare-equal sums.

Per-step pattern symbols are pre-extracted into a dense ``(steps, B)``
plan (-1 = step inactive for that query), so the search loop is
checkpoint gathers + popcounts, no per-query pattern indexing.  The
search runs as XLA on every backend: the index stays in HBM and each
step gathers only the checkpoints and words it needs.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

SB = 64                 # symbols per Occ checkpoint block
WPB = SB // 16          # packed words per block (DNA)
_EVEN = 0x55555555      # every 2-bit slot's low bit


@partial(jax.tree_util.register_dataclass,
         data_fields=("bwt", "occ", "cc", "marked", "marked_rank",
                      "samples", "sent_row", "n"),
         meta_fields=("is_dna", "sample_rate", "vocab"))
@dataclasses.dataclass(frozen=True)
class FMArrays:
    """Device view of one frozen table's FM-index (jit-friendly pytree).

    ``rows = n + 1`` BWT rows (row 0 is the ``$``-only suffix).  ``occ``
    holds exclusive prefix counts of the RAW symbol stream (the sentinel
    row's dummy 0 included — rank() subtracts it); ``cc[c]`` is
    ``C$[c] = 1 + #{symbols < c}``.  ``marked``/``marked_rank``/
    ``samples`` are the sampled-SA structures for locate(): row r is
    marked iff its text position ``SA$[r] % sample_rate == 0``, so every
    LF walk terminates within ``sample_rate`` steps."""
    bwt: jnp.ndarray          # DNA: (Wb,) uint32 packed | tokens: (Lp,) int32
    occ: jnp.ndarray          # (nblk + 1, vocab) int32 checkpoint counts
    cc: jnp.ndarray           # (vocab,) int32  C$ array
    marked: jnp.ndarray       # (Wm,) uint32 bitvector over rows
    marked_rank: jnp.ndarray  # (Wm,) int32 set bits before each word
    samples: jnp.ndarray      # (S,) int32 SA$ values of marked rows
    sent_row: jnp.ndarray     # () int32 row whose BWT symbol is $
    n: jnp.ndarray            # () int32 real text length (rows - 1)
    is_dna: bool
    sample_rate: int
    vocab: int


# ---------------------------------------------------------------------------
# rank — Occ(c, i) = occurrences of c in bwt$[0:i)
# ---------------------------------------------------------------------------
def _rank_packed(bwt, occ_flat, sent_row, c, i):
    """Vectorized packed-DNA rank: checkpoint gather + per-word popcount
    bit trick.  ``c``/``i`` int32 arrays of one shape."""
    blk = i // SB
    base = jnp.take(occ_flat, blk * 4 + c)
    rem = i - blk * SB
    pat = c.astype(jnp.uint32) * jnp.uint32(_EVEN)      # symbol repeated
    cnt = jnp.zeros_like(i)
    for j in range(WPB):
        w = jnp.take(bwt, blk * WPB + j)
        v = jnp.clip(rem - 16 * j, 0, 16)               # slots in range
        x = w ^ pat
        y = (~x) & ((~x) >> 1) & jnp.uint32(_EVEN)      # bit per match
        sh = (2 * (16 - jnp.clip(v, 1, 16))).astype(jnp.uint32)
        keep = jnp.where(v > 0, jnp.uint32(_EVEN) << sh, jnp.uint32(0))
        cnt = cnt + lax.population_count(y & keep).astype(jnp.int32)
    return base + cnt - ((c == 0) & (sent_row < i)).astype(jnp.int32)


def _rank_codes(bwt, occ_flat, sent_row, vocab, c, i):
    """Vectorized token rank: checkpoint gather + in-block compare-equal
    sum over the SB-symbol window."""
    blk = i // SB
    base = jnp.take(occ_flat, blk * vocab + c)
    rem = i - blk * SB
    offs = jnp.arange(SB, dtype=jnp.int32)
    vals = jnp.take(bwt, blk[..., None] * SB + offs)    # clips out of range
    hit = (vals == c[..., None]) & (offs < rem[..., None])
    cnt = jnp.sum(hit.astype(jnp.int32), axis=-1)
    return base + cnt - ((c == 0) & (sent_row < i)).astype(jnp.int32)


def rank(fa: FMArrays, c, i):
    """Occ(c, i) over the index — the rank primitive shared by backward
    search and LF walks."""
    occ_flat = fa.occ.reshape(-1)
    if fa.is_dna:
        return _rank_packed(fa.bwt, occ_flat, fa.sent_row, c, i)
    return _rank_codes(fa.bwt, occ_flat, fa.sent_row, fa.vocab, c, i)


# ---------------------------------------------------------------------------
# per-step symbol plan
# ---------------------------------------------------------------------------
def syms_from_packed(patt: jnp.ndarray, plen: jnp.ndarray,
                     steps: int) -> jnp.ndarray:
    """(B, W) packed patterns -> (steps, B) int32 backward-order symbols
    (step t processes pattern position ``plen - 1 - t``; -1 = inactive)."""
    j = plen[None, :].astype(jnp.int32) - 1 - jnp.arange(
        steps, dtype=jnp.int32)[:, None]                   # (steps, B)
    valid = j >= 0
    jc = jnp.clip(j, 0, steps - 1)
    words = jnp.take_along_axis(patt, (jc // 16).T, axis=1).T
    sh = (30 - 2 * (jc % 16)).astype(jnp.uint32)
    sym = ((words >> sh) & jnp.uint32(3)).astype(jnp.int32)
    return jnp.where(valid, sym, -1)


def syms_from_codes(patt: jnp.ndarray, plen: jnp.ndarray,
                    steps: int) -> jnp.ndarray:
    """(B, L) code patterns -> (steps, B) int32 backward-order symbols."""
    j = plen[None, :].astype(jnp.int32) - 1 - jnp.arange(
        steps, dtype=jnp.int32)[:, None]
    valid = j >= 0
    jc = jnp.clip(j, 0, patt.shape[1] - 1)
    sym = jnp.take_along_axis(patt, jc.T, axis=1).T.astype(jnp.int32)
    return jnp.where(valid, sym, -1)


# ---------------------------------------------------------------------------
# backward search
# ---------------------------------------------------------------------------
def search_syms(fa: FMArrays, syms: jnp.ndarray):
    """Backward search over a (steps, B) symbol plan -> (lo, hi) int32
    rows of SA$: matches occupy rows [lo, hi), count = hi - lo,
    first_rank (real SA) = lo - 1."""
    B = syms.shape[1]
    rows = fa.n.astype(jnp.int32) + 1
    lo0 = jnp.zeros((B,), jnp.int32)
    hi0 = jnp.full((B,), 1, jnp.int32) * rows

    def body(t, carry):
        lo, hi = carry
        s = lax.dynamic_slice_in_dim(syms, t, 1, axis=0)[0]
        active = s >= 0
        known = s < fa.vocab            # symbol outside the text's alphabet
        sc = jnp.clip(s, 0, fa.vocab - 1)
        lo2 = jnp.take(fa.cc, sc) + rank(fa, sc, lo)
        hi2 = jnp.take(fa.cc, sc) + rank(fa, sc, hi)
        hi2 = jnp.where(known, hi2, lo2)                # unknown: empty run
        lo = jnp.where(active, lo2, lo)
        hi = jnp.where(active, hi2, hi)
        return lo, hi

    return lax.fori_loop(0, syms.shape[0], body, (lo0, hi0))


def backward_search(fa: FMArrays, patt, plen):
    """Count-path entry: encoded batch -> (lo, hi) SA$ rows."""
    if fa.is_dna:
        steps = patt.shape[1] * 16
        syms = syms_from_packed(patt, plen, steps)
    else:
        steps = patt.shape[1]
        syms = syms_from_codes(patt, plen, steps)
    return search_syms(fa, syms)


# ---------------------------------------------------------------------------
# LF walk — locate()'s device-side primitive (used for first_pos)
# ---------------------------------------------------------------------------
def _bwt_symbol(fa: FMArrays, r):
    if fa.is_dna:
        w = jnp.take(fa.bwt, r // 16)
        return ((w >> (30 - 2 * (r % 16)).astype(jnp.uint32))
                & jnp.uint32(3)).astype(jnp.int32)
    return jnp.take(fa.bwt, r).astype(jnp.int32)


def lf_walk(fa: FMArrays, rows):
    """Text positions of SA$ rows via sampled-SA LF walks, (B,) int32.
    Every walk stops within ``sample_rate`` steps (position 0 is always
    marked, so a walk never crosses the sentinel)."""
    r = jnp.asarray(rows, jnp.int32)

    def sample_pos(rr):
        w = jnp.take(fa.marked, rr // 32)
        lowmask = (jnp.uint32(1) << (rr % 32).astype(jnp.uint32)) - 1
        idx = (jnp.take(fa.marked_rank, rr // 32)
               + lax.population_count(w & lowmask).astype(jnp.int32))
        return jnp.take(fa.samples, idx)

    def body(_, carry):
        r, steps, pos, done = carry
        w = jnp.take(fa.marked, r // 32)
        hit = (((w >> (r % 32).astype(jnp.uint32)) & jnp.uint32(1)) != 0)
        stop = hit & ~done
        pos = jnp.where(stop, sample_pos(r) + steps, pos)
        done = done | stop
        s = _bwt_symbol(fa, r)
        r2 = jnp.take(fa.cc, s) + rank(fa, s, r)
        r = jnp.where(done, r, r2)
        steps = jnp.where(done, steps, steps + 1)
        return r, steps, pos, done

    init = (r, jnp.zeros_like(r), jnp.full_like(r, -1),
            jnp.zeros(r.shape, bool))
    _, _, pos, _ = lax.fori_loop(0, fa.sample_rate + 1, body, init)
    return pos


def match_lanes(lo, hi, n):
    """Lay a batch's match ranges ``[lo, hi)`` of ``SA$`` out on
    ``4 * B`` walk lanes, B the batch's size: lane ``i < B`` holds range
    ``i``'s first row ``lo``, and lanes ``[B, 4B)`` hold the rows
    ``lo + 1 .. hi - 1`` of the ranges, in batch order.  A range is
    *settled* when all of its rows fit; one longer than the ``3B`` extra
    lanes alone is skipped, so it does not crowd out the ranges after
    it.  The lanes are handed out in batch order: the first range that
    fits the budget but not the lanes still free unsettles itself and
    every later range with extra rows (a greedy skip would be a
    sequential loop over B).  Empty ranges (and the ``$`` row's,
    ``lo == 0``) are settled with nothing to walk.  Returns (rows (4B,) int32 clipped to ``[1, n]``,
    each extra lane's range (3B,) int32 — B for an idle or unsettled
    lane —, settled (B,) bool, has_rows (B,) bool)."""
    B = lo.shape[0]
    budget = 3 * B
    has_rows = (hi > lo) & (lo >= 1)
    extra = jnp.where(has_rows, hi - lo - 1, 0)
    take = jnp.where(extra <= budget, extra, 0)
    # inclusive running sum of the lanes taken, saturating above the
    # budget so that no batch overflows int32
    end = lax.associative_scan(
        lambda a, b: jnp.minimum(a + b, budget + 1), take)
    start = jnp.concatenate([jnp.zeros((1,), end.dtype), end[:-1]])
    settled = (extra == 0) | ((extra <= budget) & (end <= budget))
    k = jnp.arange(budget, dtype=jnp.int32)
    owner = jnp.searchsorted(end, k, side="right").astype(jnp.int32)
    oc = jnp.minimum(owner, B - 1)
    rows = jnp.take(lo, oc) + 1 + k - jnp.take(start, oc)
    owner = jnp.where((owner < B) & jnp.take(settled, oc), owner, B)
    rows = jnp.clip(jnp.concatenate([lo, rows]), 1, n)
    return rows, owner, settled, has_rows


def finish_match(fa: FMArrays, lo, hi):
    """(lo, hi) -> (found, count, first_rank, first_pos, text_min),
    matching the binary-search path's conventions exactly:
    ``first_rank`` is the real-SA lower-bound row ``lo - 1`` when found
    and -1 otherwise; ``first_pos`` is the matched run's first text
    position in suffix-rank order, -1 when not found.

    ``text_min`` (2, B) int32 is the text-order reduction over each
    whole range: row 0 the smallest text position of its matches (-1
    when none or not settled), row 1 is 1 where the range was settled
    (:func:`match_lanes`).  One LF walk over all the lanes gives both:
    lane ``i < B`` is ``first_pos``, the rest the range's other rows."""
    B = lo.shape[0]
    count = hi - lo
    found = count > 0
    first_rank = jnp.where(found, lo - 1, -1)
    rows, owner, settled, has_rows = match_lanes(lo, hi, fa.n)
    pos = lf_walk(fa, rows)
    first_pos = jnp.where(found, pos[:B], -1)
    rest = jnp.full((B,), jnp.iinfo(jnp.int32).max, jnp.int32).at[
        owner].min(pos[B:], mode="drop")
    low = jnp.where(settled & has_rows, jnp.minimum(pos[:B], rest), -1)
    text_min = jnp.stack([low, settled.astype(jnp.int32)])
    return found, count.astype(jnp.int32), first_rank.astype(jnp.int32), \
        first_pos.astype(jnp.int32), text_min.astype(jnp.int32)
