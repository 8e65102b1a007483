"""Fused multi-tier scan: every LSM tier binary-searched in ONE launch.

A merged read over a ``SuffixTable`` used to dispatch once per tier —
base scan, then one jitted query per sealed run, then the memtable —
plus a per-query host loop to apply each tier's straddle-rule bounds.
With runs live that fan-out dominated read latency (~9x base-only,
BENCH_compaction.json).  This module is the fused replacement: the delta
tiers are stacked into one bucket-padded :class:`~repro.core.tablet.
TierStack` and scanned together, with the per-tier straddle masks
(``lo < g + plen <= hi``, docs/table_api.md) applied inside the same
trace.

Two implementations, cross-checked in tests/test_kernels.py:

* :func:`fused_tier_scan` — pure jnp: a vmapped batched binary search
  over the stacked tiers plus a masked in-range reduction.  This is the
  production CPU path and the oracle;
* :func:`tier_scan_pallas` — the Pallas TPU kernel (DNA-packed rows):
  a dense blocked scan in the ``tablet_scan`` style with a tier axis on
  the grid, so all tiers of a table ride one Mosaic launch.

Per query and per tier both return, over the tier's REAL rows only:

====== =====================================================================
field  meaning
====== =====================================================================
count    occurrences the tier OWNS (straddle bounds applied)
less     rows strictly before the pattern — the enumeration lower bound
matches  raw prefix-match run length (bounds NOT applied); the SA slice
         ``[less, less + matches)`` holds every candidate row, from which
         the host filters owned positions without re-searching
first_g  minimum owned GLOBAL start position (``BIG`` when count == 0)
====== =====================================================================
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import codec
from repro.core import query as Q

BLOCK_Q = 128   # patterns per tile (sublane-major axis of the compare tile)
BLOCK_R = 256   # rows per tile (lane axis, 128-aligned)
BIG = 2**30     # "no match" sentinel for first_g


def _owned_tail(ov_rank_t, hi_rank_t, pad_cnt_t, rmq_t, offset_t, lo_t,
                hi_t, plen, lb, ub):
    """From one tier's search bounds [lb, ub) to its four outputs, in
    O(B * (max_query_len + log R)) instead of a dense (B, R) mask.

    A window row at local position p is OWNED iff
    ``overlap < p + plen <= tl`` (``overlap = lo - offset``,
    ``tl = hi - offset`` = the true text length; positions ``p >= tl``
    are the pow2 bucket padding of ``padded_segment_store``, real to the
    store but never owned).  The disowned rows split into three disjoint
    sets, each precomputed host-side (see
    :class:`~repro.core.tablet.TierStack`): overlap rows indexed by
    ``ov_rank``, end rows indexed by ``hi_rank``, and pad rows counted
    by the ``pad_cnt`` prefix sums.  ``first_g`` is the min over (a)
    owned overlap rows and (b) the sparse-table range-min of middle-row
    ``g`` over the window, guarded by the high bound — if the minimum
    position fails ``p <= tl - plen``, every middle row in the window
    does."""
    K, R = rmq_t.shape
    OV = ov_rank_t.shape[0]
    plen_i = plen.astype(jnp.int32)
    overlap = lo_t - offset_t
    tl = hi_t - offset_t
    L = (ub - lb).astype(jnp.int32)                                # (B,)
    p_idx = jnp.arange(OV, dtype=jnp.int32)[None, :]

    # low bound: overlap rows (p < overlap) present in the window
    in_lo = ((ov_rank_t[None, :] >= lb[:, None])
             & (ov_rank_t[None, :] < ub[:, None]))                 # (B, OV)
    stops_in = p_idx + plen_i[:, None] <= overlap  # match END inside prefix
    excl_lo = jnp.sum(in_lo & stops_in, axis=1).astype(jnp.int32)
    own_lo = in_lo & ~stops_in & (p_idx + plen_i[:, None] <= tl)
    c_ov = jnp.min(jnp.where(own_lo, p_idx + offset_t,
                             jnp.int32(BIG)), axis=1)

    # high bound: end rows (p = tl - 1 - q) with the match running past tl
    in_hi = ((hi_rank_t[None, :] >= lb[:, None])
             & (hi_rank_t[None, :] < ub[:, None]))                 # (B, OV)
    excl_hi = jnp.sum(in_hi & (p_idx <= plen_i[:, None] - 2),
                      axis=1).astype(jnp.int32)

    # bucket-pad rows (p >= tl): never owned, counted by prefix sums
    excl_pad = jnp.take(pad_cnt_t, ub) - jnp.take(pad_cnt_t, lb)

    count = L - excl_lo - excl_hi - excl_pad
    k = jnp.zeros_like(L)                          # floor(log2 L), L >= 1
    for j in range(1, K):
        k = k + (L >= (1 << j)).astype(L.dtype)
    h = jnp.left_shift(jnp.int32(1), k)
    flat = rmq_t.reshape(-1)
    m = jnp.minimum(
        jnp.take(flat, k * R + jnp.clip(lb, 0, R - 1)),
        jnp.take(flat, k * R + jnp.clip(ub - h, 0, R - 1)))
    ok = (L > 0) & (m - offset_t <= tl - plen_i)
    c_rmq = jnp.where(ok, m, jnp.int32(BIG))
    return count, lb, L, jnp.minimum(c_ov, c_rmq)


# ---------------------------------------------------------------------------
# pure-jnp fused path (production on CPU; oracle for the kernel)
# ---------------------------------------------------------------------------
def fused_tier_scan(stack, patt, plen):
    """Scan every tier of a :class:`~repro.core.tablet.TierStack` in one
    trace.  ``patt`` is the same encoded batch the base scan takes
    (packed uint32 (B, W) for DNA, int32 codes (B, L) otherwise); returns
    ``(count, less, matches, first_g)``, each (T, B) int32.

    The per-tier metadata (``n_real`` / ``n_rows`` / ``offset`` / ``lo``
    / ``hi``) is traced DATA, so appends that stay inside a text bucket
    reuse the compilation; only bucket growth or a tier-count change
    re-specializes."""
    R = stack.rows
    steps = max(1, int(np.ceil(np.log2(R + 1))))
    use_packed = stack.is_dna and patt.dtype == jnp.uint32
    text = stack.text_packed if use_packed else stack.text_codes
    cmp = Q.compare_packed if use_packed else Q.compare_codes
    B = patt.shape[0]

    # both bounds ride ONE loop: row 0 searches the lower bound
    # (pred = lt), row 1 the upper (pred = lt | eq), with the compare
    # batched over 2B stacked positions — half the loop trips of two
    # independent searches
    patt2 = jnp.concatenate([patt, patt], axis=0)
    plen2 = jnp.concatenate([plen, plen], axis=0)
    is_ub = jnp.array([[False], [True]])                   # (2, 1)

    def one_tier(sa_t, text_t, n_real_t, n_rows_t, offset_t, lo_t, hi_t,
                 ov_rank_t, hi_rank_t, pad_cnt_t, rmq_t):
        def body(_, lohi):
            lo, hi = lohi                                  # (2, B)
            mid = (lo + hi) // 2
            pos = jnp.take(sa_t, jnp.clip(mid.reshape(-1), 0, R - 1))
            lt, eq = cmp(text_t, n_real_t, pos, patt2, plen2)
            pred = lt.reshape(2, B) | (eq.reshape(2, B) & is_ub)
            active = lo < hi
            lo = jnp.where(active & pred, mid + 1, lo)
            hi = jnp.where(active & ~pred, mid, hi)
            return lo, hi

        lo = jnp.zeros((2, B), jnp.int32)
        hi = jnp.broadcast_to(n_rows_t.astype(jnp.int32), (2, B))
        lo, hi = lax.fori_loop(0, steps, body, (lo, hi))
        return _owned_tail(ov_rank_t, hi_rank_t, pad_cnt_t, rmq_t,
                           offset_t, lo_t, hi_t, plen, lo[0], lo[1])

    return jax.vmap(one_tier)(stack.sa, text, stack.n_real, stack.n_rows,
                              stack.offset, stack.lo, stack.hi,
                              stack.ov_rank, stack.hi_rank,
                              stack.pad_cnt, stack.rmq)


def fused_table_scan(store, stack, patt, plen):
    """THE single-device merged read search: the base store AND every
    delta tier binary-searched inside ONE ``fori_loop``.  Each step
    gathers one probe row per (store, bound, query), compares all of
    them in one chain (per-row ``n_real`` — the rows come from different
    texts), and advances all bounds together, so the serial step count
    is ``max(log2 n_base, log2 R)`` instead of their sum across separate
    base and tier dispatches.

    Returns ``(base MatchResult, (count, less, matches, first_g))`` with
    exactly the :func:`~repro.core.query.query` / :func:`fused_tier_scan`
    contracts — bit-identical, just one fused launch."""
    R = stack.rows
    T = stack.num_tiers
    n = store.n_pad
    steps = max(1, int(np.ceil(np.log2(max(n, R) + 1))))
    use_packed = stack.is_dna and patt.dtype == jnp.uint32
    btext = store.text_packed if use_packed else store.text_codes
    ttext = stack.text_packed if use_packed else stack.text_codes
    B, W = patt.shape

    # probe layout: group 0 is the base, groups 1..T the tiers; within a
    # group, row 0 searches the lower bound, row 1 the upper
    patt2 = jnp.concatenate([patt, patt], axis=0)              # (2B, W)
    plen2 = jnp.concatenate([plen, plen], axis=0)
    patt_rep = jnp.tile(patt2, (T + 1, 1))
    plen_rep = jnp.tile(plen2, (T + 1,))
    n_real_all = jnp.concatenate(
        [jnp.full((1,), store.n_real, jnp.int32),
         stack.n_real.astype(jnp.int32)])                      # (T+1,)
    n_real_rep = jnp.repeat(n_real_all, 2 * B)
    is_ub = jnp.array([[False], [True]])                       # (2, 1)

    def body(_, carry):
        blo, bhi, tlo, thi = carry                 # (2, B) / (T, 2, B)
        bmid = (blo + bhi) // 2
        tmid = (tlo + thi) // 2
        bpos = jnp.take(store.sa,
                        jnp.clip(bmid.reshape(-1), 0, n - 1))  # (2B,)
        tpos = jax.vmap(
            lambda sa_t, m: jnp.take(sa_t, jnp.clip(m, 0, R - 1)))(
                stack.sa, tmid.reshape(T, 2 * B))              # (T, 2B)
        pos_all = jnp.concatenate(
            [bpos.reshape(1, -1).astype(jnp.int32),
             tpos.astype(jnp.int32)]).reshape(-1)
        if use_packed:
            wb = codec.extract_window(btext, bpos, W)
            wt = jax.vmap(
                lambda tx, p: codec.extract_window(tx, p, W))(ttext, tpos)
            win = jnp.concatenate([wb[None], wt]).reshape(-1, W)
            lt, eq = Q.compare_windows_packed(win, pos_all, n_real_rep,
                                              patt_rep, plen_rep)
        else:
            sb = Q.gather_suffix_codes(btext, store.n_real, bpos, W)
            st = jax.vmap(
                lambda tx, nr, p: Q.gather_suffix_codes(tx, nr, p, W))(
                    ttext, stack.n_real, tpos)
            suf = jnp.concatenate([sb[None], st]).reshape(-1, W)
            lt, eq = Q.compare_suffix_codes(suf, patt_rep, plen_rep)
        pred = (lt.reshape(T + 1, 2, B)
                | (eq.reshape(T + 1, 2, B) & is_ub[None]))
        bactive = blo < bhi
        blo = jnp.where(bactive & pred[0], bmid + 1, blo)
        bhi = jnp.where(bactive & ~pred[0], bmid, bhi)
        tactive = tlo < thi
        tlo = jnp.where(tactive & pred[1:], tmid + 1, tlo)
        thi = jnp.where(tactive & ~pred[1:], tmid, thi)
        return blo, bhi, tlo, thi

    blo = jnp.zeros((2, B), jnp.int32)
    bhi = jnp.full((2, B), n, jnp.int32)
    tlo = jnp.zeros((T, 2, B), jnp.int32)
    thi = jnp.broadcast_to(
        stack.n_rows.astype(jnp.int32)[:, None, None], (T, 2, B))
    blo, _, tlo, _ = lax.fori_loop(0, steps, body, (blo, bhi, tlo, thi))

    lb, ub = blo[0], blo[1]                        # base, Q.query contract
    count = ub - lb
    found = count > 0
    first_pos = jnp.take(store.sa, jnp.clip(lb, 0, n - 1))
    first_pos = jnp.where(found, first_pos, -1)
    first_rank = jnp.where(found, lb - store.pad_count, -1)
    base = Q.MatchResult(found=found, count=count,
                         first_rank=first_rank, first_pos=first_pos)

    tiers = jax.vmap(
        lambda ovr, hir, pcn, rmq_t, offset_t, lo_t, hi_t, lb_t, ub_t:
        _owned_tail(ovr, hir, pcn, rmq_t, offset_t, lo_t, hi_t, plen,
                    lb_t, ub_t))(
            stack.ov_rank, stack.hi_rank, stack.pad_cnt, stack.rmq,
            stack.offset, stack.lo, stack.hi, tlo[:, 0, :], tlo[:, 1, :])
    return base, tiers


def merge_tier_results(base, tier_count, tier_first):
    """Merge a base :class:`~repro.core.query.MatchResult` with fused
    tier outputs, in-trace (jnp) or on host (numpy): merged ``count`` is
    the sum over owners, ``first_pos`` the minimum over the base's
    reported position and every tier's first owned position, and
    ``first_rank`` keeps its base-only meaning (−1 when only delta tiers
    match — docs/table_api.md)."""
    total = base.count + jnp.sum(tier_count, axis=0).astype(base.count.dtype)
    dmin = jnp.min(tier_first, axis=0)          # BIG when a tier owns none
    cand = jnp.where(base.count > 0, base.first_pos, jnp.int32(BIG))
    first_pos = jnp.minimum(cand.astype(jnp.int32), dmin)
    found = total > 0
    first_pos = jnp.where(found & (first_pos < BIG), first_pos, -1)
    return Q.MatchResult(found=found, count=total,
                         first_rank=base.first_rank, first_pos=first_pos)


# ---------------------------------------------------------------------------
# Pallas kernel: dense blocked scan with a tier grid axis (DNA-packed)
# ---------------------------------------------------------------------------
def _tier_kernel(meta_ref, patt_ref, plen_ref, win_ref, sa_ref,
                 count_ref, less_ref, match_ref, first_ref,
                 *, n_words: int):
    t = pl.program_id(0)
    plen = plen_ref[...]                                    # (BQ, 1)
    salocal = sa_ref[0]                                     # (1, BR)
    n_real = meta_ref[t, 0]                                 # SMEM scalars
    n_rows = meta_ref[t, 1]
    offset = meta_ref[t, 2]
    lo_b = meta_ref[t, 3]
    hi_b = meta_ref[t, 4]

    bq = plen.shape[0]
    br = salocal.shape[1]
    pe = jnp.ones((bq, br), jnp.bool_)
    lt = jnp.zeros((bq, br), jnp.bool_)
    for w in range(n_words):
        a = win_ref[0, w:w + 1, :]                          # row word (1,BR)
        b = patt_ref[:, w:w + 1]                            # pattern  (BQ,1)
        r = jnp.clip(plen - w * 16, 0, 16).astype(jnp.uint32)
        full = jnp.uint32(0xFFFFFFFF)
        mask = jnp.where(r == 0, jnp.uint32(0),
                         jnp.where(r == 16, full,
                                   ~((jnp.uint32(1) << (32 - 2 * r)) - 1)))
        am = a & mask                                       # (BQ, BR)
        bm = b & mask
        lt = lt | (pe & (am < bm))
        pe = pe & (am == bm)
    truncated = salocal + plen > n_real                     # (BQ, BR)
    eq = pe & ~truncated
    lt = lt | (pe & truncated)

    row0 = pl.program_id(2) * br
    rows = row0 + jax.lax.broadcasted_iota(jnp.int32, (bq, br), 1)
    valid = rows < n_rows                                   # stack padding
    eq = eq & valid
    lt = lt & valid
    g = salocal + offset                                    # global starts
    e = g + plen
    owned = eq & (e > lo_b) & (e <= hi_b)                   # straddle rule
    # keepdims: the per-query results stay (BQ, 1) columns, the layout
    # the compare tile reduces into — no lane/sublane relayout
    first = jnp.min(jnp.where(owned, g, jnp.int32(BIG)), axis=1,
                    keepdims=True)
    cnt = jnp.sum(owned.astype(jnp.int32), axis=1, keepdims=True)
    mat = jnp.sum(eq.astype(jnp.int32), axis=1, keepdims=True)
    less = jnp.sum(lt.astype(jnp.int32), axis=1, keepdims=True)

    @pl.when(pl.program_id(2) == 0)
    def _init():
        count_ref[0] = cnt
        less_ref[0] = less
        match_ref[0] = mat
        first_ref[0] = first

    @pl.when(pl.program_id(2) != 0)
    def _acc():
        count_ref[0] += cnt
        less_ref[0] += less
        match_ref[0] += mat
        first_ref[0] = jnp.minimum(first_ref[0], first)


@functools.partial(jax.jit, static_argnames=("interpret",))
def tier_scan_pallas(patterns: jnp.ndarray, plen: jnp.ndarray,
                     windows_t: jnp.ndarray, sa: jnp.ndarray,
                     meta: jnp.ndarray, *, interpret: bool = False):
    """patterns: (BQtot, W) uint32; plen: (BQtot,) int32; windows_t:
    (T, W, BRtot) uint32 — packed windows of every tier's stacked sorted
    rows; sa: (T, BRtot) int32 LOCAL text positions of those rows; meta:
    (T, 8) int32 rows of ``[n_real, n_rows, offset, lo, hi, 0, 0, 0]``
    per tier, scalar-prefetched into SMEM.  BQtot % BLOCK_Q == 0 and
    BRtot % BLOCK_R == 0 (caller pads; rows past ``n_rows`` are masked).
    Returns (count, less, matches, first_g) int32 (T, BQtot).

    Every block's last two dimensions are (8, 128)-divisible or span
    the whole array dimension, so the layout tiles at any tier count T;
    the outputs are (T, BQtot, 1) columns inside the kernel."""
    T, W, BR = windows_t.shape
    BQ = patterns.shape[0]
    assert BQ % BLOCK_Q == 0 and BR % BLOCK_R == 0
    grid = (T, BQ // BLOCK_Q, BR // BLOCK_R)
    kernel = functools.partial(_tier_kernel, n_words=W)
    qcol = pl.BlockSpec((1, BLOCK_Q, 1), lambda t, q, r, meta: (t, q, 0))
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((BLOCK_Q, W), lambda t, q, r, meta: (q, 0)),
                pl.BlockSpec((BLOCK_Q, 1), lambda t, q, r, meta: (q, 0)),
                pl.BlockSpec((1, W, BLOCK_R),
                             lambda t, q, r, meta: (t, 0, r)),
                pl.BlockSpec((1, 1, BLOCK_R),
                             lambda t, q, r, meta: (t, 0, r)),
            ],
            out_specs=[qcol] * 4,
        ),
        out_shape=[jax.ShapeDtypeStruct((T, BQ, 1), jnp.int32)] * 4,
        interpret=interpret,
    )(meta, patterns, plen[:, None], windows_t, sa[:, None, :])
    return tuple(o[:, :, 0] for o in out)
