"""TabletStore — the Accumulo table of paper §IV, adapted to a TPU mesh.

Paper layout: one row per suffix (ROWID = start position, TEXT = suffix
chars, truncated to 1000).  Our layout (DESIGN.md §2): the text is stored
ONCE (2-bit packed for DNA, raw int32 codes for token corpora) and the
"table" is the globally sorted suffix array, range-partitioned into
contiguous tablets of m = n_pad / p rows, one per device.  Split keys
(Accumulo's METADATA table) are implicit: tablet d owns sorted rows
[d*m, (d+1)*m).

``max_query_len`` is the paper's 1000-char truncation, reborn as a compare
depth cap (queries in the paper's workload are <= 100 chars).
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import codec
from repro.core.suffix_array import build_suffix_array
from repro.core.dsa import build_suffix_array_distributed


@partial(jax.tree_util.register_dataclass,
         data_fields=("text_packed", "text_codes", "sa"),
         meta_fields=("n_real", "n_pad", "is_dna", "max_query_len"))
@dataclasses.dataclass(frozen=True)
class TabletStore:
    """One suffix-array "table".  ``sa`` is the padded, globally sorted
    suffix array; pad rows (positions >= n_real) sort first and are inert
    for every query whose codes are >= 0."""
    text_packed: Optional[jnp.ndarray]  # (n_words,) uint32 | None
    text_codes: Optional[jnp.ndarray]   # (n_pad,)  int32  | None
    sa: jnp.ndarray                     # (n_pad,)  int32
    n_real: int
    n_pad: int
    is_dna: bool
    max_query_len: int

    @property
    def pad_count(self) -> int:
        return self.n_pad - self.n_real

    def tablet_rows(self, num_tablets: int) -> int:
        assert self.n_pad % num_tablets == 0
        return self.n_pad // num_tablets


@partial(jax.tree_util.register_dataclass,
         data_fields=("text_packed", "text_codes", "sa", "n_real", "n_rows",
                      "offset", "lo", "hi", "ov_rank", "hi_rank", "pad_cnt",
                      "rmq"),
         meta_fields=("num_tiers", "rows", "is_dna", "max_query_len"))
@dataclasses.dataclass(frozen=True)
class TierStack:
    """All delta tiers of a table (sealed runs + memtable) stacked into
    one rectangular device view, so a merged read crosses the
    host->device boundary ONCE instead of once per tier.

    Row axis is padded to ``rows`` = max tier n_pad (pow2-bucketed per
    tier already, so restacking happens only when a tier outgrows its
    bucket or the tier COUNT changes — both shape changes).  Everything
    per-tier (``n_real``/``n_rows``/``offset``/``lo``/``hi``) is traced
    int32 DATA of shape (T,): memtable appends within a bucket mutate
    values, not shapes, and reuse the compiled fused scan.

    Semantics per tier t (the straddle rule, docs/table_api.md): local
    row position p maps to global position ``g = p + offset[t]``; the
    tier owns a match iff ``lo[t] < g + plen <= hi[t]``.  A prefix-match
    window [lb, ub) can contain DISOWNED rows of three disjoint kinds —
    overlap-prefix rows (``p + plen <= ov`` where ``ov = lo - offset``),
    end rows (``tl - plen < p < tl`` where ``tl = hi - offset`` is the
    true text length), and bucket-pad rows (``p >= tl``: the pow2 text
    padding of ``padded_segment_store`` is REAL to the store, so its
    symbol-0 suffixes can prefix-match).  The first two sets hold at
    most ``max_query_len - 1`` positions each; the pad set is unbounded
    but static.  Four precomputed host-side structures let the fused
    scan apply the full two-sided rule in O(max_query_len + log rows)
    per query instead of a dense O(rows) mask:

    * ``ov_rank[t, p]`` — SA rank of overlap position ``p`` (BIG when
      ``p >= ov``): the only rows the LOW bound can disown;
    * ``hi_rank[t, q]`` — SA rank of end position ``tl - 1 - q`` (BIG
      when out of range): the only REAL rows the HIGH bound can disown
      (``q <= plen - 2``);
    * ``pad_cnt[t, r]`` — # of rows among SA[0:r) with position
      ``>= tl``, so the pad rows in any window cost two gathers;
    * ``rmq[t, k, i]`` — sparse-table range-minimum over
      ``g = sa + offset`` restricted to rows with ``ov <= p < tl``, so
      the minimum owned position in an SA window costs two gathers
      (guarded by ``min_p <= tl - plen``; if the minimum itself fails
      the high bound, every row in the range does)."""
    text_packed: Optional[jnp.ndarray]  # (T, W_max)  uint32 | None
    text_codes: Optional[jnp.ndarray]   # (T, rows)   int32  | None
    sa: jnp.ndarray                     # (T, rows)   int32, pad rows 0
    n_real: jnp.ndarray                 # (T,) int32  compare depth cap
    n_rows: jnp.ndarray                 # (T,) int32  real sorted rows
    offset: jnp.ndarray                 # (T,) int32  local -> global
    lo: jnp.ndarray                     # (T,) int32  owned range, open
    hi: jnp.ndarray                     # (T,) int32  owned range, closed
    ov_rank: jnp.ndarray                # (T, OV) int32 overlap SA ranks
    hi_rank: jnp.ndarray                # (T, OV) int32 end-pos SA ranks
    pad_cnt: jnp.ndarray                # (T, rows+1) int32 pad-row prefix
    rmq: jnp.ndarray                    # (T, K, rows) int32 range-min g
    num_tiers: int
    rows: int
    is_dna: bool
    max_query_len: int


def stack_tier_stores(stores, *, offsets, bounds) -> TierStack:
    """Stack per-tier segment stores (``padded_segment_store`` outputs)
    into one :class:`TierStack`.  ``offsets[t]`` is the tier's
    local->global position shift; ``bounds[t] = (lo, hi)`` its owned
    global range.  Pad words/codes read as 0/-1 — bit-identical to what
    ``codec.extract_window``/``compare_codes`` return past each tier's
    own array, so stacking never changes a comparison."""
    assert stores, "need at least one tier"
    T = len(stores)
    rows = max(s.n_pad for s in stores)
    is_dna = stores[0].is_dna
    assert all(s.is_dna == is_dna for s in stores)
    sa = np.zeros((T, rows), np.int32)
    packed = None
    codes = None
    if is_dna:
        w_max = codec.packed_length(rows)
        packed = np.zeros((T, w_max), np.uint32)
    codes = np.full((T, rows), -1, np.int32)
    for t, s in enumerate(stores):
        sa[t, :s.n_pad] = np.asarray(s.sa)
        codes[t, :s.n_pad] = np.asarray(s.text_codes)
        if is_dna:
            pk = np.asarray(s.text_packed)
            packed[t, :pk.shape[0]] = pk
    meta = np.zeros((5, T), np.int32)
    meta[0] = [s.n_real for s in stores]
    meta[1] = [s.n_pad for s in stores]
    meta[2] = np.asarray(offsets, np.int32)
    meta[3] = [b[0] for b in bounds]
    meta[4] = [b[1] for b in bounds]
    for t, s in enumerate(stores):
        tl = int(meta[4][t]) - int(meta[2][t])    # true text length
        if not (0 <= int(meta[3][t]) - int(meta[2][t]) < tl <= s.n_real):
            raise ValueError(
                f"tier {t}: bounds ({int(meta[3][t])}, {int(meta[4][t])}) "
                f"inconsistent with offset={int(meta[2][t])}, "
                f"n_real={s.n_real}")
    overlaps = meta[3] - meta[2]                  # lo - offset, per tier
    mq1 = max(s.max_query_len for s in stores) - 1
    edge = max(int(overlaps.max()), mq1, 1)
    OV = 1 << (edge - 1).bit_length()
    K = rows.bit_length()                         # rows is a power of 2
    BIG = np.int32(2**30)
    ov_rank = np.full((T, OV), BIG, np.int32)
    hi_rank = np.full((T, OV), BIG, np.int32)
    pad_cnt = np.zeros((T, rows + 1), np.int32)
    rmq = np.full((T, K, rows), BIG, np.int32)
    for t, s in enumerate(stores):
        sa_t = sa[t, :s.n_pad]
        ov_t = int(overlaps[t])
        tl = int(meta[4][t]) - int(meta[2][t])
        in_ov = np.flatnonzero(sa_t < ov_t)
        ov_rank[t, sa_t[in_ov]] = in_ov
        at_end = np.flatnonzero((sa_t >= max(tl - OV, 0)) & (sa_t < tl))
        hi_rank[t, tl - 1 - sa_t[at_end]] = at_end
        pad_cnt[t, 1:s.n_pad + 1] = np.cumsum(sa_t >= tl)
        pad_cnt[t, s.n_pad + 1:] = pad_cnt[t, s.n_pad]
        rmq[t, 0, :s.n_pad] = np.where(
            (sa_t >= ov_t) & (sa_t < tl),
            sa_t + int(meta[2][t]), BIG)
        for k in range(1, K):
            h = 1 << (k - 1)
            rmq[t, k, :rows - h] = np.minimum(rmq[t, k - 1, :rows - h],
                                              rmq[t, k - 1, h:])
            rmq[t, k, rows - h:] = rmq[t, k - 1, rows - h:]
    return TierStack(
        text_packed=jnp.asarray(packed) if is_dna else None,
        text_codes=jnp.asarray(codes),
        sa=jnp.asarray(sa),
        n_real=jnp.asarray(meta[0]), n_rows=jnp.asarray(meta[1]),
        offset=jnp.asarray(meta[2]), lo=jnp.asarray(meta[3]),
        hi=jnp.asarray(meta[4]),
        ov_rank=jnp.asarray(ov_rank), hi_rank=jnp.asarray(hi_rank),
        pad_cnt=jnp.asarray(pad_cnt), rmq=jnp.asarray(rmq),
        num_tiers=T, rows=rows, is_dna=is_dna,
        max_query_len=min(s.max_query_len for s in stores))


def _finalize_store(codes: np.ndarray, sa, n_pad: int, *, is_dna: bool,
                    max_query_len: int) -> TabletStore:
    n_real = int(codes.shape[0])
    text_packed = jnp.asarray(codec.pack_2bit(codes)) if is_dna else None
    # generic code array padded with -1 so out-of-range gathers sort low
    text_codes = jnp.asarray(
        np.pad(codes.astype(np.int32), (0, n_pad - n_real),
               constant_values=-1))
    return TabletStore(text_packed=text_packed, text_codes=text_codes,
                       sa=jnp.asarray(sa, jnp.int32), n_real=n_real,
                       n_pad=n_pad, is_dna=bool(is_dna),
                       max_query_len=max_query_len)


def store_from_arrays(codes, sa_real, *, is_dna: bool,
                      max_query_len: int = 128, num_tablets: int = 1,
                      min_rows: int = 0) -> TabletStore:
    """Assemble a store from the text and its (already built) real-row
    suffix array — the restore path of ``repro.api.SuffixTable``: a table
    persisted on one device count is re-padded here for any other.

    Pad rows (positions n_real..n_pad-1) sort before all real rows and
    are inert for queries; their canonical order matches the distributed
    builder's: the pad suffix at position q is a run of (n_pad - q)
    minimal symbols and shorter runs are prefixes, so they sort ascending
    by run length, i.e. positions n_pad-1, n_pad-2, ..., n_real.

    ``min_rows`` raises n_pad beyond the num_tablets multiple.  (The
    memtable/run stores no longer use it — ``n_real`` is a static jit
    field, so they bucket the TEXT itself instead; see
    ``repro.api.runs.padded_segment_store``.)
    """
    codes = np.asarray(codes)
    sa_real = np.asarray(sa_real, np.int32)
    n_real = int(codes.shape[0])
    if sa_real.shape[0] != n_real:
        raise ValueError(f"sa_real has {sa_real.shape[0]} rows for "
                         f"{n_real} text symbols")
    p = num_tablets
    m = int(np.ceil(max(n_real, min_rows, 1) / p))
    n_pad = m * p
    pads = np.arange(n_pad - 1, n_real - 1, -1, dtype=np.int32)
    sa = jnp.asarray(np.concatenate([pads, sa_real]))
    return _finalize_store(codes, sa, n_pad, is_dna=bool(is_dna),
                           max_query_len=max_query_len)


def place_on_mesh(store: TabletStore, mesh,
                  axis_name: str = "tablets") -> TabletStore:
    """Commit a store to a tablet mesh: SA rows split into one
    contiguous tablet per device, text replicated on every device — the
    layouts the sharded scans read (``core/planner.py``), so a batch
    moves only its patterns, never the index."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    rows = NamedSharding(mesh, P(axis_name))
    everywhere = NamedSharding(mesh, P())
    text = {k: jax.device_put(v, everywhere)
            for k, v in (("text_packed", store.text_packed),
                         ("text_codes", store.text_codes)) if v is not None}
    return dataclasses.replace(store, sa=jax.device_put(store.sa, rows),
                               **text)


def build_tablet_store(codes, *, is_dna: bool | None = None,
                       max_query_len: int = 128,
                       num_tablets: int = 1,
                       min_rows: int = 0,
                       mesh=None, axis_name: str | None = None,
                       method: str = "bitonic") -> TabletStore:
    """Build the store.  Single-device when mesh is None, otherwise the
    distributed builder (paper's pre-processing phase on the cluster)."""
    codes = np.asarray(codes)
    if is_dna is None:
        is_dna = codes.size > 0 and codes.max() < 4

    if mesh is None:
        sa_real = build_suffix_array(codes.astype(np.int32))
        return store_from_arrays(codes, np.asarray(sa_real),
                                 is_dna=bool(is_dna),
                                 max_query_len=max_query_len,
                                 num_tablets=num_tablets,
                                 min_rows=min_rows)
    assert axis_name is not None
    sa, _pad, _ = build_suffix_array_distributed(codes, mesh, axis_name,
                                                 method=method)
    return _finalize_store(codes, sa, int(sa.shape[0]),
                           is_dna=bool(is_dna), max_query_len=max_query_len)
