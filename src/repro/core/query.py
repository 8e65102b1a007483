"""Pattern-match queries over a TabletStore (paper §V "scans").

A scan is a batched lower/upper-bound binary search over the sorted suffix
array.  The paper's "50 user threads" become the batch axis; each search
round gathers one suffix window per query and compares it against the
pattern in a single dense VMEM op (the Pallas ``pattern_scan`` kernel on
TPU; the jnp path below is the oracle and the CPU fallback).

Distributed mode mirrors an Accumulo scan fan-out: every tablet performs
the search on its local rows; because lower/upper bounds are ADDITIVE over
contiguous tablets, the global bound is a single ``psum`` — one scalar per
query crosses the wire, not rows (DESIGN.md §2).

Callers should not pick between ``query`` / ``query_sharded`` /
``query_routed`` directly: ``repro.core.planner.ScanPlanner`` selects the
execution mode, retries the routed path's sentinel counts (-1 dispatch
overflow, -2 saturated run — see ``query_routed``) to exact values, and
adds match enumeration + caching.  See docs/scan_planner.md.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.core import codec
from repro.core.tablet import TabletStore

WORD = codec.BASES_PER_WORD


@partial(jax.tree_util.register_dataclass,
         data_fields=("found", "count", "first_rank", "first_pos"),
         meta_fields=())
@dataclasses.dataclass(frozen=True)
class MatchResult:
    """Outcome of one batch of scans (paper Table II columns)."""
    found: jnp.ndarray       # (B,)  bool    — paper's ``outcome``
    count: jnp.ndarray       # (B,)  int32   — number of occurrences
    first_rank: jnp.ndarray  # (B,)  int32   — row index in the real SA
    first_pos: jnp.ndarray   # (B,)  int32   — text position of first match


@partial(jax.tree_util.register_dataclass,
         data_fields=("found", "count", "first_rank", "first_pos",
                      "text_min"),
         meta_fields=())
@dataclasses.dataclass(frozen=True)
class FMMatchResult(MatchResult):
    """A frozen-tier base read (``kernels.ops.fm_search``): the
    MatchResult, plus the text-order minimum over each query's whole
    match range where the launch settled it (``fm_scan.finish_match``)."""
    text_min: jnp.ndarray    # (2, B) int32 — [0] smallest text position
    #                          (-1 when none), [1] 1 where settled


# ---------------------------------------------------------------------------
# Pattern encoding
# ---------------------------------------------------------------------------
def encode_patterns(patterns: list[str], max_len: int):
    """list of DNA strings -> (codes (B, max_len) int32 zero-padded,
    packed (B, W) uint32, lengths (B,) int32), as device arrays.  A thin
    wrapper over :func:`repro.core.codec.encode_pattern_batch`, whose
    host arrays the table's read path uses instead; raises its
    ``ValueError``s."""
    codes, lengths = codec.encode_pattern_batch(patterns, max_len,
                                                packed=False)
    packed = codec.pack_2bit_batch(codes)
    return jnp.asarray(codes), jnp.asarray(packed), jnp.asarray(lengths)


def random_patterns(num: int, min_len: int = 1, max_len: int = 100,
                    seed: int = 0):
    """The paper's workload: random ACGT patterns, uniform length 1..100."""
    rng = np.random.default_rng(seed)
    lengths = rng.integers(min_len, max_len + 1, size=num)
    pats = ["".join(codec.DNA_ALPHABET[c]
                    for c in rng.integers(0, 4, size=int(L)))
            for L in lengths]
    return pats


# ---------------------------------------------------------------------------
# Packed compare (DNA fast path): suffix-vs-pattern at depth `plen`
# ---------------------------------------------------------------------------
def _word_masks(plen: jnp.ndarray, n_words: int) -> jnp.ndarray:
    """(B, n_words) uint32 masks keeping the first ``plen`` bases."""
    w = jnp.arange(n_words, dtype=jnp.int32)[None, :]
    r = jnp.clip(plen[:, None] - w * WORD, 0, WORD).astype(jnp.uint32)
    full = jnp.uint32(0xFFFFFFFF)
    partial_mask = jnp.where(
        r == 0, jnp.uint32(0),
        jnp.where(r == WORD, full, ~((jnp.uint32(1) << (32 - 2 * r)) - 1)))
    return partial_mask


def compare_windows_packed(window: jnp.ndarray, pos: jnp.ndarray,
                           n_real, patt_packed: jnp.ndarray,
                           plen: jnp.ndarray):
    """Returns (lt, eq) for pre-extracted packed ``window`` rows (B, W).
    ``n_real`` may be a scalar or a per-row vector — rows of a fused
    multi-store compare come from different texts."""
    n_words = patt_packed.shape[-1]
    mask = _word_masks(plen, n_words)
    a = window & mask
    b = patt_packed & mask
    eq_w = a == b
    prefix_eq = jnp.cumprod(eq_w.astype(jnp.int32), axis=-1)
    prefix_eq_shifted = jnp.concatenate(
        [jnp.ones_like(prefix_eq[:, :1]), prefix_eq[:, :-1]], axis=-1)
    first_diff = (~eq_w) & (prefix_eq_shifted == 1)
    lt_raw = jnp.any(first_diff & (a < b), axis=-1)
    eq_all = jnp.all(eq_w, axis=-1)
    truncated = pos + plen > n_real            # suffix shorter than pattern
    lt = lt_raw | (eq_all & truncated)
    eq = eq_all & ~truncated
    return lt, eq


def compare_packed(packed_text: jnp.ndarray, n_real: int,
                   pos: jnp.ndarray, patt_packed: jnp.ndarray,
                   plen: jnp.ndarray):
    """Returns (lt, eq): suffix(pos) < pattern, suffix starts-with pattern.
    All (B,) bool.  Handles text-boundary truncation exactly."""
    window = codec.extract_window(packed_text, pos, patt_packed.shape[-1])
    return compare_windows_packed(window, pos, n_real, patt_packed, plen)


def gather_suffix_codes(codes: jnp.ndarray, n_real, pos: jnp.ndarray,
                        length: int) -> jnp.ndarray:
    """(B, length) int32 suffix windows at ``pos``; reads past ``n_real``
    come back -1 (< any real code), which is what makes truncated
    suffixes sort first without an explicit fix-up."""
    offs = jnp.arange(length, dtype=jnp.int32)[None, :]
    idx = pos[:, None] + offs
    return jnp.where(idx < n_real,
                     jnp.take(codes, jnp.clip(idx, 0, codes.shape[0] - 1)),
                     -1)


def compare_suffix_codes(suf: jnp.ndarray, patt_codes: jnp.ndarray,
                         plen: jnp.ndarray):
    """(lt, eq) for pre-gathered token suffix windows (B, L)."""
    L = patt_codes.shape[-1]
    offs = jnp.arange(L, dtype=jnp.int32)[None, :]
    valid = offs < plen[:, None]
    eq_w = jnp.where(valid, suf == patt_codes, True)
    prefix_eq = jnp.cumprod(eq_w.astype(jnp.int32), axis=-1)
    prefix_eq_shifted = jnp.concatenate(
        [jnp.ones_like(prefix_eq[:, :1]), prefix_eq[:, :-1]], axis=-1)
    first_diff = (~eq_w) & (prefix_eq_shifted == 1)
    lt = jnp.any(first_diff & (suf < patt_codes), axis=-1)
    eq = jnp.all(eq_w, axis=-1)
    return lt, eq


def compare_codes(codes: jnp.ndarray, n_real: int,
                  pos: jnp.ndarray, patt_codes: jnp.ndarray,
                  plen: jnp.ndarray):
    """Generic token path (vocab-sized alphabets).  codes is the padded
    int32 text; out-of-range reads are -1 (< any real code)."""
    suf = gather_suffix_codes(codes, n_real, pos, patt_codes.shape[-1])
    return compare_suffix_codes(suf, patt_codes, plen)


def _compare(store: TabletStore, pos, patt, plen):
    if store.is_dna and patt.dtype == jnp.uint32:
        return compare_packed(store.text_packed, store.n_real, pos, patt, plen)
    return compare_codes(store.text_codes, store.n_real, pos, patt, plen)


# ---------------------------------------------------------------------------
# Batched binary search
# ---------------------------------------------------------------------------
def _bounded_search(sa: jnp.ndarray, pred_fn, batch: int, n_rows: int,
                    varying_axis=None):
    """Per-query first index in [0, n_rows] where pred(sa[idx]) is False.
    pred = 'suffix is still before the target'.  ``varying_axis``: when run
    inside shard_map with a device-varying ``sa``, the loop carry must be
    marked varying over that axis (VMA tracking)."""
    steps = max(1, int(np.ceil(np.log2(n_rows + 1))))

    def body(_, lohi):
        lo, hi = lohi
        mid = (lo + hi) // 2
        pos = jnp.take(sa, jnp.clip(mid, 0, n_rows - 1))
        pred = pred_fn(pos)
        active = lo < hi
        lo = jnp.where(active & pred, mid + 1, lo)
        hi = jnp.where(active & ~pred, mid, hi)
        return lo, hi

    lo = jnp.zeros((batch,), jnp.int32)
    hi = jnp.full((batch,), n_rows, jnp.int32)
    if varying_axis is not None:
        lo = lax.pcast(lo, varying_axis, to="varying")
        hi = lax.pcast(hi, varying_axis, to="varying")
    lo, _ = lax.fori_loop(0, steps, body, (lo, hi))
    return lo


def query(store: TabletStore, patt, plen) -> MatchResult:
    """Single-device scan batch.  ``patt`` is packed uint32 (B, W) for DNA or
    int32 codes (B, L) for token corpora; ``plen`` (B,) int32."""
    B = patt.shape[0]
    n = store.n_pad

    lb = _bounded_search(
        store.sa, lambda pos: _compare(store, pos, patt, plen)[0], B, n)
    ub = _bounded_search(
        store.sa,
        lambda pos: (lambda lt, eq: lt | eq)(*_compare(store, pos, patt, plen)),
        B, n)
    count = ub - lb
    found = count > 0
    first_pos = jnp.take(store.sa, jnp.clip(lb, 0, n - 1))
    first_pos = jnp.where(found, first_pos, -1)
    first_rank = jnp.where(found, lb - store.pad_count, -1)
    return MatchResult(found=found, count=count,
                       first_rank=first_rank, first_pos=first_pos)


# ---------------------------------------------------------------------------
# Distributed scan (inside shard_map): additive bounds + one psum
# ---------------------------------------------------------------------------
def query_sharded(sa_local: jnp.ndarray, store_meta: TabletStore,
                  patt, plen, axis_name) -> MatchResult:
    """Paper-faithful Accumulo fan-out: every tablet searches its local rows
    for every query.  ``sa_local`` is this device's tablet (m rows);
    ``store_meta`` carries the (replicated) text and static metadata — its
    ``sa`` field is ignored.  Returns replicated MatchResult."""
    m = sa_local.shape[0]
    p = lax.psum(1, axis_name)
    d = lax.axis_index(axis_name)
    B = patt.shape[0]

    # both bounds in one search over 2B slots: half the loop iterations
    # (and launched operations) of two searches, each as wide as both
    upper = lax.broadcasted_iota(jnp.int32, (2 * B,), 0) >= B
    patt2 = lax.concatenate([patt, patt], 0)
    plen2 = lax.concatenate([plen, plen], 0)

    def before(pos):
        lt, eq = _compare(store_meta, pos, patt2, plen2)
        return lt | (upper & eq)

    bounds = lax.psum(_bounded_search(sa_local, before, 2 * B, m,
                                      varying_axis=axis_name), axis_name)
    lb, ub = lax.slice(bounds, (0,), (B,)), lax.slice(bounds, (B,), (2 * B,))
    count = ub - lb
    found = count > 0
    # tablet owning the global lower bound: lb in [d*m, (d+1)*m)
    owner_is_me = (lb >= d * m) & (lb < (d + 1) * m)
    local_idx = jnp.clip(lb - d * m, 0, m - 1)
    mine = jnp.where(owner_is_me, jnp.take(sa_local, local_idx), 0)
    first_pos = lax.psum(mine, axis_name)
    first_pos = jnp.where(found, first_pos, -1)
    pad_count = store_meta.n_pad - store_meta.n_real
    first_rank = jnp.where(found, lb - pad_count, -1)
    return MatchResult(found=found, count=count,
                       first_rank=first_rank, first_pos=first_pos)


# ---------------------------------------------------------------------------
# Oracle (naive scan, paper Algorithm 1) for tests
# ---------------------------------------------------------------------------
def brute_force_count(text_codes: np.ndarray, pattern_codes: np.ndarray):
    """BruteForceSearch of paper Algorithm 1, returning (count, first_pos)."""
    n, k = len(text_codes), len(pattern_codes)
    count, first = 0, -1
    for i in range(n - k + 1):
        if (text_codes[i:i + k] == pattern_codes).all():
            count += 1
            if first < 0:
                first = i
    return count, first


# ---------------------------------------------------------------------------
# Routed scan (beyond-paper): queries travel to their owner tablet instead
# of broadcasting to all tablets.  Per-device work drops from O(B log m) to
# O(B/p log m); the price is two fixed-capacity all_to_alls (the same
# capacity-factor pattern as MoE dispatch).  Overflowed queries (hot tablet)
# come back with count = -1 — callers retry via the broadcast path.
# ---------------------------------------------------------------------------
def query_routed(sa_local: jnp.ndarray, store_meta: TabletStore,
                 patt, plen, axis_name, capacity_factor: float = 2.0
                 ) -> MatchResult:
    """Inside shard_map: ``patt``/``plen`` are the LOCAL query shard
    (B_local, W)/(B_local,).  Returns local-shard MatchResult."""
    m = sa_local.shape[0]
    p = lax.psum(1, axis_name)
    d = lax.axis_index(axis_name)
    Bl = patt.shape[0]
    W = patt.shape[1]

    # --- split keys: first suffix window of every tablet (replicated)
    first_pos = sa_local[:1]
    my_key = codec.extract_window(store_meta.text_packed, first_pos, W)
    split_keys = lax.all_gather(my_key[0], axis_name)          # (p, W)
    split_pos = lax.all_gather(first_pos[0], axis_name)        # (p,)

    # --- owner tablet per query: the tablet holding the global lower
    # bound.  a = #{tablets whose FIRST suffix < P} (strict); the lb row
    # lives in tablet a-1 (or its successor when lb sits exactly on the
    # boundary — the spill-correction pass below covers that case).
    def lt_count(q_patt, q_len):
        lt, _eq = compare_packed(store_meta.text_packed, store_meta.n_real,
                                 split_pos, jnp.broadcast_to(q_patt, (p, W)),
                                 jnp.broadcast_to(q_len, (p,)))
        return jnp.sum(lt.astype(jnp.int32))

    a = jax.vmap(lt_count)(patt, plen)                         # (Bl,)
    owner = jnp.clip(a - 1, 0, p - 1)

    # --- fixed-capacity dispatch to owners
    cap = max(4, int(np.ceil(Bl / p * capacity_factor)))
    order = jnp.argsort(owner, stable=True)
    o_s = owner[order]
    start = jnp.searchsorted(o_s, jnp.arange(p, dtype=jnp.int32))
    slot_in = jnp.arange(Bl, dtype=jnp.int32) - start[o_s]
    ok = slot_in < cap
    slot = jnp.where(ok, o_s * cap + slot_in, p * cap)

    def scatter(x, fill):
        buf = jnp.full((p * cap,) + x.shape[1:], fill, x.dtype)
        return buf.at[slot].set(jnp.where(
            ok.reshape((-1,) + (1,) * (x.ndim - 1)), x[order], fill),
            mode="drop")

    send_patt = scatter(patt, jnp.uint32(0)).reshape(p, cap, W)
    send_len = scatter(plen, jnp.int32(-1)).reshape(p, cap)
    recv_patt = lax.all_to_all(send_patt, axis_name, 0, 0).reshape(-1, W)
    recv_len = lax.all_to_all(send_len, axis_name, 0, 0).reshape(-1)

    # --- local search on my tablet only (lower bound clamps to my range)
    valid = recv_len >= 0
    rl = jnp.where(valid, recv_len, 1)
    local_lb = _bounded_search(
        sa_local, lambda pos: _compare(store_meta, pos, recv_patt, rl)[0],
        p * cap, m, varying_axis=axis_name)
    local_ub = _bounded_search(
        sa_local,
        lambda pos: (lambda lt, eq: lt | eq)(
            *_compare(store_meta, pos, recv_patt, rl)), p * cap, m,
        varying_axis=axis_name)
    # matches may spill into later tablets; count here covers the owner
    # tablet; spill is detected when ub hits the tablet end and the last
    # row still prefix-matches -> handled by one psum'd correction pass
    # against the NEXT tablet only (suffix order bounds the spill for
    # patterns shorter than the tablet span; exactness verified in tests).
    cnt = local_ub - local_lb
    fpos = jnp.where(cnt > 0,
                     jnp.take(sa_local, jnp.clip(local_lb, 0, m - 1)), -1)
    frank = jnp.where(cnt > 0, d * m + local_lb
                      - (store_meta.n_pad - store_meta.n_real), -1)

    # spill correction: ask the RIGHT neighbour how many of its rows
    # continue the match (ub == m means the run may continue).  Tablet d
    # evaluates the queries OWNED BY d-1, so patterns travel right
    # (r -> r+1) and results travel back left (r -> r-1).
    # (no spill past the last tablet — the ppermute ring wraps to tablet 0,
    # whose rows are the globally smallest suffixes, not a continuation)
    spill_possible = (cnt >= 0) & (local_ub == m) & valid & (d < p - 1)
    perm_right = [(r, (r + 1) % p) for r in range(p)]
    perm_left = [(r, (r - 1) % p) for r in range(p)]
    nb_patt = lax.ppermute(recv_patt, axis_name, perm_right)
    nb_len = lax.ppermute(rl, axis_name, perm_right)
    nb_lb = _bounded_search(
        sa_local, lambda pos: _compare(store_meta, pos, nb_patt, nb_len)[0],
        p * cap, m, varying_axis=axis_name)
    nb_ub = _bounded_search(
        sa_local,
        lambda pos: (lambda lt, eq: lt | eq)(
            *_compare(store_meta, pos, nb_patt, nb_len)), p * cap, m,
        varying_axis=axis_name)
    nb_cnt = nb_ub - nb_lb                       # neighbour's matching run
    spill_cnt = lax.ppermute(nb_cnt, axis_name, perm_left)
    spill_sat = lax.ppermute(nb_ub == m, axis_name, perm_left)
    spill_first = lax.ppermute(
        jnp.where(nb_cnt > 0, jnp.take(sa_local,
                                       jnp.clip(nb_lb, 0, m - 1)), -1),
        axis_name, perm_left)
    # global SA row of the neighbour's run start (for first_rank when the
    # whole run lives in the neighbour: a match starting exactly at the
    # tablet boundary leaves the owner's local run empty)
    spill_rank = lax.ppermute(
        jnp.where(nb_cnt > 0,
                  d * m + nb_lb - (store_meta.n_pad - store_meta.n_real),
                  -1), axis_name, perm_left)
    cnt = jnp.where(spill_possible, cnt + spill_cnt, cnt)
    fpos = jnp.where((cnt > 0) & (fpos < 0), spill_first, fpos)
    frank = jnp.where((cnt > 0) & (frank < 0), spill_rank, frank)
    # match run crosses >2 tablets (very short pattern): exact count needs
    # the broadcast path — flag with -2 (found stays exact: run nonempty)
    saturated = spill_possible & spill_sat
    cnt = jnp.where(saturated, -2, cnt)

    # --- route results back
    back_cnt = lax.all_to_all(cnt.reshape(p, cap), axis_name, 0, 0
                              ).reshape(-1)
    back_pos = lax.all_to_all(fpos.reshape(p, cap), axis_name, 0, 0
                              ).reshape(-1)
    back_rank = lax.all_to_all(frank.reshape(p, cap), axis_name, 0, 0
                               ).reshape(-1)
    # un-permute into original query order
    out_cnt = jnp.full((Bl,), -1, jnp.int32)    # -1 => overflow, retry
    take_slot = jnp.where(ok, slot, p * cap)
    gathered = jnp.where(ok, back_cnt[jnp.clip(take_slot, 0, p * cap - 1)],
                         -1)
    out_cnt = out_cnt.at[order].set(gathered, mode="drop")
    g_pos = jnp.where(ok, back_pos[jnp.clip(take_slot, 0, p * cap - 1)], -1)
    g_rank = jnp.where(ok, back_rank[jnp.clip(take_slot, 0, p * cap - 1)],
                       -1)
    out_pos = jnp.zeros((Bl,), jnp.int32).at[order].set(g_pos, mode="drop")
    out_rank = jnp.zeros((Bl,), jnp.int32).at[order].set(g_rank,
                                                         mode="drop")
    # count: >0 exact | 0 no match | -1 dispatch overflow (retry)
    #        | -2 saturated run (found=True, exact count via broadcast)
    found = (out_cnt > 0) | (out_cnt == -2)
    return MatchResult(found=found, count=out_cnt,
                       first_rank=jnp.where(found, out_rank, -1),
                       first_pos=jnp.where(found, out_pos, -1))
