"""Major compaction by MERGING — fold appended runs into the base SA
without rebuilding it from scratch.

``SuffixTable.compact()`` used to concatenate the text and re-run the full
prefix-doubling builder over all of it, so compacting a 1% append delta
cost the same as the original build.  The merge here exploits the store's
actual query contract: every compare is depth-capped at ``max_query_len``
(= L), so the suffix array only has to be sorted by each suffix's first L
symbols.  Appending ``d`` symbols perturbs that key for just the *dirty*
suffixes — the ones starting within L-1 of the old end — leaving the
``n0 - L + 1`` *clean* entries of the old SA correctly ordered as they
stand.  So:

1. **dirty-range doubling** — run the existing prefix-doubling builder
   over only the text tail ``combined[n0 - (L-1):]`` (``d + L - 1``
   symbols).  Every dirty/new suffix extends to the text end, so the
   tail's suffix array IS their true relative order.
2. **batched merge** — binary-search each dirty/new suffix's insertion
   point into the clean sequence, comparing its depth-L window (packed
   uint32 words for DNA — the same word compare as
   ``kernels/pattern_scan`` — int32 codes otherwise) against the clean
   suffixes; then one vectorized ``np.insert`` interleaves both orders.

Cost: ``O((d + L) log(d + L))`` for step 1 plus ``(d + L)·log(n0)``
depth-L compares for step 2 — versus ``O((n0 + d) log(n0 + d))`` full
doubling rounds for the rebuild.  ``benchmarks/compaction_bench.py``
reports the measured ratio.

Tie semantics: suffixes sharing an entire L-symbol window (impossible for
random text at L=128, routine for adversarial repeats) are ordered with
the new/dirty entries first (the lower-bound insertion lands before equal
clean entries), in true suffix order among themselves — any order inside
such a block satisfies every depth-capped query, so counts and positions
stay exact; only ``first_rank``-order cosmetics may differ from a
from-scratch build on such inputs (see tests/test_compaction.py).

All searches run inside one jitted kernel with power-of-two padded
shapes, so repeated compactions specialize O(log) times, not once per
delta size.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.api.runs import bucket_rows as _pow2   # one padding policy:
from repro.core import codec                       # shared jit buckets
from repro.core import query as Q
from repro.core.suffix_array import build_suffix_array


def _search_body(compare_lt, clean_pad, n_clean, patt, plen):
    """First index in [0, n_clean] whose clean suffix is NOT < the query
    window — lower-bound insertion, vectorized over the query batch.
    ``n_clean`` is dynamic (clean_pad is power-of-two padded), so the loop
    runs ceil(log2(len(clean_pad)+1)) steps with a dynamic ``hi``."""
    M = clean_pad.shape[0]
    steps = max(1, int(np.ceil(np.log2(M + 1))))
    B = patt.shape[0]
    lo = jnp.zeros((B,), jnp.int32)
    hi = jnp.broadcast_to(n_clean.astype(jnp.int32), (B,))

    def body(_, lohi):
        lo, hi = lohi
        mid = (lo + hi) // 2
        pos = jnp.take(clean_pad, jnp.clip(mid, 0, M - 1))
        lt = compare_lt(pos)
        active = lo < hi
        lo = jnp.where(active & lt, mid + 1, lo)
        hi = jnp.where(active & ~lt, mid, hi)
        return lo, hi

    lo, _ = lax.fori_loop(0, steps, body, (lo, hi))
    return lo


@jax.jit
def _insertions_packed(clean_pad, n_clean, packed, n_real, patt, plen):
    """DNA path: depth-L windows as packed uint32 words, word compare."""
    return _search_body(
        lambda pos: Q.compare_packed(packed, n_real, pos, patt, plen)[0],
        clean_pad, n_clean, patt, plen)


@jax.jit
def _insertions_codes(clean_pad, n_clean, codes, n_real, patt, plen):
    """Token path: depth-L windows as int32 code rows."""
    return _search_body(
        lambda pos: Q.compare_codes(codes, n_real, pos, patt, plen)[0],
        clean_pad, n_clean, patt, plen)


def merge_delta_sa(combined: np.ndarray, n0: int, base_sa_real: np.ndarray,
                   *, is_dna: bool, max_query_len: int) -> np.ndarray:
    """Real-row suffix array of ``combined`` (= old text of length ``n0``
    plus the appended delta), merged from ``base_sa_real`` instead of
    rebuilt.  Falls back to the full builder when the base is smaller
    than one compare window (nothing clean to keep)."""
    combined = np.asarray(combined)
    n1 = int(combined.shape[0])
    n0 = int(n0)
    d = n1 - n0
    L = int(max_query_len)
    if d <= 0:
        return np.asarray(base_sa_real, np.int32)
    if n0 <= L:
        return np.asarray(build_suffix_array(combined.astype(np.int32)))

    base_sa_real = np.asarray(base_sa_real, np.int32)
    if base_sa_real.shape[0] != n0:
        raise ValueError(f"base SA has {base_sa_real.shape[0]} rows for "
                         f"{n0} base symbols")
    cut = n0 - L                           # clean suffixes: start <= cut
    clean = base_sa_real[base_sa_real <= cut]            # (n0 - L + 1,)

    # 1) dirty-range doubling: suffixes starting in [cut+1, n1) all run to
    # the text end, so the tail's SA is their true mutual order.
    tail = combined[cut + 1:]
    sa_tail = np.asarray(build_suffix_array(tail.astype(np.int32)))
    new_pos = sa_tail.astype(np.int64) + (cut + 1)       # (d + L - 1,)
    B = int(new_pos.shape[0])
    plen = np.minimum(L, n1 - new_pos).astype(np.int32)

    # 2) batched lower-bound merge, shapes power-of-two padded so the
    # jitted search recompiles O(log) times across compactions.
    Bp = _pow2(B)
    pos_p = np.concatenate(
        [new_pos, np.zeros(Bp - B, np.int64)]).astype(np.int32)
    plen_p = np.concatenate([plen, np.ones(Bp - B, np.int32)])
    Mc = int(clean.shape[0])
    clean_pad = np.concatenate(
        [clean, np.zeros(_pow2(Mc) - Mc, np.int32)])
    n_clean = jnp.asarray(Mc, jnp.int32)

    if is_dna:
        W = codec.packed_length(L)
        packed = codec.pack_2bit(combined)
        packed = np.concatenate(
            [packed, np.zeros(_pow2(packed.shape[0]) - packed.shape[0],
                              np.uint32)])
        patt = codec.extract_window(jnp.asarray(packed),
                                    jnp.asarray(pos_p), W)
        ins = _insertions_packed(jnp.asarray(clean_pad), n_clean,
                                 jnp.asarray(packed),
                                 jnp.asarray(n1, jnp.int32),
                                 patt, jnp.asarray(plen_p))
    else:
        codes32 = combined.astype(np.int32)
        codes_pad = np.concatenate(
            [codes32, np.full(_pow2(n1) - n1, -1, np.int32)])
        offs = np.arange(L, dtype=np.int64)
        idx = pos_p.astype(np.int64)[:, None] + offs[None, :]
        patt = np.where(idx < n1, codes_pad[np.clip(idx, 0, n1 - 1)], -1)
        ins = _insertions_codes(jnp.asarray(clean_pad), n_clean,
                                jnp.asarray(codes_pad),
                                jnp.asarray(n1, jnp.int32),
                                jnp.asarray(patt),
                                jnp.asarray(plen_p))

    ins = np.asarray(ins)[:B]
    # np.insert places values before clean[ins[k]], preserving the given
    # (true suffix) order among entries that share an insertion point.
    return np.insert(clean, ins, new_pos.astype(np.int32))
