"""Jit'd public wrappers around the Pallas kernels.

On CPU the kernels execute via ``interpret=True``; on TPU
they compile to Mosaic.  Wrappers handle padding to kernel block multiples
and layout transposition so callers keep natural (B, W) shapes.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import codec
from repro.core import query as _q
from repro.kernels import fm_scan as _fm
from repro.kernels import pack2bit as _pk
from repro.kernels import pattern_scan as _ps
from repro.kernels import tablet_scan as _ts
from repro.kernels import tier_scan as _tier


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _pad_to(x, mult, axis, fill=0):
    n = x.shape[axis]
    pad = (-n) % mult
    if pad == 0:
        return x, n
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=fill), n


def pack2bit(codes) -> jnp.ndarray:
    """uint8 codes {0..3} -> packed uint32 words (kernel-backed)."""
    codes = jnp.asarray(codes)
    n = codes.shape[0]
    n_words = codec.packed_length(n)
    n_words_pad = int(np.ceil(n_words / _pk.BLOCK_WORDS)) * _pk.BLOCK_WORDS
    flat = jnp.zeros((n_words_pad * 16,), jnp.uint32).at[:n].set(
        codes.astype(jnp.uint32))
    lanes = flat.reshape(n_words_pad, 16).T          # slot-major (16, words)
    packed = _pk.pack2bit_pallas(lanes, interpret=_interpret())
    return packed[:n_words]


def pattern_compare(windows, patterns, plen, pos, *, n_real: int):
    """(B, W) windows/patterns, (B,) plen/pos -> (lt, le, eq) bool (B,)."""
    wt, B = _pad_to(windows.T.astype(jnp.uint32), _ps.BLOCK_B, 1)
    pt, _ = _pad_to(patterns.T.astype(jnp.uint32), _ps.BLOCK_B, 1)
    pl_, _ = _pad_to(plen.astype(jnp.int32), _ps.BLOCK_B, 0)
    po_, _ = _pad_to(pos.astype(jnp.int32), _ps.BLOCK_B, 0)
    lt, le, eq = _ps.pattern_compare_pallas(
        wt, pt, pl_, po_, n_real=n_real, interpret=_interpret())
    return (lt[:B].astype(bool), le[:B].astype(bool), eq[:B].astype(bool))


def tablet_scan(patterns, plen, windows, pos, *, n_real: int):
    """Linear scan of BR sorted-row windows by BQ patterns.
    patterns (BQ, W), plen (BQ,), windows (BR, W), pos (BR,).
    Returns (count, less, first_row) int32 (BQ,); first_row == 2**30 when
    no match.  Row padding uses pos=n_real & window=~0 so padded rows never
    match and never count as 'less'."""
    pt, BQ = _pad_to(patterns.T.astype(jnp.uint32), _ts.BLOCK_Q, 1)
    pl_, _ = _pad_to(plen.astype(jnp.int32), _ts.BLOCK_Q, 0, fill=1)
    wt, BR = _pad_to(windows.T.astype(jnp.uint32), _ts.BLOCK_R, 1)
    po_, _ = _pad_to(pos.astype(jnp.int32), _ts.BLOCK_R, 0, fill=n_real)
    count, less, first = _ts.tablet_scan_pallas(
        pt, pl_, wt, po_, n_real=n_real, n_rows=BR, interpret=_interpret())
    return count[:BQ], less[:BQ], first[:BQ]


def tier_scan(stack, patterns, plen):
    """Kernel-backed fused tier scan (DNA-packed tables only).
    patterns (B, W) uint32, plen (B,) int32; returns (count, less,
    matches, first_g) int32 (T, B) — same contract as
    ``tier_scan.fused_tier_scan``."""
    T = stack.num_tiers
    R = stack.rows
    W = patterns.shape[1]
    windows = jax.vmap(
        lambda pk, sa_t: codec.extract_window(pk, sa_t, W))(
            stack.text_packed, stack.sa)                    # (T, R, W)
    wt, _ = _pad_to(jnp.transpose(windows, (0, 2, 1)).astype(jnp.uint32),
                    _tier.BLOCK_R, 2)
    sa_p, _ = _pad_to(stack.sa.astype(jnp.int32), _tier.BLOCK_R, 1)
    pt, B = _pad_to(patterns.astype(jnp.uint32), _tier.BLOCK_Q, 0)
    pl_, _ = _pad_to(plen.astype(jnp.int32), _tier.BLOCK_Q, 0, fill=1)
    meta = jnp.zeros((T, 8), jnp.int32)
    meta = meta.at[:, 0].set(stack.n_real.astype(jnp.int32))
    meta = meta.at[:, 1].set(stack.n_rows.astype(jnp.int32))
    meta = meta.at[:, 2].set(stack.offset.astype(jnp.int32))
    meta = meta.at[:, 3].set(stack.lo.astype(jnp.int32))
    meta = meta.at[:, 4].set(stack.hi.astype(jnp.int32))
    count, less, matches, first = _tier.tier_scan_pallas(
        pt, pl_, wt, sa_p, meta, interpret=_interpret())
    return count[:, :B], less[:, :B], matches[:, :B], first[:, :B]


def tier_scan_auto(stack, patterns, plen):
    """Pick the Pallas tier kernel on TPU for packed-DNA batches; the jnp
    binary-search path everywhere else (it is also the oracle)."""
    if (not _interpret()) and stack.is_dna and patterns.dtype == jnp.uint32:
        return tier_scan(stack, patterns, plen)
    return _tier.fused_tier_scan(stack, patterns, plen)


@jax.jit
def fused_tiers(stack, patterns, plen):
    """One launch over all delta tiers: (count, less, matches, first_g),
    each (T, B) int32.  Used by mesh tables, where the base scan already
    runs inside its own shard_map dispatch."""
    return tier_scan_auto(stack, patterns, plen)


@jax.jit
def fused_single(store, stack, patterns, plen):
    """THE single-device merged read: base binary search + all delta
    tiers + the merge, one jitted launch end to end.  Returns
    (merged MatchResult, base MatchResult, (count, less, matches,
    first_g)).

    On the jnp path the base and tier searches share ONE fori_loop
    (``tier_scan.fused_table_scan``), so a merged read pays the serial
    depth of the deepest store, not the sum; with the Pallas kernel the
    dense tier scan rides its own launch next to the base search."""
    if (not _interpret()) and stack.is_dna and patterns.dtype == jnp.uint32:
        base = _q.query(store, patterns, plen)
        tiers = tier_scan(stack, patterns, plen)
    else:
        base, tiers = _tier.fused_table_scan(store, stack, patterns, plen)
    merged = _tier.merge_tier_results(base, tiers[0], tiers[3])
    return merged, base, tiers


@jax.jit
def fm_search(arrays, patterns, plen):
    """Frozen-tier base read: FM backward search + one LF walk over every
    hit of the batch (``fm_scan.finish_match``), a single jitted launch.
    Same MatchResult contract as ``query``, plus ``text_min``: the
    text-order minimum of each settled query's matches, so the table
    walks on the host only the ranges the launch did not settle.  The
    search is XLA on every backend (the index stays in HBM)."""
    if arrays.is_dna and patterns.dtype == jnp.uint32:
        syms = _fm.syms_from_packed(patterns, plen, patterns.shape[1] * 16)
    else:
        syms = _fm.syms_from_codes(patterns, plen, patterns.shape[1])
    lo, hi = _fm.search_syms(arrays, syms)
    found, count, first_rank, first_pos, text_min = _fm.finish_match(
        arrays, lo, hi)
    return _q.FMMatchResult(found=found, count=count, first_rank=first_rank,
                            first_pos=first_pos, text_min=text_min)
