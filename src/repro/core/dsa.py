"""Distributed suffix-array construction (paper §IV pre-processing phase).

Prefix doubling where every sort is a distributed sort over the mesh axis
(``dsort``): each device ever holds only n/p rows — this is the Accumulo
tablet-ingest analogue.  The text is padded to p*m with a virtual minimal
symbol (initial rank -1, smaller than every real code), which (a) keeps
blocks equal-size for the collectives and (b) makes suffix order of real
positions identical to the unpadded text (a run of minimal symbols is the
standard ``$`` terminator generalized).  Pad suffixes occupy the first
``pad_count`` rows of the sorted order; queries are unaffected because all
real patterns compare greater than the pad symbol.

All functions here run INSIDE shard_map over ``axis_name``.
``build_suffix_array_distributed`` is the host-side convenience wrapper.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.core.dsort import (bitonic_sort_sharded, sample_sort_sharded,
                              sort_sharded_auto)
from repro.distributed.sharding import mesh_axis_size


def _axis_size(axis_name) -> int:
    return lax.psum(1, axis_name)


def _sort(operands, num_keys, axis_name, method):
    if method == "sample":
        return sort_sharded_auto(operands, num_keys=num_keys,
                                 axis_name=axis_name)
    if method == "sample_unsafe":  # dry-run/roofline: pure sample-sort HLO
        out, _ = sample_sort_sharded(operands, num_keys=num_keys,
                                     axis_name=axis_name)
        return out
    return bitonic_sort_sharded(operands, num_keys=num_keys,
                                axis_name=axis_name)


def _shift_ranks(rank, k: int, n_pad: int, axis_name):
    """nxt[i] = rank[gpos_i + k] in text-order sharding, -1 past the end.
    k is a static Python int; the source spans <= 2 neighbour blocks."""
    p = _axis_size(axis_name)
    m = rank.shape[0]
    d = lax.axis_index(axis_name)
    s0 = (k // m) % p
    perm0 = [(r, (r - s0) % p) for r in range(p)]
    perm1 = [(r, (r - s0 - 1) % p) for r in range(p)]
    from0 = lax.ppermute(rank, axis_name, perm0) if s0 else rank
    from1 = lax.ppermute(rank, axis_name, perm1)
    combined = jnp.concatenate([from0, from1])
    r = k % m
    nxt = lax.slice(combined, (r,), (r + m,))
    gpos = d * m + jnp.arange(m, dtype=jnp.int32)
    return jnp.where(gpos + k < n_pad, nxt, -1).astype(jnp.int32)


def _relabel_sharded(rank_s, nxt_s, axis_name):
    """Dense new ranks for globally sorted (rank, nxt) rows."""
    p = _axis_size(axis_name)
    d = lax.axis_index(axis_name)
    # previous row's key (from left neighbour's last row)
    perm = [(r, (r + 1) % p) for r in range(p)]
    prev_rank = lax.ppermute(rank_s[-1:], axis_name, perm)
    prev_nxt = lax.ppermute(nxt_s[-1:], axis_name, perm)
    pr = jnp.concatenate([prev_rank, rank_s[:-1]])
    pn = jnp.concatenate([prev_nxt, nxt_s[:-1]])
    changed = ((rank_s != pr) | (nxt_s != pn)).astype(jnp.int32)
    # global row 0 is never "changed" (rank 0 by definition)
    changed = changed.at[0].set(jnp.where(d == 0, 0, changed[0]))
    local_cum = jnp.cumsum(changed)
    totals = lax.all_gather(local_cum[-1], axis_name)            # (p,)
    offset = jnp.sum(jnp.where(jnp.arange(p) < d, totals, 0))
    return (offset + local_cum).astype(jnp.int32)


def build_suffix_array_sharded(codes_local, *, n_real: int, axis_name,
                               method: str = "bitonic",
                               num_steps: int | None = None):
    """Inside shard_map: codes_local is this device's text block (m,), already
    padded globally to p*m (pad values ignored — ranks forced to -1).
    Returns (sa_local, rank_local): device d holds sorted rows
    [d*m, (d+1)*m) of the padded suffix array and text-order ranks."""
    p = _axis_size(axis_name)
    m = codes_local.shape[0]
    n_pad = p * m
    d = lax.axis_index(axis_name)
    gpos = d * m + jnp.arange(m, dtype=jnp.int32)

    rank = jnp.where(gpos < n_real, codes_local.astype(jnp.int32), -1)
    if num_steps is None:
        num_steps = max(1, int(np.ceil(np.log2(n_pad))))

    # densify initial ranks: sort by (rank,), relabel, scatter back by gpos
    r_s, g_s = _sort((rank, gpos), 1, axis_name, method)
    new_r = _relabel_sharded(r_s, r_s, axis_name)
    g_back, rank = _sort((g_s, new_r), 1, axis_name, method)
    sa = gpos

    k = 1
    for _ in range(num_steps):
        nxt = _shift_ranks(rank, k, n_pad, axis_name)
        r_s, n_s, sa = _sort((rank, nxt, gpos), 2, axis_name, method)
        new_r = _relabel_sharded(r_s, n_s, axis_name)
        _, rank = _sort((sa, new_r), 1, axis_name, method)
        k *= 2
    return sa, rank


def make_superchunk_sorter(mesh, axis_name: str, method: str = "sample"):
    """Jitted mesh sort of one (key, nxt, idx) super-chunk for the staged
    build (``repro.core.build_pipeline``).  All three operands are int32
    of equal length divisible by the axis size; rows sort ascending by the
    full triple (idx last forces deterministic ties, so the result matches
    a stable 2-key sort of text-ordered rows bit-for-bit)."""
    spec = P(axis_name)

    @jax.jit
    @functools.partial(jax.shard_map, mesh=mesh, in_specs=(spec,) * 3,
                       out_specs=(spec,) * 3)
    def run(key, nxt, idx):
        return _sort((key, nxt, idx), 3, axis_name, method)

    return run


def build_suffix_array_distributed(codes: np.ndarray, mesh, axis_name: str,
                                   method: str = "bitonic"):
    """Host-side wrapper: pads, shard_maps, returns (sa_padded, pad_count).
    Real suffix array = sa_padded[pad_count:]."""
    p = mesh_axis_size(mesh, axis_name)
    n_real = int(len(codes))
    m = int(np.ceil(n_real / p))
    n_pad = m * p
    padded = np.zeros((n_pad,), dtype=np.int32)
    padded[:n_real] = np.asarray(codes, dtype=np.int32)

    spec = P(axis_name)
    fn = functools.partial(build_suffix_array_sharded, n_real=n_real,
                           axis_name=axis_name, method=method)

    @functools.partial(jax.shard_map, mesh=mesh, in_specs=(spec,),
                       out_specs=(spec, spec))
    def run(c):
        return fn(c)

    sa, rank = jax.jit(run)(padded)
    return sa, n_pad - n_real
