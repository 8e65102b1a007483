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

The build is one looped program: the doubling rounds are the body of a
``lax.while_loop`` (the shift by ``k`` is a traced ``dynamic_slice``), so
the lowered program does not grow with the text or the rounds, and the
loop exits as soon as every rank is distinct — after about log2 of the
longest repeat rather than log2 n rounds, with the same result.

All functions here run INSIDE shard_map over ``axis_name``.
``build_suffix_array_distributed`` is the host-side convenience wrapper.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

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


def _shift_ranks(rank, k, n_pad: int, axis_name):
    """nxt[i] = rank[gpos_i + k] in text-order sharding, -1 past the end.
    ``k`` may be traced.  The source rows span at most two blocks, the
    ones ``s`` and ``s + 1`` to the right with ``s = (k // m) % p``;
    ``s`` is 0 for every ``k < m``, and only a repeat at least ``m``
    long reaches a larger one, so each ``s`` is a static pair of
    ``ppermute``s picked by ``lax.switch``."""
    p = _axis_size(axis_name)
    m = rank.shape[0]
    d = lax.axis_index(axis_name)

    def blocks(s: int):
        def from_right(x):
            ahead0 = [(r, (r - s) % p) for r in range(p)]
            ahead1 = [(r, (r - s - 1) % p) for r in range(p)]
            from0 = lax.ppermute(x, axis_name, ahead0) if s else x
            return jnp.concatenate(
                [from0, lax.ppermute(x, axis_name, ahead1)])
        return from_right

    combined = lax.switch((k // m) % p, [blocks(s) for s in range(p)], rank)
    nxt = lax.dynamic_slice(combined, (k % m,), (m,))
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
    Returns (sa_local, rank_local, rounds): device d holds sorted rows
    [d*m, (d+1)*m) of the padded suffix array and text-order ranks, and
    ``rounds`` counts the sort rounds run, the densifying one included.

    Every round, the densifying one included, is the body of one
    ``lax.while_loop``, so the program's size does not depend on the
    text length.  The loop stops once every rank is distinct (a ``pmax``
    over the mesh): from then on a round leaves the order and the ranks
    as they are, so the result is the one ``num_steps`` doubling rounds
    give, after about log2 of the longest repeat.  ``num_steps`` caps the
    doubling rounds (default ceil(log2 n_pad))."""
    p = _axis_size(axis_name)
    m = codes_local.shape[0]
    n_pad = p * m
    d = lax.axis_index(axis_name)
    gpos = d * m + jnp.arange(m, dtype=jnp.int32)

    rank = jnp.where(gpos < n_real, codes_local.astype(jnp.int32), -1)
    if num_steps is None:
        num_steps = max(1, int(np.ceil(np.log2(n_pad))))

    # round 0 densifies the initial ranks: with k = 0 the second key is
    # the rank itself, so it sorts by rank and relabels ties alike
    def more(carry):
        _, _, _, rounds, done = carry
        return (rounds <= num_steps) & ~done

    def one_round(carry):
        rank, _, k, rounds, _ = carry
        nxt = _shift_ranks(rank, k, n_pad, axis_name)
        r_s, n_s, sa = _sort((rank, nxt, gpos), 2, axis_name, method)
        new_r = _relabel_sharded(r_s, n_s, axis_name)
        _, rank = _sort((sa, new_r), 1, axis_name, method)
        # ranks are dense and sorted here: all distinct iff the largest,
        # on the last tablet, is n_pad - 1
        done = lax.pmax(new_r[-1], axis_name) == n_pad - 1
        return rank, sa, jnp.maximum(k * 2, 1), rounds + 1, done

    carry = (rank, gpos, jnp.int32(0), jnp.int32(0), jnp.bool_(False))
    rank, sa, _, rounds, _ = lax.while_loop(more, one_round, carry)
    return sa, rank, rounds


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


@dataclasses.dataclass(frozen=True)
class DistributedBuild:
    """What one distributed build did: sort rounds run (the densifying
    one included), seconds to lower and compile its program, and the
    tablet count."""
    rounds: int
    compile_s: float
    devices: int


def build_suffix_array_distributed(codes: np.ndarray, mesh, axis_name: str,
                                   method: str = "bitonic", tracer=None):
    """Host-side wrapper: pads, compiles the shard_mapped build ahead of
    time, runs it; returns (sa_padded, pad_count, DistributedBuild).
    Real suffix array = sa_padded[pad_count:].  With a ``tracer`` the
    compile and the run are its spans ``build.compile`` and
    ``build.rounds``."""
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
                       out_specs=(spec, spec, P()))
    def run(c):
        return fn(c)

    span = (tracer.span if tracer is not None
            else lambda name: contextlib.nullcontext())
    t0 = time.perf_counter()
    with span("build.compile"):
        text = jax.ShapeDtypeStruct((n_pad,), jnp.int32,
                                    sharding=NamedSharding(mesh, spec))
        compiled = jax.jit(run).lower(text).compile()
    compile_s = time.perf_counter() - t0
    with span("build.rounds"):
        sa, _rank, rounds = compiled(padded)
        rounds = int(rounds)
    return sa, n_pad - n_real, DistributedBuild(
        rounds=rounds, compile_s=compile_s, devices=p)
