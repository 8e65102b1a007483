"""GPipe-style pipeline parallelism over a mesh axis (DESIGN.md §5).

The layer stack is split into ``p`` contiguous stages (one per device along
``axis_name``); microbatches stream through with ``ppermute`` hand-offs.
Forward runs p + n_micro - 1 ticks; backward falls out of jax.grad because
ppermute is differentiable (its transpose is the reverse permute), giving
the classic GPipe fill-drain schedule without hand-written backward.

This composes with the TP/FSDP axes: stage params live sharded over the
remaining axes; only the layer dimension moves to the pipeline axis.
Intended for the `pod` axis of the multi-pod mesh (2 stages) but generic.

All functions run INSIDE shard_map over ``axis_name``.
"""
from __future__ import annotations

from functools import partial
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax


def pipeline_apply(stage_fn: Callable, stage_params, x_micro, axis_name):
    """Run ``stage_fn(params, h) -> h`` over p pipeline stages.

    stage_params: this device's stage's params (layers for my stage).
    x_micro: (n_micro, mb, ...) microbatched input, REPLICATED across the
    pipeline axis (every stage sees the stream; only stage 0's injection
    matters).  Returns (n_micro, mb, ...) outputs valid on the LAST stage
    (replicated back via ppermute broadcast at the end).
    """
    p = lax.psum(1, axis_name)
    d = lax.axis_index(axis_name)
    n_micro = x_micro.shape[0]
    ticks = n_micro + p - 1
    mb_shape = x_micro.shape[1:]

    fwd_perm = [(r, (r + 1) % p) for r in range(p)]

    def tick(carry, t):
        recv, outs = carry
        # stage 0 injects microbatch t (if in range); others take recv
        mb_idx = jnp.clip(t, 0, n_micro - 1)
        inject = x_micro[mb_idx]
        h_in = jnp.where(d == 0, inject, recv)
        h_out = stage_fn(stage_params, h_in)
        # last stage writes its result for microbatch t - (p - 1)
        out_idx = t - (p - 1)
        do_write = (d == p - 1) & (out_idx >= 0)
        w_idx = (jnp.clip(out_idx, 0, n_micro - 1),) \
            + (0,) * len(mb_shape)
        old = lax.dynamic_slice(outs, w_idx, (1,) + mb_shape)
        new = jnp.where(do_write, h_out[None], old)
        outs = lax.dynamic_update_slice(outs, new, w_idx)
        recv_next = lax.ppermute(h_out, axis_name, fwd_perm)
        return (recv_next, outs), None

    outs0 = jnp.zeros((n_micro,) + mb_shape, x_micro.dtype)
    recv0 = jnp.zeros(mb_shape, x_micro.dtype)
    recv0 = lax.pcast(recv0, axis_name, to="varying")
    outs0 = lax.pcast(outs0, axis_name, to="varying")
    (_, outs), _ = lax.scan(tick, (recv0, outs0), jnp.arange(ticks))
    # broadcast final outputs from the last stage to all stages (masked
    # psum — ppermute can't fan out one source to many destinations)
    outs = lax.psum(jnp.where(d == p - 1, outs, 0), axis_name)
    return outs


def stage_slice(stacked_params, axis_name, n_layers_total: int):
    """Split a (L, ...) stacked param tree into this device's stage:
    (L/p, ...) via dynamic_slice on the layer dim."""
    p = lax.psum(1, axis_name)
    d = lax.axis_index(axis_name)
    per = n_layers_total // p

    def sl(x):
        start = (d * per,) + (0,) * (x.ndim - 1)
        return lax.dynamic_slice(x, start, (per,) + x.shape[1:])

    return jax.tree.map(sl, stacked_params)
