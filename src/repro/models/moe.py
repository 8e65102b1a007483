"""Mixture-of-Experts FFN (token-choice top-k, capacity dropping, shared
experts) — DeepSeek-V3 / Kimi-K2 / Jamba MoE blocks.

Dispatch is sort-based (GShard-style priority, choice-major so first
choices win slots): tokens are argsorted by expert id, positions within
each expert group come from a searchsorted start table, tokens beyond
capacity are dropped.  The expert buffers are (E, C, d) einsums — E shards
over the `model` mesh axis (expert parallelism); the scatter/gather at the
boundary is where GSPMD inserts the all_to_all.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.config import ModelConfig
from repro.models.layers import _dense_init, init_mlp, mlp

# set by ep_sharding() below: mesh enabling the shard_map EP dispatch path
_EP_MESH = None


def init_moe(cfg: ModelConfig, key, dtype):
    d, E, f = cfg.d_model, cfg.num_experts, cfg.moe_d_ff
    ks = jax.random.split(key, 5)
    p = {
        "router": _dense_init(ks[0], (d, E), d, jnp.float32),  # fp32 router
        "wi": _dense_init(ks[1], (E, d, f), d, dtype),
        "wg": _dense_init(ks[2], (E, d, f), d, dtype),
        "wo": _dense_init(ks[3], (E, f, d), f, dtype),
    }
    if cfg.num_shared_experts:
        p["shared"] = init_mlp(cfg, ks[4], dtype,
                               d_ff=cfg.moe_d_ff * cfg.num_shared_experts)
    return p


def moe_ffn(cfg: ModelConfig, p, x, shard=None):
    """x: (B, S, d) -> (out, aux_loss).  Capacity per expert is
    ceil(T * k / E * capacity_factor); dropped tokens pass through the
    shared expert (and residual) only.

    ``shard`` (the model-wide constraint callback) pins the dispatch
    buffers to P('model', data, None).  NOTE: GSPMD cannot partition the
    data-dependent dispatch scatter either way (see _moe_ffn_ep below,
    which is the production path whenever ``ep_sharding`` is active)."""
    # EP pays off when there is real token volume; at decode (T ~ batch)
    # the per-step FSDP weight gather dominates (measured 8x WORSE on
    # deepseek decode_32k), so small-T calls stay on the XLA path.
    if _EP_MESH is not None and cfg.num_experts % \
            _EP_MESH.shape.get("model", 1) == 0 \
            and x.shape[0] * x.shape[1] >= 4096:
        return _moe_ffn_ep(cfg, p, x, _EP_MESH)
    if shard is None:
        shard = lambda t, _n: t
    B, S, d = x.shape
    T = B * S
    k = cfg.experts_per_token
    E = cfg.num_experts
    xt = x.reshape(T, d)

    logits = (xt.astype(jnp.float32) @ p["router"])             # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gates, idx = jax.lax.top_k(probs, k)                        # (T, k)
    gates = gates / jnp.sum(gates, axis=-1, keepdims=True)

    # --- aux load-balance loss (Switch-style)
    me = jnp.mean(probs, axis=0)                                # (E,)
    one_hot_top1 = jax.nn.one_hot(idx[:, 0], E, dtype=jnp.float32)
    ce = jnp.mean(one_hot_top1, axis=0)
    aux = E * jnp.sum(me * ce) * cfg.router_aux_weight

    # --- dispatch (choice-major priority)
    C = int(np.ceil(T * k / E * cfg.moe_capacity_factor))
    C = max(4, -(-C // 4) * 4)
    flat_e = idx.T.reshape(-1)                                  # (k*T,)
    flat_t = jnp.tile(jnp.arange(T, dtype=jnp.int32), k)
    flat_g = idx_gates = gates.T.reshape(-1)
    order = jnp.argsort(flat_e, stable=True)
    e_s = flat_e[order]
    t_s = flat_t[order]
    g_s = flat_g[order]
    start = jnp.searchsorted(e_s, jnp.arange(E, dtype=jnp.int32),
                             side="left")
    pos = jnp.arange(k * T, dtype=jnp.int32) - start[e_s]
    keep = pos < C
    slot = jnp.where(keep, e_s * C + pos, E * C)                # drop -> off

    buf = jnp.zeros((E * C, d), x.dtype)
    buf = buf.at[slot].add(jnp.where(keep[:, None], xt[t_s], 0),
                           mode="drop")
    h = buf.reshape(E, C, d)

    # --- expert FFN (E sharded over `model` = EP; C over data)
    if cfg.mlp_act == "swiglu":
        z = jax.nn.silu(jnp.einsum("ecd,edf->ecf", h, p["wg"])) \
            * jnp.einsum("ecd,edf->ecf", h, p["wi"])
    else:
        z = jax.nn.gelu(jnp.einsum("ecd,edf->ecf", h, p["wi"]))
    y = jnp.einsum("ecf,efd->ecd", z, p["wo"]).reshape(E * C, d)

    # --- combine
    back = jnp.where(keep[:, None], y[jnp.clip(slot, 0, E * C - 1)], 0)
    out = jnp.zeros((T, d), x.dtype)
    out = out.at[t_s].add(back * g_s[:, None].astype(x.dtype), mode="drop")

    if cfg.num_shared_experts:
        out = out + mlp(cfg, p["shared"], xt)
    return out.reshape(B, S, d), aux


# ---------------------------------------------------------------------------
# Explicit expert-parallel dispatch (shard_map) — the production path.
#
# GSPMD cannot partition the data-dependent dispatch scatter: it replicates
# the (E*C, d) buffers per device (~930 GB/dev on deepseek-v3 train_4k),
# and sharding constraints only add reshard copies (measured worse, see
# EXPERIMENTS.md §Perf iteration F).  The fix is structural: inside
# shard_map each model-axis shard owns E/tp experts and sees its data-row's
# tokens (already replicated over the model axis), scatters LOCALLY into an
# (E_local, C_local, d) buffer, runs its experts, and contributes a partial
# combine; one psum over the model axis completes the output.  No global
# scatter ever exists.  Enabled via ``ep_sharding(mesh)``.
# ---------------------------------------------------------------------------
class ep_sharding:
    """Context manager enabling the shard_map EP path during tracing."""

    def __init__(self, mesh):
        self.mesh = mesh

    def __enter__(self):
        global _EP_MESH
        self._saved = _EP_MESH
        _EP_MESH = self.mesh
        return self

    def __exit__(self, *exc):
        global _EP_MESH
        _EP_MESH = self._saved
        return False


def _moe_ffn_ep(cfg: ModelConfig, p, x, mesh):
    from jax import lax
    from jax.sharding import PartitionSpec as P
    from repro.distributed.sharding import data_axes

    d_axes = data_axes(mesh)
    m_size = mesh.shape["model"]
    dp = int(np.prod([mesh.shape[a] for a in d_axes])) if d_axes else 1
    E, k, d, f = (cfg.num_experts, cfg.experts_per_token, cfg.d_model,
                  cfg.moe_d_ff)
    E_local = E // m_size
    B, S, _ = x.shape

    def local_fn(x_loc, router, wi, wg, wo):
        # weights arrive (E_local, d/dp, f): FSDP-gather the d dim
        wi = lax.all_gather(wi, d_axes, axis=1, tiled=True)
        wg = lax.all_gather(wg, d_axes, axis=1, tiled=True) \
            if wg is not None else None
        wo = lax.all_gather(wo, d_axes, axis=2, tiled=True)
        Bl, S_, _ = x_loc.shape
        T = Bl * S_
        xt = x_loc.reshape(T, d)
        logits = xt.astype(jnp.float32) @ router
        probs = jax.nn.softmax(logits, axis=-1)
        gates, idx = jax.lax.top_k(probs, k)
        gates = gates / jnp.sum(gates, axis=-1, keepdims=True)
        me = jnp.mean(probs, axis=0)
        ce = jnp.mean(jax.nn.one_hot(idx[:, 0], E, dtype=jnp.float32),
                      axis=0)
        aux = E * jnp.sum(me * ce) * cfg.router_aux_weight

        tp = lax.axis_index("model")
        e0 = tp * E_local
        flat_e = idx.T.reshape(-1)
        flat_t = jnp.tile(jnp.arange(T, dtype=jnp.int32), k)
        local = (flat_e >= e0) & (flat_e < e0 + E_local)
        le = jnp.where(local, flat_e - e0, E_local)       # E_local = trash
        order = jnp.argsort(le, stable=True)
        le_s, t_s = le[order], flat_t[order]
        start = jnp.searchsorted(le_s, jnp.arange(E_local + 1,
                                                  dtype=jnp.int32))
        C = int(np.ceil(T * k / E * cfg.moe_capacity_factor))
        C = max(4, -(-C // 4) * 4)
        pos = jnp.arange(k * T, dtype=jnp.int32) - start[jnp.clip(
            le_s, 0, E_local)]
        keep = (le_s < E_local) & (pos < C)
        slot_sorted = jnp.where(keep, le_s * C + pos, E_local * C)
        # un-sort slots back to (choice-major) flat order, then dispatch
        # PER CHOICE: k scatters whose source is xt itself — the (k*T, d)
        # gathered copy (15 GB fp32 in backward at deepseek scale) never
        # exists (§Perf iteration F5).
        slot_flat = jnp.zeros((k * T,), jnp.int32).at[order].set(
            slot_sorted)
        buf = jnp.zeros((E_local * C + 1, d), x_loc.dtype)
        for j in range(k):
            sl = jnp.minimum(slot_flat[j * T:(j + 1) * T], E_local * C)
            buf = buf.at[sl].add(xt)
        buf = buf[:-1]                        # trash row collects drops
        h = buf.reshape(E_local, C, d)
        if cfg.mlp_act == "swiglu":
            z = jax.nn.silu(jnp.einsum("ecd,edf->ecf", h, wg)) \
                * jnp.einsum("ecd,edf->ecf", h, wi)
        else:
            z = jax.nn.gelu(jnp.einsum("ecd,edf->ecf", h, wi))
        y = jnp.einsum("ecf,efd->ecd", z, wo).reshape(E_local * C, d)
        out = jnp.zeros((T, d), x_loc.dtype)
        for j in range(k):
            sl = slot_flat[j * T:(j + 1) * T]
            ok_j = sl < E_local * C
            contrib = jnp.where(ok_j[:, None],
                                y[jnp.clip(sl, 0, E_local * C - 1)], 0)
            out = out + contrib * gates[:, j:j + 1].astype(x_loc.dtype)
        out = lax.psum(out, "model")          # partial combines -> full
        return out.reshape(Bl, S_, d), jnp.full((1,), aux)

    in_specs = (P(d_axes, None, None), P(),
                P("model", d_axes, None), P("model", d_axes, None),
                P("model", None, d_axes))
    out_specs = (P(d_axes, None, None), P(d_axes))
    fn = jax.shard_map(local_fn, mesh=mesh, in_specs=in_specs,
                       out_specs=out_specs)
    out, aux = fn(x, p["router"], p["wi"],
                  p.get("wg"), p["wo"])
    total = out
    if cfg.num_shared_experts:
        total = total + mlp(cfg, p["shared"], x.reshape(-1, d)
                            ).reshape(B, S, d)
    return total, jnp.mean(aux)
