import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

# (note: no `from __future__ import annotations` here — the XLA_FLAGS env
# set MUST be the first statements, before any jax import, since jax locks
# the device count on first init.)

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this proves the distribution config is coherent (sharding
propagates, collectives legal, memory fits) and extracts the roofline
inputs: ``compiled.cost_analysis()`` (FLOPs / HBM bytes),
``compiled.memory_analysis()`` (per-device residency) and the collective
bytes parsed from the optimized HLO (launch/hlo_analysis.py).

Results are cached incrementally under experiments/dryrun/<cell>.json so
the 84-cell matrix can be filled across multiple invocations:

    PYTHONPATH=src python -m repro.launch.dryrun --arch yi-6b \
        --shape train_4k --mesh single
    PYTHONPATH=src python -m repro.launch.dryrun --all
"""
import argparse
import dataclasses
import json
import time
import traceback

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import get_config, list_archs
from repro.distributed import sharding as shd
from repro.launch import specs as SP
from repro.launch.hlo_analysis import (HBM_BW, analytic_memory_floor,
                                       collective_bytes, roofline_terms)
from repro.launch.mesh import make_production_mesh, make_tablet_mesh
from repro.models import decode_step, init_decode_caches, prefill
from repro.models.config import ModelConfig
from repro.training import OptConfig, make_train_step, train_state_init

OUT_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                       "experiments", "dryrun")
PARAM_DTYPE = jnp.bfloat16


def _opt_for(cfg: ModelConfig) -> OptConfig:
    big = cfg.param_count() > 3e11
    return OptConfig(kind="adafactor" if big else "adamw",
                     b1=0.0 if big else 0.9,
                     state_dtype=jnp.bfloat16 if cfg.param_count() > 5e10
                     else jnp.float32)


def _ns(mesh, tree):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), tree,
                        is_leaf=lambda x: isinstance(x, P))


def _sds_tree(tree):
    return jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                        tree)


def lower_train(cfg: ModelConfig, mesh, shape_name: str,
                microbatches: int = 1, seq_shard: bool = True,
                unroll: bool = False, loss_chunk=None):
    opt_cfg = _opt_for(cfg)
    state_shapes = jax.eval_shape(
        lambda: train_state_init(cfg, opt_cfg, jax.random.PRNGKey(0),
                                 dtype=PARAM_DTYPE))
    pspecs = shd.param_specs(state_shapes.params, mesh)
    ospecs = shd.opt_state_specs(opt_cfg, state_shapes.params, pspecs)
    sspecs = type(state_shapes)(params=pspecs, opt_state=ospecs, step=P())
    batch = SP.batch_specs(cfg, shape_name)
    bspecs = shd.batch_spec_tree(batch, mesh)
    shard_fn = shd.make_shard_fn(mesh, seq_shard=seq_shard)
    step_fn = make_train_step(cfg, opt_cfg, microbatches=microbatches,
                              shard=shard_fn, scan_unroll=unroll,
                              loss_chunk=loss_chunk)
    jitted = jax.jit(step_fn,
                     in_shardings=(_ns(mesh, sspecs), _ns(mesh, bspecs)),
                     out_shardings=(_ns(mesh, sspecs), None),
                     donate_argnums=(0,))
    with jax.set_mesh(mesh):
        return jitted.lower(state_shapes, batch)


def lower_prefill(cfg: ModelConfig, mesh, shape_name: str,
                  seq_shard: bool = True, unroll: bool = False):
    state_shapes = jax.eval_shape(
        lambda: __import__("repro.models", fromlist=["init_params"])
        .init_params(cfg, jax.random.PRNGKey(0), PARAM_DTYPE))
    pspecs = shd.param_specs(state_shapes, mesh)
    batch = SP.batch_specs(cfg, shape_name)
    bspecs = shd.batch_spec_tree(batch, mesh)
    shard_fn = shd.make_shard_fn(mesh, seq_shard=seq_shard)
    info = SP.SHAPES[shape_name]

    def fn(params, b):
        return prefill(cfg, params, b, max_len=info["seq_len"],
                       shard=shard_fn, scan_unroll=unroll)

    jitted = jax.jit(fn, in_shardings=(_ns(mesh, pspecs),
                                       _ns(mesh, bspecs)))
    with jax.set_mesh(mesh):
        return jitted.lower(state_shapes, batch)


def lower_decode(cfg: ModelConfig, mesh, shape_name: str,
                 unroll: bool = False):
    from repro.models import init_params
    info = SP.SHAPES[shape_name]
    B, S = info["global_batch"], info["seq_len"]
    param_shapes = jax.eval_shape(
        lambda: init_params(cfg, jax.random.PRNGKey(0), PARAM_DTYPE))
    pspecs = shd.param_specs(param_shapes, mesh)
    cache_shapes = SP.decode_cache_shapes(cfg, shape_name, PARAM_DTYPE)
    cspecs = shd.cache_specs(cache_shapes, mesh, B)
    batch = SP.batch_specs(cfg, shape_name)
    bspecs = shd.batch_spec_tree(batch, mesh)
    shard_fn = shd.make_shard_fn(mesh, seq_shard=False)

    def fn(params, tokens, caches, embeds):
        return decode_step(cfg, params, tokens, caches, shard=shard_fn,
                           embeds=embeds, scan_unroll=unroll)

    tokens = batch.get("tokens")
    embeds = batch.get("embeds")
    tspec = bspecs.get("tokens")
    espec = bspecs.get("embeds")
    jitted = jax.jit(fn, in_shardings=(
        _ns(mesh, pspecs),
        _ns(mesh, tspec) if tspec is not None else None,
        _ns(mesh, cspecs),
        _ns(mesh, espec) if espec is not None else None),
        out_shardings=(None, _ns(mesh, cspecs)),
        donate_argnums=(2,))
    with jax.set_mesh(mesh):
        return jitted.lower(param_shapes, tokens, cache_shapes, embeds)


def lower_sa_serve(mesh, routed: bool = False):
    """The paper's own workload: distributed tablet scan on the production
    mesh (flattened to 1-D tablets).  ``routed``: the beyond-paper
    owner-routing path (queries sharded, all_to_all dispatch) instead of
    the paper-faithful broadcast fan-out."""
    import functools
    from repro.configs.dna_suffix import CONFIG as SA
    from repro.core import query as Q
    from repro.core.tablet import TabletStore

    n_dev = int(np.prod(list(mesh.shape.values())))
    n_pad = ((SA.text_len + n_dev - 1) // n_dev) * n_dev
    W = SA.max_query_len // 16
    store_meta = TabletStore(
        text_packed=jax.ShapeDtypeStruct(((SA.text_len + 15) // 16,),
                                         jnp.uint32),
        text_codes=None, sa=jax.ShapeDtypeStruct((n_pad,), jnp.int32),
        n_real=SA.text_len, n_pad=n_pad, is_dna=True,
        max_query_len=SA.max_query_len)
    tmesh = make_tablet_mesh(n_dev)
    B = 1024

    if routed:
        @functools.partial(jax.shard_map, mesh=tmesh,
                           in_specs=(P("tablets"), None, P("tablets"),
                                     P("tablets")),
                           out_specs=P("tablets"))
        def serve(sa_local, meta, patt, plen):
            return Q.query_routed(sa_local, meta, patt, plen, "tablets")
    else:
        @functools.partial(jax.shard_map, mesh=tmesh,
                           in_specs=(P("tablets"), None, P(), P()),
                           out_specs=P())
        def serve(sa_local, meta, patt, plen):
            return Q.query_sharded(sa_local, meta, patt, plen, "tablets")

    jitted = jax.jit(serve)
    with jax.set_mesh(tmesh):
        return jitted.lower(
            store_meta.sa, store_meta,
            jax.ShapeDtypeStruct((B, W), jnp.uint32),
            jax.ShapeDtypeStruct((B,), jnp.int32))


def lower_sa_build(mesh, method="bitonic"):
    """One prefix-doubling construction step, tablet-sharded."""
    import functools
    from repro.configs.dna_suffix import CONFIG as SA
    from repro.core.dsa import build_suffix_array_sharded

    n_dev = int(np.prod(list(mesh.shape.values())))
    tmesh = make_tablet_mesh(n_dev)
    m = ((SA.text_len + n_dev - 1) // n_dev)
    n_pad = m * n_dev

    @functools.partial(jax.shard_map, mesh=tmesh, in_specs=(P("tablets"),),
                       out_specs=(P("tablets"), P("tablets")))
    def build(codes_local):
        return build_suffix_array_sharded(
            codes_local, n_real=SA.text_len, axis_name="tablets",
            method=method, num_steps=1)[:2]

    jitted = jax.jit(build)
    with jax.set_mesh(tmesh):
        return jitted.lower(jax.ShapeDtypeStruct((n_pad,), jnp.int32))


# ---------------------------------------------------------------------------
def _compile_stats(lowered) -> dict:
    """Compile and pull raw per-partition stats.

    NOTE: XLA's cost_analysis on a GSPMD-partitioned module reports
    PER-PARTITION flops/bytes and counts while-loop bodies ONCE.  The
    collective parser weights loop bodies by trip count itself; flops/bytes
    of scanned layer stacks are recovered by the layer-count probes in
    ``run_cell`` (linear extrapolation over n_periods — exact for
    homogeneous periods)."""
    t0 = time.time()
    compiled = lowered.compile()
    compile_s = time.time() - t0
    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    if isinstance(cost, (list, tuple)):
        cost = cost[0]
    hlo = compiled.as_text()
    return {
        "compile_s": round(compile_s, 1),
        "flops_dev": float(cost.get("flops", 0.0)),
        "hbm_dev": float(cost.get("bytes accessed", 0.0)),
        "collective": collective_bytes(hlo),
        "memory": {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", 0),
            "output_bytes": getattr(mem, "output_size_in_bytes", 0),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", 0),
            "peak_bytes_estimate": (
                getattr(mem, "argument_size_in_bytes", 0)
                + getattr(mem, "temp_size_in_bytes", 0)),
        },
    }


def _probe_cfg(cfg: ModelConfig, n_periods: int) -> ModelConfig:
    return dataclasses.replace(
        cfg, num_layers=cfg.first_dense_layers + n_periods * cfg.period,
        mtp_depth=0)


def _lower_for(cfg, mesh, shape_name, kind, opts, unroll=False):
    if kind == "train":
        return lower_train(cfg, mesh, shape_name,
                           microbatches=opts.get("microbatches", 1),
                           seq_shard=opts.get("seq_shard", True),
                           unroll=unroll,
                           loss_chunk=opts.get("loss_chunk"))
    if kind == "prefill":
        return lower_prefill(cfg, mesh, shape_name,
                             seq_shard=opts.get("seq_shard", True),
                             unroll=unroll)
    return lower_decode(cfg, mesh, shape_name, unroll=unroll)


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             opts: dict | None = None) -> dict:
    opts = opts or {}
    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = int(np.prod(list(mesh.shape.values())))
    label = f"{arch}:{shape_name}:{'2x16x16' if multi_pod else '16x16'}"

    if arch == "dna-suffix":
        if shape_name == "serve":
            lowered = lower_sa_serve(mesh, routed=opts.get("routed", False))
        else:
            lowered = lower_sa_build(mesh, method=opts.get("sort", "bitonic"))
        st = _compile_stats(lowered)
        flops = st["flops_dev"] * chips
        hbm = st["hbm_dev"] * chips
        res = {"label": label, "chips": chips, "kind": shape_name,
               "compile_s": st["compile_s"], "hlo_flops": flops,
               "hbm_bytes": hbm, "collective": st["collective"],
               "memory": st["memory"],
               "roofline": roofline_terms(flops, hbm,
                                          st["collective"]["bytes"] * chips,
                                          chips)}
        return res

    cfg = get_config(arch)
    ok, why = SP.cell_runnable(cfg, shape_name)
    if not ok:
        return {"label": label, "skipped": why}
    kind = SP.SHAPES[shape_name]["kind"]

    import contextlib
    from repro.models import layers as _L
    from repro.models import moe as _M
    chunk_ctx = (
        _L.attn_chunking(opts["attn_threshold"],
                         opts.get("attn_chunk", 1024))
        if opts.get("attn_threshold") else contextlib.nullcontext())
    ep_ctx = (_M.ep_sharding(mesh) if opts.get("ep") and cfg.is_moe
              else contextlib.nullcontext())

    # ---- main compile: the production artifact (memory + collectives)
    with chunk_ctx, ep_ctx:
        lowered = _lower_for(cfg, mesh, shape_name, kind, opts)
    st = _compile_stats(lowered)

    # ---- layer-count probes: recover true flops/bytes of the scanned stack
    prefix, period, n_periods = (cfg.first_dense_layers, cfg.period,
                                 (cfg.num_layers - cfg.first_dense_layers)
                                 // cfg.period)
    probes = {}
    if n_periods > 1 and not opts.get("no_probes"):
        for k in (1, 2):
            pcfg = _probe_cfg(cfg, k)
            with chunk_ctx, ep_ctx:
                pl = _lower_for(pcfg, mesh, shape_name, kind,
                                dict(opts, microbatches=1), unroll=True)
            pst = _compile_stats(pl)
            probes[k] = pst
        per_period_f = probes[2]["flops_dev"] - probes[1]["flops_dev"]
        per_period_b = probes[2]["hbm_dev"] - probes[1]["hbm_dev"]
        # mtp (stripped from probes) contributes ~1 period of train flops
        mtp_f = per_period_f * (1.0 if (cfg.mtp_depth and kind == "train")
                                else 0.0) / max(period, 1)
        flops_dev = (probes[1]["flops_dev"]
                     + (n_periods - 1) * per_period_f + mtp_f)
        hbm_dev = probes[1]["hbm_dev"] + (n_periods - 1) * per_period_b
        mb = opts.get("microbatches", 1)
        if kind == "train" and mb > 1:
            # probes ran mb=1 over the full batch: same total flops; bytes
            # scale mildly with re-reads of params per microbatch
            hbm_dev = hbm_dev  # conservative: keep probe value
    else:
        flops_dev = st["flops_dev"]
        hbm_dev = st["hbm_dev"]

    flops = flops_dev * chips
    hbm = hbm_dev * chips
    coll_global = st["collective"]["bytes"] * chips
    res = {
        "label": label, "chips": chips, "kind": kind,
        "compile_s": st["compile_s"],
        "hlo_flops": flops, "hbm_bytes": hbm,
        "hlo_flops_per_dev": flops_dev, "hbm_bytes_per_dev": hbm_dev,
        "collective": st["collective"], "memory": st["memory"],
        "roofline": roofline_terms(flops, hbm, coll_global, chips),
        "probe_compile_s": [probes[k]["compile_s"] for k in sorted(probes)],
    }
    # useful-FLOPs ratio (6ND / 2ND model)
    info = SP.SHAPES[shape_name]
    tokens = info["global_batch"] * (info["seq_len"] if kind != "decode"
                                     else 1)
    n_active = cfg.active_param_count()
    model_flops = (6 if kind == "train" else 2) * n_active * tokens
    res["model_flops"] = model_flops
    res["useful_ratio"] = model_flops / max(flops, 1)
    floor = analytic_memory_floor(cfg, info, kind, chips)
    res["memory_floor_bytes_per_dev"] = floor
    res["memory_floor_s"] = floor / HBM_BW
    return res


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", choices=["single", "multi", "both"],
                    default="both")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--loss-chunk", type=int, default=None)
    ap.add_argument("--attn-threshold", type=int, default=None)
    ap.add_argument("--attn-chunk", type=int, default=1024)
    ap.add_argument("--routed", action="store_true")
    ap.add_argument("--ep", action="store_true")
    ap.add_argument("--no-seq-shard", action="store_true")
    ap.add_argument("--sort", default="bitonic")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--tag", default="")
    args = ap.parse_args()

    os.makedirs(OUT_DIR, exist_ok=True)
    archs = list_archs() + ["dna-suffix"] if args.all else [args.arch]
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]

    for arch in archs:
        shapes = (["serve", "build"] if arch == "dna-suffix"
                  else list(SP.SHAPES))
        if args.shape:
            shapes = [args.shape]
        for shape in shapes:
            for mp in meshes:
                cell = f"{arch}__{shape}__{'multi' if mp else 'single'}"
                if args.tag:
                    cell += f"__{args.tag}"
                path = os.path.join(OUT_DIR, cell + ".json")
                if os.path.exists(path) and not args.force:
                    print(f"[cached] {cell}")
                    continue
                print(f"[lower+compile] {cell} ...", flush=True)
                t0 = time.time()
                try:
                    res = run_cell(arch, shape, mp, {
                        "microbatches": args.microbatches,
                        "seq_shard": not args.no_seq_shard,
                        "sort": args.sort,
                        "loss_chunk": args.loss_chunk,
                        "attn_threshold": args.attn_threshold,
                        "attn_chunk": args.attn_chunk,
                        "routed": args.routed,
                        "ep": args.ep,
                    })
                    res["wall_s"] = round(time.time() - t0, 1)
                except Exception as e:  # noqa: BLE001 — record failures too
                    res = {"label": cell, "error": f"{type(e).__name__}: {e}",
                           "traceback": traceback.format_exc()[-2000:]}
                with open(path, "w") as f:
                    json.dump(res, f, indent=1, default=str)
                status = ("SKIP" if res.get("skipped")
                          else "FAIL" if res.get("error") else "ok")
                print(f"[{status}] {cell} ({time.time() - t0:.0f}s)",
                      flush=True)


if __name__ == "__main__":
    main()
