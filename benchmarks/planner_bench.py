"""Planner throughput benchmark: broadcast vs routed vs routed+retry.

Runs the three distributed scan executions over a forced multi-device host
mesh (XLA host platform devices) and records queries/second plus retry
rates to ``BENCH_planner.json`` at the repo root — the ISSUE's acceptance
artifact.

    PYTHONPATH=src python benchmarks/planner_bench.py --devices 8
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

# must be set before jax initializes its backends
_ap = argparse.ArgumentParser()
_ap.add_argument("--devices", type=int, default=8)
_ap.add_argument("--text-len", type=int, default=200_000)
_ap.add_argument("--batch", type=int, default=512)
_ap.add_argument("--reps", type=int, default=5)
_ap.add_argument("--capacity-factor", type=float, default=1.0)
_ap.add_argument("--out", default=None)
ARGS = _ap.parse_args()
if "--xla_force_host_platform_device_count" not in os.environ.get(
        "XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + f" --xla_force_host_platform_device_count={ARGS.devices}").strip()

import jax                                                   # noqa: E402
import numpy as np                                           # noqa: E402

from repro.core import query as Q                            # noqa: E402
from repro.core.codec import random_dna                      # noqa: E402
from repro.core.planner import (MODE_BROADCAST, MODE_ROUTED,  # noqa: E402
                                ScanPlanner)
from repro.core.tablet import build_tablet_store             # noqa: E402


def _time(fn, reps):
    out = fn()                                    # compile + warm
    jax.block_until_ready(getattr(out, "count", out))
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn()
    jax.block_until_ready(getattr(out, "count", out))
    return (time.perf_counter() - t0) / reps


def main() -> None:
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    p = len(jax.devices())
    mesh = jax.make_mesh((p,), ("tablets",))
    codes = random_dna(ARGS.text_len, seed=0)
    store = build_tablet_store(codes, is_dna=True, num_tablets=p)
    pats = Q.random_patterns(ARGS.batch, 1, 100, seed=1)
    _, pp, pl = Q.encode_patterns(pats, 112)
    B = ARGS.batch

    planner = ScanPlanner(store, mesh=mesh,
                          capacity_factor=ARGS.capacity_factor)
    results = {}
    runs = [
        ("broadcast", dict(mode=MODE_BROADCAST)),
        ("routed_noretry", dict(mode=MODE_ROUTED, retry=False)),
        ("routed_retry", dict(mode=MODE_ROUTED, retry=True)),
    ]
    for name, kw in runs:
        planner.reset_stats()
        dt = _time(lambda kw=kw: planner.scan_encoded(pp, pl, **kw),
                   ARGS.reps)
        s = planner.stats
        results[name] = {
            "us_per_query": round(dt / B * 1e6, 3),
            "queries_per_s": round(B / dt),
            "retried_overflow_per_batch":
                s.retried_overflow / max(s.batches, 1),
            "retried_saturated_per_batch":
                s.retried_saturated / max(s.batches, 1),
        }
        print(f"{name}: {results[name]}", flush=True)

    # sanity: retried path must be exact vs the single-device oracle
    ref = Q.query(store, pp, pl)
    res = planner.scan_encoded(pp, pl, mode=MODE_ROUTED, retry=True)
    exact = bool((np.asarray(res.count) == np.asarray(ref.count)).all())
    results["routed_retry"]["exact_vs_oracle"] = exact
    if not exact:
        print("WARNING: routed+retry counts diverge from oracle",
              file=sys.stderr)

    payload = {
        "bench": "scan_planner_throughput",
        "devices": p,
        "text_len": ARGS.text_len,
        "batch": B,
        "capacity_factor": ARGS.capacity_factor,
        "results": results,
    }
    out = ARGS.out or os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "BENCH_planner.json")
    with open(out, "w") as f:
        json.dump(payload, f, indent=2)
        f.write("\n")
    print(f"wrote {out}")


if __name__ == "__main__":
    main()
