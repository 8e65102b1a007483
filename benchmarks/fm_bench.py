"""Frozen FM-index tier benchmark: serving cost and residency vs the SA.

Measures, over live/frozen twin tables (docs/storage_tiers.md):

* ``fm_count_us_per_query``   — frozen count() at 1x and 10x text size
                                (backward search is O(pattern_len): the
                                two must be ~flat);
* ``sa_count_us_per_query``   — the live twin's binary-search count();
* ``fm_over_sa_bytes_x``      — resident index bytes, frozen FM over the
                                live twin's raw SA rows (acceptance:
                                <= 0.25, target ~0.125 counting the
                                device text the freeze also drops);
* ``freeze_syms_per_s``       — freeze() throughput;
* ``locate`` µs and exactness flags (count/locate bit-identical to the
  live SA path on the same patterns).

Writes ``BENCH_fm.json`` at the repo root.  ``--smoke`` shrinks every
dimension for the weekly CI job.

    PYTHONPATH=src python benchmarks/fm_bench.py [--smoke]
"""
from __future__ import annotations

import argparse
import json
import os
import time

import numpy as np

ARGS = None


def _parse(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--text-len", type=int, default=100_000,
                    help="1x size; the flatness probe also runs 10x this")
    ap.add_argument("--batch", type=int, default=256)
    ap.add_argument("--max-pattern", type=int, default=64)
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--top-k", type=int, default=4)
    ap.add_argument("--smoke", action="store_true",
                    help="tiny sizes for CI smoke runs")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    if args.smoke:
        args.text_len, args.batch, args.reps = 8_000, 64, 5
    return args


def _time(fn, reps: int) -> float:
    """Best-of-reps: the gated metric here is a RATIO of two tiny
    timings, so the min (the noise floor) is the honest estimator —
    averaging lets one scheduler hiccup swing the ratio 2x run-to-run."""
    import jax
    fn()                                       # compile + warm
    best = float("inf")
    for _ in range(max(reps, 3)):
        t0 = time.perf_counter()
        out = fn()
        jax.block_until_ready(getattr(out, "count", out))
        best = min(best, time.perf_counter() - t0)
    return best


def _twins(n: int, seed: int):
    from repro.api import SuffixTable
    from repro.core.codec import random_dna
    codes = random_dna(n, seed=seed)
    live = SuffixTable.from_codes(codes, is_dna=True)
    froz = SuffixTable.from_codes(codes, is_dna=True)
    t0 = time.perf_counter()
    froz.freeze()
    return live, froz, time.perf_counter() - t0


def run(args) -> dict:
    from repro.core import query as Q

    live, froz, freeze_s = _twins(args.text_len, seed=0)
    pats = Q.random_patterns(args.batch, 1, args.max_pattern, seed=1)
    patt, plen = live.planner.encode(pats)

    sa_dt = _time(lambda: live.scan_encoded(patt, plen), args.reps)
    fm_dt = _time(lambda: froz.scan_encoded(patt, plen), args.reps)
    loc_dt = _time(lambda: froz.scan_batch(np.asarray(patt),
                                           np.asarray(plen),
                                           top_k=args.top_k), args.reps)

    # bit-identity on the measured patterns (count AND text-order locate)
    a = live.scan_batch(np.asarray(patt), np.asarray(plen),
                        top_k=args.top_k)
    b = froz.scan_batch(np.asarray(patt), np.asarray(plen),
                        top_k=args.top_k)
    count_ok = bool(np.array_equal(a.count, b.count))
    locate_ok = bool(np.array_equal(a.positions, b.positions)
                     and np.array_equal(a.first_pos, b.first_pos))

    # residency: frozen FM vs the live twin's raw SA rows, same text
    lrb = live.stats()["tiers"]["resident_bytes"]
    frb = froz.stats()["tiers"]["resident_bytes"]

    # flatness: the same batch against a 10x text — O(plen) backward
    # search must not scale with n (the SA path's log n barely moves
    # either; the ratio is the honest probe)
    _, froz10, _ = _twins(args.text_len * 10, seed=2)
    fm10_dt = _time(lambda: froz10.scan_encoded(patt, plen), args.reps)

    return {
        "bench": "fm_frozen_tier",
        "text_len": args.text_len,
        "batch": args.batch,
        "max_pattern": args.max_pattern,
        "results": {
            "fm_count_us_per_query_1x":
                round(fm_dt / args.batch * 1e6, 3),
            "fm_count_us_per_query_10x":
                round(fm10_dt / args.batch * 1e6, 3),
            "count_flat_10x_over_1x_x":
                round(fm10_dt / max(fm_dt, 1e-12), 3),
            "sa_count_us_per_query":
                round(sa_dt / args.batch * 1e6, 3),
            "fm_locate_us_per_query":
                round(loc_dt / args.batch * 1e6, 3),
            "fm_over_sa_bytes_x":
                round(frb["fm"] / max(lrb["base_sa"], 1), 4),
            "fm_bytes_per_symbol":
                round(frb["fm"] / args.text_len, 4),
            "sa_bytes_per_symbol":
                round(lrb["base_sa"] / args.text_len, 4),
            "freeze_syms_per_s": round(args.text_len / max(freeze_s,
                                                           1e-12)),
            "count_identical": count_ok,
            "locate_identical": locate_ok,
        },
    }


def bench_fm():
    """benchmarks/run.py entry: (us_per_frozen_count_query, derived)."""
    args = _parse(["--smoke"])
    payload = run(args)
    return (payload["results"]["fm_count_us_per_query_1x"],
            payload["results"])


def main() -> None:
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    args = _parse()
    payload = run(args)
    for k, v in payload["results"].items():
        print(f"{k}: {v}", flush=True)
    out = args.out or os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "BENCH_fm.json")
    with open(out, "w") as f:
        json.dump(payload, f, indent=2)
        f.write("\n")
    print(f"wrote {out}")


if __name__ == "__main__":
    main()
