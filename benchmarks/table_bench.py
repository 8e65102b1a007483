"""Table API benchmark: ingest throughput and merged-read overhead.

Measures, over a ``repro.api.SuffixTable``:

* ``create``          — initial build throughput (bases/s);
* ``append``          — memtable ingest throughput including the first
                        post-append read (which pays the memtable rebuild);
* ``read_base``       — encoded scan throughput with an empty memtable
                        (pure planner delegation);
* ``read_merged``     — the same batch with a populated memtable (base +
                        memtable fan-out and host-side merge);
* ``compact``         — fold-into-base throughput (bases/s).

Writes ``BENCH_table.json`` at the repo root.  ``--smoke`` shrinks every
dimension for the weekly CI job.

    PYTHONPATH=src python benchmarks/table_bench.py [--smoke]
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
    ap.add_argument("--text-len", type=int, default=200_000)
    ap.add_argument("--batch", type=int, default=256)
    ap.add_argument("--append-chunk", type=int, default=2_000)
    ap.add_argument("--appends", type=int, default=8)
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--smoke", action="store_true",
                    help="tiny sizes for CI smoke runs")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    if args.smoke:
        args.text_len, args.batch = 20_000, 64
        args.append_chunk, args.appends, args.reps = 500, 3, 2
    return args


def _time(fn, reps: int) -> float:
    fn()                                       # compile + warm
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn()
    import jax
    jax.block_until_ready(getattr(out, "count", out))
    return (time.perf_counter() - t0) / reps


def run(args) -> dict:
    from repro.api import SuffixTable
    from repro.core import query as Q
    from repro.core.codec import random_dna

    codes = random_dna(args.text_len, seed=0)
    t0 = time.perf_counter()
    table = SuffixTable.from_codes(codes, is_dna=True)
    int(table.count(["ACGT"])[0])              # force build + first read
    create_s = time.perf_counter() - t0

    pats = Q.random_patterns(args.batch, 1, 100, seed=1)
    patt, plen = table.planner.encode(pats)

    base_dt = _time(lambda: table.scan_encoded(patt, plen), args.reps)

    # ingest: append chunks, paying the memtable rebuild via one probe read
    t0 = time.perf_counter()
    for a in range(args.appends):
        table.append(random_dna(args.append_chunk, seed=2 + a))
        table.scan_encoded(patt[:1], plen[:1])
    ingest_s = time.perf_counter() - t0
    appended = args.appends * args.append_chunk

    merged_dt = _time(lambda: table.scan_encoded(patt, plen), args.reps)

    t0 = time.perf_counter()
    table.compact()
    compact_s = time.perf_counter() - t0
    post_dt = _time(lambda: table.scan_encoded(patt, plen), args.reps)

    # exactness spot check: merged reads vs the compacted base
    res = table.scan_encoded(patt, plen)
    probe = SuffixTable.from_codes(
        np.asarray(table.store.text_codes[:table.store.n_real],
                   ).astype(np.uint8), is_dna=True)
    ref = probe.scan_encoded(patt, plen)
    exact = bool((np.asarray(res.count) == np.asarray(ref.count)).all())

    return {
        "bench": "suffix_table_ops",
        "text_len": args.text_len,
        "batch": args.batch,
        "appended": appended,
        "results": {
            "create_bases_per_s": round(args.text_len / create_s),
            "append_bases_per_s": round(appended / ingest_s),
            "read_base_us_per_query": round(base_dt / args.batch * 1e6, 3),
            "read_merged_us_per_query":
                round(merged_dt / args.batch * 1e6, 3),
            "merged_read_overhead_x":
                round(merged_dt / max(base_dt, 1e-12), 3),
            "read_post_compact_us_per_query":
                round(post_dt / args.batch * 1e6, 3),
            "compact_bases_per_s":
                round((args.text_len + appended) / compact_s),
            "exact_vs_rebuilt_base": exact,
        },
    }


def bench_table_ops():
    """benchmarks/run.py entry: (us_per_merged_query, derived)."""
    args = _parse(["--smoke"])
    payload = run(args)
    return (payload["results"]["read_merged_us_per_query"],
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
        "BENCH_table.json")
    with open(out, "w") as f:
        json.dump(payload, f, indent=2)
        f.write("\n")
    print(f"wrote {out}")


if __name__ == "__main__":
    main()
