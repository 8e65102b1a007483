"""LSM compaction benchmark: merge-based major compaction vs full rebuild,
and sustained append throughput with minor compaction.

Measures, over a ``repro.api.SuffixTable``:

* ``major_merge`` / ``major_rebuild`` — folding a small append delta
  (default 5% of the base) into the base suffix array via the merge path
  (``repro.api.compaction``: dirty-range prefix doubling + batched
  window-compare insertion) vs the old from-scratch rebuild, same data;
* ``append_flat`` — sustained ingest (append + probe read per chunk)
  with one ever-growing memtable, the pre-run-tier behaviour;
* ``append_minor`` — the same ingest with ``memtable_limit`` sealing the
  memtable into immutable runs, which bounds the per-read index rebuild;
* ``read_with_runs`` — merged-read cost with live runs vs base-only;
* an exactness check of merged reads against the Algorithm 1 brute-force
  oracle, with live runs and after the merge compaction.

Writes ``BENCH_compaction.json`` at the repo root.  ``--smoke`` shrinks
every dimension for the weekly CI job.

    PYTHONPATH=src python benchmarks/compaction_bench.py [--smoke]
"""
from __future__ import annotations

import argparse
import json
import os
import time

import numpy as np


def _parse(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--text-len", type=int, default=200_000)
    ap.add_argument("--delta-frac", type=float, default=0.05)
    ap.add_argument("--append-chunk", type=int, default=500)
    ap.add_argument("--memtable-limit", type=int, default=2_000)
    ap.add_argument("--batch", type=int, default=64)
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--smoke", action="store_true",
                    help="tiny sizes for CI smoke runs")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    if args.smoke:
        args.text_len, args.append_chunk = 20_000, 250
        args.memtable_limit, args.batch, args.reps = 1_000, 32, 5
    return args


def _ingest(table, chunks, probe):
    """Append chunks, paying the per-append index rebuild via one probe
    read each (the memtable/run stores are rebuilt lazily on read)."""
    patt, plen = probe
    t0 = time.perf_counter()
    for c in chunks:
        table.append(c)
        table.scan_encoded(patt, plen)
    return time.perf_counter() - t0


def _time_compaction(make_table, *, merge: bool, reps: int) -> float:
    """Median wall time of one major compaction (state is consumed, so a
    fresh table is built per rep; construction is outside the clock)."""
    times = []
    for _ in range(reps):
        table = make_table()
        if not merge:
            # force the pre-merge behaviour: from-scratch rebuild
            import repro.api.table as T
            combined = np.concatenate(
                [table._codes, table._delta_codes()])
            t0 = time.perf_counter()
            sa_real = np.asarray(
                T.build_suffix_array(combined.astype(np.int32)))
            table._codes = combined
            table._attach(combined, sa_real)
            dt = time.perf_counter() - t0
        else:
            t0 = time.perf_counter()
            table.compact()
            dt = time.perf_counter() - t0
        times.append(dt)
    return float(np.median(times))


def run(args) -> dict:
    from repro.api import SuffixTable
    from repro.core import codec, query as Q

    n = args.text_len
    d = int(n * args.delta_frac)
    codes = codec.random_dna(n, seed=0)
    delta = codec.random_dna(d, seed=1)
    pats = Q.random_patterns(args.batch, 1, 100, seed=2)

    def fresh_with_delta():
        t = SuffixTable.from_codes(codes, is_dna=True)
        t.append(delta)
        t.minor_compact()
        return t

    # warm both paths once (jit compilation priced out of the medians)
    _time_compaction(fresh_with_delta, merge=True, reps=1)
    _time_compaction(fresh_with_delta, merge=False, reps=1)
    merge_s = _time_compaction(fresh_with_delta, merge=True, reps=args.reps)
    rebuild_s = _time_compaction(fresh_with_delta, merge=False,
                                 reps=args.reps)

    # exactness: merged reads with a live run + after merge compaction
    t = fresh_with_delta()
    combined = np.concatenate([codes, delta])
    probes = pats[:16] + [codec.decode_dna(combined[n - 4:n + 6])]
    live = t.scan(probes)
    t.compact()
    post = t.scan(probes)
    exact = True
    for i, p in enumerate(probes):
        pc = codec.encode_dna(p).astype(np.int32)
        want, _ = Q.brute_force_count(combined.astype(np.int32), pc)
        exact &= int(live.count[i]) == want == int(post.count[i])

    # sustained ingest: flat memtable vs minor-compaction run tier
    n_chunks = max(4, d // args.append_chunk)
    chunks = [codec.random_dna(args.append_chunk, seed=10 + i)
              for i in range(n_chunks)]
    appended = n_chunks * args.append_chunk

    flat = SuffixTable.from_codes(codes, is_dna=True)
    probe = flat.planner.encode(pats[:1])
    flat_s = _ingest(flat, chunks, probe)
    minor = SuffixTable.from_codes(codes, is_dna=True,
                                   memtable_limit=args.memtable_limit)
    minor_s = _ingest(minor, chunks, probe)

    # merged read overhead with the run tier live (median of per-rep
    # wall times — single-batch timings at these sizes are noisy)
    import jax

    def _read_time(table, patt, plen, reps):
        jax.block_until_ready(table.scan_encoded(patt, plen).count)  # warm
        times = []
        for _ in range(reps):
            t0 = time.perf_counter()
            jax.block_until_ready(table.scan_encoded(patt, plen).count)
            times.append(time.perf_counter() - t0)
        return float(np.median(times))

    read_reps = max(args.reps, 10)
    patt, plen = minor.planner.encode(pats)
    runs_dt = _read_time(minor, patt, plen, read_reps)
    base_only = SuffixTable.from_codes(codes, is_dna=True)
    base_dt = _read_time(base_only, patt, plen, read_reps)

    return {
        "bench": "lsm_compaction",
        "text_len": n,
        "delta_len": d,
        "append_chunk": args.append_chunk,
        "memtable_limit": args.memtable_limit,
        "results": {
            "major_merge_s": round(merge_s, 4),
            "major_rebuild_s": round(rebuild_s, 4),
            "merge_speedup_x": round(rebuild_s / max(merge_s, 1e-9), 2),
            "merge_bases_per_s": round((n + d) / max(merge_s, 1e-9)),
            "append_flat_bases_per_s": round(appended / flat_s),
            "append_minor_bases_per_s": round(appended / minor_s),
            "append_speedup_x": round(flat_s / max(minor_s, 1e-9), 2),
            "runs_live_after_ingest": len(minor.runs),
            "read_with_runs_us_per_query":
                round(runs_dt / args.batch * 1e6, 3),
            "read_base_us_per_query":
                round(base_dt / args.batch * 1e6, 3),
            "read_with_runs_over_base_x":
                round(runs_dt / max(base_dt, 1e-9), 2),
            "exact_vs_brute_force": bool(exact),
        },
    }


def bench_compaction():
    """benchmarks/run.py entry: (major_merge_ms, derived)."""
    args = _parse(["--smoke"])
    payload = run(args)
    return (payload["results"]["major_merge_s"] * 1e3,
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
        "BENCH_compaction.json")
    with open(out, "w") as f:
        json.dump(payload, f, indent=2)
        f.write("\n")
    print(f"wrote {out}")


if __name__ == "__main__":
    main()
