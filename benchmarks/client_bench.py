"""Client frontend benchmark: coalesced vs per-call dispatch.

Simulates ``--concurrency`` callers each holding ONE single-pattern
typed ``Query`` (the paper's Table IV shape: many users, one lookup
each) and measures queries/sec plus per-query latency p50/p95 through
three dispatch paths over the same ``repro.api.Database``:

* ``per_call``   — every caller's query is its own planner invocation
                   (``db.query``, batch of 1): the pre-redesign cost
                   model, one jitted dispatch per caller;
* ``coalesced``  — the wave is grouped inline into one bucket-padded
                   planner invocation (``db.query_many``);
* ``scheduler``  — callers submit into the shared ``QueryScheduler``
                   window and the worker drains them as one batch —
                   the real cross-caller path, window wait included.

Per-query results are checked BIT-IDENTICAL across all three paths
(counts, first positions, and top-k position rows), and the table's
string cache is cleared between arms so nothing is served from memory.

Writes ``BENCH_client.json`` at the repo root.  ``--smoke`` shrinks
every dimension for the weekly CI job.

    PYTHONPATH=src python benchmarks/client_bench.py [--smoke]
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
    ap.add_argument("--concurrency", type=int, default=128,
                    help="simulated concurrent single-query callers "
                         "per wave")
    ap.add_argument("--waves", type=int, default=4,
                    help="timed waves per dispatch path")
    ap.add_argument("--top-k", type=int, default=4)
    ap.add_argument("--max-pattern", type=int, default=24)
    ap.add_argument("--window-ms", type=float, default=2.0)
    ap.add_argument("--smoke", action="store_true",
                    help="tiny sizes for CI smoke runs")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    if args.smoke:
        args.text_len, args.concurrency, args.waves = 20_000, 64, 2
    if args.concurrency < 1 or args.waves < 1:
        ap.error("need --concurrency >= 1 and --waves >= 1")
    return args


def _percentiles(lat_ms: list[float]) -> dict:
    a = np.asarray(lat_ms)
    return {"p50_ms": round(float(np.percentile(a, 50)), 3),
            "p95_ms": round(float(np.percentile(a, 95)), 3)}


def _key(res) -> tuple:
    """Comparable identity of one QueryResult (bit-identity check)."""
    pos = (tuple() if res.positions is None
           else tuple(int(x) for x in np.asarray(res.positions).ravel()))
    return (tuple(int(c) for c in res.count),
            tuple(int(p) for p in res.first_pos),
            tuple(bool(f) for f in res.found), pos)


def run(args) -> dict:
    from repro.api import Database, Query, SuffixTable
    from repro.core import query as Q
    from repro.core.codec import random_dna

    table = SuffixTable.from_codes(random_dna(args.text_len, seed=0),
                                   is_dna=True)
    db = Database.in_memory(coalesce_window_ms=args.window_ms)
    db.attach("dna", table)

    # distinct patterns per wave slot so the result set is non-trivial;
    # the cache is cleared between arms anyway
    pats = Q.random_patterns(args.concurrency, 2, args.max_pattern, seed=1)
    queries = [Query.scan("dna", [p], top_k=args.top_k) for p in pats]

    # warm both jit shapes (B=1 bucket and the coalesced bucket)
    db.query(queries[0])
    db.query_many(queries)

    results: dict[str, list] = {}
    timings: dict[str, dict] = {}

    def record(name: str, qps: float, lat_ms: list[float]):
        timings[name] = {"queries_per_s": round(qps),
                         **_percentiles(lat_ms)}

    # -- per-call: one dispatch per caller ----------------------------------
    lat, t_total = [], 0.0
    for _ in range(args.waves):
        table.clear_cache()
        got = []
        t0 = time.perf_counter()
        for q in queries:
            tq = time.perf_counter()
            got.append(db.query(q))
            lat.append((time.perf_counter() - tq) * 1e3)
        t_total += time.perf_counter() - t0
        results.setdefault("per_call", got)
    record("per_call", args.waves * args.concurrency / t_total, lat)

    # -- coalesced inline: one bucket-padded dispatch per wave --------------
    lat, t_total = [], 0.0
    for _ in range(args.waves):
        table.clear_cache()
        t0 = time.perf_counter()
        got = db.query_many(queries)
        dt = time.perf_counter() - t0
        t_total += dt
        lat.extend([dt * 1e3] * len(queries))   # every caller waits the wave
        results.setdefault("coalesced", got)
    record("coalesced", args.waves * args.concurrency / t_total, lat)

    # -- scheduler: cross-caller window, worker-thread drain ----------------
    lat, t_total = [], 0.0
    for _ in range(args.waves):
        table.clear_cache()
        t0 = time.perf_counter()
        futs = [db.submit(q) for q in queries]
        got = [f.result(timeout=60.0) for f in futs]
        dt = time.perf_counter() - t0
        t_total += dt
        lat.extend([dt * 1e3] * len(queries))
        results.setdefault("scheduler", got)
    record("scheduler", args.waves * args.concurrency / t_total, lat)

    # -- low load: few callers with think time (adaptive fast path) ---------
    # 4 callers, ~10 ms apart, distinct patterns: arrivals are sparser
    # than the window, so the adaptive scheduler should dispatch inline
    # instead of sleeping out the coalesce window per query.
    import threading

    n_low = max(2, min(4, args.concurrency))
    per_caller = 6 if args.smoke else 12
    low_pats = Q.random_patterns(n_low * per_caller, 2, args.max_pattern,
                                 seed=3)
    low_qs = [Query.scan("dna", [p], top_k=args.top_k) for p in low_pats]
    think_s = 0.010
    low_lat: list[float] = []
    low_res: dict[int, object] = {}
    lock = threading.Lock()

    def low_caller(c: int):
        for r in range(per_caller):
            time.sleep(think_s)
            idx = c * per_caller + r
            tq = time.perf_counter()
            res = db.submit(low_qs[idx]).result(timeout=60.0)
            dt = (time.perf_counter() - tq) * 1e3
            with lock:
                low_res[idx] = res
                if r > 0:               # first round warms the EWMA/jit
                    low_lat.append(dt)

    table.clear_cache()
    fast0 = db.scheduler.stats.fast_path_queries
    threads = [threading.Thread(target=low_caller, args=(c,))
               for c in range(n_low)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(120.0)
    low = _percentiles(low_lat)
    low_fast = db.scheduler.stats.fast_path_queries - fast0
    low_identical = all(
        _key(low_res[i]) == _key(db.query(low_qs[i]))
        for i in range(n_low * per_caller))
    db.close()

    identical = all(
        _key(a) == _key(b) == _key(c)
        for a, b, c in zip(results["per_call"], results["coalesced"],
                           results["scheduler"])) and low_identical
    speedup = (timings["coalesced"]["queries_per_s"]
               / max(timings["per_call"]["queries_per_s"], 1))
    sched_speedup = (timings["scheduler"]["queries_per_s"]
                     / max(timings["per_call"]["queries_per_s"], 1))
    return {
        "bench": "client_coalescing",
        "text_len": args.text_len,
        "concurrency": args.concurrency,
        "waves": args.waves,
        "top_k": args.top_k,
        "window_ms": args.window_ms,
        "results": {
            **{f"{name}_{k}": v for name, t in timings.items()
               for k, v in t.items()},
            "coalesced_speedup_x": round(speedup, 2),
            "scheduler_speedup_x": round(sched_speedup, 2),
            "coalesced_low_load_p50_ms": low["p50_ms"],
            "coalesced_low_load_p95_ms": low["p95_ms"],
            # intentionally NOT named *_x: the low-load p50 has a fixed
            # floor (worker wakeup + one dispatch), so this ratio is NOT
            # scale-invariant — the gate compares it same-config only
            "low_load_p50_over_per_call": round(
                low["p50_ms"] / max(timings["per_call"]["p50_ms"], 1e-9), 2),
            "low_load_fast_path_queries": int(low_fast),
            "bit_identical": identical,
        },
    }


def bench_client():
    """benchmarks/run.py entry: (us_per_coalesced_query, derived)."""
    args = _parse(["--smoke"])
    payload = run(args)
    r = payload["results"]
    us = 1e6 / max(r["coalesced_queries_per_s"], 1)
    return us, r


def main() -> None:
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    args = _parse()
    payload = run(args)
    for k, v in payload["results"].items():
        print(f"{k}: {v}", flush=True)
    r = payload["results"]
    if not r["bit_identical"]:
        raise SystemExit("FAIL: coalesced results diverge from per-call")
    out = args.out or os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "BENCH_client.json")
    with open(out, "w") as f:
        json.dump(payload, f, indent=2)
        f.write("\n")
    print(f"wrote {out}")


if __name__ == "__main__":
    main()
