"""Serving-plane benchmark: N worker processes x M closed-loop clients.

Measures the three serving-plane claims end to end against a real
multi-process deployment (``repro.serving.plane``), on one table built
once per run:

* **scale** — routed queries/s with 4 tablet workers vs 1.  Every
  worker holds a per-process device lock with a per-pattern service
  floor (``--device-floor-ms``), modeling one logical accelerator per
  tablet server; on a single-core host the floors are sleeps, which
  OVERLAP across worker processes exactly like independent accelerators
  would, so the scale factor is honest about dispatch parallelism while
  staying deterministic.  The table carries no delta for this arm (the
  owner's delta fan-in would otherwise serialize the full batch through
  one process and measure the short-circuit, not the scaling);
* **hedge** — per-call p99 with hedged reads on vs off, against 2
  tablets x 2 replicas where the PRIMARY replica of every tablet
  randomly injects ``--slow-ms`` stalls (the paper's 771 ms straggler
  events, scaled down).  Injection is pinned to replica 0 — a
  designated victim, as fault-injection harnesses do — so a backup RPC
  fired at the hedge deadline always lands on a healthy process and
  the gated gain metric measures the hedge path itself instead of
  coin-flipping on rare both-replicas-slow events;
* **overload** — an abusive tenant hammering the plane through a tight
  router token-bucket quota while an in-quota tenant keeps its own
  closed loop: the abuser's shed rate and the in-quota tenant's p95
  inflation over its own unloaded baseline.

Results are checked **bit-identical** against the in-process
``SuffixTable`` the build produced (the oracle handle is kept open the
whole run, never reopened).  Writes ``BENCH_plane.json`` at the repo
root; ``--smoke`` shrinks every dimension for the weekly CI job.

    PYTHONPATH=src python benchmarks/plane_bench.py [--smoke]
"""
from __future__ import annotations

import argparse
import json
import os
import tempfile
import threading
import time

import numpy as np


def _parse(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--text-len", type=int, default=120_000)
    ap.add_argument("--clients", type=int, default=6,
                    help="closed-loop client threads in the scale arm")
    ap.add_argument("--batch", type=int, default=16,
                    help="patterns per routed scan in the scale arm")
    ap.add_argument("--rounds", type=int, default=12,
                    help="batches per client thread in the scale arm")
    ap.add_argument("--device-floor-ms", type=float, default=6.0,
                    help="per-pattern service floor inside each worker's "
                         "device lock (the accelerator-per-worker model)")
    ap.add_argument("--hedge-calls", type=int, default=200,
                    help="single-pattern calls per hedging mode")
    ap.add_argument("--slow-ms", type=float, default=60.0,
                    help="injected straggler stall in the hedge arm")
    ap.add_argument("--slow-p", type=float, default=0.08,
                    help="per-RPC straggler probability in the hedge arm")
    ap.add_argument("--hedge-deadline-ms", type=float, default=15.0)
    ap.add_argument("--overload-seconds", type=float, default=6.0,
                    help="duration of the loaded phase per tenant arm")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny sizes for CI smoke runs")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    if args.smoke:
        args.text_len, args.rounds = 16_000, 4
        args.hedge_calls, args.overload_seconds = 100, 2.5
    if args.clients < 1 or args.batch < 1 or args.rounds < 1:
        ap.error("need --clients/--batch/--rounds >= 1")
    return args


def _pcts(lat_ms: list[float]) -> dict:
    a = np.asarray(lat_ms)
    return {"p50_ms": round(float(np.percentile(a, 50)), 3),
            "p95_ms": round(float(np.percentile(a, 95)), 3),
            "p99_ms": round(float(np.percentile(a, 99)), 3)}


def _patterns(n: int, seed: int, lmin: int = 4, lmax: int = 16):
    """Random DNA patterns, >= lmin long: very short patterns prefix-
    match several split keys and get double-routed, which is correct
    but makes the scale arm measure routing fan-out, not workers."""
    rng = np.random.default_rng(seed)
    return ["".join("ACGT"[c] for c in rng.integers(0, 4, size=int(L)))
            for L in rng.integers(lmin, lmax + 1, size=n)]


def _closed_loop(remote, pats_per_thread, batch):
    """Each thread scans its batches back to back; returns (wall_s,
    per-call latencies ms, total patterns)."""
    lat: list[float] = []
    lock = threading.Lock()
    total = sum(len(p) for p in pats_per_thread)

    def worker(pats):
        mine = []
        for i in range(0, len(pats), batch):
            t0 = time.perf_counter()
            remote.scan(pats[i:i + batch])
            mine.append((time.perf_counter() - t0) * 1e3)
        with lock:
            lat.extend(mine)

    threads = [threading.Thread(target=worker, args=(p,))
               for p in pats_per_thread]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return time.perf_counter() - t0, lat, total


def _scale_arm(args, root, name, n_tablets, seed) -> float:
    """Deploy n_tablets x 1 plane, hammer it, return patterns/s."""
    from repro.serving.plane import ServingPlane
    with ServingPlane.deploy(
            root, name, n_tablets, replicas=1,
            device_floor_ms=args.device_floor_ms,
            max_inflight=args.clients + 2,
            metrics_interval_s=0.0) as plane:
        remote = plane.remote_table(hedge_enabled=False)
        try:
            remote.scan(_patterns(args.batch, seed=99))     # warm dials
            per_thread = [
                _patterns(args.rounds * args.batch, seed=seed + c)
                for c in range(args.clients)]
            wall, _lat, total = _closed_loop(remote, per_thread,
                                             args.batch)
            return total / wall
        finally:
            remote.close()


def run(args) -> dict:
    from repro.api import Database, Query
    from repro.core.codec import random_dna
    from repro.serving.plane import ServingPlane

    tmp = tempfile.mkdtemp(prefix="plane-bench-")
    root = os.path.join(tmp, "root")
    db = Database(root)
    # the oracle handle: kept open for the whole run — reopening a root
    # whose commit log is held would re-attach the live segment
    table = db.create_table("plane", random_dna(args.text_len, seed=0),
                            is_dna=True, max_query_len=32)
    results: dict = {}

    # -- scale: 1 worker vs 4 -----------------------------------------------
    qps1 = _scale_arm(args, root, "plane", 1, seed=10)
    qps4 = _scale_arm(args, root, "plane", 4, seed=10)
    results["routed_1w_queries_per_s"] = round(qps1, 1)
    results["routed_4w_queries_per_s"] = round(qps4, 1)
    results["scale_factor_4w_vs_1w_x"] = round(qps4 / max(qps1, 1e-9), 2)

    # -- bit-identicality + overload on a fresh 4x1 plane ---------------------
    with ServingPlane.deploy(root, "plane", 4, replicas=1,
                             device_floor_ms=args.device_floor_ms / 2,
                             metrics_interval_s=0.0):
        remote = db.connect_plane("plane", attach_as="plane@bench")
        probe = _patterns(64, seed=21, lmin=1, lmax=24) + ["ACGT", "A"]
        local = table.scan(probe, top_k=8)
        routed = remote.scan(probe, top_k=8)
        results["bit_identical"] = bool(
            np.array_equal(np.asarray(local.count), routed.count)
            and np.array_equal(np.asarray(local.first_pos),
                               routed.first_pos)
            and np.array_equal(np.asarray(local.positions),
                               routed.positions))

        # unloaded baseline: the in-quota tenant alone
        inq = _patterns(400, seed=31)

        def inquota_loop(seconds: float) -> list[float]:
            lat, i, t_end = [], 0, time.perf_counter() + seconds
            while time.perf_counter() < t_end:
                pats = [inq[(i + j) % len(inq)] for j in range(4)]
                i += 4
                t0 = time.perf_counter()
                r = db.query(Query.scan("plane@bench", pats,
                                        tenant="tenant-a"))
                if r.ok:
                    lat.append((time.perf_counter() - t0) * 1e3)
            return lat

        unloaded = _pcts(inquota_loop(args.overload_seconds))

        # loaded: two abuser threads behind a tight token bucket
        remote.router.set_quota("abuser", rate_per_s=20.0, burst=32.0)
        abuse_sent = [0]
        abuse_shed = [0]
        stop = threading.Event()

        def abuser():
            pats = _patterns(16, seed=41)
            while not stop.is_set():
                r = db.query(Query.scan("plane@bench", pats,
                                        tenant="abuser"))
                abuse_sent[0] += 1
                if r.overloaded:
                    abuse_shed[0] += 1
                # remote abusers are paced by their own network RTT and
                # don't share the serving host's interpreter; without
                # this the spin loop measures GIL contention between
                # bench threads on a 1-core host, not plane behavior
                time.sleep(0.002)

        threads = [threading.Thread(target=abuser) for _ in range(2)]
        for t in threads:
            t.start()
        loaded = _pcts(inquota_loop(args.overload_seconds))
        stop.set()
        for t in threads:
            t.join()
        results["inquota_unloaded_p95_ms"] = unloaded["p95_ms"]
        results["inquota_loaded_p95_ms"] = loaded["p95_ms"]
        results["inquota_p95_over_unloaded_x"] = round(
            loaded["p95_ms"] / max(unloaded["p95_ms"], 1e-9), 2)
        results["abuser_shed_rate"] = round(
            abuse_shed[0] / max(abuse_sent[0], 1), 3)
        results["abuser_batches_sent"] = abuse_sent[0]
        results["router_quota_shed"] = remote.router.quota_shed

    # -- hedge: stragglers with and without the backup RPC --------------------
    with ServingPlane.deploy(root, "plane", 2, replicas=2,
                             device_floor_ms=1.0,
                             inject_slow_ms=args.slow_ms,
                             inject_slow_p=args.slow_p,
                             inject_slow_replica=0,
                             metrics_interval_s=0.0) as plane:
        pats = _patterns(args.hedge_calls, seed=51)
        hstats = {}
        for hedged in (False, True):
            rt = plane.remote_table(
                hedge_enabled=hedged,
                hedge_deadline_ms=args.hedge_deadline_ms)
            try:
                rt.scan(pats[:1])                           # warm dials
                lat = []
                for p in pats:
                    t0 = time.perf_counter()
                    rt.scan([p])
                    lat.append((time.perf_counter() - t0) * 1e3)
                mode = "hedged" if hedged else "unhedged"
                hstats[mode] = _pcts(lat)
                if hedged:
                    results["hedge_fired"] = rt.router.hedge_fired
                    results["hedge_wins"] = rt.router.hedge_wins
            finally:
                rt.close()
        results["unhedged_p99_ms"] = hstats["unhedged"]["p99_ms"]
        results["hedged_p99_ms"] = hstats["hedged"]["p99_ms"]
        results["hedged_p99_gain_x"] = round(
            hstats["unhedged"]["p99_ms"]
            / max(hstats["hedged"]["p99_ms"], 1e-9), 2)

    db.close()
    return {
        "bench": "plane_swarm",
        "text_len": args.text_len,
        "clients": args.clients,
        "batch": args.batch,
        "rounds": args.rounds,
        "device_floor_ms": args.device_floor_ms,
        "hedge_calls": args.hedge_calls,
        "slow_ms": args.slow_ms,
        "slow_p": args.slow_p,
        "hedge_deadline_ms": args.hedge_deadline_ms,
        "overload_seconds": args.overload_seconds,
        "results": results,
    }


def bench_plane():
    """benchmarks/run.py entry: (us per routed pattern at 4 workers,
    derived)."""
    args = _parse(["--smoke"])
    payload = run(args)
    r = payload["results"]
    us = 1e6 / max(r["routed_4w_queries_per_s"], 1e-9)
    return us, r


def main() -> None:
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    args = _parse()
    payload = run(args)
    for k, v in payload["results"].items():
        print(f"{k}: {v}", flush=True)
    if not payload["results"]["bit_identical"]:
        raise SystemExit("FAIL: routed results diverge from the "
                         "single-process oracle")
    out = args.out or os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "BENCH_plane.json")
    with open(out, "w") as f:
        json.dump(payload, f, indent=2)
        f.write("\n")
    print(f"wrote {out}")


if __name__ == "__main__":
    main()
