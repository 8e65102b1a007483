"""Serving observability benchmark: the tentpole's proof-of-work.

Three questions, one scripted load run (docs/observability.md):

* **served latency from the feed** — the load runs through the real
  ``Database``/``QueryScheduler`` path with the table's metrics
  emitter streaming ``stats()`` into a ``metrics.jsonl`` feed; the
  reported p50/p95 are aggregated FROM THAT FEED (the same rows
  ``serve.py --dump-stats`` and ``check_regression.py --from-feed``
  read), so the number gated in CI is what a serving process actually
  recorded about itself, not a bench-side stopwatch;
* **tracing overhead** — the per-query tracing layer must be ~free on
  the inline fast path: per-call latency is measured with the tracers
  enabled vs disabled (min-of-alternating-reps to kill scheduler
  noise) and reported as ``trace_overhead_x`` (gated, lower-better);
  results are checked bit-identical across both arms.  The cost of one
  span alone is reported in microseconds (``span_us_disabled``,
  ``span_us``, and ``span_us_profiled`` while a ``jax.profiler`` trace
  records, when every span is also a profiler annotation);
* **tuned launcher effect** — a fresh subprocess imports jax and runs
  one dispatch under the default env vs the ``--tuned`` preset
  (TF_CPP_MIN_LOG_LEVEL=4 + tcmalloc report threshold; the LD_PRELOAD
  half lives in ``launch/run.sh`` and needs the .so present, so it is
  applied when available);  startup seconds and stderr log bytes are
  reported, plus ``tuned_not_noisier`` (the preset must never ADD log
  noise — gated as a flag).  The probes run before this process
  touches JAX: an accelerator belongs to one process, and a child that
  needs it after the parent has claimed it fails or hangs.  When the
  parent already holds a backend (the ``benchmarks/run.py`` entry), the
  children are pinned to the CPU.

Writes ``BENCH_serving.json`` at the repo root; ``--feed-out PATH``
additionally copies the load run's feed for ``--from-feed`` gating.
``--smoke`` shrinks every dimension for the weekly CI job.

    PYTHONPATH=src python benchmarks/serving_bench.py [--smoke] \\
        [--feed-out bench-out/serving_feed.jsonl]
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import subprocess
import sys
import tempfile
import time

import numpy as np


def _parse(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--text-len", type=int, default=200_000)
    ap.add_argument("--concurrency", type=int, default=128,
                    help="simulated concurrent callers per load wave")
    ap.add_argument("--waves", type=int, default=4,
                    help="load waves through the scheduler")
    ap.add_argument("--probe-calls", type=int, default=60,
                    help="sequential per-call probes per overhead rep")
    ap.add_argument("--reps", type=int, default=3,
                    help="alternating enabled/disabled overhead reps")
    ap.add_argument("--max-pattern", type=int, default=24)
    ap.add_argument("--smoke", action="store_true",
                    help="tiny sizes for CI smoke runs")
    ap.add_argument("--feed-out", default=None,
                    help="copy the load run's metrics.jsonl here "
                         "(input for check_regression --from-feed)")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    if args.smoke:
        args.text_len, args.concurrency = 20_000, 32
        args.waves, args.probe_calls = 2, 30
    if args.concurrency < 1 or args.waves < 1 or args.probe_calls < 1:
        ap.error("need positive --concurrency/--waves/--probe-calls")
    return args


def _rand_patterns(rng, n: int, max_len: int) -> list[str]:
    lens = rng.integers(3, max(4, max_len), size=n)
    return ["".join(rng.choice(list("ACGT"), size=int(L)))
            for L in lens]


def _set_tracers(db, table, enabled: bool) -> None:
    table.tracer.enabled = enabled
    db.scheduler.tracer.enabled = enabled


def _served_load(args, db, table, name: str, feed_path: str) -> dict:
    """Scripted load with the feed on; served stats come FROM the feed."""
    from repro.api import Query
    from repro.serving.metrics import aggregate_metrics

    rng = np.random.default_rng(7)

    def wave():
        pats = _rand_patterns(rng, args.concurrency, args.max_pattern)
        futs = [db.submit(Query.count(name, [p])) for p in pats]
        for f in futs:
            r = f.result(timeout=60.0)
            assert r.ok, r.error
        # plus one coalesced burst per wave (query_many inline path)
        out = db.query_many([Query.scan(name, pats[:8], top_k=4)])
        assert all(r.ok for r in out)
        return len(futs) + 8

    # unrecorded warmup first, so the feed describes steady-state
    # serving rather than one-time jit spikes: batches pad to
    # power-of-two buckets, so compile every bucket the load can hit
    # (count path up to `concurrency`, the top-k scan-burst bucket),
    # then run one throwaway wave for the scheduler's adaptive state
    b = 1
    while b <= args.concurrency:
        pats = _rand_patterns(rng, b, args.max_pattern)
        assert all(r.ok for r in db.query_many(
            [Query.count(name, [p]) for p in pats]))
        b *= 2
    assert all(r.ok for r in db.query_many(
        [Query.scan(name, _rand_patterns(rng, 8, args.max_pattern),
                    top_k=4)]))
    wave()
    table.tracer.reset()
    table.start_metrics(feed_path, interval_s=0.2, name=name)
    t0 = time.perf_counter()
    n_queries = 0
    for _ in range(args.waves):
        n_queries += wave()
    wall_s = time.perf_counter() - t0
    table.stop_metrics()               # final row carries the last word
    agg = aggregate_metrics(feed_path)["summary"]
    return {
        "queries": int(n_queries),
        "wall_s": round(wall_s, 3),
        "queries_per_s": round(n_queries / max(wall_s, 1e-9), 1),
        "feed_emitters": int(agg["emitters"]),
        "feed_queries": int(agg["queries"]),
        "p50_ms": agg["p50_ms_median"],
        "p95_ms": agg["p95_ms_max"],
    }


SPAN_COST_SPANS = 100_000        # spans a round of the per-span cost
SPAN_COST_ROUNDS = 5             # the figure is the best round


def _us_per_span(tracer) -> float:
    best = float("inf")
    for _ in range(SPAN_COST_ROUNDS):
        t0 = time.perf_counter()
        for _ in range(SPAN_COST_SPANS):
            with tracer.span("dispatch"):
                pass
        best = min(best, time.perf_counter() - t0)
    return best / SPAN_COST_SPANS * 1e6


def _span_cost() -> dict:
    """Microseconds per span: a disabled tracer, an enabled one with no
    profiler session, and an enabled one while a trace records (written
    to a temporary directory and dropped)."""
    import jax

    from repro.serving.trace import Tracer

    out = {"span_us_disabled": _us_per_span(Tracer("table",
                                                   enabled=False)),
           "span_us": _us_per_span(Tracer("table"))}
    with tempfile.TemporaryDirectory(prefix="serving_bench_trace_") as d:
        with jax.profiler.trace(d):
            out["span_us_profiled"] = _us_per_span(Tracer("table"))
    return {k: round(v, 4) for k, v in out.items()}


def _overhead(args, db, table, name: str) -> dict:
    """Per-call fast-path latency, tracers enabled vs disabled —
    min-of-alternating-reps so one GC hiccup can't fake a regression."""
    from repro.api import Query

    rng = np.random.default_rng(11)
    pats = _rand_patterns(rng, args.probe_calls, args.max_pattern)
    db.query(Query.count(name, [pats[0]]))        # warm the jit caches

    def arm(enabled: bool):
        _set_tracers(db, table, enabled)
        table.planner.invalidate_cache()          # no cache cross-talk
        lat = []
        keys = []
        for p in pats:
            t0 = time.perf_counter()
            r = db.query(Query.count(name, [p]))
            lat.append((time.perf_counter() - t0) * 1e3)
            keys.append((int(r.count[0]), int(r.first_pos[0])))
        return float(np.median(lat)), keys

    on_best, off_best = float("inf"), float("inf")
    on_keys = off_keys = None
    for _ in range(args.reps):
        m, k = arm(True)
        on_best, on_keys = min(on_best, m), k
        m, k = arm(False)
        off_best, off_keys = min(off_best, m), k
    _set_tracers(db, table, True)
    return {
        "p50_on_ms": round(on_best, 4),
        "p50_off_ms": round(off_best, 4),
        "trace_overhead_x": round(on_best / max(off_best, 1e-9), 3),
        "bit_identical": on_keys == off_keys,
    }


_STARTUP_CODE = (
    "import time,sys; t0=time.perf_counter(); "
    "import jax, jax.numpy as jnp; "
    "jnp.zeros(16).block_until_ready(); "
    "print(round(time.perf_counter()-t0, 3))"
)


def _startup(env_extra: dict) -> tuple[float, int]:
    """(import+first-dispatch seconds, stderr bytes) in a fresh child."""
    from jax._src import xla_bridge
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env.update(env_extra)
    if xla_bridge.backends_are_initialized():
        env["JAX_PLATFORMS"] = "cpu"        # the chip is this process's
    proc = subprocess.run([sys.executable, "-c", _STARTUP_CODE],
                          capture_output=True, text=True, env=env,
                          timeout=300)
    if proc.returncode != 0:
        raise RuntimeError(f"startup probe failed: {proc.stderr[-500:]}")
    return float(proc.stdout.strip().splitlines()[-1]), len(proc.stderr)


def _tuned_effect() -> dict:
    """Default env vs the --tuned preset (plus tcmalloc when the .so
    exists — the launch/run.sh half), one fresh subprocess each."""
    tuned_env = {"TF_CPP_MIN_LOG_LEVEL": "4",
                 "TCMALLOC_LARGE_ALLOC_REPORT_THRESHOLD": "60000000000"}
    for so in ("/usr/lib/x86_64-linux-gnu/libtcmalloc.so.4",
               "/usr/lib/x86_64-linux-gnu/libtcmalloc_minimal.so.4"):
        if os.path.exists(so):
            tuned_env["LD_PRELOAD"] = so
            break
    default_s, default_log = _startup({"TF_CPP_MIN_LOG_LEVEL": "2"})
    tuned_s, tuned_log = _startup(tuned_env)
    return {
        "startup_default_s": default_s,
        "startup_tuned_s": tuned_s,
        "log_bytes_default": default_log,
        "log_bytes_tuned": tuned_log,
        "tcmalloc_preloaded": "LD_PRELOAD" in tuned_env,
        "tuned_not_noisier": tuned_log <= default_log,
    }


def run(args) -> dict:
    tuned = _tuned_effect()                 # before this process uses JAX
    from repro.api import Database, SuffixTable
    from repro.core.codec import random_dna

    table = SuffixTable.from_codes(random_dna(args.text_len, seed=0),
                                   is_dna=True)
    db = Database.in_memory()
    name = "serving_bench"
    db.attach(name, table)

    tmp = tempfile.mkdtemp(prefix="serving_bench_")
    feed_path = os.path.join(tmp, "metrics.jsonl")
    try:
        served = _served_load(args, db, table, name, feed_path)
        overhead = _overhead(args, db, table, name)
        span_cost = _span_cost()
        if args.feed_out:
            os.makedirs(os.path.dirname(os.path.abspath(args.feed_out)),
                        exist_ok=True)
            shutil.copyfile(feed_path, args.feed_out)
            print(f"feed copied to {args.feed_out}", flush=True)
    finally:
        db.close()
        shutil.rmtree(tmp, ignore_errors=True)
    return {
        "bench": "serving_observability",
        "text_len": args.text_len,
        "concurrency": args.concurrency,
        "waves": args.waves,
        "probe_calls": args.probe_calls,
        "reps": args.reps,
        "results": {
            "served": served,
            **overhead,
            **span_cost,
            **tuned,
        },
    }


def bench_serving():
    """benchmarks/run.py entry: (us_per_served_query, derived)."""
    args = _parse(["--smoke"])
    payload = run(args)
    r = payload["results"]
    us = 1e6 / max(r["served"]["queries_per_s"], 1)
    return us, {"trace_overhead_x": r["trace_overhead_x"],
                "served_p50_ms": r["served"]["p50_ms"],
                "tuned_not_noisier": r["tuned_not_noisier"]}


def main() -> None:
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    args = _parse()
    payload = run(args)

    def flat(d, pre=""):
        for k, v in d.items():
            if isinstance(v, dict):
                flat(v, pre + k + ".")
            else:
                print(f"{pre}{k}: {v}", flush=True)

    flat(payload["results"])
    r = payload["results"]
    if not r["bit_identical"]:
        raise SystemExit("FAIL: results diverge with tracing disabled")
    out = args.out or os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "BENCH_serving.json")
    with open(out, "w") as f:
        json.dump(payload, f, indent=2)
        f.write("\n")
    print(f"wrote {out}")


if __name__ == "__main__":
    main()
