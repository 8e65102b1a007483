#!/usr/bin/env python3
"""Where a benchmark cell's device idle time goes, by program span.

    python3 benchmarks/idle_by_span.py --workload <cell> --seed <n> \\
        --seconds <s>

Runs one traced window of a ``chipbench`` cell (``chipbench/harness.py``,
as ``chipbench/run.py --trace 1`` does) and reads the profiler trace the
harness reads.  Where ``trace_reduce.idle_gaps`` names a whole gap after
the one host span over its midpoint, this splits every idle instant of
the chip to the innermost program span open at that instant on any host
thread (the shortest one where threads differ): the ``table.*``,
``sched.*``, ``router.*`` and ``worker.*`` spans of ``repro``'s tracers
and the harness's ``chipbench.request``.  An instant under none of them
is ``no program span``.  The split is exact, not sampled.

The report (one JSON object, written to
``chiprun_out/idle_by_span_<cell>_<seed>.json`` and summed up on
standard output) holds the harness's result line, the idle split in
seconds and as a share of idle, the midpoint rule's names for
comparison, each program span's count and summed milliseconds inside the
window, the requests (``chipbench.request``) and scheduler waves
(``sched.execute``) the window held, and the table planner's counters
(``PlannerStats``, ``input_copybacks`` among them) after the window and
their change over it.  It refuses to run (exit 2) unless
JAX sees a TPU with the cell's number of chips.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

import numpy as np  # noqa: E402

CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CHIPBENCH = os.path.join(CHECKOUT, "chipbench")
sys.path[:0] = [CHIPBENCH, os.path.join(CHECKOUT, "src")]

import trace_reduce  # noqa: E402

PROGRAM = ("table.", "sched.", "router.", "worker.", "chipbench.request")
NO_SPAN = "no program span"
BETWEEN = "between ops of a program"
# the names trace_reduce.idle_gaps gives where no program span is named
HARNESS = ("chipbench.request", "chipbench.window", "no host span")


def _program_lines(reduced: trace_reduce.Reduced) -> list:
    """Per host thread, its program spans as (starts, ends, names)
    sorted by start, outer spans before the inner ones they hold."""
    out = []
    for plane in reduced.host:
        for line in plane["lines"]:
            ev = [e for e in line["events"] if e[0].startswith(PROGRAM)]
            if ev:
                ev.sort(key=lambda e: (e[1], -e[2]))
                out.append(([float(e[1]) for e in ev],
                            [float(e[1] + e[2]) for e in ev],
                            [e[0] for e in ev]))
    return out


def _innermost(starts, ends, names) -> tuple:
    """The thread's innermost open span as disjoint segments: (segment
    starts, segment ends, span lengths, span names)."""
    s0, s1, lens, who = [], [], [], []

    def emit(a, b, j):
        if b > a:
            s0.append(a)
            s1.append(b)
            lens.append(ends[j] - starts[j])
            who.append(names[j])

    stack: list[int] = []
    cur = 0.0
    for i, start in enumerate(starts):
        while stack and ends[stack[-1]] <= start:
            j = stack.pop()
            emit(cur, ends[j], j)
            cur = ends[j]
        if stack:
            emit(cur, start, stack[-1])
        stack.append(i)
        cur = start
    while stack:
        j = stack.pop()
        emit(cur, ends[j], j)
        cur = max(cur, ends[j])
    return (np.asarray(s0), np.asarray(s1), np.asarray(lens, np.float64),
            who)


def _idle(reduced: trace_reduce.Reduced, dev, busy) -> tuple:
    """The chip's idle time in the window: the intervals that neither an
    operation nor a program run covers, and the seconds between the
    operations of a run (as ``trace_reduce.idle_gaps`` counts them)."""
    runs = trace_reduce._intervals(trace_reduce._line(
        dev, trace_reduce.MODULES_LINE))
    cover = trace_reduce._union(np.concatenate([busy, runs]),
                                reduced.lo, reduced.hi)
    inside = (trace_reduce._total(cover) - trace_reduce._total(busy)) / 1e9
    edges = np.concatenate([[reduced.lo], cover.reshape(-1), [reduced.hi]])
    gaps = edges.reshape(-1, 2)
    return gaps[gaps[:, 1] > gaps[:, 0]], inside


def idle_split(reduced: trace_reduce.Reduced) -> dict:
    """``{name: seconds}`` of the chip's idle time in the window, split
    by the innermost program span, averaged over the chips."""
    segs = [_innermost(*ln) for ln in _program_lines(reduced)]
    tot: dict[str, float] = {}
    for dev, busy in zip(reduced.devices, reduced.busy):
        gaps, inside = _idle(reduced, dev, busy)
        if inside > 0:
            tot[BETWEEN] = tot.get(BETWEEN, 0.0) + inside
        if not gaps.size:
            continue
        cuts = np.unique(np.concatenate(
            [gaps.reshape(-1)] + [np.concatenate([s0, s1])
                                  for s0, s1, _, _ in segs]))
        lo, hi = cuts[:-1], cuts[1:]
        mid = (lo + hi) / 2
        g = np.searchsorted(gaps[:, 0], mid, "right") - 1
        idle = (g >= 0) & (mid < gaps[np.maximum(g, 0), 1])
        lo, hi, mid = lo[idle], hi[idle], mid[idle]
        best = np.full(mid.size, np.inf)
        name = np.full(mid.size, NO_SPAN, dtype=object)
        for s0, s1, lens, who in segs:
            if not s0.size:
                continue
            k = np.searchsorted(s0, mid, "right") - 1
            kk = np.maximum(k, 0)
            better = (k >= 0) & (mid < s1[kk]) & (lens[kk] < best)
            best = np.where(better, lens[kk], best)
            name = np.where(better, np.asarray(who, dtype=object)[kk], name)
        for n in np.unique(name):
            sec = float((hi - lo)[name == n].sum()) / 1e9
            tot[str(n)] = tot.get(str(n), 0.0) + sec
    n_dev = max(1, len(reduced.devices))
    return {k: v / n_dev for k, v in tot.items()}


def span_sums(reduced: trace_reduce.Reduced) -> dict:
    """``{name: [count, ms]}`` of the program spans that start inside
    the window, their durations summed."""
    out: dict[str, list] = {}
    for plane in reduced.host:
        for line in plane["lines"]:
            for name, start, dur in line["events"]:
                if (name.startswith(PROGRAM)
                        and reduced.lo <= start < reduced.hi):
                    c = out.setdefault(name, [0, 0.0])
                    c[0] += 1
                    c[1] += dur / 1e6
    return dict(sorted(out.items()))


def _shares(named: dict, idle: float) -> list:
    """[[name, seconds, % of idle]], largest first."""
    return [[k, v, 100.0 * v / idle]
            for k, v in sorted(named.items(), key=lambda kv: -kv[1])]


def planner_counters(before: dict, after: dict) -> dict:
    """The table planner's whole-number counters after the window
    (``total``, set-up's warm-up included) and their change over it
    (``window``)."""
    keys = [k for k, v in after.items() if isinstance(v, int)]
    return {"total": {k: after[k] for k in keys},
            "window": {k: after[k] - before.get(k, 0) for k in keys}}


def report(trace: dict) -> dict:
    """The idle split, the midpoint rule's names and the span sums of
    one traced window."""
    reduced = trace_reduce.Reduced(trace)
    split = idle_split(reduced)
    midpoint = dict(reduced.idle_gaps())
    sums = span_sums(reduced)
    idle = (sum(split.values()) or 1.0)
    return {
        "window_s": reduced.window_s,
        "busy_s": reduced.busy_s,
        "idle_s": idle,
        "idle_by_span": _shares(split, idle),
        "idle_by_midpoint": _shares(midpoint, idle),
        "harness_share_by_span": 100.0 * sum(
            split.get(k, 0.0) for k in HARNESS + (NO_SPAN,)) / idle,
        "harness_share_by_midpoint": 100.0 * sum(
            midpoint.get(k, 0.0) for k in HARNESS) / idle,
        "requests": sums.get("chipbench.request", [0, 0.0])[0],
        "waves": sums.get("sched.execute", [0, 0.0])[0],
        "spans": sums,
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)

    import run as chip_run          # chipbench/run.py, first on the path
    jax = chip_run.start_jax()
    devices = jax.devices()
    if devices[0].platform != "tpu":
        chip_run.log(f"FAIL: no TPU (platform {devices[0].platform!r})")
        return 2
    import harness
    cell = harness.load_cell(args.workload)
    if len(devices) != cell.chips:
        chip_run.log(f"FAIL: {len(devices)} chips visible, the cell asks "
                     f"for {cell.chips}")
        return 2

    # keep the trace the harness reads, so it is read once, and the
    # planner's counters the metric readers are handed
    kept = {}
    read = trace_reduce.from_xplane
    load_reader = harness.load_reader

    def keep(path):
        kept["trace"] = read(path)
        return kept["trace"]

    def keep_planner(bench_dir, metric):
        reader = load_reader(bench_dir, metric)

        def read_metric(ctx):
            kept["planner"] = ctx["planner"]
            return reader(ctx)
        return read_metric

    trace_reduce.from_xplane = keep
    harness.load_reader = keep_planner
    work = os.path.join(chip_run.STATE, "idle_by_span", args.workload)
    try:
        result = harness.run_cell(cell, seed=args.seed,
                                  seconds=args.seconds, trace=True,
                                  work_dir=work, t_start=T_START,
                                  log=chip_run.log)
    finally:
        trace_reduce.from_xplane = read
        harness.load_reader = load_reader
        shutil.rmtree(work, ignore_errors=True)
    out = {"workload": args.workload, "seed": args.seed,
           "seconds": args.seconds, "result": result,
           **report(kept["trace"]),
           "planner": planner_counters(*kept.get("planner", ({}, {})))}
    path = os.path.join(
        CHECKOUT, "chiprun_out",
        f"idle_by_span_{args.workload}_{args.seed}.json")
    os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
    with open(path, "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps({k: out[k] for k in (
        "workload", "seed", "idle_s", "harness_share_by_span",
        "harness_share_by_midpoint", "requests", "waves", "planner")}
        | {"idle_by_span": out["idle_by_span"][:8],
           "correct": result["correct"], "out": path}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
