"""One run of one cell: set-up, warm-up, the measured window, the check
against the plain reference, and the metrics.

Everything a cell is made of is found by name from ``BENCHMARK.json``:
its configuration file (``configs/<config>.json``), its traffic mix
(``mixes/<traffic>.json``) and the reader of each per-layer metric that
lists it (``metrics/<metric>.py``, a module with ``read(ctx)`` that
returns a number, or None where it finds nothing to read).  A new cell
or metric is new files and entries, never an edit here.

``run_cell`` takes the cell as loaded, so a test can run it at a tiny
size on the CPU; ``run.py`` is the command line, which also makes sure
a TPU is there.
"""
from __future__ import annotations

import contextlib
import copy
import dataclasses
import gc
import importlib.util
import json
import os
import shutil
import statistics
import sys
import threading
import time

import numpy as np

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.dirname(BENCH_DIR)
TABLE = "text"
CHECK_PATTERNS = 4096           # answered patterns the reference checks
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"

if BENCH_DIR not in sys.path:
    sys.path.insert(0, BENCH_DIR)
if os.path.join(CHECKOUT, "src") not in sys.path:
    sys.path.insert(0, os.path.join(CHECKOUT, "src"))

import reference  # noqa: E402
import textgen  # noqa: E402
import trace_reduce  # noqa: E402
import traffic  # noqa: E402


# ---------------------------------------------------------------------------
# finding a cell's files by name
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class Cell:
    name: str
    config: dict
    mix: dict
    chips: int
    end_to_end: list          # BENCHMARK.json entries this cell reports
    per_layer: list
    bench_dir: str


def load_cell(name: str, root: str = CHECKOUT) -> Cell:
    """The cell ``name`` of ``<root>/BENCHMARK.json`` with its files."""
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; have {sorted(cells)}")
    w = cells[name]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    bench_dir = os.path.join(root, bench["paths"][0])
    with open(os.path.join(root, conf["file"])) as f:
        config = json.load(f)
    with open(os.path.join(bench_dir, "mixes", w["traffic"] + ".json")) as f:
        mix = json.load(f)

    def mine(m):
        return name in m.get("workloads", [name])

    return Cell(name=name, config=config, mix=mix, chips=int(w["chips"]),
                end_to_end=[m for m in bench["end_to_end"] if mine(m)],
                per_layer=[m for m in bench["per_layer"] if mine(m)],
                bench_dir=bench_dir)


def load_reader(bench_dir: str, metric: str):
    """The ``read`` function of ``metrics/<metric>.py``."""
    path = os.path.join(bench_dir, "metrics", metric + ".py")
    spec = importlib.util.spec_from_file_location(
        "chipbench_metric_" + metric.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def load_peaks(device_kind: str, bench_dir: str = BENCH_DIR) -> dict:
    """The published peaks of ``device_kind``; an unknown chip is an
    error, never a default."""
    with open(os.path.join(bench_dir, "peaks.json")) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device_kind {device_kind!r} in "
                       f"peaks.json (have {sorted(table)})")
    return table[device_kind]


# ---------------------------------------------------------------------------
# compilations inside the window
# ---------------------------------------------------------------------------
class CompileCounter:
    """Counts programs compiled or loaded from the cache (each fires
    JAX's backend-compile event).  JAX's listeners cannot be removed, so
    one counter is registered per process."""

    _lock = threading.Lock()
    _instance = None

    def __init__(self):
        self.count = 0

    def _on(self, event: str, duration: float, **kw) -> None:
        if event == BACKEND_COMPILE:
            self.count += 1

    @classmethod
    def get(cls) -> "CompileCounter":
        with cls._lock:
            if cls._instance is None:
                import jax
                cls._instance = cls()
                jax.monitoring.register_event_duration_secs_listener(
                    cls._instance._on)
            return cls._instance


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------
def _annotate(on: bool):
    if not on:
        return lambda name: contextlib.nullcontext()
    import jax
    return jax.profiler.TraceAnnotation


def _stats(obj) -> dict:
    return copy.deepcopy(obj.as_dict())


def _quantile(values, q: float) -> float:
    """The ``q`` quantile (0 < q < 1) by the exclusive method of
    ``statistics.quantiles``."""
    if len(values) < 2:
        return float(values[0]) if values else float("nan")
    return statistics.quantiles(values, n=100, method="exclusive")[
        int(round(q * 100)) - 1]


def run_cell(cell: Cell, *, seed: int, seconds: float, trace: bool,
             work_dir: str, t_start: float | None = None,
             log=print) -> dict:
    """Set up, warm up, measure for ``seconds`` and check; returns the
    result object the command prints (``correct``, ``attempted``,
    ``failed``, ``metrics``, ``device``, optionally ``breakdown``, and
    ``checks`` last).  ``t_start`` is when set-up began (the process's
    start, for the command)."""
    import jax

    from repro.api import Database

    t_start = time.perf_counter() if t_start is None else t_start
    annotate = _annotate(trace)
    config, mix = cell.config, cell.mix
    rng = np.random.default_rng(int(seed) % (1 << 63))
    if os.path.isdir(work_dir):
        shutil.rmtree(work_dir)
    os.makedirs(work_dir)
    phases: dict[str, float] = {}

    with annotate("chipbench.setup.text"):
        text = textgen.generate(config["text"], int(seed) % (1 << 63))
    log(f"text {text.size} bases at {time.perf_counter() - t_start:.3f} s")
    tcfg = config["table"]
    db = Database(os.path.join(work_dir, "db"))
    try:
        with annotate("chipbench.setup.create"):
            t0 = time.perf_counter()
            table = db.create_table(TABLE, text,
                                    max_query_len=int(tcfg["max_query_len"]),
                                    wal=bool(tcfg["wal"]))
            phases["create_s"] = time.perf_counter() - t0
        log(f"create_s {phases['create_s']:.3f}")
        if tcfg.get("frozen"):
            with annotate("chipbench.setup.freeze"):
                t0 = time.perf_counter()
                db.freeze(TABLE, sample_rate=int(tcfg["fm_sample_rate"]))
                phases["freeze_s"] = time.perf_counter() - t0
            log(f"freeze_s {phases['freeze_s']:.3f}")
        with annotate("chipbench.setup.traffic"):
            pools = traffic.make_pools(text, mix, TABLE, table.cache_size,
                                       rng)
        with annotate("chipbench.setup.warm"):
            traffic.warm(db, TABLE, text, mix, rng)
        store = table.store
        shapes = {"n_pad": int(store.n_pad),
                  "max_query_len": int(store.max_query_len),
                  "frozen": table.fm is not None}
        setup_s = time.perf_counter() - t_start
        log(f"setup_s {setup_s:.3f}")

        # -- the window ----------------------------------------------------
        counter = CompileCounter.get()
        table.tracer.reset()
        db.scheduler.tracer.reset()
        sched0 = _stats(db.scheduler.stats)
        plan0 = _stats(table.planner.stats)
        trace_dir = os.path.join(work_dir, "trace")
        if trace:
            # the Python tracer would record every call of every thread
            # and slow the host path it measures
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
        compiles0 = counter.count
        # set-up's objects (the request pools above all) are left out of
        # the collector's passes, so its pauses in the window are the
        # program's own
        gc.collect()
        gc.freeze()
        try:
            with annotate(trace_reduce.WINDOW_SPAN):
                records, t_open, t_close, unanswered = \
                    traffic.run_closed_loop(db, mix, pools, seconds,
                                            annotate)
        finally:
            gc.unfreeze()
        compiles = counter.count - compiles0
        spans = {"table": table.tracer.snapshot(),
                 "scheduler": db.scheduler.tracer.snapshot()}
        sched1 = _stats(db.scheduler.stats)
        plan1 = _stats(table.planner.stats)
        if trace:
            jax.profiler.stop_trace()
        devices = jax.devices()[:cell.chips]
        peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
                   for d in devices)
        log(f"window {seconds} s: {len(records)} requests, "
            f"{unanswered} unanswered, {compiles} compiles inside")

        sa = None
        if table.fm is None:
            sa = np.asarray(table.store.sa)[table.store.pad_count:]
    finally:
        db.close()
    del table, db, pools

    # -- the check ----------------------------------------------------------
    checks = check(text, records, mix, seed, unanswered, sa)
    del sa

    # -- the metrics --------------------------------------------------------
    ppr = int(mix["patterns_per_request"])
    done = [r for r in records if r.t_done <= t_close]
    ok = [r for r in done if r.result.ok]
    lat_ms = [(r.t_done - r.t_send) * 1e3 for r in ok]
    e2e = {"read_p95_ms": (_quantile(lat_ms, 0.95), "ms"),
           "read_patterns_per_s": (len(ok) * ppr / (t_close - t_open),
                                   "patterns/s"),
           "setup_s": (setup_s, "s")}
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": peak}
    out = {"correct": all(c["ok"] for c in checks.values()),
           "attempted": len(records) + unanswered,
           "failed": checks["failed"]["value"] + unanswered}
    metrics = {}
    breakdown = None
    if not trace:
        for m in cell.end_to_end:
            value, unit = e2e[m["name"]]
            metrics[m["name"]] = {"value": float(value), "unit": unit}
    else:
        reduced = trace_reduce.Reduced(trace_reduce.from_xplane(
            trace_reduce.find_xplane(trace_dir)))
        if reduced.busy_s is not None:
            device["busy_s"] = reduced.busy_s
            device["window_s"] = reduced.window_s
            breakdown = reduced.breakdown()
        peaks = (load_peaks(dev.device_kind, cell.bench_dir)
                 if dev.platform == "tpu" else None)
        ctx = {"config": config, "mix": mix, "phases": phases,
               "spans": spans, "scheduler": (sched0, sched1),
               "planner": (plan0, plan1), "shapes": shapes,
               "trace": reduced, "peaks": peaks,
               "window": (t_open, t_close), "records": records}
        for m in cell.per_layer:
            value = load_reader(cell.bench_dir, m["name"])(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": float(value),
                                      "unit": m["unit"]}
    out["metrics"] = metrics
    out["device"] = device
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["compiles_in_window"] = compiles
    out["checks"] = {k: {f: v for f, v in c.items() if f != "ok"}
                     for k, c in checks.items()}
    return out


# ---------------------------------------------------------------------------
# the check against the plain reference
# ---------------------------------------------------------------------------
def check(text, records, mix: dict, seed: int, unanswered: int, sa
          ) -> dict:
    """Every number compared, with its limit: a sample (drawn from the
    seed) of the patterns answered, compared exactly with the reference;
    requests that never came back or came back as errors (a refused or
    overloaded request is no fast answer); the suffix array's order
    where the table keeps one."""
    pats, count, first, pos = [], [], [], []
    top_k = int(mix.get("top_k", 0)) if mix["kind"] == "scan" else 0
    for r in records:
        res = r.result
        if not res.ok:
            continue
        pats.extend(r.query.patterns)
        count.append(np.asarray(res.count))
        first.append(np.asarray(res.first_pos))
        if top_k:
            pos.append(np.asarray(res.positions))
    checks = {"unanswered": {"value": unanswered, "max": 0},
              "failed": {"value": sum(1 for r in records
                                      if not r.result.ok), "max": 0}}
    rng = np.random.default_rng([int(seed) % (1 << 63), 1])
    take = np.sort(rng.choice(len(pats), size=min(len(pats), CHECK_PATTERNS),
                              replace=False)) if pats else np.zeros(0, int)
    checks["checked"] = {"value": int(take.size), "min": 1}
    if take.size:
        ref = reference.Reference(text)
        want_c, want_p = ref.answers([traffic.to_codes(pats[i])
                                      for i in take], max(top_k, 1))
        got_c = np.concatenate(count)[take]
        got_f = np.concatenate(first)[take]
        checks["count_wrong"] = {
            "value": int((got_c != want_c).sum()), "max": 0}
        checks["first_pos_wrong"] = {
            "value": int((got_f != want_p[:, 0]).sum()), "max": 0}
        if top_k:
            got_p = np.concatenate(pos)[take]
            checks["positions_wrong"] = {
                "value": int((got_p != want_p).any(axis=1).sum()),
                "max": 0}
    if sa is not None:
        ordered = reference.sa_in_order(
            np.asarray(text), sa, np.random.default_rng(int(seed) % (1 << 63)))
        checks["sa_unordered"] = {"value": int(not ordered), "max": 0}
    for c in checks.values():
        c["ok"] = c["value"] >= c.get("min", c["value"]) \
            and c["value"] <= c.get("max", c["value"])
    return checks
