"""Observability layer: span histograms against a numpy oracle, tracer
semantics, the stats → feed → aggregate round-trip, span/wall
consistency on real queries, the ``--from-feed`` gate, the docs link
checker, and the ``--tuned`` env preset in a fresh interpreter
(docs/observability.md)."""
import importlib.util
import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

from repro.api import Database, Query, SuffixTable
from repro.core import codec
from repro.serving.metrics import aggregate_metrics, table_record
from repro.serving.trace import SpanHistogram, Tracer

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _quantile_oracle(samples):
    """The documented rule: sorted sample at int(frac*n), clamped."""
    data = np.sort(np.asarray(samples, np.float64))
    n = len(data)
    return {f"p{int(f * 100)}_ms":
            round(float(data[min(n - 1, int(f * n))]), 4)
            for f in (0.50, 0.95, 0.99)}


def test_span_histogram_matches_numpy_oracle():
    rng = np.random.default_rng(0)
    samples = rng.lognormal(0.0, 1.0, size=500)
    h = SpanHistogram()
    for s in samples:
        h.record(float(s))
    q = h.quantiles()
    assert {k: q[k] for k in ("p50_ms", "p95_ms", "p99_ms")} \
        == _quantile_oracle(samples)
    assert q["n"] == 500 and q["total"] == 500
    assert q["sum_ms"] == pytest.approx(float(samples.sum()), rel=1e-6)


def test_span_histogram_ring_wraparound_keeps_latest_window():
    rng = np.random.default_rng(1)
    samples = rng.uniform(0.1, 50.0, size=200)
    h = SpanHistogram(size=64)
    for s in samples:
        h.record(float(s))
    q = h.quantiles()
    # the ring retains exactly the most recent 64 samples
    assert {k: q[k] for k in ("p50_ms", "p95_ms", "p99_ms")} \
        == _quantile_oracle(samples[-64:])
    assert q["n"] == 64 and q["total"] == 200
    assert q["sum_ms"] == pytest.approx(float(samples.sum()), rel=1e-6)


def test_empty_histogram_and_bad_size():
    q = SpanHistogram().quantiles()
    assert q == {"p50_ms": 0.0, "p95_ms": 0.0, "p99_ms": 0.0,
                 "n": 0, "total": 0, "sum_ms": 0.0}
    with pytest.raises(ValueError):
        SpanHistogram(size=0)


def test_tracer_spans_measure_and_snapshot_sorts():
    tr = Tracer("table")
    with tr.span("zz_outer"):
        with tr.span("aa_inner"):
            time.sleep(0.01)
    tr.record("manual", 2.5)
    snap = tr.snapshot()
    assert list(snap) == sorted(snap) == ["aa_inner", "manual",
                                          "zz_outer"]
    assert snap["aa_inner"]["p50_ms"] >= 10.0 * 0.9
    assert snap["zz_outer"]["p50_ms"] >= snap["aa_inner"]["p50_ms"]
    assert snap["manual"] == {"p50_ms": 2.5, "p95_ms": 2.5,
                              "p99_ms": 2.5, "n": 1, "total": 1,
                              "sum_ms": 2.5}
    tr.reset()
    assert tr.snapshot() == {}


def test_disabled_tracer_is_shared_noop():
    tr = Tracer("table", enabled=False)
    assert tr.span("a") is tr.span("b")       # one shared null context
    with tr.span("a"):
        pass
    tr.record("a", 1.0)
    assert tr.snapshot() == {}
    tr.enabled = True                         # runtime re-enable works
    tr.record("a", 1.0)
    assert tr.snapshot()["a"]["n"] == 1


def test_query_spans_sum_close_to_total_wall():
    """dispatch + merge happen inside scan_batch, so their accumulated
    time can never exceed the end-to-end ``total`` span."""
    table = SuffixTable.from_codes(codec.random_dna(20_000, seed=0),
                                   is_dna=True)
    # distinct patterns each round: the result cache must not collapse
    # the scans we are timing
    for p in ["ACGT", "GATTACA", "TTT", "CCGA", "TAGC"]:
        out = table.scan([p, p + "A"])
        assert int(np.asarray(out.count).sum()) >= 0
    lat = table.stats()["latency"]
    assert {"encode", "dispatch", "merge", "total"} <= set(lat)
    assert lat["total"]["n"] == 5
    inner = lat["dispatch"]["sum_ms"] + lat["merge"]["sum_ms"]
    assert inner <= lat["total"]["sum_ms"] * 1.05 + 0.1
    assert lat["total"]["p50_ms"] > 0.0


def test_scheduler_and_planner_spans_appear():
    with Database.in_memory() as db:
        db.attach("t", SuffixTable.from_codes(
            codec.random_dna(10_000, seed=1), is_dna=True))
        futs = [db.submit(Query.count("t", ["ACG", "TTAA"]))
                for _ in range(4)]
        for f in futs:
            assert f.result(timeout=30.0).ok
        st = db.stats()
        sched_lat = st["scheduler"]["latency"]
        assert sched_lat["execute"]["n"] >= 1
        assert "coalesce_wait" in sched_lat or \
            st["scheduler"]["fast_path_queries"] > 0
        # planner spans ride the table's tracer, one dispatch_* per mode
        tbl_lat = st["tables"]["t"]["latency"]
        assert any(k.startswith("dispatch") for k in tbl_lat)


# the spans a read leaves in a profiler trace, by their profiler names
READ_SPANS = {"table.cache_lookup", "table.encode", "table.upload",
              "table.plen_check", "table.dispatch_single", "table.wait",
              "table.dispatch", "table.merge", "table.cache_fill",
              "sched.execute", "sched.window", "sched.deliver"}


def _traced_reads(tmp_path, *, enabled=True):
    """Inline count and scan(top_k) queries plus a windowed submit on a
    tiny table under ``jax.profiler.trace``; returns the program's host
    events ``{name: [(start_ns, end_ns), ...]}`` and both tracers'
    ``stats()["latency"]`` keys."""
    import jax

    sys.path.insert(0, os.path.join(REPO, "chipbench"))
    import trace_reduce

    with Database.in_memory(adaptive_window=False) as db:
        table = db.attach("t", SuffixTable.from_codes(
            codec.random_dna(10_000, seed=4), is_dna=True))
        for q in (Query.count("t", ["ACGT", "TTAG"]),
                  Query.scan("t", ["GATC"], top_k=3)):
            assert db.query(q).ok             # compile outside the trace
        table.clear_cache()
        for tr in (table.tracer, db.scheduler.tracer):
            tr.reset()
            tr.enabled = enabled
        with jax.profiler.trace(str(tmp_path / "trace")):
            assert db.query(Query.count("t", ["ACGTA", "TTAGC"])).ok
            assert db.query(Query.scan("t", ["GATCA", "CC"], top_k=3)).ok
            assert db.submit(Query.count("t", ["GGAT"])).result(30.0).ok
        keys = (set(table.stats()["latency"]),
                set(db.scheduler.stats_snapshot()["latency"]))
    events: dict[str, list] = {}
    trace = trace_reduce.from_xplane(
        trace_reduce.find_xplane(str(tmp_path / "trace")))
    for plane in trace["planes"]:
        if plane["name"] != trace_reduce.HOST_PLANE:
            continue
        for line in plane["lines"]:
            for name, start, dur in line["events"]:
                if name.startswith(("table.", "sched.")):
                    events.setdefault(name, []).append((start, start + dur))
    return events, keys


def test_read_spans_land_in_the_profiler_trace(tmp_path):
    events, _keys = _traced_reads(tmp_path)
    assert READ_SPANS <= set(events)
    # the device read splits into leaves inside its dispatch span
    outer = events["table.dispatch"]
    for leaf in ("table.upload", "table.dispatch_single", "table.wait"):
        for lo, hi in events[leaf]:
            assert any(a <= lo and hi <= b for a, b in outer), leaf


def test_disabled_tracer_emits_no_profiler_spans(tmp_path):
    events, keys = _traced_reads(tmp_path, enabled=False)
    assert events == {}
    assert keys == (set(), set())


def test_latency_keys_keep_their_bare_names(tmp_path):
    _events, (table_keys, sched_keys) = _traced_reads(tmp_path)
    assert table_keys == {"cache_lookup", "encode", "upload", "plen_check",
                          "dispatch_single", "wait", "dispatch", "merge",
                          "cache_fill", "total"}
    assert sched_keys == {"execute", "window", "deliver", "coalesce_wait"}


def test_span_annotation_is_the_profilers_trace_annotation(tmp_path):
    import jax.profiler

    from repro.serving import trace
    assert issubclass(jax.profiler.TraceAnnotation, trace._Annotation)
    tr = Tracer("router")
    # no profiler session: a plain timed span, no annotation made
    span = tr.span("request")
    assert type(span) is trace._Span
    with span:
        pass
    assert tr._labels == {}
    with jax.profiler.trace(str(tmp_path / "trace")):
        span = tr.span("request")
        assert isinstance(span, trace._AnnotatedSpan)
        with span:
            pass
    assert tr._labels == {"request": "router.request"}
    assert tr.snapshot()["request"]["n"] == 2


def test_headline_is_the_feed_rows_latency_scalars():
    tr = Tracer("worker")
    assert tr.headline("service") == {"p50_ms": 0.0, "p95_ms": 0.0,
                                      "p99_ms": 0.0, "n": 0}
    for ms in (1.0, 2.0, 3.0, 4.0):
        tr.record("service", ms)
    snap = tr.snapshot()["service"]
    assert tr.headline("service") == {k: snap[k] for k in
                                      ("p50_ms", "p95_ms", "p99_ms", "n")}


def test_stats_to_feed_round_trip(tmp_path):
    """One schema end to end: stats() → table_record → metrics.jsonl →
    aggregate_metrics, with typed scalars the aggregator can sum."""
    feed = str(tmp_path / "metrics.jsonl")
    with Database.in_memory() as db:
        table = db.attach("rt", SuffixTable.from_codes(
            codec.random_dna(10_000, seed=2), is_dna=True))
        # final-row-only mode; name= overrides the anonymous table's id
        table.start_metrics(feed, interval_s=0.0, name="rt")
        for _ in range(3):
            assert db.query(Query.count("rt", ["ACGT"])).ok
        table.stop_metrics()

    rows = [json.loads(ln) for ln in open(feed) if ln.strip()]
    assert len(rows) == 1
    row = rows[0]
    assert row["role"] == "table" and row["table"] == "rt"
    assert row["pid"] == os.getpid()
    assert isinstance(row["queries"], int) and row["queries"] >= 1
    for k in ("p50_ms", "p95_ms", "p99_ms"):
        assert isinstance(row[k], float)
    # the full stats tree rides along for drill-down
    assert {"tiers", "planner", "latency", "cache"} <= set(row["stats"])
    # and the row is exactly what table_record would produce again
    assert set(row) - {"ts"} == set(table_record("rt", row["stats"]))

    agg = aggregate_metrics(feed)["summary"]
    assert agg["tables"] == 1 and agg["workers"] == 0
    assert agg["queries"] == row["queries"]
    assert agg["p50_ms_median"] == row["p50_ms"]
    assert agg["p95_ms_max"] == row["p95_ms"]


def _load_check_regression():
    spec = importlib.util.spec_from_file_location(
        "check_regression",
        os.path.join(REPO, "benchmarks", "check_regression.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_from_feed_gate(tmp_path, capsys):
    """--from-feed aggregates worker+table rows and gates against the
    baseline's served block at the sanity ratio."""
    cr = _load_check_regression()
    feed = tmp_path / "feed.jsonl"
    rows = [
        {"role": "worker", "tablet": 0, "replica": 0, "pid": 1,
         "queries": 10, "p50_ms": 1.0, "p95_ms": 2.0, "ts": 1.0},
        {"role": "worker", "tablet": 0, "replica": 0, "pid": 1,
         "queries": 30, "p50_ms": 2.0, "p95_ms": 4.0, "ts": 2.0},
        {"role": "table", "table": "t", "pid": 2,
         "queries": 5, "p50_ms": 4.0, "p95_ms": 6.0, "ts": 2.0},
        {"role": "router", "pid": 3, "rpcs": 40, "ts": 2.0},
    ]
    feed.write_text("\n".join(json.dumps(r) for r in rows)
                    + "\n{torn line\n")
    agg = cr.aggregate_feed(str(feed))
    assert agg["emitters"] == 3           # latest-per-key, router incl.
    assert agg["serving_emitters"] == 2   # router is not a server
    assert agg["queries"] == 35           # latest worker row + table row
    assert agg["p50_ms"] == 4.0 and agg["p95_ms"] == 6.0

    baseline = tmp_path / "BENCH_serving.json"
    baseline.write_text(json.dumps(
        {"bench": "serving_observability",
         "results": {"served": {"p50_ms": 2.0, "p95_ms": 3.0}}}))
    assert cr.check_feed(str(feed), str(baseline), ratio=3.0) == []
    fails = cr.check_feed(str(feed), str(baseline), ratio=1.5)
    assert len(fails) == 2                # both quantiles over 1.5x
    empty = tmp_path / "empty.jsonl"
    empty.write_text("\n")
    assert cr.check_feed(str(empty), str(baseline), ratio=3.0)
    capsys.readouterr()                   # swallow the gate's prints


def test_docs_link_checker_green():
    """The committed docs tree must pass its own CI gate."""
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools",
                                      "check_docs_links.py")],
        capture_output=True, text=True, timeout=60)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "docs link check OK" in proc.stdout


def test_serve_tuned_env_lands_before_jax_fresh_process():
    """From a fresh interpreter --tuned must apply the env preset
    before the jax import (jax reads env once) and say so."""
    env = dict(os.environ)
    for k in ("TF_CPP_MIN_LOG_LEVEL",
              "TCMALLOC_LARGE_ALLOC_REPORT_THRESHOLD"):
        env.pop(k, None)
    env["PYTHONPATH"] = os.path.join(REPO, "src") + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.serve",
         "--text-len", "1500", "--queries", "60", "--batch", "24",
         "--max-pattern", "12", "--top-k", "2", "--page-size", "16",
         "--coalesce-window", "0.5", "--tuned"],
        env=env, capture_output=True, text=True, timeout=600).stdout
    assert ("[tune  ] preset: TF_CPP_MIN_LOG_LEVEL=4 "
            "TCMALLOC_LARGE_ALLOC_REPORT_THRESHOLD=60000000000") in out
    assert "jax already imported" not in out
    assert "[trace ] span p50/p95/p99 ms:" in out
