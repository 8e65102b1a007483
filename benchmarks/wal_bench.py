"""Commit-log benchmark: acked appends/sec, fsync-per-append vs group
commit.

``--concurrency`` client threads each push ``--appends`` chunks into
one table.  Two arms over identical workloads:

* ``fsync_per_append`` — the pre-group-commit discipline: each append
  logs, fsyncs, and acks while still HOLDING the table's write lock
  (``SuffixTable.append`` under ``run_exclusive``), so every ack pays
  its own fsync and writers queue behind each other's disk waits;
* ``group_commit``     — ``Database.append``: the mutation is applied
  under the lock but the fsync is awaited OUTSIDE it, and a small
  window lets concurrent writers batch into ONE fsync per wave before
  acking — the write-side mirror of the ``QueryScheduler``'s read-side
  coalescing.

After the group-commit arm the root is copied (a simulated crash — the
live handle is abandoned) and reopened to verify every acked append was
recovered: ``recovered_all_acked`` must be true.

Writes ``BENCH_wal.json`` at the repo root.  ``--smoke`` shrinks every
dimension for the weekly CI job.

    PYTHONPATH=src python benchmarks/wal_bench.py [--smoke]
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import tempfile
import threading
import time


def _parse(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--text-len", type=int, default=20_000)
    ap.add_argument("--concurrency", type=int, default=8,
                    help="concurrent client writer threads")
    ap.add_argument("--appends", type=int, default=40,
                    help="chunks appended per thread per arm")
    ap.add_argument("--chunk", type=int, default=32,
                    help="bases per appended chunk")
    ap.add_argument("--window-ms", type=float, default=2.0,
                    help="group-commit window for the batched arm")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny sizes for CI smoke runs")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    if args.smoke:
        args.text_len, args.appends = 5_000, 15
    if args.concurrency < 1 or args.appends < 1:
        ap.error("need --concurrency >= 1 and --appends >= 1")
    return args


def _run_arm(db, table: str, *, concurrency: int, appends: int,
             chunk: int, serial_ack: bool) -> dict:
    from repro.core.codec import random_dna
    errs: list[Exception] = []
    barrier = threading.Barrier(concurrency + 1)
    t_obj = db.table(table)

    def push(c) -> None:
        if serial_ack:
            # fsync-per-append: ack (fsync wait) INSIDE the table lock —
            # the pre-group-commit write path
            db.scheduler.run_exclusive(t_obj, lambda: t_obj.append(c))
        else:
            db.append(table, c)      # fsync awaited outside the lock

    def writer(tid: int) -> None:
        try:
            chunks = [random_dna(chunk, seed=1000 * tid + j)
                      for j in range(appends)]
            barrier.wait()
            for c in chunks:
                push(c)
        except Exception as e:  # noqa: BLE001
            errs.append(e)

    threads = [threading.Thread(target=writer, args=(i,))
               for i in range(concurrency)]
    for t in threads:
        t.start()
    barrier.wait()
    t0 = time.perf_counter()
    for t in threads:
        t.join()
    dt = time.perf_counter() - t0
    if errs:
        raise errs[0]
    total = concurrency * appends
    log = db.table(table).stats()["wal"]["log"]
    return {"acked_per_s": total / dt, "total_acked": total,
            "wall_s": dt, "fsyncs": log["fsyncs"],
            "appends_per_fsync": total / max(log["fsyncs"], 1)}


def run(args) -> dict:
    from repro.api import Database, SuffixTable
    from repro.core.codec import random_dna

    root = tempfile.mkdtemp(prefix="wal_bench_")
    try:
        base = random_dna(args.text_len, seed=0)
        arms = {}
        for name, window, serial in (("fsync_per_append", 0.0, True),
                                     ("group_commit", args.window_ms,
                                      False)):
            db = Database(root, group_commit_ms=window)
            db.create_table(name, base, is_dna=True,
                            group_commit_ms=window)
            arms[name] = _run_arm(db, name,
                                  concurrency=args.concurrency,
                                  appends=args.appends, chunk=args.chunk,
                                  serial_ack=serial)
            db.close()

        # crash + reopen the group-commit table: every ack must survive
        crash = root + "_crash"
        shutil.copytree(root, crash)
        t = SuffixTable.open("group_commit", root=crash)
        want = args.text_len + (args.concurrency * args.appends
                                * args.chunk)
        recovered = bool(len(t) == want)
        shutil.rmtree(crash, ignore_errors=True)

        speedup = (arms["group_commit"]["acked_per_s"]
                   / max(arms["fsync_per_append"]["acked_per_s"], 1e-9))
        return {
            "bench": "wal_group_commit",
            "text_len": args.text_len,
            "concurrency": args.concurrency,
            "appends_per_thread": args.appends,
            "chunk": args.chunk,
            "window_ms": args.window_ms,
            "results": {
                "fsync_per_append_acked_per_s":
                    round(arms["fsync_per_append"]["acked_per_s"], 1),
                "fsync_per_append_appends_per_fsync":
                    round(arms["fsync_per_append"]["appends_per_fsync"],
                          2),
                "group_commit_acked_per_s":
                    round(arms["group_commit"]["acked_per_s"], 1),
                "group_commit_appends_per_fsync":
                    round(arms["group_commit"]["appends_per_fsync"], 2),
                "group_commit_speedup_x": round(speedup, 2),
                "recovered_all_acked": recovered,
            },
        }
    finally:
        shutil.rmtree(root, ignore_errors=True)


def bench_wal():
    """benchmarks/run.py entry: (us_per_acked_append, derived)."""
    args = _parse(["--smoke"])
    payload = run(args)
    res = payload["results"]
    return (1e6 / max(res["group_commit_acked_per_s"], 1e-9), res)


def main() -> None:
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    args = _parse()
    payload = run(args)
    for k, v in payload["results"].items():
        print(f"{k}: {v}", flush=True)
    if not payload["results"]["recovered_all_acked"]:
        raise SystemExit("acked appends were LOST across crash+reopen")
    out = args.out or os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "BENCH_wal.json")
    with open(out, "w") as f:
        json.dump(payload, f, indent=2)
        f.write("\n")
    print(f"wrote {out}")


if __name__ == "__main__":
    main()
