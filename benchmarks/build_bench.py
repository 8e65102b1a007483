"""Staged-build benchmark: bases/s vs corpus size, plus the out-of-core
proof (docs/build_pipeline.md).

Two arms:

* **sweep** — for each corpus size, build the suffix array with the
  in-memory builder (``core.suffix_array.build_suffix_array``) and the
  staged pipeline (``core.build_pipeline.staged_suffix_array``) and
  report bases/s for both plus the staged/in-memory overhead ratio.
  Results must be bit-identical (``sweep_bit_identical``).
* **out-of-core** — a subprocess warms the jit caches at the target
  chunk shape, reads its own ``VmPeak`` from ``/proc/self/status``,
  then hard-caps its address space with
  ``resource.setrlimit(RLIMIT_AS, VmPeak + headroom)`` and builds a
  corpus ``>= 8x`` the per-chunk device budget with ``spill_dir`` set,
  streaming SA shards straight to a file.  The parent verifies the
  streamed SA bit-identical against an UNCAPPED in-memory build.  At
  full size the headroom is smaller than the in-memory builder's
  ``n * 24 B`` working set, so the cap is one the one-shot build could
  not have met — the staged pipeline's memory bound is real, not
  nominal.  (Spill I/O uses ``np.save``/``tofile`` block reads, never
  mmap — mapped files would count against ``RLIMIT_AS`` and void the
  proof.)  The child is pinned to the CPU by its own environment
  (``JAX_PLATFORMS=cpu``): the bound it proves is on host memory, and
  an accelerator belongs to one process — the parent's.

Writes ``BENCH_build.json`` at the repo root; the committed baseline is
refreshed from ``--smoke`` so the weekly CI gate compares like against
like (benchmarks/check_regression.py).

    PYTHONPATH=src python benchmarks/build_bench.py [--smoke]
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

# Runs with its address space capped; prints one "OOB_RESULT {json}" line.
_OOB_CHILD = r"""
import json, os, resource, sys, time
import numpy as np

n, chunk_rows, headroom_mb = (int(a) for a in sys.argv[1:4])
spill_dir, out_path = sys.argv[4], sys.argv[5]
seed = int(sys.argv[6])

from repro.core.build_pipeline import staged_suffix_array

codes = np.random.default_rng(seed).integers(0, 4, size=n, dtype=np.int32)

# Warm every jit cache at the REAL chunk shape (the sort pads each
# super-chunk to chunk_rows, so any warm corpus compiles the same
# kernels) and touch the spill/merge/emit paths once.
warm_dir = os.path.join(spill_dir, "warm")
staged_suffix_array(codes[:max(2, 3 * chunk_rows // 2)],
                    chunk_rows=chunk_rows, spill_dir=warm_dir,
                    emit_shard=lambda i, blk: None)


def _vm_kb(field):
    with open("/proc/self/status") as f:
        for line in f:
            if line.startswith(field):
                return int(line.split()[1])
    return 0


vm_peak_kb = _vm_kb("VmPeak:")
cap_bytes = vm_peak_kb * 1024 + headroom_mb * (1 << 20)
resource.setrlimit(resource.RLIMIT_AS, (cap_bytes, cap_bytes))

t0 = time.perf_counter()
with open(out_path, "wb") as out:
    _, stats = staged_suffix_array(
        codes, chunk_rows=chunk_rows, spill_dir=spill_dir,
        emit_shard=lambda i, blk: out.write(
            np.ascontiguousarray(blk, dtype=np.int32).tobytes()))
wall_s = time.perf_counter() - t0

print("OOB_RESULT " + json.dumps({
    "built_under_cap": True,
    "cap_mb": round(cap_bytes / 2**20, 1),
    "vm_peak_before_cap_mb": round(vm_peak_kb / 1024, 1),
    "peak_rss_mb": round(_vm_kb("VmHWM:") / 1024, 1),
    "spill_bytes": int(stats.spill_bytes),
    "rounds": stats.rounds,
    "n_chunks": stats.n_chunks,
    "wall_s": round(wall_s, 3),
}))
"""


def _parse(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--sweep-sizes", type=int, nargs="+",
                    default=[100_000, 400_000])
    ap.add_argument("--chunk-rows", type=int, default=1 << 13,
                    help="device chunk for the staged sweep arm")
    ap.add_argument("--oob-n", type=int, default=1 << 21,
                    help="out-of-core corpus size (bases)")
    ap.add_argument("--oob-chunk-rows", type=int, default=1 << 13)
    ap.add_argument("--headroom-mb", type=int, default=32,
                    help="RLIMIT_AS slack above post-warmup VmPeak")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny sizes for CI smoke runs")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    if args.smoke:
        args.sweep_sizes = [20_000, 60_000]
        args.chunk_rows = 1 << 12
        args.oob_n = 1 << 18
        args.oob_chunk_rows = 1 << 12
    if args.oob_n < 8 * args.oob_chunk_rows:
        ap.error("--oob-n must be >= 8x --oob-chunk-rows "
                 "(the out-of-core claim needs a multi-chunk corpus)")
    return args


def _sweep_one(n: int, chunk_rows: int, seed: int) -> dict:
    from repro.core.build_pipeline import staged_suffix_array
    from repro.core.suffix_array import build_suffix_array

    codes = np.random.default_rng(seed).integers(0, 4, size=n,
                                                 dtype=np.int32)
    ref = np.asarray(build_suffix_array(codes))        # compile pass
    t0 = time.perf_counter()
    ref = np.asarray(build_suffix_array(codes))
    t_mem = time.perf_counter() - t0

    sa, _ = staged_suffix_array(codes, chunk_rows=chunk_rows)  # compile
    t0 = time.perf_counter()
    sa, _ = staged_suffix_array(codes, chunk_rows=chunk_rows)
    t_staged = time.perf_counter() - t0

    return {
        "in_memory_bases_per_s": round(n / max(t_mem, 1e-9), 1),
        "staged_bases_per_s": round(n / max(t_staged, 1e-9), 1),
        "bit_identical": bool(np.array_equal(ref, sa)),
    }


def _run_oob(n: int, chunk_rows: int, headroom_mb: int,
             seed: int = 7) -> dict:
    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    tmp = tempfile.mkdtemp(prefix="build_bench_oob_")
    try:
        spill = os.path.join(tmp, "spill")
        out_path = os.path.join(tmp, "sa.bin")
        env = dict(os.environ)
        env["PYTHONPATH"] = os.path.join(repo_root, "src") + os.pathsep \
            + env.get("PYTHONPATH", "")
        env["JAX_PLATFORMS"] = "cpu"        # the chip stays the parent's
        proc = subprocess.run(
            [sys.executable, "-c", _OOB_CHILD, str(n), str(chunk_rows),
             str(headroom_mb), spill, out_path, str(seed)],
            capture_output=True, text=True, env=env, timeout=1800)
        lines = [ln for ln in proc.stdout.splitlines()
                 if ln.startswith("OOB_RESULT ")]
        if proc.returncode != 0 or not lines:
            tail = (proc.stderr or proc.stdout).strip()[-500:]
            return {"oob_built_under_cap": False,
                    "oob_bit_identical": False,
                    "oob_error": tail or "child died without output"}
        info = json.loads(lines[-1][len("OOB_RESULT "):])

        # bit-identity vs the one-shot builder, run HERE with no cap
        from repro.core.suffix_array import build_suffix_array
        codes = np.random.default_rng(seed).integers(0, 4, size=n,
                                                     dtype=np.int32)
        ref = np.asarray(build_suffix_array(codes), dtype=np.int32)
        got = np.fromfile(out_path, dtype=np.int32)
        return {
            "oob_built_under_cap": bool(info["built_under_cap"]),
            "oob_bit_identical": bool(np.array_equal(ref, got)),
            "oob_budget_multiple_x": round(n / chunk_rows, 1),
            "oob_cap_mb": info["cap_mb"],
            "oob_peak_rss_mb": info["peak_rss_mb"],
            "oob_spill_mb": round(info["spill_bytes"] / 2**20, 1),
            "oob_rounds": info["rounds"],
            "oob_wall_s": info["wall_s"],
        }
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def run(args) -> dict:
    sweep = {}
    all_identical = True
    for n in args.sweep_sizes:
        one = _sweep_one(n, args.chunk_rows, seed=n)
        all_identical &= one.pop("bit_identical")
        sweep[f"n{n}"] = one

    oob = _run_oob(args.oob_n, args.oob_chunk_rows, args.headroom_mb)

    largest = sweep[f"n{args.sweep_sizes[-1]}"]
    overhead = (largest["in_memory_bases_per_s"]
                / max(largest["staged_bases_per_s"], 1e-9))
    results = {
        "staged_bases_per_s": largest["staged_bases_per_s"],
        "in_memory_bases_per_s": largest["in_memory_bases_per_s"],
        "staged_overhead_over_in_memory_x": round(overhead, 2),
        "sweep_bit_identical": all_identical,
        "sweep": sweep,
    }
    results.update(oob)
    return {
        "bench": "staged_build",
        "sweep_sizes": args.sweep_sizes,
        "chunk_rows": args.chunk_rows,
        "oob_n": args.oob_n,
        "oob_chunk_rows": args.oob_chunk_rows,
        "headroom_mb": args.headroom_mb,
        "results": results,
    }


def bench_build():
    """benchmarks/run.py entry: (us per staged build at smoke size,
    derived)."""
    args = _parse(["--smoke"])
    payload = run(args)
    res = payload["results"]
    n = args.sweep_sizes[-1]
    us = 1e6 * n / max(res["staged_bases_per_s"], 1e-9)
    return (us, {k: v for k, v in res.items() if k != "sweep"})


def main() -> None:
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    args = _parse()
    payload = run(args)
    for k, v in payload["results"].items():
        print(f"{k}: {v}", flush=True)
    res = payload["results"]
    if not res["sweep_bit_identical"]:
        raise SystemExit("staged sweep is NOT bit-identical to the "
                         "in-memory builder")
    if not (res["oob_built_under_cap"] and res["oob_bit_identical"]):
        raise SystemExit("out-of-core build failed under the RLIMIT_AS "
                         f"cap: {res.get('oob_error', 'not identical')}")
    out = args.out or os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "BENCH_build.json")
    with open(out, "w") as f:
        json.dump(payload, f, indent=2)
        f.write("\n")
    print(f"wrote {out}")


if __name__ == "__main__":
    main()
