"""Benchmark harness: one function per paper table + engine micro-benches +
the roofline summary (read from dry-run artifacts).

Prints ``name,us_per_call,derived`` CSV as required.
"""
from __future__ import annotations

import json
import sys


def main() -> None:
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    from benchmarks import build_bench, client_bench, compaction_bench, \
        fm_bench, kernel_bench, paper_tables, plane_bench, roofline, \
        serving_bench, table_bench, wal_bench

    benches = [
        ("table1_preprocess_build", paper_tables.bench_build_table1),
        ("table3_single_process_scans", paper_tables.bench_single_table3),
        ("table4_multi_user_scans", paper_tables.bench_multi_table4),
        ("table5_correlations", paper_tables.bench_correlation_table5),
        ("fig1_latency_histogram", paper_tables.bench_histogram_fig1),
        ("kernel_pattern_compare", kernel_bench.bench_pattern_compare),
        ("kernel_binary_search_1M_rows", kernel_bench.bench_binary_search),
        ("planner_scan_1M_rows", kernel_bench.bench_planner_scan),
        ("kernel_pack_2bit", kernel_bench.bench_pack_throughput),
        ("table_merged_scan", table_bench.bench_table_ops),
        ("lsm_compaction", compaction_bench.bench_compaction),
        ("fm_frozen_tier", fm_bench.bench_fm),
        ("client_coalescing", client_bench.bench_client),
        ("wal_group_commit", wal_bench.bench_wal),
        ("staged_build", build_bench.bench_build),
        ("plane_swarm", plane_bench.bench_plane),
        ("serving_observability", serving_bench.bench_serving),
    ]
    print("name,us_per_call,derived")
    for name, fn in benches:
        try:
            us, derived = fn()
            print(f"{name},{us:.3f},\"{json.dumps(derived)}\"", flush=True)
        except Exception as e:  # noqa: BLE001
            print(f"{name},ERROR,\"{type(e).__name__}: {e}\"", flush=True)

    summary = roofline.summarize()
    print(f"roofline_cells,0,\"{json.dumps(summary)}\"")


if __name__ == "__main__":
    main()
