#!/usr/bin/env python3
"""Run one benchmark cell on the chip and print its result line.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \\
        --trace <0|1>

The cell is an entry of ``BENCHMARK.json``'s ``workloads``.  The command
refuses to run (exit 2, no result) unless JAX sees a TPU with exactly
the cell's number of chips.  JAX's persistent compilation cache is the
program's own (``repro.launch.compile_cache``): where
``JAX_COMPILATION_CACHE_DIR`` says, or else ``<checkout>/.jax_cache``, a
fixed path, so only the first run of a cell in a checkout compiles.
Progress goes to standard error,
ending with each number the check compared beside its limit; the last
line of standard output is the result object.  ``--trace 1`` profiles
the window and reports the cell's per-layer metrics instead of its
end-to-end ones.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.dirname(HERE)
STATE = os.path.join(CHECKOUT, ".chipbench")
CACHE_MAX_BYTES = 4 << 30       # room for the 0.6 GB four-chip build


def log(*a) -> None:
    print(*a, file=sys.stderr, flush=True)


def start_jax():
    """Import JAX with the TPU runtime's logs inside the checkout, and
    turn on the program's compile cache, holding every program however
    short its compile; returns the ``jax`` module."""
    os.environ["TPU_LOG_DIR"] = os.path.join(STATE, "tpu_logs")
    os.makedirs(os.environ["TPU_LOG_DIR"], exist_ok=True)
    import jax
    sys.path[:0] = [HERE, os.path.join(CHECKOUT, "src")]
    from repro.launch.compile_cache import enable_compile_cache
    log(f"compile cache {enable_compile_cache()}")
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_compilation_cache_max_size", CACHE_MAX_BYTES)
    return jax


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    jax = start_jax()
    devices = jax.devices()
    dev = devices[0]
    log(f"platform {dev.platform} device_kind {dev.device_kind} "
        f"device_count {len(devices)}")
    if dev.platform != "tpu":
        log(f"FAIL: no TPU (platform {dev.platform!r})")
        return 2

    import harness
    cell = harness.load_cell(args.workload)
    if len(devices) != cell.chips:
        log(f"FAIL: {len(devices)} chips visible, the cell asks for "
            f"{cell.chips}")
        return 2

    work = os.path.join(STATE, "runs", args.workload)
    try:
        result = harness.run_cell(cell, seed=args.seed,
                                  seconds=args.seconds,
                                  trace=bool(args.trace), work_dir=work,
                                  t_start=T_START, log=log)
    finally:
        shutil.rmtree(work, ignore_errors=True)
    print(json.dumps(result), flush=True)
    for name, c in result["checks"].items():
        limit = " ".join(f"{k} {v}" for k, v in c.items() if k != "value")
        log(f"check {name} {c['value']} {limit}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
