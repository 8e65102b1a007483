#!/usr/bin/env python3
"""Run a cell's control on the chip: the cell as the benchmark runs it,
with one guarantee of its configuration broken underneath, to read the
upper end of each compared number.  The benchmark's own runs never run
this.

    python3 chipbench/control.py --workload <cell> --seeds 1,2,3 \\
        --seconds <s>

The guarantee broken is text order: ``first_pos`` is the smallest
occurrence position and ``scan(top_k)`` the smallest ``top_k``
positions.  The control serves the planner's own string scan
(``ScanPlanner.scan``: the first match and the first ``top_k`` in
suffix-rank order, no host range minimum) in place of the table's, the
shortcut that would save the host merge.  Each seed is one line on
standard output: the seed, ``correct`` and the compared numbers.  All
seeds run in one process (set-up is long), each with a fresh table.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
import run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)

    jax = run.start_jax()
    if jax.devices()[0].platform != "tpu":
        run.log("FAIL: no TPU")
        return 2
    import harness
    from repro.api.table import SuffixTable
    SuffixTable.scan = (lambda self, patterns, top_k=0:
                        self.planner.scan(patterns, top_k=top_k))
    cell = harness.load_cell(args.workload)
    work = os.path.join(run.STATE, "runs", "control." + args.workload)
    for seed in (int(s) for s in args.seeds.split(",")):
        try:
            res = harness.run_cell(cell, seed=seed, seconds=args.seconds,
                                   trace=False, work_dir=work,
                                   t_start=time.perf_counter(), log=run.log)
        finally:
            shutil.rmtree(work, ignore_errors=True)
        print(json.dumps({"seed": seed, "correct": res["correct"],
                          "checks": res["checks"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
