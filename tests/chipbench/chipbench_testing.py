"""Shared helpers of the benchmark's CPU tests: load the harness and
shrink a cell to a size a test run holds."""
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(REPO, "chipbench")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import harness  # noqa: E402

CELLS = ("chr1.scan.users50", "dna50.count20.frozen", "dna50.count20.live")
TINY_TEXT = 30000


def tiny(cell):
    """The cell with its text cut to ``TINY_TEXT`` bases and its repeat
    lengths cut 20-fold, so repeats still recur in it."""
    text = cell.config["text"]
    text["length"] = TINY_TEXT
    for el in text["repeats"]:
        el["length"] = [max(20, el["length"][0] // 20),
                        max(30, el["length"][1] // 20)]
        if "consensus" in el:
            el["consensus"] = max(30, el["consensus"] // 20)
    return cell


def run_tiny(name, tmp_path, *, seed=77, seconds=1.0, trace=False,
             root=REPO):
    cell = tiny(harness.load_cell(name, root=root))
    return harness.run_cell(cell, seed=seed, seconds=seconds, trace=trace,
                            work_dir=str(tmp_path / "work"),
                            log=lambda *a: None)
