"""``benchmarks/idle_by_span.py``: the chip's idle time split by the
innermost program span, on a hand-made trace with known answers."""
import importlib.util
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOOL = os.path.join(REPO, "benchmarks", "idle_by_span.py")

_spec = importlib.util.spec_from_file_location("idle_by_span", TOOL)
idle_by_span = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(idle_by_span)


def _trace():
    # window 1000..2000 ns; the chip runs 1000..1100 and 1500..1600, and
    # its second program idles 1600..1650 between its operations
    ops = [["fusion.1", 1000, 100], ["fusion.2", 1500, 100]]
    modules = [["jit_query(1)", 1000, 100], ["jit_query(1)", 1500, 150]]
    main = [["chipbench.window", 1000, 1000],
            ["chipbench.request", 1000, 950],
            ["table.dispatch", 1050, 400],
            ["table.wait", 1300, 150],
            ["np.asarray(jax.Array)", 1310, 90],     # not a program span
            ["table.merge", 1450, 100]]
    sched = [["sched.window", 1700, 100]]
    return {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "events": ops},
            {"name": "XLA Modules", "events": modules}]},
        {"name": "/host:CPU", "lines": [
            {"name": "main", "events": main},
            {"name": "scheduler", "events": sched}]}]}


def test_idle_splits_by_the_innermost_program_span():
    rep = idle_by_span.report(_trace())
    split = {k: v for k, v, _share in rep["idle_by_span"]}
    assert split == pytest.approx({
        "table.dispatch": 200e-9,            # 1100..1300
        "table.wait": 150e-9,                # 1300..1450, inside dispatch
        "table.merge": 50e-9,                # 1450..1500
        "between ops of a program": 50e-9,   # 1600..1650
        "chipbench.request": 200e-9,         # 1650..1700, 1800..1950
        "sched.window": 100e-9,              # shorter than the request
        "no program span": 50e-9})           # 1950..2000
    assert rep["idle_s"] == pytest.approx(1000e-9 - rep["busy_s"])
    assert sum(s for _k, _v, s in rep["idle_by_span"]) == pytest.approx(100)
    assert rep["harness_share_by_span"] == pytest.approx(250 / 8)


def test_midpoint_rule_and_span_sums_beside_the_split():
    rep = idle_by_span.report(_trace())
    # the midpoint rule names each whole gap after one span
    midpoint = {k: v for k, v, _share in rep["idle_by_midpoint"]}
    assert midpoint == pytest.approx({"table.wait": 400e-9,
                                      "chipbench.request": 350e-9,
                                      "between ops of a program": 50e-9})
    assert rep["harness_share_by_midpoint"] == pytest.approx(350 / 8)
    assert rep["requests"] == 1 and rep["waves"] == 0
    assert rep["spans"]["table.dispatch"] == [1, pytest.approx(400e-6)]
    assert "np.asarray(jax.Array)" not in rep["spans"]
    assert "chipbench.window" not in rep["spans"]


def test_refuses_to_run_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, TOOL, "--workload", "dna50.count20.live",
         "--seed", "1", "--seconds", "1"],
        capture_output=True, text=True, env=env, timeout=300, cwd=REPO)
    assert proc.returncode == 2, proc.stderr[-2000:]
    assert "no TPU" in proc.stderr


def test_planner_counters_after_and_over_the_window():
    before = {"batches": 3, "queries": 40, "input_copybacks": 0,
              "mode_counts": {"single": 3}}
    after = {"batches": 10, "queries": 200, "input_copybacks": 0,
             "mode_counts": {"single": 10}}
    got = idle_by_span.planner_counters(before, after)
    assert got == {
        "total": {"batches": 10, "queries": 200, "input_copybacks": 0},
        "window": {"batches": 7, "queries": 160, "input_copybacks": 0}}
    assert idle_by_span.planner_counters({}, {}) == {"total": {},
                                                     "window": {}}
