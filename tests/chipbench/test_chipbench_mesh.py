"""The four-tablet cell ``chr1.mesh4.users50`` at a tiny size on four
forced CPU devices, in one subprocess (the other tests must see one
device): correct, every wave broadcast, and the fan-out counter read;
the mesh roofline's bytes on hand-counted shapes; and the trace
readers on a hand-made two-chip trace."""
import json
import os
import subprocess
import sys

import pytest

from chipbench_testing import BENCH, REPO, harness

CELL = "chr1.mesh4.users50"
CODE = r"""
import json, sys, tempfile
sys.path.insert(0, %(tests)r)
from chipbench_testing import harness, tiny
from repro.core.planner import ScanPlanner

# every batch the planner accounts, and where the harness snapshots its
# counters (before and after the window)
calls, marks = [], []
account = ScanPlanner._account
stats = harness._stats


def log_account(self, chosen, B, n_real):
    calls.append([chosen, B, n_real])
    return account(self, chosen, B, n_real)


def keep(obj):
    d = stats(obj)
    if "mode_counts" in d:
        marks.append((len(calls), d))
    return d


ScanPlanner._account = log_account
harness._stats = keep
out = {}
for trace in (False, True):
    cell = tiny(harness.load_cell(%(cell)r))
    res = harness.run_cell(cell, seed=2147483659, seconds=1.0, trace=trace,
                           work_dir=tempfile.mkdtemp(), log=lambda *a: None)
    (i0, before), (i1, after) = marks[-2:]
    res["planner"] = [before, after]
    res["batches"] = calls[i0:i1]
    out["traced" if trace else "untraced"] = res
print(json.dumps(out))
"""

@pytest.fixture(scope="module")
def runs():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run(
        [sys.executable, "-c",
         CODE % {"tests": os.path.dirname(os.path.abspath(__file__)),
                 "cell": CELL}],
        capture_output=True, text=True, env=env, timeout=300)
    assert proc.returncode == 0, proc.stderr[-4000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("kind", ["untraced", "traced"])
def test_mesh_cell_is_correct_at_a_tiny_size(runs, kind):
    res = runs[kind]
    assert res["correct"], res["checks"]
    assert res["device"]["count"] == 4
    assert res["attempted"] > 0 and res["failed"] == 0
    assert all(c["value"] == 0 for name, c in res["checks"].items()
               if name != "checked"), res["checks"]
    assert res["checks"]["checked"]["value"] > 0
    assert res["compiles_in_window"] == 0


def _window(res, key):
    before, after = res["planner"]
    return after[key] - before[key]


@pytest.mark.parametrize("kind", ["untraced", "traced"])
def test_every_wave_is_broadcast_to_the_four_tablets(runs, kind):
    """50 users send waves of at most 50 patterns, below
    ``routed_min_batch`` (64), so every wave goes to all four tablets,
    the 64 bucket a wave of more than 32 fills included."""
    res = runs[kind]
    batches = res["batches"]
    assert batches and len(batches) == _window(res, "batches")
    assert all(mode == "broadcast" for mode, _, _ in batches), batches
    for _, B, n_real in batches:
        assert n_real <= 50 and B == 1 << (n_real - 1).bit_length()
    before, after = res["planner"]
    assert (after["mode_counts"]["broadcast"]
            - before["mode_counts"]["broadcast"]) == len(batches)


@pytest.mark.parametrize("kind", ["untraced", "traced"])
def test_tablet_searches_count_the_fan_out(runs, kind):
    """Four tablet searches a broadcast pattern: a window of broadcast
    waves counts four times its queries."""
    res = runs[kind]
    assert _window(res, "tablet_searches") == 4 * _window(res, "queries")


def test_traced_run_reads_four_tablet_searches_a_pattern(runs):
    """On the CPU the trace holds no chip: the device metrics are left
    out, and the counter reads the tablet count."""
    metrics = runs["traced"]["metrics"]
    assert set(metrics) == {"mesh.tablet_searches_per_pattern"}
    assert metrics["mesh.tablet_searches_per_pattern"]["value"] == 4.0
    assert set(runs["untraced"]["metrics"]) == {
        "read_p95_ms", "read_patterns_per_s", "setup_s"}


def test_mesh_roofline_bytes_match_a_hand_count():
    mod = harness.load_reader(BENCH, "kernel.mesh_search_roofline")
    need = mod.__globals__["bytes_needed"]
    # 4000 rows on 4 tablets: 1000 each, 10 steps per bound;
    # max_query_len 20 -> W = 2 words, 3 gathered; per step 4 + 12
    # bytes, two bounds, one first_pos entry, on each of the 4 tablets
    assert need(3, 4, 4000, 20) == 3 * 4 * (2 * 10 * 16 + 4)
    # 2^26 rows on 4 tablets, max_query_len 112: 25 steps, W = 7
    assert need(1, 4, 1 << 26, 112) == 4 * (2 * 25 * (4 + 4 * 8) + 4)


def test_mesh_cell_is_declared_on_four_chips():
    cell = harness.load_cell(CELL, root=REPO)
    assert cell.chips == 4
    assert cell.config["tablets"] == 4
    assert cell.mix["clients"] == 50 and cell.mix["top_k"] == 8
    assert {m["name"] for m in cell.per_layer} == {
        "kernel.mesh_search_roofline", "mesh.collective_share",
        "mesh.tablet_searches_per_pattern"}


def _two_chip_trace():
    """Window 1000..3000 ns on two chips, each running ``jit_broadcast``
    twice (400 ns each) with an all-reduce inside; an all-reduce of
    another program and one outside every run are not counted."""
    ar = "%psum_invariant.22 = s32[32]{0:T(128)S(1)} all-reduce(%x)"
    ar2 = "%all-reduce.2 = (s32[32]{0}, s32[32]{0}) all-reduce(%a, %b)"
    fus = "%fusion.7 = s32[32]{0} fusion(%p), kind=kLoop"
    planes = [{"name": "/host:CPU", "lines": [
        {"name": "python", "events": [["chipbench.window", 1000, 2000]]}]}]
    for dev, extra in ((0, 0), (1, 100)):
        planes.append({"name": f"/device:TPU:{dev}", "lines": [
            {"name": "XLA Modules", "events": [
                ["jit_broadcast(3)", 1100, 400],
                ["jit_broadcast(3)", 2000, 400],
                ["jit_broadcast_other(4)", 2600, 200]]},
            {"name": "XLA Ops", "events": [
                [fus, 1100, 250], [ar, 1350, 100 + extra],
                [fus, 2000, 300], [ar2, 2300, 50],
                [ar, 2650, 100], [ar, 2900, 50]]}]})
    return {"planes": planes}


def test_collective_share_and_mesh_roofline_read_a_trace():
    import trace_reduce
    tr = trace_reduce.Reduced(_two_chip_trace())
    share = harness.load_reader(BENCH, "mesh.collective_share")
    # all-reduce time inside the runs: chip 0 100 + 50, chip 1 150
    # (its 200 ns op is cut at the run's end) + 50, over 800 ns a chip
    assert share({"trace": tr}) == pytest.approx(100 * 175 / 800)
    roof = harness.load_reader(BENCH, "kernel.mesh_search_roofline")
    ctx = {"trace": tr, "peaks": {"hbm_bytes_per_s": 819e9},
           "config": {"tablets": 2},
           "shapes": {"frozen": False, "n_pad": 2000, "max_query_len": 20},
           "planner": ({"queries": 0, "pad_slots": 0},
                       {"queries": 5, "pad_slots": 3})}
    need = roof.__globals__["bytes_needed"](8, 2, 2000, 20)
    assert roof(ctx) == pytest.approx(100 * need / (2 * 819e9) / 800e-9)
    # no chip in the trace, or the parent's counters: nothing to read
    cpu = trace_reduce.Reduced({"planes": _two_chip_trace()["planes"][:1]})
    assert share({"trace": cpu}) is None and roof(dict(ctx, trace=cpu)) is None
    fan = harness.load_reader(BENCH, "mesh.tablet_searches_per_pattern")
    assert fan({"planner": ({"queries": 1}, {"queries": 9})}) is None
