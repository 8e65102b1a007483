"""``table.device_min_share``: the frozen tier's text-order first_pos
settled in the search launch, read from the planner's counter."""
import pytest

from chipbench_testing import BENCH, harness, run_tiny


def test_device_min_share_reads_the_counter_or_nothing():
    share = harness.load_reader(BENCH, "table.device_min_share")
    # a program without the counter, or no patterns in the window
    assert share({"planner": ({"queries": 0}, {"queries": 9})}) is None
    assert share({"planner": ({"queries": 4, "device_min_settled": 4},
                              {"queries": 4, "device_min_settled": 4})}) \
        is None
    assert share({"planner": ({"queries": 8, "device_min_settled": 3},
                              {"queries": 264,
                               "device_min_settled": 259})}) == 100.0
    assert share({"planner": ({"queries": 0, "device_min_settled": 0},
                              {"queries": 8, "device_min_settled": 6})}) \
        == pytest.approx(75.0)


def test_traced_frozen_run_reports_device_min_share(tmp_path):
    res = run_tiny("dna50.count20.frozen", tmp_path, trace=True)
    assert res["correct"], res["checks"]
    got = res["metrics"]["table.device_min_share"]
    assert got["unit"] == "%"
    assert 0 < got["value"] <= 100
