"""The table's read-path spans, read by the per-layer metrics of both
dna50 cells from a traced run at a tiny size on the CPU."""
import pytest

from chipbench_testing import run_tiny

SPAN_METRICS = ("table.upload_us_per_pattern", "table.wait_us_per_pattern",
                "table.cache_us_per_pattern")


@pytest.mark.parametrize("name", ["dna50.count20.frozen",
                                  "dna50.count20.live"])
def test_traced_dna50_run_splits_dispatch(name, tmp_path):
    res = run_tiny(name, tmp_path, trace=True)
    assert res["correct"], res["checks"]
    got = res["metrics"]
    for metric in SPAN_METRICS:
        assert got[metric]["unit"] == "us"
        assert got[metric]["value"] > 0, metric
    # upload and wait are leaves inside dispatch
    assert (got["table.upload_us_per_pattern"]["value"]
            + got["table.wait_us_per_pattern"]["value"]
            <= got["table.dispatch_us_per_pattern"]["value"])
