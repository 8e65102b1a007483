"""Each cell's set-up, window and reference comparison at a tiny size on
the CPU, and the command's refusal to run without a TPU."""
import json
import os
import subprocess
import sys

import pytest

from chipbench_testing import CELLS, REPO, run_tiny


def test_command_refuses_to_run_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chipbench", "run.py"),
         "--workload", "dna50.count20.live", "--seed", "2147483659",
         "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, timeout=300)
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr
    assert not any(ln.startswith("{") for ln in proc.stdout.splitlines())


@pytest.mark.parametrize("name", CELLS)
def test_cell_is_correct_at_a_tiny_size(name, tmp_path):
    res = run_tiny(name, tmp_path)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["metrics"]) == {"read_p95_ms", "read_patterns_per_s",
                                   "setup_s"}
    assert all(v["value"] > 0 for v in res["metrics"].values())
    assert res["checks"]["checked"]["value"] > 0
    assert res["compiles_in_window"] == 0
    assert list(res)[-1] == "checks"
    json.dumps(res)


def test_traced_run_reports_the_per_layer_metrics_it_can_read(tmp_path):
    """On the CPU the trace holds no chip, so the device metrics are
    left out and the span and counter metrics are there."""
    res = run_tiny("chr1.scan.users50", tmp_path, trace=True)
    assert res["correct"], res["checks"]
    assert set(res["metrics"]) == {
        "sched.coalesce_wait_ms", "sched.patterns_per_batch",
        "table.merge_us_per_pattern", "table.dispatch_us_per_pattern",
        "build.create_s"}
    assert "busy_s" not in res["device"]
    assert res["metrics"]["sched.patterns_per_batch"]["value"] >= 1
