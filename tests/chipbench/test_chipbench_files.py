"""The harness finds a configuration, a mix and a metric added as files
only; peaks are keyed by device kind; the roofline bytes functions."""
import json
import math
import shutil

import pytest

from chipbench_testing import BENCH, REPO, harness, run_tiny


def test_cell_config_mix_and_metric_added_as_files_only(tmp_path):
    root = tmp_path / "checkout"
    shutil.copytree(BENCH, root / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(f"{REPO}/BENCHMARK.json", root / "BENCHMARK.json")
    bench = json.loads((root / "BENCHMARK.json").read_text())
    conf = json.loads(
        (root / "chipbench/configs/pizzachili_dna50.json").read_text())
    conf["name"] = "tiny_dna"
    (root / "chipbench/configs/tiny_dna.json").write_text(json.dumps(conf))
    mix = json.loads(
        (root / "chipbench/mixes/count20.batch256.json").read_text())
    mix.update(clients=2, patterns_per_request=16, length=[8, 30])
    (root / "chipbench/mixes/count.small.json").write_text(json.dumps(mix))
    (root / "chipbench/metrics/sched.batches.py").write_text(
        "def read(ctx):\n"
        "    before, after = ctx['scheduler']\n"
        "    return after['batches'] - before['batches']\n")
    bench["configs"].append({"name": "tiny_dna", "source": "test",
                             "file": "chipbench/configs/tiny_dna.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "tiny.count", "config": "tiny_dna",
                               "traffic": "count.small", "chips": 1,
                               "why": "test"})
    bench["per_layer"].append({"name": "sched.batches", "unit": "batches",
                               "better": "higher",
                               "source": "program_counter",
                               "layer": "client scheduler",
                               "moves": "read_patterns_per_s",
                               "workloads": ["tiny.count"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    res = run_tiny("tiny.count", tmp_path, trace=True, root=str(root))
    assert res["correct"], res["checks"]
    assert res["metrics"]["sched.batches"]["value"] > 0
    assert set(res["metrics"]) == {"sched.batches"}


def test_unknown_device_kind_has_no_peaks():
    assert harness.load_peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="no peaks"):
        harness.load_peaks("cpu")


def test_roofline_bytes_match_a_hand_count():
    base = harness.load_reader(BENCH, "kernel.base_search_roofline")
    mod = base.__globals__
    # 1000 rows: 10 steps per bound; max_query_len 20 -> W = 2 words,
    # 3 gathered; per step 4 + 12 bytes, two bounds, one final entry
    assert mod["bytes_needed"](3, 1000, 20) == 3 * (2 * 10 * 16 + 4)
    assert math.ceil(math.log2(1001)) == 10
    fm = harness.load_reader(BENCH, "kernel.fm_search_roofline")
    # 20 symbols, two range ends, checkpoint + word = 8 bytes each
    assert fm.__globals__["bytes_needed"](5, 20.0) == 5 * 20 * 2 * 8
