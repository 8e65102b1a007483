"""chip_smoke.py: refuses to run without a TPU, and its phases agree with
the host reference at a small size on the CPU."""
import importlib.util
import os
import subprocess
import sys

from conftest import REPO, SRC

SCRIPT = os.path.join(REPO, "chip_smoke.py")


def _load():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_chip_smoke_fails_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run([sys.executable, SCRIPT, "--text-len", "4096"],
                          capture_output=True, text=True, env=env,
                          timeout=300)
    assert proc.returncode != 0
    assert '"ok": true' not in proc.stdout
    assert "no TPU" in proc.stdout


def test_chip_smoke_phases_match_reference_on_cpu(tmp_path):
    smoke = _load()
    lines = []
    records = smoke.run_phases(20000, seed=3, batch=256, root=str(tmp_path),
                               out=lines.append)
    assert {r["phase"] for r in records} == {
        "build", "base", "tiers", "compact", "frozen"}
    assert all(r["match"] for r in records), records
    assert any(ln.startswith("[tiers ] num_tiers") for ln in lines)
