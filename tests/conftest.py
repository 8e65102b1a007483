import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SRC = os.path.join(REPO, "src")

# Tests leave JAX's persistent compilation cache off: the entry points
# they drive (serve.main, the benches) turn it on for real runs.  Set
# before any jax import; child processes inherit it.
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"


def run_multidevice(code: str, n_devices: int = 8, timeout: int = 600):
    """Run ``code`` in a subprocess with n host devices (smoke tests and
    benches must see 1 device, so multi-device tests are subprocesses)."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_devices}"
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=timeout)
    if proc.returncode != 0:
        raise AssertionError(
            f"multidevice subprocess failed:\n--- stdout ---\n"
            f"{proc.stdout[-4000:]}\n--- stderr ---\n{proc.stderr[-4000:]}")
    return proc.stdout


@pytest.fixture
def multidevice():
    return run_multidevice
