import os
import sys

import pytest

# Tests must see exactly ONE device (the dry-run sets its own 512-device
# flag in a separate process). Never set XLA_FLAGS here.
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))


def run_in_subprocess(script: str, n_devices: int = 8, timeout: int = 560):
    """Run a python snippet with a multi-device host platform."""
    import subprocess
    env = dict(os.environ)
    env["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={n_devices}")
    env["PYTHONPATH"] = os.path.join(
        os.path.dirname(__file__), "..", "src")
    r = subprocess.run([sys.executable, "-c", script], env=env,
                       capture_output=True, text=True, timeout=timeout)
    if r.returncode != 0:
        raise AssertionError(
            f"subprocess failed:\nSTDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr}")
    return r.stdout


@pytest.fixture(scope="session")
def subproc():
    return run_in_subprocess
