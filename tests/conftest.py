import os
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent
SRC = REPO / "src"


def run_dist_script(script: str, *args: str, devices: int = 8,
                    timeout: int = 900, extra_env: dict | None = None) -> str:
    """Run a tests/dist/ script in a subprocess with N virtual devices.

    The main pytest process must keep a single CPU device (smoke tests and
    benches see the real topology); multi-device checks therefore run in
    subprocesses that set XLA_FLAGS before importing jax.
    """
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    # virtual CPU workers by design: never reach for a chip the parent
    # process may hold
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = f"{SRC}:{env.get('PYTHONPATH', '')}"
    if extra_env:
        env.update(extra_env)
    proc = subprocess.run(
        [sys.executable, str(REPO / "tests" / "dist" / script), *args],
        capture_output=True, text=True, timeout=timeout, env=env,
    )
    if proc.returncode != 0:
        raise AssertionError(
            f"{script} failed (rc={proc.returncode})\n"
            f"--- stdout ---\n{proc.stdout}\n--- stderr ---\n{proc.stderr}"
        )
    return proc.stdout


@pytest.fixture(scope="session")
def dist():
    return run_dist_script
