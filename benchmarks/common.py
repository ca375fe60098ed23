"""Shared benchmark helpers: subprocess runners, timing, CSV output."""
from __future__ import annotations

import json
import os
import subprocess
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
SRC = REPO / "src"
RESULTS = REPO / "results"


def run_sub(script: str, *args: str, devices: int = 1,
            timeout: int = 1800) -> dict:
    """Run a benchmark worker in a subprocess with N virtual devices.

    Workers print a single JSON dict on the last line of stdout.
    """
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    # virtual CPU workers by design: never reach for a chip the parent
    # process may hold
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = f"{SRC}:{env.get('PYTHONPATH', '')}"
    proc = subprocess.run(
        [sys.executable, str(REPO / "benchmarks" / script), *args],
        capture_output=True, text=True, timeout=timeout, env=env)
    if proc.returncode != 0:
        raise RuntimeError(f"{script} {args} failed:\n{proc.stderr[-2000:]}")
    return json.loads(proc.stdout.strip().splitlines()[-1])


def time_fn(fn, *args, warmup: int = 2, iters: int = 10) -> float:
    """Median wall time per call in seconds (after warmup).

    Thin wrapper over :func:`repro.obs.time_fn` — the shared span/timer
    API — keeping this module's historical float return."""
    from repro.obs import time_fn as obs_time_fn
    return obs_time_fn(fn, *args, warmup=warmup, iters=iters).median


def emit(name: str, us_per_call: float, derived: str):
    print(f"{name},{us_per_call:.1f},{derived}")
