"""Shared set-up of the benchmark's CPU tests: import the harness from
``bench/`` and build a small copy of a real cell."""
import copy
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
BENCH = ROOT / "bench"
FIXTURES = Path(__file__).resolve().parent / "fixtures"
for p in (str(ROOT / "src"), str(BENCH)):
    if p not in sys.path:
        sys.path.insert(0, p)

import faults  # noqa: E402
import harness  # noqa: E402
import spec  # noqa: E402
import trace_reduce  # noqa: E402

SMALL_ATOMS = 1372    # 7^3 fcc cells: 4x4x4 engine cells, 3x3x3 reference cells


def small_cell(workload: str = "lammps-lj-32k.nve.dd4",
               n_atoms: int = SMALL_ATOMS):
    """The cell as committed, at ``n_atoms`` atoms and one warm-up block:
    the same configuration, traffic, engine options and limits."""
    cell = copy.deepcopy(spec.resolve_cell(spec.load_benchmark(), workload))
    cell["config"]["n_atoms"] = n_atoms
    cell["traffic"]["warmup_blocks"] = 1
    cell["cell"]["chips"] = 1
    return cell


def run_small(cell, seed: int = 2**31 + 17, trace: bool = False):
    """One run of ``cell`` on the CPU: the harness minus its chip check;
    a window of a single block."""
    import time
    import jax
    return harness.run_cell(cell, seed, 0.0, trace, jax.devices(),
                            time.perf_counter())
