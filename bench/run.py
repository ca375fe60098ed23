#!/usr/bin/env python3
"""Benchmark entry: one run of one cell of ``BENCHMARK.json``.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout on a machine that holds the chips the
cell asks for.  Exits non-zero, printing no result, where JAX finds no
accelerator or fewer chips than the cell needs.
"""
import time

T_START = time.perf_counter()     # set-up is timed from process start

import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
# libtpu writes its logs under /tmp unless told otherwise
os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

import harness  # noqa: E402

if __name__ == "__main__":
    sys.exit(harness.main(t_start=T_START))
