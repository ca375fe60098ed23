#!/usr/bin/env python3
"""Readings that the correctness limits of a cell are set from.

    python bench/calibrate.py --workload lammps-lj-32k.nve.dd4 --seeds 101-112 \
        [--blocks 4] [--fault NAME] [--out chiprun_out/calib]

For each seed, at the cell's own size and through the timed path
(``MDEngine.simulate``, warm-up blocks first): a short window of
``--blocks`` blocks, the traffic's ``checked_blocks`` consecutive blocks
drawn from the seed as the benchmark draws them, and the numbers
compared for ``correct`` twice --

* ``program``: the program's blocks against the float64 reference
  chained over them from the program's state at the first one's start;
* ``control``: the reference computed with bfloat16 pair arithmetic,
  put in the program's place, against the same float64 reference.

The lower reading of a number is the largest the program gives, the
upper the smallest the control gives.  With ``--fault`` the program runs
with that fault of ``faults.py`` planted, and its readings are the
fault's.  One engine serves every seed: each seed's system is binned
into it.  Writes one JSON line per seed and a summary to ``--out``;
needs the chips the cell asks for.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from concurrent.futures import ThreadPoolExecutor  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
# libtpu writes its logs under /tmp unless told otherwise
os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

import numpy as np  # noqa: E402

import faults  # noqa: E402
import harness  # noqa: E402
import spec  # noqa: E402


def seeds_arg(text: str):
    out = []
    for part in text.split(","):
        lo, _, hi = part.partition("-")
        out.extend(range(int(lo), int(hi or lo) + 1))
    return out


def state_for(engine, config, arrays):
    """A seed's system binned into ``engine``'s layout, on its mesh."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.core.md.system import MDSystem
    dt = np.dtype(config["dtype"])
    system = MDSystem(box=arrays["box"], pos=arrays["pos"].astype(dt),
                      vel=arrays["vel"].astype(dt),
                      charge=arrays["charge"].astype(dt),
                      typ=arrays["typ"], params=engine.system.params)
    cell_f, cell_i = engine.bin_host(system)
    shard = NamedSharding(engine.mesh, P("z", "y", "x"))
    return (jax.device_put(cell_f, shard), jax.device_put(cell_i, shard))


def one_seed(engine, cell, ref, seed: int, blocks: int) -> dict:
    import jax
    config, traffic = cell["config"], cell["traffic"]
    arrays = ref.make_system(config, seed)
    n = arrays["pos"].shape[0]
    n_steps = harness.call_steps(config, traffic)
    state = state_for(engine, config, arrays)
    for _ in range(int(traffic["warmup_blocks"])):
        state, _m, _d = engine.simulate(n_steps, state=state, collect=True)
    calls = []
    for _ in range(blocks):
        out, m, d = engine.simulate(n_steps, state=state, collect=True)
        calls.append((state, out, m, d))
        state = out
    jax.block_until_ready(state)
    ks = harness.compared_calls(seed, len(calls), traffic["checked_blocks"])
    start = harness.atoms_by_id(calls[ks[0]][0], n)
    got = [harness.program_block(calls[k], n) for k in ks]
    final = harness.atoms_by_id(calls[-1][1], n)
    exact = harness.integrity(harness.dropped_per_call(calls), final, n)
    del calls, state
    t0 = time.perf_counter()
    # the float64 reference and the bfloat16 control side by side
    with ThreadPoolExecutor(2) as ex:
        want, control = ex.map(
            lambda dt: harness.reference_chain(ref, config, arrays, start,
                                               n_steps, len(got), dt),
            (np.float64, ref.BF16))
    t_ref = time.perf_counter() - t0
    prog = harness.compare_chain(arrays["box"], got, want)
    ctrl = harness.compare_chain(arrays["box"], control, want)
    return {"seed": seed, "blocks": list(ks), "program": {**exact, **prog},
            "control": ctrl, "reference_s": t_ref}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=seeds_arg, required=True)
    ap.add_argument("--blocks", type=int, default=4)
    ap.add_argument("--fault", choices=sorted(faults.FAULTS))
    ap.add_argument("--out", default="chiprun_out/calib")
    args = ap.parse_args()
    cell = spec.resolve_cell(spec.load_benchmark(), args.workload)
    devices = harness.require_accelerator(int(cell["cell"]["chips"]))
    harness.enable_compile_cache()
    out = Path(args.out)
    out.mkdir(parents=True, exist_ok=True)
    ref = spec.reference(cell["config"])
    name = args.workload + (f".{args.fault}" if args.fault else "")
    with (faults.planted(args.fault) if args.fault
          else contextlib.nullcontext()):
        engine, _ = harness.build(cell["config"], int(cell["cell"]["chips"]),
                                  args.seeds[0])
        rows = calibrate(engine, cell, ref, args.seeds, args.blocks,
                         out / f"{name}.jsonl")
    names = rows[0]["control"].keys()
    limits = cell["config"]["limits"]
    summary = {
        "workload": args.workload, "fault": args.fault, "seeds": args.seeds,
        "device": {"kind": devices[0].device_kind, "count": len(devices)},
        "lower": {k: max(r["program"][k] for r in rows) for k in names},
        "upper": {k: min(r["control"][k] for r in rows) for k in names},
        "program_min": {k: min(r["program"][k] for r in rows)
                        for k in names},
        "exact_max": {k: max(r["program"][k] for r in rows)
                      for k in ("atoms_missing", "atoms_dropped")},
        "program_correct": [all(r["program"][k] <= limits[k] for k in limits)
                            for r in rows],
        "seconds": time.perf_counter() - T_START,
    }
    (out / f"{name}.summary.json").write_text(json.dumps(summary, indent=1))
    print(json.dumps(summary), flush=True)
    return 0


def calibrate(engine, cell, ref, seeds, blocks: int, path: Path) -> list:
    rows = []
    with open(path, "a") as fh:
        for seed in seeds:
            row = one_seed(engine, cell, ref, seed, blocks)
            rows.append(row)
            fh.write(json.dumps(row) + "\n")
            fh.flush()
            print(json.dumps(row), flush=True)
    return rows


if __name__ == "__main__":
    sys.exit(main())
