"""Worker: MD step timing for one (devices, backend, size) cell -> JSON.

Usage (positional args kept for benchmarks/figures.py compatibility):

  python -m benchmarks.md_worker BACKEND N_ATOMS [STEPS]
      [--pipeline {off,double_buffer}] [--pipeline-depth D]
      [--overlap-rebin] [--halo-width N]
      [--halo-pulses N] [--force-backend {dense,sparse,pallas}]
      [--safety F] [--nstprune N] [--inner-radius R]
      [--wire-dtype {bfloat16,float16,int8_ef,float32}]
      [--out results/dryrun]

Emits one JSON record with per-step timing plus the plan's overlap model
(``overlapped_bytes``, ``exposed_phases`` at the chosen window depth),
the alpha-beta latency model (``modeled_*``, for the modeled-vs-measured
figures), and the force engine's evaluated-work accounting
(``prune_ratio``, ``pairs_per_s``, the per-pair-bound tier ladders and
the rolling-prune columns); with ``--out`` the record is also written to
``<out>/md__<backend>__<n>__<pipeline>[__dD][__or][__wW][__pP][__wdF]
[__fbB][__sS][__npN].json``.
"""
import argparse
import json
from pathlib import Path

import jax

from repro.core.halo_plan import HaloSpec
from repro.core.md import MDEngine, force_backends, make_grappa_like
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_md_mesh
from repro.obs import MetricsRegistry, span, time_fn


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("backend")
    ap.add_argument("n_atoms", type=int)
    ap.add_argument("steps", type=int, nargs="?", default=40)
    ap.add_argument("--pipeline", default="off",
                    choices=("off", "double_buffer"))
    ap.add_argument("--pipeline-depth", type=int, default=2,
                    help="in-flight window depth (extended-force ring "
                         "slots; 2 = double-buffered halos)")
    ap.add_argument("--overlap-rebin", action="store_true",
                    help="fuse rebin/migration + prune into the block "
                         "program's final region (GROMACS DLB analogue)")
    ap.add_argument("--halo-width", type=int, default=1)
    ap.add_argument("--halo-pulses", type=int, default=1)
    ap.add_argument("--force-backend", default="dense",
                    choices=force_backends(),
                    help="NB force engine (pair_schedule registry)")
    ap.add_argument("--safety", type=float, default=2.2,
                    help="cell capacity safety factor (occupancy sweep)")
    ap.add_argument("--nstprune", type=int, default=0,
                    help="rolling inner-prune cadence (dual pair list; "
                         "0 = outer list only)")
    ap.add_argument("--inner-radius", type=float, default=None,
                    help="inner cutoff of the rolling prune (default: "
                         "r_cut + 3-sigma drift over nstprune steps)")
    ap.add_argument("--wire-dtype", default=None,
                    choices=("bfloat16", "float16", "int8_ef", "float32"),
                    help="compressed halo payload format (force-return "
                         "direction; coordinates ride the f32 floor)")
    ap.add_argument("--out", default=None,
                    help="directory for the JSON record (e.g. "
                         "results/dryrun)")
    ap.add_argument("--trace", action="store_true",
                    help="thread per-step obs/* ledger counters through "
                         "the block programs (barrier-neutral)")
    ap.add_argument("--obs-jsonl", default=None,
                    help="write the run's metrics-registry records here "
                         "(input of `python -m repro.obs`)")
    args = ap.parse_args()
    enable_compile_cache()

    system = make_grappa_like(args.n_atoms, seed=1)
    mesh = make_md_mesh()
    w = args.halo_width
    spec = HaloSpec(axis_names=("z", "y", "x"), widths=(w, w, w),
                    backend=args.backend,
                    pulses=None if args.halo_pulses == 1
                    else (args.halo_pulses,) * 3)
    reg = MetricsRegistry()
    eng = MDEngine(system, mesh, spec, pipeline=args.pipeline,
                   pipeline_depth=args.pipeline_depth,
                   overlap_rebin=args.overlap_rebin,
                   force_backend=args.force_backend,
                   capacity_safety=args.safety,
                   nstprune=args.nstprune,
                   inner_radius=args.inner_radius,
                   wire_dtype=args.wire_dtype,
                   obs=reg, trace=args.trace)

    state, _, _ = eng.simulate(4, collect=False)         # compile + warmup
    with span("simulate", reg, steps=args.steps) as sp:
        state, _, _ = eng.simulate(args.steps, state=state, collect=False)
        # the returned state is async-dispatched: block before the clock
        # stops so the final block's tail is inside the measurement
        sp.sync(state)
    dt = sp.dur / args.steps

    # device-side decomposition (paper Fig. 6 analogue): time the force
    # pass (halo fwd + NB kernel + halo rev) through the selected backend
    cf, ci = state
    t_force_pass = time_fn(eng.force_fn, cf, ci, warmup=1, iters=10,
                           name="force_pass", registry=reg).median

    stats = eng.halo_stats()
    overlap = eng.overlap_stats()
    lat = stats["latency"]
    pair = eng.pair_stats()
    n_dev = len(jax.devices())
    record = {
        "devices": n_dev,
        "mode": args.backend,
        "pipeline": args.pipeline,
        "pipeline_depth": args.pipeline_depth,
        "overlap_rebin": args.overlap_rebin,
        "halo_width": w,
        "halo_pulses": args.halo_pulses,
        "n_atoms": args.n_atoms,
        "dd": [int(mesh.shape[a]) for a in ("z", "y", "x")],
        "ms_per_step": dt * 1e3,
        "ms_force_pass": t_force_pass * 1e3,
        "atom_steps_per_s": args.n_atoms / dt,
        "halo_total_bytes": stats["total_bytes"],
        "halo_critical_bytes":
        stats[f"{eng.plan.backend.critical_path}_critical_bytes"],
        # index-payload + occupancy-adjusted accounting (HaloPlan.stats)
        "halo_bytes_index": stats["bytes_index"],
        "halo_useful_bytes": stats["useful_bytes"],
        "halo_occupancy": stats["occupancy"],
        # compressed-wire accounting (HaloSpec.wire_dtype; None = dense)
        "wire_dtype": args.wire_dtype,
        "wire_itemsize_fwd": stats.get("wire_itemsize_fwd"),
        "wire_itemsize_rev": stats.get("wire_itemsize_rev"),
        "wire_bytes": stats.get("wire_bytes"),
        "wire_reduction": stats.get("wire_reduction"),
        # per-step overlap model (the step-pipeline scaling story)
        "overlapped_bytes": overlap["overlapped_bytes_per_step"],
        "exposed_phases": overlap["exposed_phases_per_step"],
        "exchanged_bytes": overlap["exchanged_bytes_per_step"],
        # alpha-beta latency model (modeled-vs-measured crossover)
        "modeled_serialized_s": lat["serialized_time_s"],
        "modeled_fused_s": lat["fused_time_s"],
        "modeled_speedup": lat["fused_speedup"],
        # force engine: evaluated-work accounting (pair_schedule) — the
        # tier ladders are the per-pair slot bounds, global_kexec_* the
        # old single-rectangle accounting the ladders improve on, and
        # the *_inner columns the rolling dual pair list's schedule
        "force_backend": args.force_backend,
        "capacity_safety": args.safety,
        "nstprune": args.nstprune,
        "inner_radius": pair.get("inner_radius"),
        "prune_ratio": pair["prune_ratio"],
        "evaluated_slot_pairs_per_step": pair["evaluated_slot_pairs"],
        "outer_slot_pairs_per_step": pair.get("outer_slot_pairs"),
        "global_kexec_slot_pairs_per_step":
        pair.get("global_kexec_slot_pairs"),
        "per_pair_bound_gain": pair.get("per_pair_bound_gain"),
        "tiers": pair.get("tiers"),
        "tiers_inner": pair.get("tiers_inner"),
        "inner_overflow_blocks": pair.get("inner_overflow_blocks"),
        "dense_slot_pairs_per_step": pair["dense_slot_pairs"],
        "pairs_per_s": pair["evaluated_slot_pairs"] * n_dev / dt,
    }
    reg.emit("bench", **record)
    if args.obs_jsonl:
        if args.trace:
            # a short collected run so the per-step obs/* ledger counters
            # land in the JSONL (off the timed path above)
            eng.simulate(min(args.steps, 8), state=state, collect=True)
        path = Path(args.obs_jsonl)
        path.parent.mkdir(parents=True, exist_ok=True)
        reg.to_jsonl(path)
    print(json.dumps(record))
    if args.out:
        out_dir = Path(args.out)
        out_dir.mkdir(parents=True, exist_ok=True)
        name = f"md__{args.backend}__{args.n_atoms}__{args.pipeline}"
        if args.pipeline_depth != 2:
            name += f"__d{args.pipeline_depth}"
        if args.overlap_rebin:
            name += "__or"
        if w != 1:
            name += f"__w{w}"
        if args.halo_pulses != 1:
            name += f"__p{args.halo_pulses}"
        if args.wire_dtype:
            name += f"__wd{args.wire_dtype}"
        if args.force_backend != "dense":
            name += f"__fb{args.force_backend}"
        if args.safety != 2.2:
            name += f"__s{args.safety:g}"
        if args.nstprune:
            name += f"__np{args.nstprune}"
        (out_dir / f"{name}.json").write_text(json.dumps(record, indent=1))


if __name__ == "__main__":
    main()
