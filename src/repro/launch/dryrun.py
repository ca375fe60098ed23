import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this:
  1. builds the production mesh ((16,16) or (2,16,16)) and ShardingCtx,
  2. lowers + compiles the step program from ShapeDtypeStruct inputs
     (no allocation),
  3. prints compiled.memory_analysis() (proves it fits) and
     cost_analysis() (XLA's own FLOPs/bytes),
  4. parses the optimized HLO with trip-count multipliers
     (launch/hlo_analysis.py) and derives the three roofline terms,
  5. writes results/dryrun/<arch>__<shape>__<mesh><tag>.json.

Usage:
  python -m repro.launch.dryrun --arch qwen3-1.7b --shape train_4k
  python -m repro.launch.dryrun --all [--mesh single|multi|both] [--force]
  python -m repro.launch.dryrun --halo                 # HaloPlan cells
  python -m repro.launch.dryrun --md --force-backend sparse
                                  # MD force-engine cells (prune ratio)
  python -m repro.launch.dryrun --summarize   # markdown table from JSONs
"""
import argparse
import dataclasses
import json
import traceback
from pathlib import Path

import jax
import numpy as np

from repro.obs import default_registry
from repro.obs import span as obs_span
from repro.configs import ARCH_IDS, SHAPES, get_config, shape_applicable
from repro.launch import hlo_analysis
from repro.launch.mesh import make_production_mesh
from repro.launch.steps import (
    active_param_count,
    batch_specs,
    make_ctx,
    make_decode_step,
    make_prefill_step,
    make_train_step,
    param_count,
)

RESULTS = Path(__file__).resolve().parents[3] / "results" / "dryrun"


def lower_cell(arch: str, shape_name: str, multi_pod: bool,
               overrides=None, tag: str = ""):
    cfg = get_config(arch)
    if overrides:
        cfg = dataclasses.replace(cfg, **overrides.get("cfg", {}))
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    ctx = make_ctx(cfg, shape, mesh,
                   fsdp=(overrides or {}).get("fsdp"))
    kw = {}
    for k in ("moe_dispatch", "zero2"):
        if overrides and k in overrides:
            kw[k] = overrides[k]

    with jax.set_mesh(mesh):
        if shape.kind == "train":
            prog = make_train_step(cfg, shape, ctx,
                                   microbatches=(overrides or {})
                                   .get("microbatches"),
                                   pod_compress=(overrides or {})
                                   .get("pod_compress"), **kw)
            args = (prog.abstract_params, prog.abstract_opt)
            if "pod" in mesh.axis_names and \
                    (overrides or {}).get("pod_compress"):
                args = args + (prog.abstract_params,)   # EF state
            bshapes, _ = batch_specs(cfg, shape, ctx)
            args = args + (bshapes,)
            lowered = prog.step_fn.lower(*args)
            extra = {"microbatches": prog.microbatches}
        elif shape.kind == "prefill":
            kw.pop("zero2", None)
            fn, model, _ = make_prefill_step(cfg, shape, ctx, **kw)
            bshapes, _ = batch_specs(cfg, shape, ctx)
            lowered = fn.lower(model.abstract(), bshapes)
            extra = {}
        else:
            kw.pop("zero2", None)
            fn, model, _ = make_decode_step(cfg, shape, ctx, **kw)
            bshapes, _ = batch_specs(cfg, shape, ctx)
            cache = model.cache_shapes(shape.global_batch, shape.seq_len)
            lowered = fn.lower(model.abstract(), bshapes["tokens"],
                               bshapes["pos"], cache)
            extra = {}
    return lowered, cfg, shape, ctx, extra


def run_cell(arch: str, shape_name: str, multi_pod: bool, overrides=None,
             tag: str = "", verbose: bool = True):
    mesh_name = "multi" if multi_pod else "single"
    reg = default_registry()
    sp_cell = None
    record = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
              "tag": tag, "ok": False}
    try:
      with obs_span("dryrun/cell", reg, arch=arch, shape=shape_name,
                    mesh=mesh_name) as sp_cell:
        cfg = get_config(arch)
        shape = SHAPES[shape_name]
        ok, why = shape_applicable(cfg, shape)
        if not ok:
            record.update({"skipped": why, "ok": True})
            return record
        with obs_span("dryrun/lower", reg) as sp_lower:
            lowered, cfg, shape, ctx, extra = lower_cell(
                arch, shape_name, multi_pod, overrides, tag)
        with obs_span("dryrun/compile", reg) as sp_compile:
            compiled = lowered.compile()

        mem = compiled.memory_analysis()
        mem_d = {k: int(getattr(mem, k)) for k in (
            "argument_size_in_bytes", "output_size_in_bytes",
            "temp_size_in_bytes", "alias_size_in_bytes",
            "generated_code_size_in_bytes")}
        cost = compiled.cost_analysis() or {}
        cost_d = {k: float(v) for k, v in cost.items()
                  if isinstance(v, (int, float)) and
                  k in ("flops", "bytes accessed")}
        parsed = hlo_analysis.analyze(compiled.as_text())

        chips = int(np.prod([lowered._lowering.compile_args[
            "num_partitions"]])) if False else \
            len(jax.devices()[:512 if multi_pod else 256])
        n_act = active_param_count(cfg)
        n_tot = param_count(cfg)
        tokens = shape.global_batch * (
            shape.seq_len if shape.kind == "train" else
            (shape.seq_len if shape.kind == "prefill" else 1))
        factor = 6.0 if shape.kind == "train" else 2.0
        model_flops = factor * n_act * tokens
        chips = 512 if multi_pod else 256
        tp = 16
        dp = chips // tp
        # analytic per-device traffic lower bound (see hlo_analysis)
        cache_local = 0.0
        if shape.kind == "decode":
            n_attn = sum(1 for s in cfg.pattern_unit
                         if s.kind == "attn") * cfg.n_units \
                + (cfg.n_layers if cfg.is_encdec else 0)
            kv_eff = max(cfg.n_kv_heads, 1)
            cache_local = (shape.global_batch * shape.seq_len * kv_eff *
                           cfg.head_dim * 2 * 2 * max(n_attn, 1)) / chips
        analytic = hlo_analysis.analytic_memory_bytes(
            n_params_stored=n_tot / tp,           # per-device weight reads
            n_params_active=n_act / tp,
            tokens_local=tokens / max(dp, 1),
            d_model=cfg.d_model, n_layers=cfg.n_layers,
            kind=shape.kind,
            opt_bytes_per_param=8.0 * tp / chips,  # ZeRO: states /chips
            cache_bytes_local=cache_local)
        terms = hlo_analysis.roofline_terms(parsed, model_flops / chips,
                                            analytic_bytes=analytic)

        record.update({
            "ok": True,
            "lower_s": round(sp_lower.dur, 1),
            "compile_s": round(sp_compile.dur, 1),
            "memory": mem_d,
            "device_total_bytes": mem_d["argument_size_in_bytes"] +
            mem_d["output_size_in_bytes"] + mem_d["temp_size_in_bytes"] -
            mem_d["alias_size_in_bytes"],
            "cost_analysis": cost_d,
            "parsed": {k: v for k, v in parsed.items()},
            "params": param_count(cfg),
            "active_params": n_act,
            "model_flops": model_flops,
            "roofline": terms,
            **extra,
        })
        if verbose:
            print(f"  memory_analysis: {mem_d}")
            print(f"  cost_analysis:   {cost_d}")
            print(f"  parsed:          flops={parsed['flops']:.3e} "
                  f"bytes={parsed['bytes']:.3e} "
                  f"coll={parsed['collective_bytes']:.3e}")
            print(f"  roofline:        {terms}")
    except Exception as e:  # noqa
        record["error"] = f"{type(e).__name__}: {e}"
        record["traceback"] = traceback.format_exc()[-4000:]
        if verbose:
            print(record["traceback"])
    finally:
        if sp_cell is not None and sp_cell.dur is not None:
            record["wall_s"] = round(sp_cell.dur, 1)
        jax.clear_caches()
    return record


def cell_path(arch, shape, mesh_name, tag=""):
    return RESULTS / f"{arch}__{shape}__{mesh_name}{tag}.json"


# ---- halo-plan cells (paper Fig. 5 analogue, compiled) -----------------------

HALO_DD = {"1d": (4, 1, 1), "2d": (4, 4, 1), "3d": (4, 4, 4)}
HALO_BACKENDS = ("serialized", "fused", "pallas", "signal")


def halo_cell_name(dd_name: str, backend: str, width: int = 1,
                   pulses: int = 1, pipeline: str = "off",
                   depth: int = 2, wire_dtype=None) -> str:
    name = f"halo__{dd_name}__{backend}"
    if width != 1:
        name += f"__w{width}"
    if pulses != 1:
        name += f"__p{pulses}"
    if pipeline != "off":
        name += f"__{pipeline}"
        if depth != 2:
            name += f"__d{depth}"
    if wire_dtype:
        name += f"__wd{wire_dtype}"
    return name


def run_halo_cell(dd_name: str, backend: str, local=(8, 8, 8), feat: int = 4,
                  width: int = 1, pulses: int = 1, pipeline: str = "off",
                  depth: int = 2, wire_dtype=None, verbose: bool = True):
    """Lower + compile one HaloPlan.fwd cell and record plan + HLO stats.

    The plan-reported byte/critical-path numbers are the canonical ones
    (results/make_tables.py reads them); the compiled-HLO collective bytes
    cross-check that XLA moves what the plan says it moves.  ``width`` /
    ``pulses`` select the width>1 multi-pulse schedules; ``pipeline`` /
    ``depth`` select the per-step overlap model recorded under
    ``overlap`` (the depth sweep makes the exposed-phase amortization of
    deeper in-flight windows measurable before real-mesh runs);
    ``wire_dtype`` selects a compressed payload format whose
    direction-aware byte accounting lands in ``plan_stats``.
    """
    from repro.core.halo_plan import HaloPlan, HaloSpec
    from repro.launch.mesh import make_mesh

    sp_cell = None
    record = {"kind": "halo", "dd": dd_name, "backend": backend,
              "local": list(local), "width": width, "pulses": pulses,
              "pipeline": pipeline, "pipeline_depth": depth,
              "wire_dtype": wire_dtype, "ok": False}
    try:
      with obs_span("dryrun/halo_cell", default_registry(), dd=dd_name,
                    backend=backend) as sp_cell:
        dd = HALO_DD[dd_name]
        mesh = make_mesh(dd, ("z", "y", "x"))
        # width 0 on non-decomposed dims: a 1D DD exchanges z-slabs only
        widths = tuple(width if n > 1 else 0 for n in dd)
        pulses_per_dim = tuple(pulses if w else 1 for w in widths)
        spec = HaloSpec(axis_names=("z", "y", "x"), widths=widths,
                        backend=backend, dtype="float32",
                        feature_elems=feat, pulses=pulses_per_dim,
                        wire_dtype=wire_dtype)
        plan = HaloPlan.build(spec, mesh)
        gshape = tuple(n * d for n, d in zip(local, dd)) + (feat,)
        arg = jax.ShapeDtypeStruct(gshape, np.float32)
        lowered = jax.jit(lambda a: plan.fwd(a)).lower(arg)
        compiled = lowered.compile()
        parsed = hlo_analysis.analyze(compiled.as_text())
        stats = plan.stats(local, pipeline=pipeline, depth=depth)
        record.update({
            "ok": True,
            "devices": int(np.prod(dd)),
            # latency + overlap models live inside plan_stats (single
            # source of truth; make_tables reads them from there)
            "plan_stats": stats,
            "hlo_collective_bytes": parsed["collective_bytes"],
            "hlo_bytes": parsed["bytes"],
        })
        if verbose:
            st = record["plan_stats"]
            print(f"  plan: total={st['total_bytes']} "
                  f"ser_crit={st['serialized_critical_bytes']} "
                  f"fused_crit={st['fused_critical_bytes']} "
                  f"exposed/step={st['exposed_phases_per_step']}")
            if wire_dtype:
                print(f"  wire: bytes={st['wire_bytes']} "
                      f"reduction={st['wire_reduction']:.2f}x")
            print(f"  hlo collective bytes: {parsed['collective_bytes']:.3e}")
    except Exception as e:  # noqa: BLE001
        record["error"] = f"{type(e).__name__}: {e}"
        record["traceback"] = traceback.format_exc()[-4000:]
        if verbose:
            print(record["traceback"])
    finally:
        if sp_cell is not None and sp_cell.dur is not None:
            record["wall_s"] = round(sp_cell.dur, 1)
        jax.clear_caches()
    return record


def run_halo_cells(force: bool = False, width: int = 1, pulses: int = 1,
                   pipeline: str = "off", depth: int = 2, wire_dtype=None):
    RESULTS.mkdir(parents=True, exist_ok=True)
    for dd_name in HALO_DD:
        for backend in HALO_BACKENDS:
            name = halo_cell_name(dd_name, backend, width, pulses,
                                  pipeline, depth, wire_dtype)
            path = RESULTS / f"{name}.json"
            if path.exists() and not force:
                print(f"[skip] {path.name} exists")
                continue
            print(f"[halo] {dd_name} x {backend} w={width} p={pulses} "
                  f"pipeline={pipeline} depth={depth} "
                  f"wire={wire_dtype}", flush=True)
            rec = run_halo_cell(dd_name, backend, width=width,
                                pulses=pulses, pipeline=pipeline,
                                depth=depth, wire_dtype=wire_dtype)
            path.write_text(json.dumps(rec, indent=1))
            print(f"[done] {path.name}: {'OK' if rec['ok'] else 'FAIL'} "
                  f"({rec['wall_s']}s)", flush=True)


# ---- MD force-engine cells (pair-schedule backends on a live DD mesh) --------

def run_md_cell(force_backend: str = "dense", halo_backend: str = "fused",
                n_atoms: int = 800, steps: int = 6, dd=(2, 2, 2),
                pipeline: str = "off", depth: int = 2,
                overlap_rebin: bool = False, nstprune: int = 0,
                wire_dtype=None, verbose: bool = True):
    """Run a short DD simulation and record the chosen force backend, its
    prune ratio / evaluated-work accounting (tier ladders, rolling-prune
    columns), the occupancy-adjusted halo byte accounting
    (``bytes_index`` / ``useful_bytes``), and the overlap model at the
    engine's pipeline depth."""
    from repro.core.halo_plan import HaloSpec
    from repro.core.md import MDEngine, make_grappa_like
    from repro.launch.mesh import make_mesh

    sp_cell = None
    dd_name = f"{sum(1 for d in dd if d > 1)}d"
    record = {"kind": "mdforce", "dd": dd_name, "backend": halo_backend,
              "force_backend": force_backend, "pipeline": pipeline,
              "pipeline_depth": depth, "overlap_rebin": overlap_rebin,
              "nstprune": nstprune, "wire_dtype": wire_dtype,
              "n_atoms": n_atoms, "ok": False}
    try:
      with obs_span("dryrun/md_cell", default_registry(), dd=dd_name,
                    backend=halo_backend,
                    force_backend=force_backend) as sp_cell:
        mesh = make_mesh(dd, ("z", "y", "x"))
        system = make_grappa_like(n_atoms, seed=1)
        spec = HaloSpec(axis_names=("z", "y", "x"), widths=(1, 1, 1),
                        backend=halo_backend)
        eng = MDEngine(system, mesh, spec, pipeline=pipeline,
                       pipeline_depth=depth, overlap_rebin=overlap_rebin,
                       force_backend=force_backend, nstprune=nstprune,
                       wire_dtype=wire_dtype)
        _, metrics, diags = eng.simulate(steps)
        record.update({
            "ok": True,
            "devices": int(np.prod(dd)),
            "pair_stats": eng.pair_stats(),
            "halo_stats": {k: v for k, v in eng.halo_stats().items()
                           if k in ("total_bytes", "bytes_index",
                                    "useful_bytes", "occupancy",
                                    "wire_bytes", "wire_reduction",
                                    "wire_itemsize_fwd",
                                    "wire_itemsize_rev")},
            "overlap": eng.overlap_stats(),
            "pe_final": float(np.asarray(metrics["pe"])[-1]),
            "n_atoms_conserved": int(np.asarray(diags[-1]["n_atoms"]))
            == n_atoms,
        })
        if verbose:
            ps = record["pair_stats"]
            print(f"  force_backend={force_backend} "
                  f"prune_ratio={ps['prune_ratio']:.2f}x "
                  f"evaluated={ps['evaluated_slot_pairs']} "
                  f"(dense {ps['dense_slot_pairs']})")
    except Exception as e:  # noqa: BLE001
        record["error"] = f"{type(e).__name__}: {e}"
        record["traceback"] = traceback.format_exc()[-4000:]
        if verbose:
            print(record["traceback"])
    finally:
        if sp_cell is not None and sp_cell.dur is not None:
            record["wall_s"] = round(sp_cell.dur, 1)
        jax.clear_caches()
    return record


def run_md_cells(force_backend: str, force: bool = False,
                 halo_backend: str = "fused", pipeline: str = "off",
                 depth: int = 2, overlap_rebin: bool = False,
                 nstprune: int = 0, wire_dtype=None):
    RESULTS.mkdir(parents=True, exist_ok=True)
    name = f"mdforce__3d__{halo_backend}__{force_backend}"
    if pipeline != "off":
        name += f"__{pipeline}"
        if depth != 2:
            name += f"__d{depth}"
    if overlap_rebin:
        name += "__or"
    if nstprune:
        name += f"__np{nstprune}"
    if wire_dtype:
        name += f"__wd{wire_dtype}"
    path = RESULTS / f"{name}.json"
    if path.exists() and not force:
        print(f"[skip] {path.name} exists")
        return
    print(f"[mdforce] 3d x {halo_backend} x force={force_backend} "
          f"pipeline={pipeline} depth={depth} "
          f"overlap_rebin={overlap_rebin} nstprune={nstprune} "
          f"wire={wire_dtype}", flush=True)
    rec = run_md_cell(force_backend=force_backend,
                      halo_backend=halo_backend, pipeline=pipeline,
                      depth=depth, overlap_rebin=overlap_rebin,
                      nstprune=nstprune, wire_dtype=wire_dtype)
    path.write_text(json.dumps(rec, indent=1))
    print(f"[done] {path.name}: {'OK' if rec['ok'] else 'FAIL'} "
          f"({rec['wall_s']}s)", flush=True)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="single",
                    choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--tag", default="")
    ap.add_argument("--summarize", action="store_true")
    ap.add_argument("--halo", action="store_true",
                    help="compile HaloPlan cells (results/dryrun/halo__*)")
    ap.add_argument("--md", action="store_true",
                    help="run MD force-engine cells "
                         "(results/dryrun/mdforce__*)")
    ap.add_argument("--force-backend", default="dense",
                    help="NB force engine for --md cells "
                         "(dense|sparse|pallas)")
    ap.add_argument("--halo-width", type=int, default=1,
                    help="halo width per decomposed dim for --halo cells")
    ap.add_argument("--halo-pulses", type=int, default=1,
                    help="pulses per dim (GROMACS two-pulse case: 2)")
    ap.add_argument("--pipeline", default="off",
                    choices=["off", "double_buffer"],
                    help="step-pipeline overlap model recorded with "
                         "--halo cells")
    ap.add_argument("--pipeline-depth", type=int, default=2,
                    help="in-flight window depth for the overlap model "
                         "(--halo) / the engine ring (--md)")
    ap.add_argument("--overlap-rebin", action="store_true",
                    help="fuse rebin/migration + prune into the --md "
                         "block program (GROMACS DLB analogue)")
    ap.add_argument("--nstprune", type=int, default=0,
                    help="rolling inner-prune cadence for --md cells "
                         "(dual pair list; 0 = outer list only)")
    ap.add_argument("--wire-dtype", default=None,
                    choices=["bfloat16", "float16", "int8_ef", "float32"],
                    help="compressed halo payload format for --halo/--md "
                         "cells (HaloSpec.wire_dtype)")
    ap.add_argument("--moe-dispatch", default=None)
    ap.add_argument("--pod-compress", default=None)
    ap.add_argument("--microbatches", type=int, default=None)
    ap.add_argument("--zero2", action="store_true")
    ap.add_argument("--mamba-dtype", default=None)
    ap.add_argument("--remat-policy", default=None)
    args = ap.parse_args()

    if args.summarize:
        summarize()
        return
    if args.halo:
        run_halo_cells(force=args.force, width=args.halo_width,
                       pulses=args.halo_pulses, pipeline=args.pipeline,
                       depth=args.pipeline_depth,
                       wire_dtype=args.wire_dtype)
        return
    if args.md:
        run_md_cells(force_backend=args.force_backend, force=args.force,
                     pipeline=args.pipeline, depth=args.pipeline_depth,
                     overlap_rebin=args.overlap_rebin,
                     nstprune=args.nstprune, wire_dtype=args.wire_dtype)
        return

    RESULTS.mkdir(parents=True, exist_ok=True)
    archs = ARCH_IDS if args.all or not args.arch else \
        [args.arch.replace("-", "_").replace(".", "_")]
    shapes = list(SHAPES) if args.all or not args.shape else [args.shape]
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]

    overrides = {}
    if args.moe_dispatch:
        overrides["moe_dispatch"] = args.moe_dispatch
    if args.pod_compress:
        overrides["pod_compress"] = args.pod_compress
    if args.microbatches:
        overrides["microbatches"] = args.microbatches
    if args.zero2:
        overrides["zero2"] = True
    if args.mamba_dtype:
        overrides.setdefault("cfg", {})["mamba_scan_dtype"] = \
            args.mamba_dtype
    if args.remat_policy:
        overrides.setdefault("cfg", {})["remat_policy"] = args.remat_policy

    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                mesh_name = "multi" if mp else "single"
                path = cell_path(arch, shape, mesh_name, args.tag)
                if path.exists() and not args.force:
                    print(f"[skip] {path.name} exists")
                    continue
                print(f"[cell] {arch} x {shape} x {mesh_name}", flush=True)
                rec = run_cell(arch, shape, mp, overrides or None, args.tag)
                path.write_text(json.dumps(rec, indent=1))
                status = "OK" if rec["ok"] else "FAIL"
                print(f"[done] {path.name}: {status} "
                      f"({rec['wall_s']}s)", flush=True)


def summarize():
    rows = []
    for p in sorted(RESULTS.glob("*.json")):
        r = json.loads(p.read_text())
        rows.append(r)
    print(f"| arch | shape | mesh | status | GB/dev | flops/dev | "
          f"coll B/dev | compute s | memory s | coll s | dominant | "
          f"roofline frac |")
    print("|" + "---|" * 12)
    for r in rows:
        if r.get("skipped"):
            print(f"| {r['arch']} | {r['shape']} | {r['mesh']} | "
                  f"{r['skipped']} |" + " |" * 8)
            continue
        if not r["ok"]:
            print(f"| {r['arch']} | {r['shape']} | {r['mesh']} | FAIL "
                  f"{r.get('error', '')[:60]} |" + " |" * 8)
            continue
        t = r["roofline"]
        print(f"| {r['arch']} | {r['shape']} | {r['mesh']} | ok "
              f"| {r['device_total_bytes'] / 1e9:.2f} "
              f"| {r['parsed']['flops']:.2e} "
              f"| {r['parsed']['collective_bytes']:.2e} "
              f"| {t['compute_s']:.2e} | {t['memory_s']:.2e} "
              f"| {t['collective_s']:.2e} | {t['dominant']} "
              f"| {t.get('roofline_fraction', 0):.3f} |")


if __name__ == "__main__":
    main()
