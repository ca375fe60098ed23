"""Benchmark functions, one per paper table/figure.

All output rows are ``name,us_per_call,derived`` CSV (benchmarks/run.py).
CPU wall-clocks use virtual host devices (all sharing one core), so
absolute numbers are not TPU predictions; the *structural* quantities
(chained collective bytes/phases, overlap ratios) are the paper-relevant
signals and are derived from the pulse schedule and compiled HLO.
"""
from __future__ import annotations

import json
from pathlib import Path

import numpy as np

from benchmarks.common import RESULTS, emit, run_sub
from repro.core.halo_plan import HaloPlan, HaloSpec


def fig3_intranode_strong_scaling(quick: bool = False):
    """Paper Fig. 3: same system, 1..8 devices, MPI(serialized) vs
    NVSHMEM(fused).  Wall-clock per MD step + measured speedup, plotted
    against the plan's alpha-beta latency model (``modeled_*`` fields of
    the worker record) so the sweep shows the modeled-vs-measured
    crossover as domains shrink."""
    sizes = [1200] if quick else [1200, 2400]
    devs = [1, 8] if quick else [1, 2, 4, 8]
    for n_atoms in sizes:
        base = {}
        modeled = {}
        for d in devs:
            for mode in ("serialized", "fused"):
                try:
                    r = run_sub("md_worker.py", mode, str(n_atoms), "30",
                                devices=d)
                except RuntimeError as e:
                    emit(f"fig3/{n_atoms}atoms/{d}dev/{mode}", -1,
                         f"error={str(e)[:60]}")
                    continue
                base[(d, mode)] = r["ms_per_step"]
                modeled[d] = r.get("modeled_speedup")
                emit(f"fig3/{n_atoms}atoms/{d}dev/{mode}",
                     r["ms_per_step"] * 1e3,
                     f"dd={'x'.join(map(str, r['dd']))};"
                     f"atomsteps_per_s={r['atom_steps_per_s']:.0f}")
        for d in devs:
            if (d, "serialized") in base and (d, "fused") in base:
                s = base[(d, "serialized")] / base[(d, "fused")]
                m = modeled.get(d)
                emit(f"fig3/{n_atoms}atoms/{d}dev/speedup", 0.0,
                     f"fused_over_serialized={s:.3f}"
                     + (f";modeled={m:.3f}" if m else ""))


def fig5_multinode_critical_path():
    """Paper Fig. 5 analogue: per-DD-dimensionality chained halo bytes.

    At scale the iteration rate is bounded by the chained (serialized)
    communication; we report the plan-derived critical-path bytes for
    1D/2D/3D DD at the paper's ~90k atoms/GPU operating point, serialized
    vs fused, plus the dependent fraction that drives the gap.
    """
    from repro.launch.mesh import make_mesh

    plan = HaloPlan.build(
        HaloSpec(axis_names=("z", "y", "x"), widths=(1, 1, 1),
                 dtype="float32", feature_elems=4),
        make_mesh((1, 1, 1), ("z", "y", "x")))
    for dd, name in [((4, 1, 1), "1D"), ((4, 4, 1), "2D"),
                     ((4, 4, 4), "3D")]:
        # paper operating point: 90k atoms PER DEVICE; the box grows with
        # the device count, per-domain cells = global cells / dd
        n_dev = int(np.prod(dd))
        box = (90_000 * n_dev / 0.78) ** (1 / 3)
        gcells = max(2, int(box / 2.7))
        local = tuple(max(1, gcells // d) for d in dd)
        stats = plan.stats(local)
        ratio = stats["fused_critical_bytes"] / \
            max(stats["serialized_critical_bytes"], 1)
        lat = stats["latency"]
        emit(f"fig5/{name}dd/serialized_critical_KB", 0.0,
             f"{stats['serialized_critical_bytes'] / 1e3:.1f}")
        emit(f"fig5/{name}dd/fused_critical_KB", 0.0,
             f"{stats['fused_critical_bytes'] / 1e3:.1f}")
        emit(f"fig5/{name}dd/fused_over_serialized", 0.0, f"{ratio:.3f}")
        emit(f"fig5/{name}dd/dependent_fraction", 0.0,
             f"{stats['dependent_fraction']:.4f}")
        emit(f"fig5/{name}dd/alpha_beta_model_us", 0.0,
             f"serialized={lat['serialized_time_s'] * 1e6:.2f};"
             f"fused={lat['fused_time_s'] * 1e6:.2f};"
             f"modeled_speedup={lat['fused_speedup']:.3f}")

    # modeled crossover sweep (fixed 3D-DD schedules, shrinking per-domain
    # blocks): with one pulse per dim both designs pay the same number of
    # alphas, so the fused advantage is bandwidth-side and decays to 1 as
    # bytes shrink; GROMACS' two-pulse dims double the serialized message
    # count (6 msgs vs 3 phases), so the small-domain limit approaches 2x
    # — the paper's strong-scaling crossover between the two regimes.
    plan2 = HaloPlan.build(
        HaloSpec(axis_names=("z", "y", "x"), widths=(2, 2, 2),
                 dtype="float32", feature_elems=4, pulses=(2, 2, 2)),
        make_mesh((1, 1, 1), ("z", "y", "x")))
    for L in (32, 16, 8, 4, 2):
        for tag, p in (("p1", plan), ("p2", plan2)):
            lat = p.stats((L, L, L))["latency"]
            emit(f"fig5/crossover3d/{tag}/local{L}", 0.0,
                 f"serialized_us={lat['serialized_time_s'] * 1e6:.2f};"
                 f"fused_us={lat['fused_time_s'] * 1e6:.2f};"
                 f"modeled_speedup={lat['fused_speedup']:.3f}")


def fig6_overlap_decomposition(quick: bool = False):
    """Paper Fig. 6-8 analogue: local vs non-local (halo+NB) decomposition
    per DD dimensionality, serialized vs fused."""
    devs = [8] if quick else [2, 4, 8]
    for d in devs:
        rows = {}
        for mode in ("serialized", "fused"):
            try:
                r = run_sub("md_worker.py", mode, "2400", "20", devices=d)
            except RuntimeError as e:
                emit(f"fig6/{d}dev/{mode}", -1, f"error={str(e)[:60]}")
                continue
            rows[mode] = r
            emit(f"fig6/{d}dev/{mode}/force_pass",
                 r["ms_force_pass"] * 1e3,
                 f"step_ms={r['ms_per_step']:.2f};"
                 f"dd={'x'.join(map(str, r['dd']))}")
        if len(rows) == 2:
            emit(f"fig6/{d}dev/nonlocal_ratio", 0.0,
                 f"fused_over_serialized="
                 f"{rows['fused']['ms_force_pass'] / rows['serialized']['ms_force_pass']:.3f}")


def roofline_table():
    """§Roofline: one row per dry-run cell from results/dryrun/*.json."""
    files = sorted((RESULTS / "dryrun").glob("*__single.json"))
    for p in files:
        r = json.loads(p.read_text())
        if r.get("skipped"):
            emit(f"roofline/{r['arch']}/{r['shape']}", 0.0, r["skipped"])
            continue
        if not r.get("ok"):
            emit(f"roofline/{r['arch']}/{r['shape']}", -1.0, "FAIL")
            continue
        t = r["roofline"]
        emit(f"roofline/{r['arch']}/{r['shape']}",
             t["bound_s"] * 1e6,
             f"dominant={t['dominant']};compute_s={t['compute_s']:.3e};"
             f"memory_s={t['memory_s']:.3e};"
             f"collective_s={t['collective_s']:.3e};"
             f"frac={t.get('roofline_fraction', 0):.4f};"
             f"frac_analytic={t.get('roofline_fraction_analytic', 0):.4f}")


def lm_microbench(quick: bool = False):
    """Reduced-config LM step timings (train/prefill/decode) + ring
    attention fused-vs-serialized."""
    import dataclasses

    import jax
    import jax.numpy as jnp

    from benchmarks.common import time_fn
    from repro.configs import SHAPES, get_config
    from repro.launch.mesh import make_mesh
    from repro.launch.steps import make_ctx, make_train_step
    from repro.models import build_model
    from repro.optim import adamw
    from repro.parallel.sharding import ShardingCtx

    archs = ["qwen3-1.7b"] if quick else \
        ["qwen3-1.7b", "olmoe-1b-7b", "rwkv6-3b", "jamba-v0.1-52b"]
    mesh = make_mesh((1, 1), ("data", "model"))
    for arch in archs:
        cfg = get_config(arch).reduce()
        shape = dataclasses.replace(SHAPES["train_4k"], seq_len=64,
                                    global_batch=4)
        ctx = make_ctx(cfg, shape, mesh, fsdp=False)
        prog = make_train_step(cfg, shape, ctx, microbatches=1,
                               donate=False)
        model = prog.model
        params = model.init(jax.random.PRNGKey(0))
        opt = adamw.init_state(params)
        tokens = jnp.ones((4, 65), jnp.int32)
        batch = {"tokens": tokens}
        if cfg.prefix_tokens:
            batch["prefix_embeds"] = jnp.zeros((4, cfg.prefix_tokens,
                                                cfg.d_model))
        if cfg.is_encdec:
            batch["frames"] = jnp.zeros((4, cfg.encoder_seq, cfg.d_model))
        dt = time_fn(lambda: prog.step_fn(params, opt, batch), iters=5)
        emit(f"lm/{arch}/train_step", dt * 1e6,
             f"tok_per_s={4 * 64 / dt:.0f}")

        pre = jax.jit(model.prefill)
        dt = time_fn(lambda: pre(params, {"tokens": tokens[:, :64],
                                          **{k: v for k, v in batch.items()
                                             if k != "tokens"}}), iters=5)
        emit(f"lm/{arch}/prefill", dt * 1e6, f"tok_per_s={4 * 64 / dt:.0f}")

        cache = model.init_cache(4, 96)
        dec = jax.jit(model.decode_step)
        tok = jnp.ones((4, 1), jnp.int32)
        dt = time_fn(lambda: dec(params, tok, jnp.int32(64), cache),
                     iters=5)
        emit(f"lm/{arch}/decode_step", dt * 1e6, f"tok_per_s={4 / dt:.0f}")


def nb_bench(smoke: bool = False):
    """NB force-engine suite: dense vs sparse vs pallas -> BENCH_nb.json.

    Sweeps force backends across mesh shapes (device counts) and
    occupancy fractions (capacity safety factors: occupied fraction of a
    cell's K slots is ~1/safety), recording step wall-time, evaluated
    slot pairs, prune ratio, and pairs/s per cell; the sparse backend is
    additionally run with the rolling dual pair list (``--nstprune 5``)
    so the per-pair-bound tier ladders AND the rolling-prune schedule
    each get a column.  The checked-in ``results/BENCH_nb.json`` is the
    perf baseline future PRs must beat; the summary asserts two claims —
    >= 2x fewer evaluated slot pairs than dense at the default 2.2
    safety, and the tier ladders never exceeding the old global-k_exec
    single-rectangle accounting (``per_pair_bound_gain >= 1``).
    ``smoke`` (CI) runs the single 1-device cell set in interpret mode.

    Both modes (over)write ``results/BENCH_nb.json`` with a ``smoke``
    flag in the record: the checked-in baseline is the ``--full`` sweep —
    don't commit a smoke run over it (``make_tables.py nb`` prints the
    mode so a degraded file is visible at a glance).
    """
    cfgs = [(1, 600, 8)] if smoke else [(1, 600, 20), (8, 1800, 12)]
    safeties = [2.2] if smoke else [2.2, 3.3]
    # (force_backend, nstprune) variants; key names the summary column
    variants = (("dense", 0), ("sparse", 0), ("sparse", 5), ("pallas", 0))
    cells = []
    for devices, n_atoms, steps in cfgs:
        for safety in safeties:
            for fb, nstprune in variants:
                key = fb + (f"-np{nstprune}" if nstprune else "")
                tag = f"nb/{devices}dev/{n_atoms}atoms/s{safety:g}/{key}"
                extra = ["--nstprune", str(nstprune)] if nstprune else []
                try:
                    r = run_sub("md_worker.py", "fused", str(n_atoms),
                                str(steps), "--force-backend", fb,
                                "--safety", str(safety), *extra,
                                devices=devices)
                except RuntimeError as e:
                    emit(tag, -1, f"error={str(e)[:60]}")
                    continue
                r["variant"] = key
                cells.append(r)
                emit(tag, r["ms_per_step"] * 1e3,
                     f"slot_pairs={r['evaluated_slot_pairs_per_step']};"
                     f"prune_ratio={r['prune_ratio']:.2f};"
                     f"pairs_per_s={r['pairs_per_s']:.3e}")

    summary = []
    for devices, n_atoms, _ in cfgs:
        for safety in safeties:
            sub = {c["variant"]: c for c in cells
                   if c["devices"] == devices and c["n_atoms"] == n_atoms
                   and c["capacity_safety"] == safety}
            if "dense" not in sub or "sparse" not in sub:
                continue
            sparse = sub["sparse"]
            row = {
                "devices": devices, "n_atoms": n_atoms, "safety": safety,
                "slot_pair_reduction":
                    sub["dense"]["evaluated_slot_pairs_per_step"]
                    / max(sparse["evaluated_slot_pairs_per_step"], 1),
                "sparse_step_speedup":
                    sub["dense"]["ms_per_step"]
                    / max(sparse["ms_per_step"], 1e-9),
                # per-pair slot bounds vs the old global-k_exec rectangle
                "global_kexec_slot_pairs":
                    sparse.get("global_kexec_slot_pairs_per_step"),
                "per_pair_bound_gain":
                    sparse.get("per_pair_bound_gain"),
            }
            if "sparse-np5" in sub:
                roll = sub["sparse-np5"]
                row["rolling_prune_slot_pairs"] = \
                    roll["evaluated_slot_pairs_per_step"]
                row["rolling_prune_overflow_blocks"] = \
                    roll.get("inner_overflow_blocks")
            summary.append(row)
            emit(f"nb/{devices}dev/{n_atoms}atoms/s{safety:g}/reduction",
                 0.0, f"slot_pairs={row['slot_pair_reduction']:.2f}x;"
                 f"step_speedup={row['sparse_step_speedup']:.2f}x;"
                 f"bound_gain={row['per_pair_bound_gain']}")
    default = [r for r in summary if r["safety"] == 2.2]
    ok = bool(default) and all(r["slot_pair_reduction"] >= 2.0
                               for r in default)
    ok_bounds = bool(default) and all(
        (r.get("per_pair_bound_gain") or 0) >= 1.0 for r in default)
    out = {
        "suite": "nb", "smoke": smoke, "cells": cells, "summary": summary,
        "target_2x_at_default_safety": ok,
        "per_pair_bounds_beat_global_kexec": ok_bounds,
    }
    path = RESULTS / "BENCH_nb.json"
    path.write_text(json.dumps(out, indent=1))
    emit("nb/target_2x_at_default_safety", 0.0, str(ok))
    emit("nb/per_pair_bounds_beat_global_kexec", 0.0, str(ok_bounds))


def pipeline_bench(smoke: bool = False, out: str = None):
    """Perf-trajectory suite: backend x pipeline mode x depth cells ->
    schema-versioned ``results/BENCH_pipeline.json``.

    Each cell records step latency, the exposed-phase and overlapped-byte
    columns of the overlap model, and the dual-list prune ratio — the
    quantities the checked-in baseline gates (``python -m repro.obs gate``
    in the CI ``perf-smoke`` job; tolerances live in the file's ``gate``
    section, see :mod:`repro.obs.gate`).  One extra traced run writes a
    metrics JSONL + Perfetto ``trace.json`` sample
    (``results/obs/pipeline_smoke.jsonl`` / ``results/trace_pipeline.json``).

    The committed baseline is the ``--smoke`` cell set (CI re-runs it
    verbatim); ``--full`` adds the 8-device sweep for local trajectory
    work without touching the gated file unless ``--out`` points at it.
    """
    from repro.obs import SCHEMA_VERSION, DEFAULT_GATE, export_trace

    # (backend, pipeline, depth, nstprune)
    grid = [("serialized", "off", 2, 0),
            ("fused", "double_buffer", 2, 0),
            ("pallas", "double_buffer", 3, 0),
            ("signal", "double_buffer", 2, 4),
            ("signal", "double_buffer", 3, 4),
            ("signal", "double_buffer", 4, 4)]
    cfgs = [(1, 600, 8)] if smoke else [(1, 600, 12), (8, 1800, 12)]
    cells = []
    for devices, n_atoms, steps in cfgs:
        for backend, mode, depth, nstprune in grid:
            tag = (f"pipeline/{devices}dev/{backend}/{mode}/d{depth}"
                   + (f"/np{nstprune}" if nstprune else ""))
            extra = ["--nstprune", str(nstprune)] if nstprune else []
            try:
                r = run_sub("md_worker.py", backend, str(n_atoms),
                            str(steps), "--pipeline", mode,
                            "--pipeline-depth", str(depth),
                            "--force-backend", "sparse", *extra,
                            devices=devices)
            except RuntimeError as e:
                emit(tag, -1, f"error={str(e)[:60]}")
                continue
            cells.append(r)
            emit(tag, r["ms_per_step"] * 1e3,
                 f"exposed_phases={r['exposed_phases']:.3g};"
                 f"overlapped_bytes={r['overlapped_bytes']};"
                 f"prune_ratio={r['prune_ratio']:.2f}")

    # deeper windows must expose monotonically fewer phases per step
    sweep = sorted((c["pipeline_depth"], c["exposed_phases"])
                   for c in cells
                   if c["mode"] == "signal" and c["devices"] == cfgs[0][0])
    exposed_monotone = all(a[1] >= b[1]
                           for a, b in zip(sweep, sweep[1:]))
    emit("pipeline/exposed_phases_monotone_in_depth", 0.0,
         str(exposed_monotone))

    # traced sample: metrics JSONL -> Perfetto trace of the measured
    # host spans and counters (CI uploads both as artifacts)
    obs_jsonl = RESULTS / "obs" / "pipeline_smoke.jsonl"
    trace_path = RESULTS / "trace_pipeline.json"
    try:
        run_sub("md_worker.py", "signal", str(cfgs[0][1]), "6",
                "--pipeline", "double_buffer", "--pipeline-depth", "3",
                "--force-backend", "sparse", "--nstprune", "4",
                "--trace", "--obs-jsonl", str(obs_jsonl), devices=1)
        trace = export_trace(obs_jsonl, trace_path)
        emit("pipeline/trace_events", 0.0, str(len(trace["traceEvents"])))
    except RuntimeError as e:
        emit("pipeline/trace", -1, f"error={str(e)[:60]}")

    doc = {
        "suite": "pipeline",
        "schema_version": SCHEMA_VERSION,
        "smoke": smoke,
        "cells": cells,
        "exposed_phases_monotone_in_depth": exposed_monotone,
        "gate": DEFAULT_GATE,
    }
    path = Path(out) if out else RESULTS / "BENCH_pipeline.json"
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(json.dumps(doc, indent=1))
    emit("pipeline/cells", 0.0, str(len(cells)))


def halo_wire_bench(smoke: bool = False, out: str = None):
    """Compressed-wire suite: wire_dtype x backend cells ->
    schema-versioned ``results/BENCH_halo_wire.json``.

    Measured cells run ``md_worker.py --wire-dtype`` and record the
    direction-aware byte accounting next to step latency (the MD system
    is float32, so the named rev format compresses the force return
    while coordinates ride the f32 floor: bf16 -> 4/3 bytes overall,
    int8_ef -> ~1.6x).  A plan-level ``predicted`` table quantifies the
    f64-payload case (coordinates drop to the f32 floor too: bf16 ->
    8/3 ~ 2.7x) without paying for an x64 MD run.  The checked-in
    baseline gates the byte columns exactly and latency at the usual
    noise factor (``python -m repro.obs gate`` in CI perf-smoke).
    """
    from repro.obs import SCHEMA_VERSION, DEFAULT_GATE
    from repro.launch.mesh import make_mesh

    # (wire_dtype, backend, pipeline, depth) — None = dense baseline;
    # the pipelined signal cells exercise the wire-dtyped slot ring
    grid = [(None, "fused", "off", 2),
            ("float32", "fused", "off", 2),
            ("bfloat16", "fused", "off", 2),
            ("float16", "fused", "off", 2),
            ("int8_ef", "fused", "off", 2),
            ("bfloat16", "signal", "double_buffer", 2),
            ("int8_ef", "signal", "double_buffer", 3)]
    cfgs = [(1, 600, 8)] if smoke else [(1, 600, 12), (8, 1800, 12)]
    cells = []
    for devices, n_atoms, steps in cfgs:
        for wd, backend, mode, depth in grid:
            tag = (f"halo_wire/{devices}dev/{backend}/{mode}/"
                   f"{wd or 'dense'}")
            extra = ["--wire-dtype", wd] if wd else []
            try:
                r = run_sub("md_worker.py", backend, str(n_atoms),
                            str(steps), "--pipeline", mode,
                            "--pipeline-depth", str(depth),
                            "--force-backend", "sparse", *extra,
                            devices=devices)
            except RuntimeError as e:
                emit(tag, -1, f"error={str(e)[:60]}")
                continue
            cells.append(r)
            emit(tag, r["ms_per_step"] * 1e3,
                 f"wire_bytes={r['wire_bytes']};"
                 f"wire_reduction={r['wire_reduction']:.3f}")

    # byte accounting must order by rev itemsize on the f32 payload:
    # int8_ef > bf16 = f16 > f32 = dense = 1.0
    red = {c["wire_dtype"]: c["wire_reduction"] for c in cells
           if c["devices"] == cfgs[0][0]}
    monotone = (red.get("int8_ef", 0) > red.get("bfloat16", 0)
                >= red.get("float16", 0) > 1.0
                and abs(red.get("float32", 1.0) - 1.0) < 1e-9
                and abs(red.get(None, 1.0) - 1.0) < 1e-9)
    emit("halo_wire/reduction_monotone_in_itemsize", 0.0, str(monotone))

    # plan-level predictions for the f64-payload regime (the paper-scale
    # claim: bf16 halves-and-then-some the exchanged bytes because the
    # coordinate direction drops to the f32 floor as well)
    mesh = make_mesh((1, 1, 1), ("z", "y", "x"))
    predicted = []
    for dtype in ("float32", "float64"):
        for wd in ("float32", "bfloat16", "float16", "int8_ef"):
            plan = HaloPlan.build(
                HaloSpec(axis_names=("z", "y", "x"), widths=(1, 1, 1),
                         backend="fused", dtype=dtype, feature_elems=4,
                         wire_dtype=wd), mesh)
            st = plan.stats((8, 8, 8))
            predicted.append({
                "dtype": dtype, "wire_dtype": wd,
                "wire_itemsize_fwd": st["wire_itemsize_fwd"],
                "wire_itemsize_rev": st["wire_itemsize_rev"],
                "wire_bytes": st["wire_bytes"],
                "wire_reduction": round(st["wire_reduction"], 4),
                "wire_speedup_fused": round(
                    st["latency_wire"]["wire_speedup_fused"], 4),
            })
    pred64 = {p["wire_dtype"]: p["wire_reduction"] for p in predicted
              if p["dtype"] == "float64"}
    bf16_halves_f64 = pred64.get("bfloat16", 0) > 2.0
    emit("halo_wire/bf16_f64_reduction", 0.0,
         f"{pred64.get('bfloat16', 0):.2f}x (>2x={bf16_halves_f64})")

    doc = {
        "suite": "halo_wire",
        "schema_version": SCHEMA_VERSION,
        "smoke": smoke,
        "cells": cells,
        "predicted": predicted,
        "reduction_monotone_in_itemsize": monotone,
        "bf16_f64_reduction_over_2x": bf16_halves_f64,
        "gate": {
            **DEFAULT_GATE,
            # cells differ by wire format at a fixed backend: the wire
            # column is part of the cell identity and the byte columns
            # it determines are exact invariants of the code
            "key_fields": ["mode", "wire_dtype", "pipeline",
                           "pipeline_depth", "devices", "n_atoms"],
            "exact": DEFAULT_GATE["exact"] + [
                "wire_itemsize_fwd", "wire_itemsize_rev", "wire_bytes"],
            "rel_tol": {**DEFAULT_GATE["rel_tol"],
                        "wire_reduction": 1e-6},
        },
    }
    path = Path(out) if out else RESULTS / "BENCH_halo_wire.json"
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(json.dumps(doc, indent=1))
    emit("halo_wire/cells", 0.0, str(len(cells)))


def resilience_bench(smoke: bool = False, out: str = None):
    """Fault-recovery suite: fault site x recovery mode cells ->
    schema-versioned ``results/BENCH_resilience.json``.

    Every :data:`~repro.resilience.faults.ALL_FAULT_SITES` entry is
    provoked through :class:`~repro.resilience.runner.ResilientMDRunner`
    on a single-device mesh and the recovery contract is recorded per
    cell: detection latency (steps from injection to health trip),
    rollback cost (re-simulated steps), the action the policy landed on,
    and whether the repaired trajectory is bitwise equal to the
    fault-free reference.  Cells are keyed on ``(site, mode)`` — the
    ``gate`` section carries its own ``key_fields`` so ``python -m
    repro.obs gate`` indexes them correctly — and the contract columns
    are gated *exact*: a latency or rollback-cost drift is a semantic
    change to the recovery path, not noise.  ``degraded_step_ratio``
    (degraded-mode step time over healthy step time) rides the
    timing-factor envelope like every other wall-clock key.
    """
    import tempfile
    import time as _time

    from repro.core.md import MDEngine, make_grappa_like
    from repro.launch.mesh import make_mesh
    from repro.obs import SCHEMA_VERSION
    from repro.resilience import (FaultPlan, FaultSpec, ProcessKilled,
                                  RecoveryPolicy, ResilientMDRunner)

    n_steps, nstlist = 18, 6
    mesh = make_mesh((1, 1, 1), ("z", "y", "x"))
    system = make_grappa_like(300, seed=11, nstlist=nstlist)
    tmp = Path(tempfile.mkdtemp(prefix="bench_resilience_"))

    ref_eng = MDEngine(system, mesh)
    (cf_r, ci_r), _, _ = ref_eng.simulate(n_steps)
    ref_cf, ref_ci = np.asarray(cf_r), np.asarray(ci_r)

    eng = MDEngine(system, mesh, inject=True, health=True)

    def timed_run(runner):
        t0 = _time.perf_counter()
        res = runner.run(n_steps, resume=False)
        return res, (_time.perf_counter() - t0) * 1e3 / n_steps

    # healthy (disarmed) run: the step-time denominator + bitwise anchor
    r0 = ResilientMDRunner(eng, tmp / "ck_healthy")
    ((cf0, ci0), _, rep0), healthy_ms = timed_run(r0)
    bitwise0 = bool(np.array_equal(np.asarray(cf0), ref_cf)
                    and np.array_equal(np.asarray(ci0), ref_ci))

    cells = [{"site": "none", "mode": "healthy",
              "detection_latency_steps": 0, "wasted_steps": 0,
              "n_recoveries": 0, "final_action": "none",
              "bitwise": bitwise0, "resharded": False,
              "ms_per_step": healthy_ms, "degraded_step_ratio": 1.0}]

    def add_cell(site, mode, report, ms, bitwise, action, latency=0,
                 **extra):
        cell = {"site": site, "mode": mode,
                "detection_latency_steps": int(latency),
                "wasted_steps": int(report["wasted_steps"]),
                "n_recoveries": len(report["recoveries"]),
                "final_action": action, "bitwise": bool(bitwise),
                "resharded": bool(report["resharded"]),
                "ms_per_step": ms,
                "degraded_step_ratio": ms / max(healthy_ms, 1e-9), **extra}
        cells.append(cell)
        emit(f"resilience/{site}/{mode}", ms * 1e3,
             f"latency={cell['detection_latency_steps']};"
             f"wasted={cell['wasted_steps']};action={action};"
             f"bitwise={cell['bitwise']}")

    def bitwise_vs_ref(cf, ci):
        return bool(np.array_equal(np.asarray(cf), ref_cf)
                    and np.array_equal(np.asarray(ci), ref_ci))

    # one-shot scan faults -> rollback, bitwise repair
    for site, step in (("halo_corrupt", 8), ("force_nan", 13),
                       ("signal_drop", 2)):
        r = ResilientMDRunner(eng, tmp / f"ck_{site}",
                              plan=FaultPlan([FaultSpec(site, step)]))
        ((cf, ci), _, rep), ms = timed_run(r)
        rec = rep["recoveries"][0]
        add_cell(site, "recover", rep, ms, bitwise_vs_ref(cf, ci),
                 rec["action"], rec["detection_latency_steps"])

    # sticky faults -> degrade ladder (serialized halo / dense forces)
    for site, rung in (("signal_drop", "serialized_halo"),
                       ("force_nan", "dense_forces")):
        e = MDEngine(system, mesh, inject=True, health=True)
        r = ResilientMDRunner(
            e, tmp / f"ck_{site}_sticky",
            plan=FaultPlan([FaultSpec(site, 2, sticky=True)]),
            policy=RecoveryPolicy(max_retries=1, backoff_base_s=0.0))
        ((cf, ci), _, rep), ms = timed_run(r)
        add_cell(site, "degrade", rep, ms, bitwise_vs_ref(cf, ci),
                 "degrade", rep["recoveries"][0]["detection_latency_steps"],
                 rung=rep["recoveries"][-1]["detail"])

    # forced inner-ladder overflow -> the engine's own outer fallback
    import warnings as _warnings
    with _warnings.catch_warnings():
        _warnings.simplefilter("ignore")
        e_ovf = MDEngine(system, mesh, inject=True, health=True,
                         force_backend="sparse", nstprune=3)
    r = ResilientMDRunner(e_ovf, tmp / "ck_ovf",
                          plan=FaultPlan([FaultSpec("inner_overflow", 6)]))
    with _warnings.catch_warnings():
        _warnings.simplefilter("ignore")
        ((cf, ci), _, rep), ms = timed_run(r)
    falls = [x for x in rep["recoveries"]
             if x["action"] == "engine_fallback"]
    add_cell("inner_overflow", "recover", rep, ms,
             bool(np.isfinite(np.asarray(cf)).all()),
             "engine_fallback", 0, fallback=falls[0]["detail"])

    # process kill -> checkpoint auto-resume
    r = ResilientMDRunner(eng, tmp / "ck_kill",
                          plan=FaultPlan([FaultSpec("proc_kill", 12)]))
    try:
        r.run(n_steps, resume=False)
    except ProcessKilled:
        pass
    r2 = ResilientMDRunner(eng, tmp / "ck_kill")
    t0 = _time.perf_counter()
    (cf, ci), _, rep = r2.run(n_steps)
    ms = (_time.perf_counter() - t0) * 1e3 / max(n_steps - 12, 1)
    add_cell("proc_kill", "recover", rep, ms, bitwise_vs_ref(cf, ci),
             "resume", 0, resumed_from=rep["resumed_from"])

    # device loss -> reshard onto the spare mesh
    r = ResilientMDRunner(eng, tmp / "ck_loss",
                          plan=FaultPlan([FaultSpec("device_loss", 12)]),
                          spare_mesh=make_mesh((1, 1, 1), ("z", "y", "x")))
    ((cf, ci), _, rep), ms = timed_run(r)
    add_cell("device_loss", "recover", rep, ms, False, "reshard", 0)

    doc = {
        "suite": "resilience",
        "schema_version": SCHEMA_VERSION,
        "smoke": smoke,
        "n_steps": n_steps,
        "cells": cells,
        "gate": {
            # resilience cells are keyed on fault site x recovery mode,
            # not the pipeline suite's (mode, depth, ...) identity
            "key_fields": ["site", "mode"],
            "exact": ["detection_latency_steps", "wasted_steps",
                      "n_recoveries", "final_action", "bitwise",
                      "resharded"],
            "rel_tol": {},
            "timing_factor": 10.0,
            "timing_keys": ["ms_per_step", "degraded_step_ratio"],
        },
    }
    path = Path(out) if out else RESULTS / "BENCH_resilience.json"
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(json.dumps(doc, indent=1))
    emit("resilience/cells", 0.0, str(len(cells)))


def serve_bench(smoke: bool = False, out: str = None):
    """SimServer continuous-batching suite -> ``BENCH_serve.json``.

    Two cells at 16 replicas on the CPU harness: ``solo`` runs
    one-engine-per-replica (16 engine builds, 16 traced lowerings — the
    no-server baseline), ``simserver`` serves the same 16 replicas
    through one bucketed vmapped program (1 compile, continuous
    admission).  Both walls include compilation; that *is* the
    comparison — bucketing exists to amortize traces across replicas.
    The ``summary`` cell records the headline replicas/sec speedup and
    the ``meets_2x`` acceptance bit (exact-gated: the observed margin is
    ~10x, so a flip means the batching broke, not noise).  p50/p99
    per-step latency ride the timing-factor envelope.
    """
    import time as _time

    import jax

    from repro.core.md import MDEngine, make_grappa_like
    from repro.launch.mesh import make_mesh
    from repro.obs import SCHEMA_VERSION
    from repro.serve import SimServer

    n_replicas, n_atoms, n_steps, nst = 16, 150, 20, 10
    mesh = make_mesh((1, 1, 1), ("z", "y", "x"))

    def replica(i):
        return make_grappa_like(n_atoms, seed=i, nstlist=nst,
                                box_atoms=192)

    cells = []

    def add_cell(mode, wall, step_walls, compiles, buckets, extra=None):
        sw = np.asarray(step_walls, np.float64)
        cell = {"mode": mode, "n_replicas": n_replicas,
                "n_atoms": n_atoms, "atom_bucket": 192,
                "n_steps": n_steps,
                "total_steps": n_replicas * n_steps,
                "compiles": int(compiles), "buckets": int(buckets),
                "wall_s": wall,
                "replicas_per_s": n_replicas / max(wall, 1e-9),
                "ms_per_replica": wall * 1e3 / n_replicas,
                "ms_per_step_p50": float(np.percentile(sw, 50) * 1e3),
                "ms_per_step_p99": float(np.percentile(sw, 99) * 1e3),
                **(extra or {})}
        cells.append(cell)
        emit(f"serve/{mode}", wall * 1e6 / n_replicas,
             f"replicas_per_s={cell['replicas_per_s']:.3f};"
             f"compiles={compiles};p50={cell['ms_per_step_p50']:.2f}ms")
        return cell

    # one-engine-per-replica baseline: every replica pays its own build
    # + trace; per-step latency sampled per replica
    t0 = _time.perf_counter()
    solo_steps = []
    for i in range(n_replicas):
        eng = MDEngine(replica(i), mesh, layout_atoms=192)
        t1 = _time.perf_counter()
        (_cf, _ci), _, _ = eng.simulate(n_steps, collect=False)
        jax.block_until_ready(_ci)
        solo_steps.append((_time.perf_counter() - t1) / n_steps)
    solo_wall = _time.perf_counter() - t0
    solo = add_cell("solo", solo_wall, solo_steps,
                    compiles=n_replicas, buckets=0)

    # SimServer: one bucketed vmapped program, continuous admission
    t0 = _time.perf_counter()
    srv = SimServer(mesh, block_steps=nst)
    handles = [srv.submit(replica(i), n_steps)
               for i in range(n_replicas)]
    srv.drain()
    srv_wall = _time.perf_counter() - t0
    st = srv.stats()
    assert all(h.status == "done" for h in handles)
    served = add_cell("simserver", srv_wall, srv._step_walls,
                      st["compiles"], len(st["shapes_touched"]))

    speedup = served["replicas_per_s"] / max(solo["replicas_per_s"], 1e-9)
    cells.append({"mode": "summary", "n_replicas": n_replicas,
                  "speedup_replicas_per_s": speedup,
                  "meets_2x": bool(speedup >= 2.0)})
    emit("serve/speedup", 0.0, f"{speedup:.2f}x;meets_2x={speedup >= 2.0}")

    doc = {
        "suite": "serve",
        "schema_version": SCHEMA_VERSION,
        "smoke": smoke,
        "cells": cells,
        "gate": {
            # serve cells are keyed on serving mode at a replica count
            "key_fields": ["mode", "n_replicas"],
            "exact": ["n_atoms", "atom_bucket", "n_steps", "total_steps",
                      "compiles", "buckets", "meets_2x"],
            "rel_tol": {},
            "timing_factor": 10.0,
            "timing_keys": ["ms_per_replica", "ms_per_step_p50",
                            "ms_per_step_p99"],
        },
    }
    path = Path(out) if out else RESULTS / "BENCH_serve.json"
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(json.dumps(doc, indent=1))


ALL = {
    "fig3": fig3_intranode_strong_scaling,
    "fig5": fig5_multinode_critical_path,
    "fig6": fig6_overlap_decomposition,
    "roofline": roofline_table,
    "lm": lm_microbench,
    "nb": nb_bench,
    "pipeline": pipeline_bench,
    "halo_wire": halo_wire_bench,
    "resilience": resilience_bench,
    "serve": serve_bench,
}
