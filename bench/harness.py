"""One run of one cell: set-up, a timed window, per-layer readings and
the correctness comparison against the configuration's plain reference.

    python3 bench/run.py --workload lammps-lj-32k.nve.dd4 --seed 7 --seconds 10 --trace 0

The window drives ``MDEngine.simulate`` -- the users' entry -- over whole
``nstlist`` blocks and chains the state from call to call.  Set-up builds
the system from ``--seed``, compiles (or loads from the persistent cache)
every program the window runs and runs the traffic's warm-up blocks, so
nothing compiles inside the window.  With ``--trace 1`` the window runs
under the JAX profiler and the run reports the cell's per-layer metrics
instead of its end-to-end ones.  The last line of standard output is the
result as one JSON object; the numbers compared for ``correct`` are also
printed, each beside its limit, as the last lines of standard error.
"""
from __future__ import annotations

import argparse
import gc
import json
import shutil
import sys
import tempfile
import time
from types import SimpleNamespace

import numpy as np

import spec
import trace_reduce

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class NoAccelerator(SystemExit):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def require_accelerator(chips: int):
    """The devices of the cell; never falls back to the CPU."""
    import jax
    devs = jax.devices()
    if devs[0].platform == "cpu":
        raise NoAccelerator("bench: JAX found no accelerator (only CPU "
                            "devices); the benchmark never runs on the CPU")
    if len(devs) < chips:
        raise NoAccelerator(f"bench: the cell needs {chips} chips, JAX "
                            f"found {len(devs)}")
    return devs


def enable_compile_cache() -> str:
    """The program's persistent compilation cache (``<checkout>/.jax_cache``
    unless ``JAX_COMPILATION_CACHE_DIR`` names one), holding every program
    of the window, however quick its compile."""
    import jax
    from repro.launch.compile_cache import enable_compile_cache as enable
    path = enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


class CompileCounter:
    """Counts the executables JAX compiles or loads, from its monitoring
    events; a compile inside the window is a stall that users would see."""

    def __init__(self):
        from jax._src import monitoring
        self.n = 0
        monitoring.register_event_duration_secs_listener(self._on_event)

    def close(self):
        from jax._src import monitoring
        monitoring.unregister_event_duration_listener(self._on_event)

    def _on_event(self, event, duration, **kw):
        if event == COMPILE_EVENT:
            self.n += 1


# --------------------------------------------------------------------------
# set-up
# --------------------------------------------------------------------------

def build(config: dict, chips: int, seed: int):
    """The system from ``seed`` (by the benchmark's own generator), its
    force field from the configuration's ``force_field`` kind, and the
    engine on ``make_md_mesh(chips)``."""
    from repro.core.md import MDEngine
    from repro.core.md.system import MDParams, MDSystem
    from repro.launch.mesh import make_md_mesh
    ref = spec.reference(config)
    arrays = ref.make_system(config, seed)
    dt = np.dtype(config["dtype"])
    params = MDParams(ff=spec.force_field(config), dt=float(config["dt"]),
                      mass=float(config["mass"]),
                      nstlist=int(config["nstlist"]),
                      temperature=float(config["temperature"]))
    system = MDSystem(box=arrays["box"], pos=arrays["pos"].astype(dt),
                      vel=arrays["vel"].astype(dt),
                      charge=arrays["charge"].astype(dt),
                      typ=arrays["typ"], params=params)
    engine = MDEngine(system, make_md_mesh(chips), **config["engine"])
    return engine, arrays


def call_steps(config: dict, traffic: dict) -> int:
    return int(config["nstlist"]) * int(traffic["blocks_per_call"])


# --------------------------------------------------------------------------
# the window
# --------------------------------------------------------------------------

def run_window(engine, state, n_steps: int, seconds: float, min_calls: int):
    """``simulate`` calls chained until ``seconds`` have passed, and at
    least ``min_calls`` of them (the calls that are compared); returns
    ``(calls, wall_s)``.  Each call is ``(state_in, state_out, metrics,
    diags)``; the device arrays stay referenced (not copied) so that the
    comparison can read the sampled calls after the window.  Energies
    are collected every call, as a user records them."""
    import jax
    calls = []
    t0 = time.perf_counter()
    while True:
        with jax.profiler.TraceAnnotation("bench.simulate"):
            out, metrics, diags = engine.simulate(n_steps, state=state,
                                                  collect=True)
        calls.append((state, out, metrics, diags))
        state = out
        if (time.perf_counter() - t0 >= seconds
                and len(calls) >= min_calls):
            break
    jax.block_until_ready(state)
    return calls, time.perf_counter() - t0


def peak_bytes(devices) -> int:
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks) if peaks else 0


# --------------------------------------------------------------------------
# correctness
# --------------------------------------------------------------------------

def atoms_by_id(state, n_atoms: int) -> dict:
    """Host per-atom arrays in global-id order from a cell state."""
    cell_f = np.asarray(state[0])
    cell_i = np.asarray(state[1])
    ids = cell_i[..., 0].reshape(-1)
    f = cell_f.reshape(ids.shape[0], -1).astype(np.float64)
    live = ids >= 0
    out = {"n_live": int(live.sum()),
           "n_unique": int(np.unique(ids[live]).shape[0]),
           "in_range": bool(np.all(ids[live] < n_atoms)),
           "pos": np.zeros((n_atoms, 3)), "vel": np.zeros((n_atoms, 3))}
    keep = live & (ids < n_atoms)
    out["pos"][ids[keep]] = f[keep, 0:3]
    out["vel"][ids[keep]] = f[keep, 4:7]
    return out


def program_block(call, n_atoms: int) -> dict:
    """Host copy of what one window call produced: the state at its end
    and its per-step potential energies."""
    return dict(atoms_by_id(call[1], n_atoms),
                pe=np.asarray(call[2]["pe"], np.float64))


def reference_chain(ref, config, arrays, start: dict, n_steps: int,
                    n_blocks: int, pair_dtype=np.float64) -> list:
    """The reference integrated from ``start`` over ``n_blocks`` blocks of
    ``n_steps`` steps, one after another: its state after each block and
    its per-step potential energies (charges and types from the seed)."""
    pos, vel = start["pos"], start["vel"]
    out = []
    for _ in range(n_blocks):
        pos, vel, pe = ref.verlet(pos, vel, arrays["charge"], arrays["typ"],
                                  arrays["box"], config, n_steps, pair_dtype)
        out.append({"pos": pos, "vel": vel, "pe": pe})
    return out


def compare_chain(box, got: list, want: list) -> dict:
    """The numbers compared, each the largest over the chained blocks:

    * ``pos_err``: largest minimum-image position difference, in the
      configuration's length unit;
    * ``vel_err``: largest velocity difference over the largest
      reference velocity component;
    * ``pe_err``: largest per-step potential-energy difference per atom.
    """
    nums = {"pos_err": 0.0, "vel_err": 0.0, "pe_err": 0.0}
    for g, w in zip(got, want, strict=True):
        d = g["pos"] - w["pos"]
        d -= box * np.round(d / box)
        n = w["pos"].shape[0]
        block = {
            "pos_err": float(np.abs(d).max()),
            "vel_err": float(np.abs(g["vel"] - w["vel"]).max()
                             / np.abs(w["vel"]).max()),
            "pe_err": float(np.abs(g["pe"] - w["pe"]).max() / n),
        }
        # a state or energy that is not finite is as far off as can be
        nums = {k: max(nums[k], block[k] if np.isfinite(block[k])
                       else float("inf")) for k in nums}
    return nums


LOSSES = ("migration_dropped", "migration_lost", "bin_overflow")


def dropped_per_call(calls) -> list:
    """Atoms that each call's rebins and migrations dropped or could not
    seat (the engine's own counters; zero in a sound run)."""
    return [sum(int(np.asarray(dg[k])) for dg in diags for k in LOSSES)
            for _s, _o, _m, diags in calls]


def integrity(dropped: list, final: dict, n_atoms: int) -> dict:
    """Exact counts: atoms missing from (or duplicated in) the final
    state, and atoms dropped at a rebin or migration in the window."""
    missing = n_atoms - (final["n_unique"] if final["in_range"] else 0)
    missing += final["n_live"] - final["n_unique"]
    return {"atoms_missing": int(missing), "atoms_dropped": int(sum(dropped))}


def compared_calls(seed: int, n_calls: int, n_checked: int) -> range:
    """The window calls compared: ``n_checked`` consecutive ones (all of
    them in a shorter window) from a first one drawn from the seed."""
    n = min(int(n_checked), n_calls)
    word = np.random.SeedSequence([int(seed), 1]).generate_state(1)[0]
    first = int(word % (n_calls - n + 1))
    return range(first, first + n)


# --------------------------------------------------------------------------
# one run
# --------------------------------------------------------------------------

def run_cell(cell: dict, seed: int, seconds: float, trace: bool,
             devices, t_start: float) -> dict:
    """Set-up, window, readings and comparison of one run; the result."""
    import jax
    config, traffic = cell["config"], cell["traffic"]
    chips = int(cell["cell"]["chips"])
    ref = spec.reference(config)
    counter = CompileCounter()
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    try:
        with jax.profiler.TraceAnnotation("bench.setup"):
            engine, arrays = build(config, chips, seed)
            n_steps = call_steps(config, traffic)
            state = None
            for _ in range(int(traffic["warmup_blocks"])):
                state, _m, _d = engine.simulate(n_steps, state=state,
                                                collect=True)
            jax.block_until_ready(state)
        setup_s = time.perf_counter() - t_start
        n0 = counter.n
        if trace:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
        with jax.profiler.TraceAnnotation("bench.window"):
            calls, wall = run_window(engine, state, n_steps, seconds,
                                     int(traffic["checked_blocks"]))
        if trace:
            jax.profiler.stop_trace()
        compiles = counter.n - n0
        mesh_devices = list(engine.mesh.devices.flat)
        steps = n_steps * len(calls)
        result_device = {
            "platform": devices[0].platform,
            "kind": devices[0].device_kind,
            "count": len(devices),
            "memory_peak_bytes": peak_bytes(mesh_devices),
        }
        # host copies of what the comparison and the readers need, then
        # the program's device state goes before the reference runs
        n_atoms = arrays["pos"].shape[0]
        ks = compared_calls(seed, len(calls), traffic["checked_blocks"])
        start = atoms_by_id(calls[ks[0]][0], n_atoms)
        got = [program_block(calls[k], n_atoms) for k in ks]
        final = atoms_by_id(calls[-1][1], n_atoms)
        dropped = dropped_per_call(calls)
        checks = integrity(dropped, final, n_atoms)
        pair_stats = engine.pair_stats()
        n_domains = len(mesh_devices)
        del calls, state, engine
        gc.collect()

        out = {"correct": None, "attempted": len(dropped),
               "failed": sum(1 for d in dropped if d)}
        if trace:
            reduced = trace_reduce.load(trace_dir)
            # the blocks that the trace holds (all of the window's, unless
            # the profiler's buffers ran out), one nstlist block each
            blocks = reduced.blocks or steps // int(config["nstlist"])
            # everything a per-layer reader (metrics/<name>.py) may read
            ctx = SimpleNamespace(
                reduced=reduced, steps=blocks * int(config["nstlist"]),
                blocks=blocks, compiles=compiles,
                pair_stats=pair_stats, n_domains=n_domains,
                final_pos=final["pos"], box=arrays["box"], config=config,
                ref=ref)
            metrics = {}
            for m in cell["per_layer"]:
                value = spec.metric_reader(m["name"])(ctx)
                if value is not None:
                    metrics[m["name"]] = {"value": value, "unit": m["unit"]}
            out["metrics"] = metrics
            result_device["busy_s"] = reduced.busy_s()
            result_device["window_s"] = reduced.window_s
            out["breakdown"] = reduced.breakdown()
        else:
            values = {"us_per_step": 1e6 * wall / steps, "setup_s": setup_s}
            out["metrics"] = {m["name"]: {"value": values[m["name"]],
                                          "unit": m["unit"]}
                              for m in cell["end_to_end"]}
        out["device"] = result_device
    finally:
        counter.close()
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)

    if np.isfinite(start["pos"]).all() and np.isfinite(start["vel"]).all():
        want = reference_chain(ref, config, arrays, start, n_steps,
                               len(got))
        checks.update(compare_chain(arrays["box"], got, want))
    else:       # the program's state was lost before the compared blocks
        checks.update(dict.fromkeys(("pos_err", "vel_err", "pe_err"),
                                    float("inf")))
    limits = config["limits"]
    out["correct"] = all(checks[k] <= limits[k] for k in limits)
    # JSON has no infinity: a number that is not finite is named
    out["checks"] = {k: {"value": checks[k] if np.isfinite(checks[k])
                         else str(checks[k]), "limit": limits[k]}
                     for k in limits}
    return out


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None, t_start: float | None = None) -> int:
    t_start = time.perf_counter() if t_start is None else t_start
    args = parse(argv)
    cell = spec.resolve_cell(spec.load_benchmark(), args.workload)
    devices = require_accelerator(int(cell["cell"]["chips"]))
    enable_compile_cache()
    out = run_cell(cell, args.seed, args.seconds, bool(args.trace), devices,
                   t_start)
    for name, c in out["checks"].items():
        print(f"check {name} = {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    print(json.dumps(out), flush=True)
    return 0
