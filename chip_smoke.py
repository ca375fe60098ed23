#!/usr/bin/env python3
"""Smoke run of the MD engine on TPU chips, through its user entry points.

    python chip_smoke.py             # one chip: grappa-90k, phases (a)-(c)
    python chip_smoke.py --chips 4   # v5e:2x2: grappa-360k on a (2,2,1) mesh

One chip, grappa-90k (``make_grappa_like(90_000)``, 18x18x18 cells, K=36):

  (a) the default ``MDEngine`` (``fused`` halo, ``dense`` forces): warm-up,
      then two ``nstlist=20`` blocks; everything finite, NVE drift within
      ``DENSE_F32_DRIFT_BOUND``, and forces on 512 seeded atoms against a
      float64 direct sum over all atoms;
  (b) the same state with ``force_backend="pallas"`` (the NB kernel)
      against (a)'s dense forces, and the ``pallas`` and ``signal`` halo
      backends (the halo_pack kernels) bitwise against ``serialized``;
  (c) a ``SimServer`` serving 4 replicas from the default ``BucketLadder``.

``--chips 4`` runs only the multi-chip path: grappa-360k (90k atoms per
chip) on ``make_md_mesh(4)``, a warm-up block then one block with NVE
drift within the bound,
state on all four devices, and the ``fused`` and ``signal`` halo backends
bitwise against ``serialized`` on that mesh.

Lines starting ``info:`` are informational (not the benchmark).  Any
failed check exits non-zero.  So does a host where JAX finds no
accelerator: the script never falls back to the CPU.  On success the last
stdout line is ``{"ok": true, "device": {...}}``.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

SEED = 0
N_CHECK = 512        # atoms whose forces are checked against the f64 sum
# float32 engine forces vs the float64 direct sum, relative to the largest
# reference force.  Coordinates up to L = 48.7 carry a float32 rounding of
# up to 2^-19 ~ 1.9e-6 each; the steepest term (LJ, r^-13) turns that
# relative distance error (~2e-6 at contact) into ~3e-5 of a contact pair's
# force, and a few contact pairs per atom can add up coherently.  1e-4
# keeps that margin while any wrong or missing pair (an O(1) error of some
# pair force) fails it.
FORCE_TOL = 1e-4
# pallas vs dense forces: the documented sparse/pallas parity tolerance
# (tests/test_pair_schedule.py FORCE_RTOL): identical per-pair math in a
# different summation order, relative to max |F|
PALLAS_TOL = 5e-6
# fused force return vs serialized, in float32 ulps of the largest value:
# a halo cell reached by up to 7 neighbours' contributions can round
# once per reordered addition
REV_ULPS = 8


def info(msg: str) -> None:
    print(f"info: {msg}", flush=True)


def check(ok, msg: str) -> None:
    if not ok:
        raise SystemExit(f"chip_smoke: FAILED: {msg}")


def accelerator(n_chips: int):
    import jax
    devs = jax.devices()
    if devs[0].platform == "cpu":
        raise SystemExit("chip_smoke: JAX found no accelerator (only "
                         "CPU devices); this script never runs on the CPU")
    check(len(devs) >= n_chips,
          f"needs {n_chips} devices, JAX found {len(devs)}")
    return devs


def drift_per_atom(metrics: dict, n_atoms: int) -> float:
    """The repo's NVE drift measure: (E.max - E.min) / n_atoms."""
    E = np.asarray(metrics["pe"], np.float64) + \
        np.asarray(metrics["ke"], np.float64)
    return float((E.max() - E.min()) / n_atoms)


def all_finite(*arrays) -> bool:
    return all(bool(np.all(np.isfinite(np.asarray(a)))) for a in arrays)


def timed_simulate(eng, n_steps: int, state=None):
    """``simulate`` with a host clock that stops after the device."""
    import jax
    t0 = time.perf_counter()
    (cf, ci), m, diags = eng.simulate(n_steps, state=state)
    jax.block_until_ready((cf, ci))
    return (cf, ci), m, diags, time.perf_counter() - t0


def run_blocks(eng, n_blocks: int):
    """A warm-up block (compiles, and lets the jittered-lattice start
    relax: its first ~15 steps swing the total energy by ~1.5e-3 per
    atom, which is not integrator drift), then ``n_blocks`` nstlist
    blocks, checked: finite, atoms conserved, NVE drift within the
    dense-f32 bound."""
    from repro.core.wire import DENSE_F32_DRIFT_BOUND
    sys_ = eng.system
    nst = sys_.params.nstlist
    state, _, _, t_warm = timed_simulate(eng, nst)
    info(f"warm-up block (compiles rebin + block programs): "
         f"{t_warm:.3f} s")
    (cf, ci), m, diags, t_run = timed_simulate(eng, n_blocks * nst, state)
    n_steps = n_blocks * nst
    info(f"{n_steps} steps in {t_run:.4f} s -> {1e6 * t_run / n_steps:.1f} "
         f"us/step (host clock, rebin boundaries included)")
    check(all_finite(cf, m["pe"], m["ke"]), "non-finite state or energy")
    n_live = int(np.sum(np.asarray(ci)[..., 0] >= 0))
    check(n_live == sys_.n_atoms,
          f"atoms not conserved: {n_live} of {sys_.n_atoms}")
    check(all(int(np.asarray(d["bin_overflow"])) == 0
              and int(np.asarray(d["migration_dropped"])) == 0
              for d in diags), "atoms dropped at a rebin")
    drift = drift_per_atom(m, sys_.n_atoms)
    info(f"NVE drift over {n_steps} steps: {drift:.3e} per atom "
         f"(bound {DENSE_F32_DRIFT_BOUND:.1e})")
    check(drift <= DENSE_F32_DRIFT_BOUND, f"NVE drift {drift:.3e} over "
          f"bound {DENSE_F32_DRIFT_BOUND}")
    return cf, ci


def halo_bitwise(eng, backends, cell_f, cell_i) -> None:
    """Each halo backend's coordinate, index and force-return exchanges
    on the live state, bit for bit against the ``serialized`` plan.

    The one documented exception (``tests/dist/check_halo.py``): the
    ``fused`` force return adds a halo cell's contributions in another
    order than ``serialized``, so on a multi-device mesh it may differ in
    the last bits; it is held to REV_ULPS float32 ulps of the largest
    value instead.
    """
    import jax
    from repro.core.halo_plan import HaloPlan

    def exchanges(backend):
        plan = HaloPlan.build(dataclasses.replace(eng.spec, backend=backend),
                              eng.mesh)
        ext_f = plan.fwd(cell_f[..., :4])
        ext_i = plan.fwd(cell_i, wrap_shift=None)
        return [np.asarray(jax.device_get(a))
                for a in (ext_f, ext_i, plan.rev(ext_f))]

    ref = exchanges("serialized")
    for backend in backends:
        got = exchanges(backend)
        for name, g, r in zip(("fwd coords", "fwd index", "rev forces"),
                              got, ref):
            check(g.shape == r.shape, f"{backend} halo {name} shape")
            if backend == "fused" and name == "rev forces":
                ulp = np.finfo(np.float32).eps * float(np.abs(r).max())
                diff = float(np.abs(g - r).max()) / ulp
                info(f"halo backend 'fused' rev forces: max difference "
                     f"{diff:.2f} ulp of max |value| (bound {REV_ULPS})")
                check(diff <= REV_ULPS, f"fused rev differs by {diff} ulp")
            else:
                check(np.array_equal(g, r),
                      f"{backend} halo {name} differs from serialized")
        info(f"halo backend {backend!r}: fwd coords and fwd index "
             f"bitwise == serialized, rev forces "
             f"{'within bound' if backend == 'fused' else 'bitwise'}")


def phase_a(mesh, n_atoms: int):
    """Default engine, warm-up + 2 blocks, forces vs the f64 direct sum."""
    from repro.core.md import MDEngine, direct_forces_rows, make_grappa_like
    system = make_grappa_like(n_atoms, seed=SEED)
    eng = MDEngine(system, mesh)
    lay = eng.layout
    info(f"grappa {system.n_atoms} atoms, cells {lay.global_cells}, "
         f"K={lay.capacity}, halo {eng.backend!r}, "
         f"forces {eng.force_backend!r}")
    cf, ci = run_blocks(eng, 2)
    # the rebin program re-derives the velocity-Verlet force carry: the
    # engine's forces at exactly this state
    rs = eng.begin_run((cf, ci))
    pos, force = eng.gather_by_id([rs.cell_f[..., :3], rs.force], rs.cell_i)
    rows = np.random.RandomState(SEED).choice(system.n_atoms, N_CHECK,
                                              replace=False)
    f_ref = direct_forces_rows(pos, system.charge, system.typ, system.box,
                               system.params.ff, rows)
    err = float(np.abs(force[rows] - f_ref).max() / np.abs(f_ref).max())
    info(f"forces on {N_CHECK} atoms vs float64 direct sum: max error "
         f"{err:.3e} of max |F| {np.abs(f_ref).max():.3f} "
         f"(tolerance {FORCE_TOL:.0e})")
    check(err < FORCE_TOL, f"force error {err:.3e} >= {FORCE_TOL}")
    return eng, rs


def phase_b(eng, rs) -> None:
    """Pallas NB kernel vs dense forces; halo kernels vs serialized."""
    import jax
    from repro.core.md import MDEngine
    eng_p = MDEngine(eng.system, eng.mesh, force_backend="pallas")
    t0 = time.perf_counter()
    f_p, _pe = eng_p.force_fn(rs.cell_f, rs.cell_i)
    f_p = np.asarray(jax.device_get(f_p))
    info(f"pallas force pass (prune + compile + run): "
         f"{time.perf_counter() - t0:.3f} s")
    f_d = np.asarray(jax.device_get(rs.force))
    valid = np.asarray(rs.cell_i)[..., 0] >= 0
    check(all_finite(f_p), "non-finite pallas forces")
    scale = max(float(np.abs(f_d).max()), 1.0)
    err = float(np.abs(np.where(valid[..., None], f_p - f_d, 0.0)).max()
                / scale)
    info(f"pallas NB kernel vs dense forces: max error {err:.3e} of "
         f"max |F| {scale:.3f} (tolerance {PALLAS_TOL:.0e})")
    check(err < PALLAS_TOL, f"pallas force error {err:.3e}")
    halo_bitwise(eng, ("pallas", "signal"), rs.cell_f, rs.cell_i)


def phase_c(mesh) -> None:
    """SimServer: 4 replicas from the default ladder, as launch/serve.py."""
    from repro.core.md import make_grappa_like
    from repro.serve import BucketLadder, SimServer
    ladder = BucketLadder()
    nstlist, atoms, steps = 10, 200, 40
    server = SimServer(mesh, ladder, block_steps=nstlist)
    bucket = ladder.atom_bucket_for(atoms)
    handles = [server.submit(make_grappa_like(atoms, seed=i, nstlist=nstlist,
                                              box_atoms=bucket), steps)
               for i in range(4)]
    server.drain()
    check(all(h.status == "done" for h in handles),
          f"replica states {[h.status for h in handles]}")
    for h in handles:
        out = h.result()
        check(out["steps"] >= steps and all_finite(out["atoms"]["pos"],
                                                   out["atoms"]["vel"]),
              f"replica {h.rid}: {out['steps']} steps or non-finite atoms")
    st = server.stats()
    info(f"SimServer: {st['replicas_done']} replicas done, "
         f"{st['useful_steps']} useful steps, {st['compiles']} compiles, "
         f"{st['wall_s']:.3f} s wall")


def run_one_chip() -> None:
    from repro.launch.mesh import make_md_mesh
    mesh = make_md_mesh(1)
    eng, rs = phase_a(mesh, 90_000)
    phase_b(eng, rs)
    phase_c(mesh)


def run_four_chips() -> None:
    from repro.core.md import MDEngine, make_grappa_like
    from repro.launch.mesh import make_md_mesh
    mesh = make_md_mesh(4)
    system = make_grappa_like(360_000, seed=SEED)
    eng = MDEngine(system, mesh)
    lay = eng.layout
    info(f"grappa {system.n_atoms} atoms on mesh "
         f"{dict(mesh.shape)}, cells/domain {lay.cells_per_domain}, "
         f"K={lay.capacity}, halo {eng.backend!r}")
    cf, ci = run_blocks(eng, 1)
    devices = cf.sharding.device_set
    check(len(devices) == 4, f"state on {len(devices)} devices, not 4")
    per_dev = {s.device.id: int(np.sum(np.asarray(s.data)[..., 0] >= 0))
               for s in ci.addressable_shards}
    info(f"atoms per device: {per_dev}")
    check(len(per_dev) == 4 and min(per_dev.values()) > 0,
          "state is not spread across all four devices")
    halo_bitwise(eng, ("fused", "signal"), cf, ci)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args()
    devs = accelerator(args.chips)
    from repro.launch.compile_cache import enable_compile_cache
    info(f"compile cache: {enable_compile_cache()}")
    t0 = time.perf_counter()
    if args.chips == 4:
        run_four_chips()
    else:
        run_one_chip()
    info(f"total {time.perf_counter() - t0:.1f} s")
    import jax
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
