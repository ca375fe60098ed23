"""8-virtual-device MD check: DD equivalence, migration, step pipeline.

The step-pipeline acceptance bar: on a 2x2x2 DD mesh the pipelined engine
(``backend="signal"``, ``pipeline="double_buffer"`` at any window depth
>= 2, with or without the fused ``overlap_rebin`` DLB program) must
produce trajectories bitwise-identical to the serialized non-pipelined
host-dispatched engine over >= 10 steps, including across a
rebin/migration boundary; and the 8-device run must agree with the
single-device reference physics (DD equivalence, atom conservation).

  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      PYTHONPATH=src python tests/dist/check_md.py
"""
import numpy as np

import jax

from repro.core.halo_plan import HaloSpec
from repro.core.md import MDEngine, make_grappa_like
from repro.launch.mesh import make_mesh

AXES = ("z", "y", "x")


def run(system, mesh, backend, pipeline, n_steps, pulses=None, widths=None,
        force_backend="dense", depth=2, overlap_rebin=False, nstprune=0):
    spec = HaloSpec(axis_names=AXES, widths=widths or (1, 1, 1),
                    backend=backend, pulses=pulses)
    eng = MDEngine(system, mesh, spec, pipeline=pipeline,
                   pipeline_depth=depth, overlap_rebin=overlap_rebin,
                   force_backend=force_backend, nstprune=nstprune)
    (cf, ci), metrics, diags = eng.simulate(n_steps)
    return (np.asarray(jax.device_get(cf)), np.asarray(jax.device_get(ci)),
            {k: np.asarray(v) for k, v in metrics.items()}, diags, eng)


def main():
    assert len(jax.devices()) >= 8, "need 8 virtual devices"
    mesh = make_mesh((2, 2, 2), AXES)
    system = make_grappa_like(900, seed=3)
    n_steps = 24          # nstlist=20 -> crosses one rebin/migration

    cf_ref, ci_ref, m_ref, diags_ref, eng_ref = run(
        system, mesh, "serialized", "off", n_steps)
    for d in diags_ref:
        assert int(np.asarray(d["n_atoms"])) == system.n_atoms
        assert int(np.asarray(d["bin_overflow"])) == 0
    print("serialized/off reference: atoms conserved across",
          len(diags_ref), "rebins")

    # --- pipelined put-with-signal engine: bitwise-identical trajectory ---
    # (window depths 2/3/4 and the fused overlap_rebin DLB program all
    # regroup the same per-step ops; every cell must match bit for bit)
    cases = [("signal", "double_buffer", 2, False),
             ("signal", "off", 2, False),
             ("serialized", "double_buffer", 2, False),
             ("signal", "double_buffer", 3, False),
             ("signal", "double_buffer", 4, True),
             ("serialized", "off", 2, True)]
    for backend, pipeline, depth, ovr in cases:
        cf, ci, m, diags, eng = run(system, mesh, backend, pipeline,
                                    n_steps, depth=depth,
                                    overlap_rebin=ovr)
        tag = f"{backend}/{pipeline}/d{depth}" + ("/ovr" if ovr else "")
        assert np.array_equal(cf, cf_ref), \
            f"{tag} cell_f differs from serialized/off"
        assert np.array_equal(ci, ci_ref), f"{tag} cell_i differs"
        for k in m_ref:
            assert np.array_equal(m[k], m_ref[k]), (tag, k)
        assert len(diags) == len(diags_ref), tag   # same rebin cadence
        for got_d, ref_d in zip(diags, diags_ref):
            for k in ref_d:
                assert np.array_equal(np.asarray(got_d[k]),
                                      np.asarray(ref_d[k])), (tag, k)
        print(f"{tag}: trajectory bitwise identical over {n_steps} steps")

    deep_stats = [MDEngine(system, mesh,
                           HaloSpec(axis_names=AXES, widths=(1, 1, 1),
                                    backend="signal"),
                           pipeline="double_buffer", pipeline_depth=d)
                  .overlap_stats() for d in (2, 3, 4)]
    assert all(ov["overlapped_bytes_per_step"] > 0 for ov in deep_stats)
    exposed = [ov["exposed_phases_per_step"] for ov in deep_stats]
    assert exposed[0] > exposed[1] > exposed[2], exposed
    print("overlap model exposed phases decrease with depth:", exposed)

    # --- energy sanity on the DD run -----------------------------------
    E = m_ref["pe"] + m_ref["ke"]
    assert np.all(np.isfinite(E))
    drift = float((E.max() - E.min()) / system.n_atoms)
    assert drift < 5e-3, drift
    assert np.abs(m_ref["mom"]).max() < 1e-2
    print(f"NVE drift/atom {drift:.2e}, momentum conserved")

    # --- DD equivalence: 8-device vs single-device energies ------------
    mesh1 = make_mesh((1, 1, 1), AXES)
    _, _, m1, _, _ = run(system, mesh1, "serialized", "off", n_steps)
    rel = np.abs(m_ref["pe"] - m1["pe"]) / np.abs(m1["pe"])
    assert rel.max() < 1e-4, rel.max()
    print("DD potential energies match single-device within",
          f"{rel.max():.1e}")

    # --- pruned force backends: tolerance vs the dense trajectory ------
    # (documented guarantee: same per-pair math, different summation
    # order -> NOT bitwise; positions/velocities agree to float32
    # round-off accumulated over 24 steps, energies tighter)
    pos_ref, vel_ref = eng_ref.gather_by_id(
        [cf_ref[..., 0:3], cf_ref[..., 4:7]], ci_ref)
    pruned = {}
    for fb in ("sparse", "pallas"):
        cf, ci, m, _, eng = run(system, mesh, "serialized", "off", n_steps,
                                force_backend=fb)
        pruned[fb] = (cf, ci, m)
        pos, vel = eng.gather_by_id([cf[..., 0:3], cf[..., 4:7]], ci)
        dpos = np.abs(pos - pos_ref).max()
        dvel = np.abs(vel - vel_ref).max()
        assert dpos < 1e-3 and dvel < 1e-2, (fb, dpos, dvel)
        rel_pe = np.abs(m["pe"] - m_ref["pe"]).max() / \
            np.abs(m_ref["pe"]).max()
        assert rel_pe < 1e-5, (fb, rel_pe)
        ratio = eng.pair_stats()["prune_ratio"]
        assert ratio >= 2.0, (fb, ratio)
        print(f"force_backend={fb}: 24-step trajectory within tolerance "
              f"(dpos {dpos:.1e}, dpe {rel_pe:.1e}), "
              f"prune ratio {ratio:.2f}x")

    # the fused overlap_rebin program carries the same Pallas-kernel force
    # carry as the host-dispatched rebin: bitwise-identical trajectories
    cf_p, ci_p, m_p = pruned["pallas"]
    cf, ci, m, _, _ = run(system, mesh, "serialized", "off", n_steps,
                          force_backend="pallas", overlap_rebin=True)
    assert np.array_equal(cf, cf_p) and np.array_equal(ci, ci_p), \
        "pallas/ovr trajectory differs from pallas/off"
    for k in m_p:
        assert np.array_equal(m[k], m_p[k]), ("pallas/ovr", k)
    print("pallas/off/ovr == pallas/off bitwise")

    # --- pruned backend under the step pipeline: schedule threading ----
    # sparse/off, sparse/double_buffer (any depth), and the fused
    # overlap_rebin path must stay bitwise-identical to EACH OTHER (the
    # block-constant schedule rides the StepFns ctx, and the fused
    # rebin+prune program computes the exact host-dispatched schedule),
    # for the static schedule (nstprune=0) AND the rolling dual pair
    # list (nstprune>0: in-block refreshes, host-read overflow scalar)
    for nstprune in (0, 4):
        cf_a, ci_a, m_a, d_a, eng_a = run(system, mesh, "signal", "off",
                                          n_steps, force_backend="sparse",
                                          nstprune=nstprune)
        variants = [("double_buffer", 3, False),
                    ("double_buffer", 2, True), ("off", 2, True)]
        for pipeline, depth, ovr in variants:
            cf_b, ci_b, m_b, d_b, eng_b = run(
                system, mesh, "signal", pipeline, n_steps,
                force_backend="sparse", depth=depth, overlap_rebin=ovr,
                nstprune=nstprune)
            tag = f"sparse/np{nstprune}/{pipeline}/d{depth}" + \
                ("/ovr" if ovr else "")
            assert np.array_equal(cf_a, cf_b) and \
                np.array_equal(ci_a, ci_b), \
                f"{tag} trajectory differs from sparse/off"
            for k in m_a:
                assert np.array_equal(m_a[k], m_b[k]), (tag, k)
            # the fused prune must hand the NEXT block the same exec
            # schedule (prune conservativeness across the block
            # boundary: identical surviving-pair sets, identical
            # bucketed tier ladders)
            sel_a, t_a, ti_a = eng_a._sched_exec
            sel_b, t_b, ti_b = eng_b._sched_exec
            assert (t_a, ti_a) == (t_b, ti_b), tag
            assert np.array_equal(np.asarray(jax.device_get(sel_a)),
                                  np.asarray(jax.device_get(sel_b))), tag
            assert eng_b.pair_stats()["nstprune"] == nstprune
            assert eng_b.pair_stats()["inner_overflow_blocks"] == 0, tag
            print(f"{tag} == sparse/off bitwise, same post-boundary "
                  "schedule")

    print("check_md OK")


if __name__ == "__main__":
    main()
