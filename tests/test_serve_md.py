"""SimServer conformance: replica isolation, churn, compiles, faults.

The batching contract is bitwise, not statistical: a replica served
inside a bucketed vmapped batch must produce the *identical* trajectory
to a solo :class:`MDEngine` run of the same system (same seed, same
bucket box/layout) — regardless of which bucket it lands in, which
replicas share the batch, the order replicas were admitted, or a
co-resident retiring mid-run.  Solo references are lru-cached like the
PR 4 matrix so every comparison against the same (backend, pipeline,
replica, steps) cell is computed once.

On top of the isolation matrix: the no-recompile-at-admission contract
(``serve/compiles`` == distinct shapes touched, exactly), per-lane NaN
quarantine (typed :class:`ReplicaFault`, co-residents untouched), cancel
and evacuate/resume round-trips, per-block deadlines, the engine's
block-boundary admission hook, and the wave-accounting helpers shared
with the LM server.
"""
import functools

import numpy as np
import pytest

import jax

from repro.core.md.domain import AXES
from repro.core.md.engine import MDEngine
from repro.core.md.system import make_grappa_like
from repro.launch.mesh import make_mesh
from repro.resilience.faults import WaveTimeout
from repro.runtime.serve_loop import masked_tokens
from repro.serve import (BucketLadder, CANCELLED, DONE, FAILED, PREEMPTED,
                         ReplicaFault, SimServer)

NST = 10            # block quantum: nstlist steps per dispatch
BUCKET = 256        # canonical atom bucket for most cells

# the shared replica roster: (n_atoms, seed) — sub-bucket sizes exercise
# padded lanes, distinct seeds make cross-lane leaks visible
R0, R1, R2 = (200, 5), (256, 7), (230, 9)

MATRIX = [(fb, pipe) for fb in ("dense", "sparse")
          for pipe in ("off", "double_buffer")] + [("pallas", "off")]


@functools.lru_cache(maxsize=None)
def _mesh():
    return make_mesh((1, 1, 1), AXES)


@functools.lru_cache(maxsize=None)
def _sys(n_atoms, seed):
    return make_grappa_like(n_atoms, seed=seed, nstlist=NST,
                            box_atoms=BUCKET)


@functools.lru_cache(maxsize=None)
def _solo(fb, pipe, n_atoms, seed, n_steps):
    """Solo reference trajectory under the bucket's box and layout."""
    eng = MDEngine(_sys(n_atoms, seed), _mesh(), force_backend=fb,
                   pipeline=pipe, static_ladder=(fb != "dense"),
                   layout_atoms=BUCKET)
    (cf, ci), _, _ = eng.simulate(n_steps)
    return (np.asarray(jax.device_get(cf)), np.asarray(jax.device_get(ci)))


def _server(fb, pipe, rows=(1, 2, 4), atoms=(BUCKET,), **kw):
    return SimServer(_mesh(),
                     BucketLadder(row_buckets=rows, atom_buckets=atoms),
                     block_steps=NST,
                     engine_kwargs={"force_backend": fb, "pipeline": pipe},
                     **kw)


def _assert_bitwise(out, fb, pipe, spec, n_steps):
    n, seed = spec
    cf, ci = _solo(fb, pipe, n, seed, n_steps)
    assert np.array_equal(out["cell_f"], cf), \
        f"cell_f diverged for replica {spec} under {fb}/{pipe}"
    assert np.array_equal(out["cell_i"], ci), \
        f"cell_i diverged for replica {spec} under {fb}/{pipe}"


# ---- replica isolation matrix ---------------------------------------------

@pytest.mark.parametrize("fb,pipe", MATRIX,
                         ids=[f"{fb}-{pipe}" for fb, pipe in MATRIX])
def test_batched_replicas_bitwise_match_solo(fb, pipe):
    """Three mixed-size replicas in one 4-row bucket (one lane empty):
    every lane equals its solo run bit for bit."""
    srv = _server(fb, pipe)
    handles = [(spec, srv.submit(_sys(*spec), 20))
               for spec in (R0, R1, R2)]
    srv.drain()
    for spec, h in handles:
        assert h.status == DONE
        _assert_bitwise(h.result(), fb, pipe, spec, 20)
    st = srv.stats()
    assert st["replicas_done"] == 3
    assert st["useful_steps"] == 60


@pytest.mark.parametrize("order", [(R0, R1, R2), (R2, R0, R1), (R1, R2, R0)],
                         ids=["012", "201", "120"])
def test_admission_order_is_invisible(order):
    """A 2-row bucket forces churn (the third replica waits for a freed
    row); every admission order yields the same bitwise trajectories."""
    srv = _server("sparse", "off", rows=(1, 2))
    handles = [(spec, srv.submit(_sys(*spec), 20)) for spec in order]
    srv.drain()
    for spec, h in handles:
        _assert_bitwise(h.result(), "sparse", "off", spec, 20)


def test_mid_run_neighbor_retirement_is_invisible():
    """Mixed budgets in a 2-row bucket: the short replica retires
    mid-run, a queued one is admitted into its freed row, and the
    long-running neighbor's trajectory never notices."""
    srv = _server("dense", "off", rows=(1, 2))
    ha = srv.submit(_sys(*R0), 40)   # runs blocks 1..4
    hb = srv.submit(_sys(*R1), 20)   # retires after block 2
    hc = srv.submit(_sys(*R2), 30)   # admitted into B's row at block 3
    srv.drain()
    _assert_bitwise(ha.result(), "dense", "off", R0, 40)
    _assert_bitwise(hb.result(), "dense", "off", R1, 20)
    _assert_bitwise(hc.result(), "dense", "off", R2, 30)
    # churn reused the one open table: a single compiled shape
    assert srv.stats()["compiles"] == 1
    assert srv.stats()["shapes_touched"] == [(2, BUCKET)]


# ---- compile-count contract -----------------------------------------------

def test_compile_count_equals_buckets_touched():
    """32 replicas churned through 4 shapes: the traced-lowering counter
    (incremented inside the jitted block body, i.e. once per trace)
    equals the number of distinct buckets touched — exactly."""
    ladder = BucketLadder(row_buckets=(2, 4), atom_buckets=(192, 256))
    srv = SimServer(_mesh(), ladder, block_steps=NST,
                    engine_kwargs={"force_backend": "dense"})
    batches = ([(2, 192), (4, 192), (2, 256), (4, 256)] * 2
               + [(4, 192), (4, 256)])         # 2+4+2+4 = 12, x2, +8 = 32
    total = 0
    for count, atoms in batches:
        for i in range(count):
            sys_ = make_grappa_like(atoms - (i % 2) * 8, seed=total,
                                    nstlist=NST, box_atoms=atoms)
            srv.submit(sys_, NST)
            total += 1
        srv.drain()     # table closes empty -> next batch reopens a shape
    assert total == 32
    st = srv.stats()
    assert st["replicas_done"] == 32
    touched = set(srv.scheduler.shapes_touched)
    assert touched == {(2, 192), (4, 192), (2, 256), (4, 256)}
    assert st["compiles"] == len(touched)      # == 4, gated exactly


# ---- fault quarantine ------------------------------------------------------

def test_nan_replica_quarantined_not_the_batch():
    """A poisoned lane retires with a typed ReplicaFault at its block
    boundary; the co-resident replica finishes bitwise-unchanged."""
    bad_sys = make_grappa_like(200, seed=11, nstlist=NST, box_atoms=BUCKET)
    bad_sys.vel[0] = np.inf        # NaN positions within the first block
    srv = _server("dense", "off")
    h_ok = srv.submit(_sys(*R1), 20)
    h_bad = srv.submit(bad_sys, 20)
    srv.drain()
    assert h_bad.status == FAILED
    with pytest.raises(ReplicaFault, match="non-finite"):
        h_bad.result()
    assert h_ok.status == DONE
    _assert_bitwise(h_ok.result(), "dense", "off", R1, 20)
    st = srv.stats()
    assert st["replicas_failed"] == 1 and st["replicas_done"] == 1


def test_block_deadline_raises_wave_timeout():
    srv = _server("dense", "off", wave_timeout_s=1e-9)
    srv.submit(_sys(*R0), NST)
    with pytest.raises(WaveTimeout):
        srv.run_cycle()


# ---- cancel / evacuate-resume ---------------------------------------------

def test_cancel_queued_and_running():
    srv = _server("dense", "off", rows=(1,))
    h_run = srv.submit(_sys(*R0), 40)
    h_q = srv.submit(_sys(*R1), 20)      # 1-row bucket: stays queued
    assert h_q.cancel() == CANCELLED
    assert h_q.result() is None
    srv.run_cycle()                      # block 1 for the running replica
    assert h_run.cancel() == "running"   # flagged; retires next boundary
    srv.drain()
    assert h_run.status == CANCELLED
    out = h_run.result()                 # partial state: exactly 1 block
    assert out["steps"] == NST
    _assert_bitwise(out, "dense", "off", R0, NST)


def test_evacuate_and_resume_is_bitwise():
    """Preempt a replica mid-run, readmit its snapshot on a *fresh*
    server, and the stitched trajectory equals an uninterrupted solo
    run — the device-loss recovery path, single-process edition."""
    srv = _server("dense", "off")
    h = srv.submit(_sys(*R2), 30)
    srv.run_cycle()                      # 1 of 3 blocks
    [(h_old, snap)] = srv.evacuate()
    assert h_old.status == PREEMPTED
    assert snap["steps"] == NST and snap["remaining_steps"] == 20
    srv2 = _server("dense", "off")
    h2 = srv2.submit(_sys(*R2), snap["remaining_steps"],
                     state=(snap["cell_f"], snap["cell_i"]))
    srv2.drain()
    _assert_bitwise(h2.result(), "dense", "off", R2, 30)


# ---- engine admission hook -------------------------------------------------

def test_engine_boundary_hook_fires_and_mutates():
    sys_ = _sys(*R0)
    eng = MDEngine(sys_, _mesh(), force_backend="dense")
    calls = []
    (cf, ci), _, _ = eng.simulate(3 * NST,
                                  on_boundary=lambda rs: calls.append(rs.step))
    assert calls == [NST, 2 * NST]       # interior boundaries only
    # a mutating hook visibly changes the trajectory (freeze velocities)
    def freeze(rs):
        cf = np.array(jax.device_get(rs.cell_f))   # writable copy
        cf[..., 4:7] = 0.0
        rs.cell_f = jax.numpy.asarray(cf)
    (cf2, _), _, _ = eng.simulate(2 * NST, on_boundary=freeze)
    base, _ = _solo("dense", "off", *R0, 2 * NST)
    assert not np.array_equal(np.asarray(jax.device_get(cf2)), base)
    eng_ovr = MDEngine(sys_, _mesh(), force_backend="dense",
                       overlap_rebin=True)
    with pytest.raises(ValueError, match="overlap_rebin"):
        eng_ovr.simulate(2 * NST, on_boundary=lambda rs: None)


# ---- server guardrails -----------------------------------------------------

def test_submit_validates_box_and_cadence():
    srv = _server("dense", "off")
    with pytest.raises(ValueError, match="box_atoms"):
        srv.submit(make_grappa_like(200, seed=1, nstlist=NST), 20)
    with pytest.raises(ValueError, match="nstlist"):
        srv.submit(make_grappa_like(256, seed=1, nstlist=20), 20)
    with pytest.raises(ValueError, match="atom bucket"):
        srv.submit(make_grappa_like(400, seed=1, nstlist=NST), 20)


def test_step_budget_rounds_up_to_blocks():
    srv = _server("dense", "off")
    h = srv.submit(_sys(*R0), 15)        # 1.5 blocks -> 2 blocks run
    srv.drain()
    out = h.result()
    assert out["steps"] == 20 and out["requested_steps"] == 15
    # useful-step accounting masks the padding, LM-server style
    assert srv.stats()["useful_steps"] == masked_tokens([20], [15]) == 15


# ---- dist cells ------------------------------------------------------------

@pytest.mark.dist
def test_sharded_rows_quarantine_and_device_loss(dist):
    out = dist("check_serve.py")
    assert "rep-sharded rows: 8/8 replicas bitwise" in out
    assert "quarantine: co-residents bitwise around a poisoned lane" in out
    assert "device-loss: evacuated replicas resumed bitwise on rep=4" in out
