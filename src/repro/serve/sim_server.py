"""SimServer: continuous batching of many independent MD replicas.

One vmapped block program per bucketed ``(n_rows, n_atoms)`` shape stacks
replica lanes of the existing device-local MD block bodies
(``MDEngine.local_programs``) under one ``shard_map``; replicas are
admitted into free rows and retired from finished ones at block
boundaries, so churn never recompiles — the ``serve/compiles`` counter
(incremented inside the to-be-jitted body, i.e. once per trace) equals
the number of distinct shapes ever touched.

Isolation is bitwise, not approximate: a lane's trajectory equals a solo
:class:`MDEngine` run of the same replica (same seed, same bucket box)
element-for-element, regardless of co-residents, admission order, or
neighbor retirement.  Three ingredients make that hold (proven by
``tests/test_serve_md.py``):

* every replica of an atom bucket shares the bucket's canonical box
  (``make_grappa_like(n, box_atoms=bucket)``) and hence its cell layout;
* the sparse backend runs a *static worst-case tier ladder*
  (``static_ladder=True``): the exec schedule is data-independent, and
  sentinel rows are physics-inert, so lanes never couple through shapes;
* the per-cycle order replicates the solo driver exactly — retire →
  admit → rebin (+ prune) → block — with retirement reads happening
  post-block, where the solo run's final state also sits.

Fault handling is per-lane: the engines' ``health`` observer (bitwise
neutral) reports per-step non-finite counts per lane; a poisoned lane is
retired with a typed :class:`ReplicaFault` at the next boundary while
co-residents continue untouched.  Per-block deadlines reuse the LM
server's :class:`WaveTimeout` / :class:`Watchdog` spine, and
replica-step accounting reuses its ``masked_tokens`` helper (useful
steps = the requested budget, never the padded block multiple).
"""
from __future__ import annotations

import dataclasses
import time
from typing import Dict, List, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.compat import shard_map_norep
from repro.core.md.domain import AXES
from repro.core.md.engine import MDEngine
from repro.core.md.system import MDSystem
from repro.launch.mesh import make_mesh
from repro.obs import MetricsRegistry
from repro.resilience.faults import ResilienceError, WaveTimeout
from repro.resilience.policy import Watchdog
from repro.runtime.serve_loop import masked_tokens
from repro.serve.buckets import BucketLadder
from repro.serve.scheduler import (
    DONE, FAILED, PREEMPTED, SimScheduler, TERMINAL)

__all__ = ["SimServer", "ReplicaHandle", "ReplicaFault"]


class ReplicaFault(ResilienceError):
    """A replica's trajectory went non-finite inside a batch.

    Raised *to the owning handle only*: the lane is quarantined and
    retired at the next block boundary; co-resident replicas in the same
    bucket keep running bitwise-unchanged.
    """


@dataclasses.dataclass
class _Programs:
    """Compiled batch programs for one shape (cached across reopens)."""

    blk: object
    reb: object
    prune: Optional[object]            # None for the dense backend


@dataclasses.dataclass
class _Runtime:
    """Live device state for one open table."""

    shape: Tuple[int, int]
    cell_f: object                     # (R, gz, gy, gx, K, 7)
    cell_i: object                     # (R, gz, gy, gx, K, 2)


class ReplicaHandle:
    """Client view of one submitted replica: poll / result / cancel."""

    def __init__(self, server: "SimServer", rid: int):
        self._server = server
        self.rid = rid

    @property
    def status(self) -> str:
        return self._server.scheduler.records[self.rid].status

    def poll(self) -> dict:
        rec = self._server.scheduler.records[self.rid]
        return {"status": rec.status, "steps_done": rec.steps_done,
                "budget_steps": rec.budget_steps,
                "requested_steps": rec.requested_steps,
                "shape": rec.shape, "row": rec.row}

    def result(self, wait: bool = True) -> Optional[dict]:
        """The replica's read-out state.  Blocks (serving other replicas
        too) until this replica is terminal when ``wait``.  Raises the
        quarantine error for a FAILED replica; returns ``None`` for one
        cancelled before admission."""
        if wait:
            self._server.drain(until=self.rid)
        rec = self._server.scheduler.records[self.rid]
        if rec.status not in TERMINAL:
            raise RuntimeError(
                f"replica {self.rid} still {rec.status}; pass wait=True")
        if rec.status == FAILED:
            raise rec.error
        return self._server._results.get(self.rid)

    def cancel(self) -> str:
        return self._server.scheduler.cancel(self.rid)


class SimServer:
    """Continuous-batching server over bucketed vmapped MD programs.

    ``mesh`` is either the engine's ``(z, y, x)`` mesh (replica rows live
    on one shard set) or a 4-axis ``(rep, z, y, x)`` mesh whose leading
    axis shards replica rows across devices; row rungs must then divide
    by the ``rep`` extent.  ``engine_kwargs`` pass through to the
    per-atom-bucket template engines (``force_backend``, ``pipeline``,
    ...); ``system_kwargs`` to the canonical bucket systems (density,
    cutoff, ...) — submitted replicas must share the bucket box, i.e. be
    built with ``box_atoms=<atom bucket>`` and the same ``nstlist``.
    """

    def __init__(self, mesh=None, ladder: Optional[BucketLadder] = None,
                 *, block_steps: int = 10,
                 engine_kwargs: Optional[dict] = None,
                 system_kwargs: Optional[dict] = None,
                 wave_timeout_s: Optional[float] = None,
                 watchdog: Optional[Watchdog] = None,
                 obs: Optional[MetricsRegistry] = None):
        self.mesh = mesh if mesh is not None else make_mesh((1, 1, 1), AXES)
        names = tuple(self.mesh.axis_names)
        if names == AXES:
            self.rep_axis = None
            self._tmpl_mesh = self.mesh
        elif len(names) == 4 and names[1:] == AXES:
            self.rep_axis = names[0]
            # template engines only donate their device-local bodies and
            # layout; park them on a minimal single-device (z,y,x) mesh
            self._tmpl_mesh = make_mesh((1, 1, 1), AXES)
        else:
            raise ValueError(
                f"mesh axes must be {AXES} or ('rep', *{AXES}); got {names}")
        self._row_spec = P(self.rep_axis, *AXES)
        self._lane_spec = P(self.rep_axis)
        self.ladder = ladder or BucketLadder()
        self.block_steps = int(block_steps)
        self.scheduler = SimScheduler(self.ladder, self.block_steps)
        self.engine_kwargs = dict(engine_kwargs or {})
        for k in ("layout_atoms", "health", "static_ladder", "nstprune"):
            if k in self.engine_kwargs:
                raise ValueError(f"engine_kwargs[{k!r}] is server-managed")
        self.system_kwargs = dict(system_kwargs or {})
        self.wave_timeout_s = wave_timeout_s
        self.watchdog = watchdog
        # a private registry by default: serve counters (especially the
        # compile-count contract) must not alias across servers in one
        # process; pass obs=default_registry() to publish globally
        self.obs = obs if obs is not None else MetricsRegistry()
        self._templates: Dict[int, MDEngine] = {}
        self._programs: Dict[Tuple[int, int], _Programs] = {}
        self._runtimes: Dict[Tuple[int, int], _Runtime] = {}
        self._pending_rows: Dict[int, Tuple[np.ndarray, np.ndarray]] = {}
        self._handles: Dict[int, ReplicaHandle] = {}
        self._results: Dict[int, dict] = {}
        self._blocks = 0
        self._serve_wall_s = 0.0
        self._step_walls: List[float] = []

    # ---- templates & programs ---------------------------------------------

    def _template(self, atoms: int) -> MDEngine:
        """Per-atom-bucket template engine: owns the canonical box, cell
        layout, and device-local block bodies every lane of the bucket
        reuses.  Its own (solo) compiled programs are never invoked."""
        if atoms not in self._templates:
            from repro.core.md.system import make_grappa_like
            sys_kw = dict(self.system_kwargs)
            sys_kw.setdefault("nstlist", self.block_steps)
            if sys_kw["nstlist"] != self.block_steps:
                raise ValueError("system nstlist must equal block_steps")
            tmpl_sys = make_grappa_like(atoms, seed=0, **sys_kw)
            kw = dict(self.engine_kwargs)
            fb = kw.get("force_backend", "dense")
            self._templates[atoms] = MDEngine(
                tmpl_sys, self._tmpl_mesh, health=True,
                static_ladder=(fb != "dense"), **kw)
        return self._templates[atoms]

    def _build_programs(self, shape: Tuple[int, int]) -> _Programs:
        if shape in self._programs:
            return self._programs[shape]
        _rows, atoms = shape
        tmpl = self._template(atoms)
        lp = tmpl.local_programs
        spec, lspec = self._row_spec, self._lane_spec
        nst = self.block_steps
        counter = self.obs.counter("serve/compiles")
        if tmpl.force_backend != "dense":
            # static worst-case ladder: every lane, every block runs the
            # same (M, K) tier — data-independent shapes, inert sentinels
            tiers = tmpl.pair_schedule.full_tiers(tmpl.pair_bucket)

            def body(cf, ci, force, sel):
                counter.inc()          # trace-time only: 1 per compile
                return lp["block_sched"](cf, ci, force, sel, nst, tiers, ())

            blk = jax.jit(shard_map_norep(
                jax.vmap(body), mesh=self.mesh, in_specs=(spec,) * 4,
                out_specs=(spec, spec, spec, lspec, lspec)))
            prune = jax.jit(shard_map_norep(
                jax.vmap(lp["prune"]), mesh=self.mesh,
                in_specs=(spec, spec),
                out_specs=(spec, lspec, lspec, lspec)))
        else:
            def body(cf, ci, force):
                counter.inc()          # trace-time only: 1 per compile
                return lp["block"](cf, ci, force, nst)

            blk = jax.jit(shard_map_norep(
                jax.vmap(body), mesh=self.mesh, in_specs=(spec,) * 3,
                out_specs=(spec, spec, spec, lspec)))
            prune = None
        reb = jax.jit(shard_map_norep(
            jax.vmap(lp["rebin"]), mesh=self.mesh, in_specs=(spec, spec),
            out_specs=(spec, spec, spec, lspec)))
        self._programs[shape] = _Programs(blk=blk, reb=reb, prune=prune)
        return self._programs[shape]

    def _ensure_runtime(self, shape: Tuple[int, int]) -> _Runtime:
        if shape in self._runtimes:
            return self._runtimes[shape]
        rows, atoms = shape
        if self.rep_axis is not None:
            rep = self.mesh.shape[self.rep_axis]
            if rows % rep:
                raise ValueError(
                    f"row bucket {rows} does not divide across "
                    f"{self.rep_axis}={rep}; pick row_buckets that do")
        tmpl = self._template(atoms)
        G, K = tmpl.layout.global_cells, tmpl.layout.capacity
        dtype = tmpl.system.pos.dtype
        shard = NamedSharding(self.mesh, self._row_spec)
        cf = jax.device_put(
            jnp.zeros((rows, G[0], G[1], G[2], K, 7), dtype), shard)
        ci = jax.device_put(
            jnp.full((rows, G[0], G[1], G[2], K, 2), -1, jnp.int32), shard)
        self._build_programs(shape)
        self._runtimes[shape] = _Runtime(shape=shape, cell_f=cf, cell_i=ci)
        return self._runtimes[shape]

    # ---- client API --------------------------------------------------------

    def submit(self, system: MDSystem, n_steps: int,
               state: Optional[Tuple[np.ndarray, np.ndarray]] = None
               ) -> ReplicaHandle:
        """Queue a replica for ``n_steps`` (rounded up to whole blocks).

        ``state`` resumes a previously evacuated replica from its cell
        arrays instead of binning ``system`` fresh (the device-loss
        readmission path)."""
        atoms = self.ladder.atom_bucket_for(system.n_atoms)
        tmpl = self._template(atoms)
        if not np.array_equal(np.asarray(system.box),
                              np.asarray(tmpl.system.box)):
            raise ValueError(
                f"replica box {system.box} != bucket-{atoms} box "
                f"{tmpl.system.box}; build replicas with box_atoms={atoms}")
        if system.params.nstlist != self.block_steps:
            raise ValueError(
                f"replica nstlist={system.params.nstlist} != server "
                f"block_steps={self.block_steps}")
        rid = self.scheduler.submit(system.n_atoms, n_steps)
        if state is None:
            rows = tmpl.bin_host(system)
        else:
            cf_row, ci_row = state
            want = tmpl.layout.global_cells + (tmpl.layout.capacity,)
            if tuple(cf_row.shape[:-1]) != want:
                raise ValueError(
                    f"resume state shape {cf_row.shape} does not match "
                    f"bucket-{atoms} cells {want}")
            rows = (np.asarray(cf_row), np.asarray(ci_row))
        self._pending_rows[rid] = rows
        self._handles[rid] = ReplicaHandle(self, rid)
        return self._handles[rid]

    def run_cycle(self) -> bool:
        """One boundary + block round across every live table: retire ←
        (previous cycle) → admit → rebin (+prune) → block → quarantine →
        retire.  Returns True while work remains."""
        # retire replicas flagged since the last block (client cancels):
        # they must not run another block's physics.  Budget- and
        # fault-retirements already happened post-block, where the
        # read-out state is the solo run's final state.
        for shape in self.scheduler.live_shapes():
            self._retire_due(shape)
        for adm in self.scheduler.tick():
            rt = self._ensure_runtime(adm.shape)
            cf_row, ci_row = self._pending_rows.pop(adm.rid)
            rt.cell_f = rt.cell_f.at[adm.row].set(jnp.asarray(cf_row))
            rt.cell_i = rt.cell_i.at[adm.row].set(jnp.asarray(ci_row))
        for shape in self.scheduler.live_shapes():
            self._dispatch_block(shape)
        return self.scheduler.pending() > 0

    def drain(self, until: Optional[int] = None) -> None:
        """Serve until the queue is empty (or replica ``until`` is
        terminal) — every cycle makes progress, so this terminates."""
        while self.scheduler.pending() > 0:
            if until is not None and \
                    self.scheduler.records[until].status in TERMINAL:
                return
            self.run_cycle()

    def evacuate(self) -> List[Tuple[ReplicaHandle, dict]]:
        """Retire every *resident* replica as PREEMPTED, returning their
        portable snapshots (host cell arrays + remaining budget) for
        readmission via ``submit(..., state=...)`` on a rebuilt server —
        the device-loss shrink path.  Queued replicas stay queued."""
        out = []
        for shape in list(self.scheduler.live_shapes()):
            rt = self._runtimes[shape]
            for row, rid in list(self.scheduler.occupants(shape)):
                rec = self.scheduler.records[rid]
                self._read_out(rt, rec)
                snap = dict(self._results[rid])
                snap["remaining_steps"] = \
                    rec.budget_steps - rec.steps_done
                self.scheduler.release(rid, status=PREEMPTED)
                self._clear_row(rt, row)
                out.append((self._handles[rid], snap))
        return out

    def stats(self) -> dict:
        """Serving summary: throughput, latency percentiles, compiles."""
        walls = np.asarray(self._step_walls, np.float64)
        c = self.obs.counter
        done = c("serve/replicas_done").value
        return {
            "replicas_done": done,
            "replicas_failed": c("serve/replicas_failed").value,
            "blocks": self._blocks,
            "compiles": c("serve/compiles").value,
            "shapes_touched": sorted(self.scheduler.shapes_touched),
            "useful_steps": c("serve/useful_steps").value,
            "wall_s": self._serve_wall_s,
            "replicas_per_s": done / max(self._serve_wall_s, 1e-9),
            "step_latency_p50_ms": float(np.percentile(walls, 50) * 1e3)
            if walls.size else 0.0,
            "step_latency_p99_ms": float(np.percentile(walls, 99) * 1e3)
            if walls.size else 0.0,
        }

    # ---- block dispatch ----------------------------------------------------

    def _dispatch_block(self, shape: Tuple[int, int]) -> None:
        rt = self._runtimes[shape]
        progs = self._programs[shape]
        t0 = time.time()
        cf, ci, force, _diag = progs.reb(rt.cell_f, rt.cell_i)
        if progs.prune is not None:
            sel, _cum, _cum_in, _occ = progs.prune(cf, ci)
            cf, ci, _fl, metrics, _ovf = progs.blk(cf, ci, force, sel)
        else:
            cf, ci, _fl, metrics = progs.blk(cf, ci, force)
        jax.block_until_ready(ci)
        dt = time.time() - t0
        rt.cell_f, rt.cell_i = cf, ci
        self._blocks += 1
        self._serve_wall_s += dt
        self._step_walls.append(dt / self.block_steps)
        self.obs.counter("serve/blocks").inc()
        self.obs.histogram("serve/block_s").observe(dt)
        self.obs.gauge(f"serve/occupancy/{shape[0]}x{shape[1]}").set(
            self.scheduler.occupancy(shape))
        if self.watchdog is not None:
            self.watchdog.observe(self._blocks - 1, dt)
        if self.wave_timeout_s is not None and dt > self.wave_timeout_s:
            raise WaveTimeout(
                f"bucket {shape[0]}x{shape[1]} block exceeded "
                f"{self.wave_timeout_s:.3f}s ({dt:.3f}s elapsed)")
        self.scheduler.advance(shape)
        # per-lane quarantine: the health observer is bitwise-neutral,
        # so reading it never perturbs co-residents
        bad = np.asarray(jax.device_get(metrics["health/nonfinite"]))
        bad = bad.reshape(shape[0], -1).sum(axis=1)
        for row, rid in self.scheduler.occupants(shape):
            if bad[row]:
                self.scheduler.mark_fault(rid, ReplicaFault(
                    f"replica {rid} went non-finite in bucket "
                    f"{shape[0]}x{shape[1]} row {row} "
                    f"({int(bad[row])} bad step-values); lane quarantined"))
        self._retire_due(shape)

    def _retire_due(self, shape: Tuple[int, int]) -> None:
        rt = self._runtimes[shape]
        for rid in self.scheduler.finished(shape):
            rec = self.scheduler.records[rid]
            self._read_out(rt, rec)
            row = rec.row
            rec = self.scheduler.release(rid)
            self._clear_row(rt, row)
            if rec.status == DONE:
                self.obs.counter("serve/replicas_done").inc()
                # reuse the LM wave-accounting mask: useful work is the
                # requested budget, not the padded block multiple
                self.obs.counter("serve/useful_steps").inc(masked_tokens(
                    [rec.steps_done], [rec.requested_steps]))
            elif rec.status == FAILED:
                self.obs.counter("serve/replicas_failed").inc()

    def _read_out(self, rt: _Runtime, rec) -> None:
        cf_row = np.asarray(jax.device_get(rt.cell_f[rec.row]))
        ci_row = np.asarray(jax.device_get(rt.cell_i[rec.row]))
        self._results[rec.rid] = {
            "cell_f": cf_row, "cell_i": ci_row,
            "steps": rec.steps_done,
            "requested_steps": rec.requested_steps,
            "atoms": _export_row(cf_row, ci_row, rec.n_atoms),
        }

    def _clear_row(self, rt: _Runtime, row: int) -> None:
        # a cleared row is physics-inert: no valid ids, zero occupancy —
        # rebin migrates nothing, forces see no atoms
        rt.cell_f = rt.cell_f.at[row].set(0.0)
        rt.cell_i = rt.cell_i.at[row].set(-1)


def _export_row(cf_row: np.ndarray, ci_row: np.ndarray,
                n_atoms: int) -> dict:
    """Per-atom positions/velocities in global-id order for one lane
    (the lane-local analogue of ``MDEngine.export_atoms``)."""
    ids = ci_row[..., 0].reshape(-1)
    valid = ids >= 0
    pos = np.zeros((n_atoms, 3), cf_row.dtype)
    vel = np.zeros((n_atoms, 3), cf_row.dtype)
    pos[ids[valid]] = cf_row[..., 0:3].reshape(-1, 3)[valid]
    vel[ids[valid]] = cf_row[..., 4:7].reshape(-1, 3)[valid]
    return {"pos": pos, "vel": vel}
