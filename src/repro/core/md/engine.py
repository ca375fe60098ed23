"""TPU-resident MD time-stepping with fused or serialized halo exchange.

The step structure mirrors the paper's Algorithm 2 (GPU-resident skeleton):

  1. coordinate halo exchange            (FusedPackCommX    -> exchange_fwd_*)
  2. non-bonded forces, local + non-local (NB F kernels      -> compute_forces)
  3. force halo exchange + accumulate     (FusedCommUnpackF -> exchange_rev_*)
  4. integration                          (update stream     -> velocity Verlet)

A whole ``nstlist`` block of steps is one jitted shard_map program: no
host round-trip between steps, the TPU analogue of "launch tens to
hundreds of time-steps before CPU-GPU sync" (paper §3).  The scan body is
delegated to :class:`repro.core.pipeline.StepPipeline`: ``pipeline="off"``
runs the strictly serialized reference chain, ``"double_buffer"`` the
software-pipelined schedule in which step N's force-return exchange is
issued in the same program region as step N+1's coordinate sends
(``pipeline_depth``-slot extended-force ring, signal-ledger bookkeeping;
``depth > 2`` unrolls ``depth - 1`` steps per fused region).
Re-binning/migration — GROMACS' DD + neighbor-search work — runs between
blocks as its own program, off the hot path (paper §5.4); with
``overlap_rebin=True`` the rebin/migration gather and the pair-schedule
prune are fused INTO the block program's final region instead (GROMACS'
DLB analogue: the nstlist-cadence work overlaps the last step's
force/epilogue rather than costing its own host dispatch).

State layout per device (all static shapes):
  cell_f (cz, cy, cx, K, 7)  [x, y, z, charge, vx, vy, vz]
  cell_i (cz, cy, cx, K, 2)  [atom id (-1 = empty), type]
  force  (cz, cy, cx, K, 3)  forces at t (velocity-Verlet carry)
"""
from __future__ import annotations

import dataclasses
import functools
import math
import warnings
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.compat import shard_map_norep

from repro.core.halo_plan import HaloPlan, HaloSpec
from repro.core.md import integrate
from repro.core.md.cells import CellLayout, choose_layout
from repro.core.md.domain import AXES, domain_index, rebin
from repro.core.md.forces import compute_forces
from repro.core.md.pair_schedule import (
    PAIR_BUCKET,
    SLOT_QUANTUM,
    PairSchedule,
    force_backends,
    get_force_backend,
    inner_radius as default_inner_radius,
    prune_local,
    prune_radius,
    roll_prune,
)
from repro.core.md.schedule_opt import (
    bucket,
    tier_cum,
    tier_plan,
    tier_rows,
    tier_slot_pairs,
)
from repro.core.md.schedule_opt import noop  # critical-path opt hook (§5.4)
from repro.core.md.system import MDSystem
from repro.core.pipeline import PIPELINE_MODES, StepFns, StepPipeline
from repro.core.pipeline.ledger import DISARMED, SCAN_FAULT_SITES
from repro.obs import PhaseTracer, default_registry
from repro.obs import span as obs_span


@dataclasses.dataclass
class RunState:
    """Live block-loop state of one simulation run.

    :meth:`MDEngine.begin_run` creates it; :meth:`MDEngine.run_block` and
    :meth:`MDEngine.advance_schedule` mutate it in place.  ``simulate``
    is a thin loop over these three, and the resilience runner
    (:mod:`repro.resilience`) drives the same API with fault arming,
    health reads, and checkpoint/rollback between blocks — both loops
    visit bitwise-identical states.
    """

    cell_f: jax.Array
    cell_i: jax.Array
    force: jax.Array          # velocity-Verlet force carry (post-rebin)
    sched: tuple | None       # (sel, tiers, tiers_inner) or None (dense)
    disable: bool             # next refresh falls back to the outer ladder
    step: int                 # steps completed so far
    diags: list               # per-rebin migration diagnostics


class MDEngine:
    """Binds a system + mesh + HaloSpec into jitted step/rebin programs.

    ``spec`` selects the halo backend and widths; the engine fills in the
    physics the spec leaves open (periodic wrap shifts from the box) and
    builds one :class:`HaloPlan` reused by every step/rebin/force program.
    ``pipeline`` selects the multi-step schedule (``"off"`` or
    ``"double_buffer"``, see :class:`repro.core.pipeline.StepPipeline`)
    and ``pipeline_depth`` its in-flight window (ring slots; 2 = the
    paper's double-buffered halos, >2 unrolls deeper windows);
    every (mode, depth) produces bitwise-identical trajectories.
    ``overlap_rebin=True`` fuses the between-block rebin/migration and
    pair-schedule prune into the block program's final region (one
    compiled dispatch per block instead of two or three); the fused and
    host-dispatched paths are bitwise-identical as well.

    ``force_backend`` selects the NB force engine
    (:mod:`repro.core.md.pair_schedule`): ``"dense"`` (default) is the
    unchanged 14-zone loop and keeps trajectories bitwise-identical to
    earlier engines; ``"sparse"`` / ``"pallas"`` execute the pruned
    cell-pair schedule (rebuilt every rebin, off the hot path) and match
    dense to tolerance.  ``capacity_safety`` is the per-cell slot
    headroom factor fed to :func:`choose_layout` — the padding the
    pruned backends stop paying for.  Degenerate layouts with a single
    global cell along any dim (a halo cell would alias its own periodic
    image) degrade to the dense backend with a warning instead of
    erroring.

    ``nstprune`` switches the pruned backends to GROMACS' **dual pair
    list**: the rebin-cadence prune builds the outer list at the
    Verlet-buffer radius, and every ``nstprune`` steps *inside* the
    block program a rolling prune re-partitions it with current
    coordinates at ``inner_radius`` (default
    :func:`repro.core.md.pair_schedule.inner_radius`: ``r_cut`` plus
    TWICE the 3-sigma drift over ``nstprune`` steps — both pair members
    move, same convention as the outer radius), so the evaluated tier
    ladder shrinks between rebins with no host round-trips.  The inner
    ladder is sized from the rebin-time histogram times
    ``inner_safety``; a refresh that outgrows it is counted
    (``pair_stats()["inner_overflow_blocks"]``), reported once as a
    warning, and the next block conservatively falls back to the outer
    ladder.
    """

    def __init__(self, system: MDSystem, mesh: Mesh,
                 spec: HaloSpec | None = None,
                 r_list_factor: float = 1.08, mig_frac: float = 0.125,
                 pipeline: str = "off", pipeline_depth: int = 2,
                 overlap_rebin: bool = False,
                 force_backend: str = "dense",
                 capacity_safety: float = 2.2,
                 nstprune: int = 0,
                 inner_radius: float | None = None,
                 inner_safety: float = 1.5,
                 pair_bucket: int = PAIR_BUCKET,
                 wire_dtype: str | None = None,
                 verify: str = "error",
                 obs=None, trace: bool = False,
                 inject: bool = False, health: bool = False,
                 layout_atoms: int | None = None,
                 static_ladder: bool = False):
        if spec is None:
            spec = HaloSpec(axis_names=AXES, widths=(1, 1, 1))
        if spec.axis_names != tuple(AXES):
            raise ValueError(f"MD halo spec must decompose over {AXES}, "
                             f"got {spec.axis_names}")
        if pipeline not in PIPELINE_MODES:
            raise ValueError(f"unknown pipeline mode {pipeline!r}; "
                             f"available: {PIPELINE_MODES}")
        if int(pipeline_depth) < 2:
            raise ValueError("pipeline_depth must be >= 2 (ring slots; "
                             "2 = double-buffered halos)")
        if min(spec.widths) < 1:
            raise ValueError("MD halo widths must be >= 1 (the NB stencil "
                             "consumes one halo cell layer)")
        if force_backend not in force_backends():
            raise ValueError(f"unknown force backend {force_backend!r}; "
                             f"available: {force_backends()}")
        if int(nstprune) < 0:
            raise ValueError("nstprune must be >= 0 (0 disables the "
                             "rolling inner prune)")
        if inject and overlap_rebin:
            raise ValueError(
                "inject=True is incompatible with overlap_rebin: fault "
                "epochs are block-aligned and the fused path would commit "
                "a poisoned block's rebin/migration before the health "
                "scalars are read at the boundary")
        # deterministic fault injection (repro.resilience): inject=True
        # builds the block programs with a traced fault-vector operand
        # (ledger.SCAN_FAULT_SITES layout); inject=False traces the exact
        # pre-existing programs — zero cost, bitwise-identical.  health
        # adds the pmax'd in-scan monitors (NaN/Inf counts, ledger
        # violations) to the block metrics.
        self.inject = bool(inject)
        self.health = bool(health)
        # rebuild()/reshard() recreate the engine from these; captured
        # before the tiny-box degrade below so a rebuilt engine re-derives
        # its own fallbacks for the (possibly different) new layout
        self._init_kwargs = dict(
            spec=spec, r_list_factor=r_list_factor, mig_frac=mig_frac,
            pipeline=pipeline, pipeline_depth=pipeline_depth,
            overlap_rebin=overlap_rebin, force_backend=force_backend,
            capacity_safety=capacity_safety, nstprune=nstprune,
            inner_radius=inner_radius, inner_safety=inner_safety,
            pair_bucket=pair_bucket, wire_dtype=wire_dtype, verify=verify,
            obs=obs, trace=trace, inject=inject, health=health,
            layout_atoms=layout_atoms, static_ladder=static_ladder)
        self.system = system
        self.mesh = mesh
        self.pipeline_mode = pipeline
        self.pipeline_depth = int(pipeline_depth)
        self.overlap_rebin = bool(overlap_rebin)
        mesh_shape = tuple(mesh.shape[a] for a in AXES)
        r_list = system.params.ff.r_cut * r_list_factor
        # ``layout_atoms`` sizes the cell capacity as if the system held
        # that many atoms — the SimServer bucket contract: every replica
        # of one (n_replicas, n_atoms_bucket) bucket shares the bucket's
        # layout, so a sub-bucket replica's solo reference run uses the
        # exact array shapes (and op sequence) of its batched row
        self.layout_atoms = int(layout_atoms) if layout_atoms else None
        self.layout = choose_layout(system.box, mesh_shape, r_list,
                                    self.layout_atoms or system.n_atoms,
                                    safety=capacity_safety)
        if force_backend != "dense" and min(self.layout.global_cells) < 2:
            # tiny-box path: a pair schedule cannot distinguish a halo
            # cell from its own periodic image here; fall back to the
            # dense engine (which masks self-image pairs by atom id)
            warnings.warn(
                f"layout {self.layout.global_cells} has a single global "
                f"cell along some dim; the {force_backend!r} pair "
                "schedule degrades to the 'dense' force backend",
                RuntimeWarning, stacklevel=2)
            force_backend = "dense"
        self.force_backend = force_backend
        if force_backend == "dense":
            nstprune = 0               # dual list rides the pair schedule
        # ``static_ladder``: the pruned backends execute a DATA-INDEPENDENT
        # worst-case tier ladder (every worklist row at the deepest level)
        # instead of the measured histogram's.  Exec shapes then depend on
        # the layout alone — the property the SimServer's no-recompile-at-
        # admission contract and its replica isolation both rest on: a
        # replica's ladder can neither retrace the block program nor leak
        # information about co-resident replicas.  The prune still runs
        # (``sel`` masks dropped pairs with the inert sentinel), so the
        # physics is unchanged; only the padding accounting grows.
        self.static_ladder = bool(static_ladder)
        if self.static_ladder and int(nstprune):
            raise ValueError(
                "static_ladder=True is incompatible with nstprune: the "
                "rolling inner prune exists to shrink the measured ladder "
                "the static ladder deliberately ignores")
        self.nstprune = int(nstprune)
        self.inner_safety = float(inner_safety)
        # pair-count quantum of the tier ladders: smaller = tighter exec
        # shapes (more distinct compiled block programs), larger = fewer
        # recompiles; PAIR_BUCKET is the production default
        self.pair_bucket = max(int(pair_bucket), 1)
        if self.nstprune:
            self.r_inner = float(
                default_inner_radius(system.params, self.nstprune)
                if inner_radius is None else inner_radius)
            if self.r_inner < system.params.ff.r_cut:
                raise ValueError(
                    f"inner_radius {self.r_inner} < r_cut "
                    f"{system.params.ff.r_cut}: the rolling prune would "
                    "drop interacting pairs outright")
        else:
            self.r_inner = None
        self.axis_sizes = mesh_shape
        self.mig_cap = max(64, int(self.layout.pool * mig_frac))
        self.pair_schedule = None
        self.r_prune = prune_radius(system.params)
        self._sched_exec = None     # (sel, tiers, tiers_inner) of last prune
        self._inner_overflows = 0   # blocks whose refresh outgrew the ladder
        # per-block (outer_rows, inner_rows) ladder sizes — the dual
        # list's activity trace (inner < outer = the rolling prune is
        # actually shrinking the evaluated schedule that block)
        self.sched_history: list[tuple[int, int]] = []
        if force_backend != "dense":
            self.pair_schedule = PairSchedule.build(self.layout)
            self._pair_stats = self.pair_schedule.slot_pair_stats()
            # the rebin's force carry: the whole unpruned worklist on the
            # worst-case ladder (exact, no prune or host read needed)
            self._carry_tiers = self.pair_schedule.full_tiers(
                self.pair_bucket)
            carry_slot_pairs = tier_slot_pairs(self._carry_tiers)
        else:
            # dense never builds a worklist (degenerate one-global-cell
            # layouts stay supported); mirror its accounting directly
            from repro.core.md.forces import stencil_pairs
            n_dense = len(stencil_pairs()) * self.layout.n_local_cells
            self._pair_stats = {
                "n_pairs_dense": n_dense,
                "k_capacity": self.layout.capacity,
                "dense_slot_pairs": n_dense * self.layout.capacity ** 2,
                "evaluated_slot_pairs": n_dense * self.layout.capacity ** 2,
                "prune_ratio": 1.0,
            }
            carry_slot_pairs = self._pair_stats["dense_slot_pairs"]
        self._pair_stats["force_backend"] = force_backend
        # which evaluator the rebin's force carry runs, and its slot pairs
        # per domain (one pass per rebin)
        self._carry_stats = {"carry_backend": force_backend,
                             "carry_slot_pairs": carry_slot_pairs}
        dt = system.pos.dtype
        if spec.wrap_shift is None:
            ws = np.zeros((3, 4), dt)
            for d in range(3):
                ws[d, d] = system.box[d]
            spec = spec.with_wrap_shift(ws)
        # feature layout for byte accounting: each exchanged cell carries
        # `capacity` atom slots of 4 floats (x, y, z, charge); the (K, 2)
        # int32 cell_i exchange is excluded from the canonical stats.
        # ``wire_dtype`` compresses the floating payload on the wire
        # (cell_i always rides dense); plan build runs the drift gate
        # with this engine's verify mode, so an over-aggressive wire
        # format is rejected here unless explicitly waived.
        if wire_dtype is not None:
            spec = dataclasses.replace(spec, wire_dtype=wire_dtype)
        self.wire_dtype = spec.wire_dtype
        self.plan = HaloPlan.build(
            dataclasses.replace(spec, dtype=np.dtype(dt).name,
                                feature_elems=4 * self.layout.capacity),
            mesh, verify=verify)
        self._spec = P(*AXES)
        # build-time gate: config sanity (nstprune vs block length, list
        # radii, pool/capacity factors) plus a static replay of the comm
        # schedule every block program will emit — unsafe configs are
        # rejected here with a counterexample trace instead of failing
        # deep in tracing (or corrupting trajectories silently).
        # ``verify="warn"`` downgrades to warnings, ``"off"`` skips.
        self._verify = verify
        from repro.analysis.schedule_verifier import gate_md_build
        self.schedule_report = gate_md_build(
            nstlist=int(system.params.nstlist), nstprune=self.nstprune,
            pipeline=self.pipeline_mode,
            pipeline_depth=self.pipeline_depth,
            overlap_rebin=self.overlap_rebin,
            force_backend=self.force_backend,
            n_pulses=max(1, self.plan.sched.total_pulses), verify=verify,
            inner_safety=self.inner_safety, r_list_factor=r_list_factor,
            mig_frac=mig_frac, capacity_safety=capacity_safety)
        # observability: every stats surface also publishes structured
        # records/instruments here; ``trace=True`` additionally threads
        # per-step ``obs/*`` ledger counters through the block programs
        # (barrier-neutral — trajectories stay bitwise-identical).
        self.obs = obs if obs is not None else default_registry()
        self.tracer = PhaseTracer(enabled=bool(trace))
        self.obs.emit(
            "engine_build", backend=self.backend,
            pipeline=self.pipeline_mode, pipeline_depth=self.pipeline_depth,
            overlap_rebin=self.overlap_rebin,
            force_backend=self.force_backend, nstprune=self.nstprune,
            n_atoms=system.n_atoms, global_cells=self.layout.global_cells,
            capacity=self.layout.capacity,
            schedule_safe=(None if self.schedule_report is None
                           else self.schedule_report.safe),
            **self._carry_stats)
        self._build_programs()

    @property
    def spec(self) -> HaloSpec:
        return self.plan.spec

    @property
    def backend(self) -> str:
        return self.plan.spec.backend

    def halo_stats(self) -> dict:
        """Plan-reported bytes/critical-path stats at this DD layout.

        On top of the canonical float payload this accounts the ``(K, 2)``
        int32 ``cell_i`` exchange (``bytes_index`` — hoisted to once per
        block, hence reported separately from the per-step payload) and
        the occupancy-adjusted ``useful_bytes``: the capacity padding is
        exchanged but carries no atoms.
        """
        K = self.layout.capacity
        gz, gy, gx = self.layout.global_cells
        occupancy = self.system.n_atoms / float(gz * gy * gx * K)
        return self.plan.publish_stats(self.obs,
                                       self.layout.cells_per_domain,
                                       index_elems=2 * K, index_itemsize=4,
                                       occupancy=occupancy,
                                       pipeline=self.pipeline_mode,
                                       depth=self.pipeline_depth)

    def pair_stats(self) -> dict:
        """Evaluated-slot-pair accounting of the latest pruned block.

        Per domain per step; ``prune_ratio`` is the dense-over-evaluated
        work reduction (1.0 for the dense backend).  ``carry_backend`` /
        ``carry_slot_pairs`` describe the rebin's force carry (per domain,
        once per rebin).
        """
        out = {**self._pair_stats, **self._carry_stats}
        if self.nstprune:
            # live counter, not the last _bucket_exec's snapshot: a
            # final block's overflow has no further rebin to record it
            out["inner_overflow_blocks"] = self._inner_overflows
        self.obs.emit("pair_stats", data=out)
        self.obs.gauge("md/prune_ratio").set(out.get("prune_ratio", 1.0))
        return out

    def overlap_stats(self) -> dict:
        """Per-step overlap model at this engine's pipeline mode/depth."""
        overlap = self.plan.stats(self.layout.cells_per_domain,
                                  pipeline=self.pipeline_mode,
                                  depth=self.pipeline_depth)["overlap"]
        self.obs.emit("overlap_model", backend=self.backend, data=overlap)
        return overlap

    def _trim_ext(self, ext):
        """First halo cell layer of an extended block (the NB stencil
        reaches exactly one cell); identity at the default widths."""
        if max(self.spec.widths) == 1:
            return ext
        n = self.layout.cells_per_domain
        return ext[tuple(slice(0, n[d] + 1) for d in range(3))]

    def _pad_force(self, F_trim, ext_shape):
        """Zero-pad trimmed forces back to the full extended block (layers
        beyond the first contribute nothing, the reverse path still
        returns them so widths > 1 stay trajectory-neutral)."""
        if max(self.spec.widths) == 1:
            return F_trim
        n = self.layout.cells_per_domain
        F = jnp.zeros(tuple(ext_shape[:3]) + F_trim.shape[3:], F_trim.dtype)
        return F.at[tuple(slice(0, n[d] + 1) for d in range(3))].set(F_trim)

    def _force_pass(self, cell_f, cell_i, forces=None):
        """Coordinate halo -> forces -> force halo (paper Alg. 3/6).

        ``forces(ext_f_trim, ext_i_trim)`` evaluates the NB forces on the
        trimmed extended block; the default is the dense 14-zone pass.
        Runs inside the engine's shard_map, so the plan's device-local
        methods are used; gradients through this pass would follow the
        plan's fused reverse path (``HaloPlan.exchange``).
        """
        ext_f = self.plan.fwd_local(cell_f[..., :4])
        ext_i = self.plan.fwd_local(cell_i, wrap_shift=None)
        if forces is None:
            def forces(ef, ei):
                return compute_forces(ef, ei, self.layout,
                                      self.system.params.ff)
        F_trim, pe = forces(self._trim_ext(ext_f), self._trim_ext(ext_i))
        f_local = self.plan.rev_local(self._pad_force(F_trim, ext_f.shape))
        return f_local, lax.psum(pe, AXES)

    def _force_pass_sched(self, cell_f, cell_i, sel, tiers):
        """Schedule-driven force pass (device-local, pruned backends)."""
        backend_fn = get_force_backend(self.force_backend)

        def forces(ef, ei):
            return backend_fn(
                ef, ei, self.layout, self.system.params.ff,
                sched=self.pair_schedule,
                sel=lax.slice(sel.reshape(-1), (0,), (tier_rows(tiers),)),
                tiers=tiers)
        return self._force_pass(cell_f, cell_i, forces)

    def _carry_pass(self, cell_f, cell_i):
        """The rebin's velocity-Verlet force carry, by the engine's own
        backend: the dense pass for ``dense``, else the full unpruned
        worklist on the worst-case ladder (every eighth-shell pair, masked
        at ``r_cut`` by the kernel — the same physics as the dense pass)."""
        if self.force_backend == "dense":
            return self._force_pass(cell_f, cell_i)
        sel = jnp.arange(self.pair_schedule.n_pairs, dtype=jnp.int32)
        return self._force_pass_sched(cell_f, cell_i, sel,
                                      self._carry_tiers)

    # ---- step physics, split at the halo seams (StepFns) -------------------

    def _make_step_fns(self) -> StepFns:
        """The per-step physics as pipeline callbacks.

        ``ctx`` carries the block-constant arrays: ``cell_i`` (atom
        ids/types never change within a block — migration runs between
        blocks), its pre-exchanged extension ``ext_i``, and — for the
        pruned force backends — the current pair schedule (``pair_sel``
        packed-pair prefix + the static ``tiers`` ladder), so both
        pipeline modes execute the same worklist.  With the rolling
        inner prune the engine swaps ``pair_sel``/``tiers`` between
        sub-blocks; each sub-block's ctx is still block-constant.
        """
        params = self.system.params
        mass, dt = params.mass, params.dt
        layout, ff = self.layout, params.ff
        backend_fn = get_force_backend(self.force_backend)
        sched = self.pair_schedule

        def eval_forces(ext_f_trim, ext_i_trim, ctx):
            if "pair_sel" not in ctx:      # dense: the unchanged path
                return compute_forces(ext_f_trim, ext_i_trim, layout, ff)
            return backend_fn(ext_f_trim, ext_i_trim, layout, ff,
                              sched=sched, sel=ctx["pair_sel"],
                              tiers=ctx["tiers"])

        def begin(cell_f, force, ctx):
            valid = ctx["cell_i"][..., 0] >= 0
            vmask = valid[..., None]
            # velocity Verlet: kick-drift
            vel_half = cell_f[..., 4:7] + jnp.where(
                vmask, force * (dt / (2 * mass)), 0.0)
            pos_new = cell_f[..., :3] + jnp.where(vmask, vel_half * dt, 0.0)
            cell_f = cell_f.at[..., :3].set(pos_new)
            return cell_f, vel_half, cell_f[..., :4]

        def force(ext_f, ctx):
            F_trim, pe = eval_forces(self._trim_ext(ext_f),
                                     ctx["ext_i_trim"], ctx)
            return self._pad_force(F_trim, ext_f.shape), \
                {"pe": lax.psum(pe, AXES)}

        def finish(cell_f, vel_half, f_new, ctx):
            valid = ctx["cell_i"][..., 0] >= 0
            vmask = valid[..., None]
            f_new = jnp.where(vmask, f_new, 0.0)
            # kick; the where between the product and the sum (same form
            # as the kick-drift in ``begin``) keeps the rounding fixed —
            # a bare mul+add can FMA-contract differently depending on how
            # the surrounding halo-backend graph fuses
            vel_new = vel_half + jnp.where(vmask,
                                           f_new * (dt / (2 * mass)), 0.0)
            cell_f = cell_f.at[..., 4:7].set(jnp.where(vmask, vel_new, 0.0))
            ke = integrate.kinetic_energy(vel_new, valid, mass)
            mom = integrate.momentum(jnp.where(vmask, vel_new, 0.0),
                                     valid, mass)
            noop()  # schedule-optimization hook (see schedule_opt)
            m = {"ke": ke, "mom": mom}
            if self.health:
                # in-scan NaN/Inf monitor: one pmax-free psum'd int32 per
                # step over positions/velocities and the returned forces;
                # a pure observer of barrier-pinned state, so trajectories
                # stay bitwise-identical with health on
                bad = (jnp.sum(~jnp.isfinite(cell_f), dtype=jnp.int32)
                       + jnp.sum(~jnp.isfinite(f_new), dtype=jnp.int32))
                m["health/nonfinite"] = lax.psum(bad, AXES)
            return cell_f, f_new, m

        return StepFns(begin=begin, force=force, finish=finish)

    # ---- programs ----------------------------------------------------------

    def _block_ctx(self, cell_i):
        return {"cell_i": cell_i,
                "ext_i_trim": self._trim_ext(
                    self.plan.fwd_local(cell_i, wrap_shift=None))}

    def _build_programs(self):
        layout, mig_cap = self.layout, self.mig_cap
        # verify="off": the engine's own gate already verified a superset
        # (block length, nstprune sub-blocks, rebin fusion) of what the
        # pipeline-level gate would re-probe
        self.pipeline = StepPipeline.build(self.plan, self._make_step_fns(),
                                           mode=self.pipeline_mode,
                                           depth=self.pipeline_depth,
                                           verify="off",
                                           tracer=self.tracer,
                                           inject=self.inject)
        sc = self.tracer.scope

        def run_pipe(cell_f, force, n_steps, ctx):
            """Pipeline invocation + the per-invocation ledger monitor."""
            cell_f, f_last, m, led = self.pipeline.run_local(
                cell_f, force, n_steps, ctx)
            if self.health:
                # ledger-invariant monitor: 1 iff any put-with-signal
                # bookkeeping law was violated over this invocation
                # (undrained deposits, acquire-before-release, slot
                # clobber) — pmax'd so every device reports the global
                # verdict, read with the other boundary scalars
                lg = self.pipeline.ledger
                bad = (jnp.not_equal(lg.in_flight(led), 0)
                       | ~lg.consistent(led)
                       | ~lg.window_safe(led)).astype(jnp.int32)
                m = {**m, "health/led_violation": lax.pmax(bad, AXES)[None]}
            return cell_f, f_last, m

        def block_impl(cell_f, cell_i, force, fv, n_steps):
            ctx = self._block_ctx(cell_i)
            if fv is not None:
                ctx["fault_vec"] = fv
            cell_f, f_last, metrics = run_pipe(cell_f, force, n_steps, ctx)
            return cell_f, cell_i, f_last, metrics

        def block(cell_f, cell_i, force, n_steps):
            return block_impl(cell_f, cell_i, force, None, n_steps)

        def block_sched_impl(cell_f, cell_i, force, sel, fv, n_steps,
                             tiers, tiers_inner):
            """Pruned-backend block; ``tiers``/``tiers_inner`` static.

            With an inner ladder the block is a python-unrolled chain of
            ``nstprune``-step sub-blocks: each starts with the rolling
            prune (current-coordinate re-partition of the outer prefix,
            :func:`repro.core.md.pair_schedule.roll_prune`) and runs the
            step pipeline over the inner ladder only.  The returned
            overflow scalar counts survivors the static ladder could not
            seat (0 = the inner approximation held).
            """
            ctx = self._block_ctx(cell_i)
            sel_flat = sel.reshape(-1)
            zero = jnp.zeros((), jnp.int32)
            if not tiers_inner:
                ctx["pair_sel"] = lax.slice(sel_flat, (0,),
                                            (tier_rows(tiers),))
                ctx["tiers"] = tiers
                if fv is not None:
                    ctx["fault_vec"] = fv
                cell_f, f_last, metrics = run_pipe(cell_f, force, n_steps,
                                                   ctx)
                return cell_f, cell_i, f_last, metrics, zero
            L = self.pair_schedule.levels
            budget = jnp.asarray(tier_cum(tiers_inner, SLOT_QUANTUM, L),
                                 jnp.int32)
            n_inner = tier_rows(tiers_inner)
            sel_exec = lax.slice(sel_flat, (0,), (tier_rows(tiers),))
            overflow, f_cur, chunks, done = zero, force, [], 0
            while done < n_steps:
                take = min(self.nstprune, n_steps - done)
                # the done=0 refresh re-derives the inner partition the
                # boundary prune already saw (same coordinates) — kept
                # deliberately: sel stays outer-packed so force_fn /
                # the outer-ladder fallback remain valid on it, and the
                # cost is one exchange + sort per nstlist block, off
                # the per-step path
                with sc("roll_prune"):
                    ext_f = self.plan.fwd_local(cell_f[..., :4])
                    sel_exec, cum_s = roll_prune(
                        self.pair_schedule, sel_exec, self._trim_ext(ext_f),
                        ctx["ext_i_trim"], self.r_inner)
                overflow = jnp.maximum(
                    overflow, jnp.max(jnp.maximum(cum_s - budget, 0)))
                ctx_s = dict(ctx)
                ctx_s["pair_sel"] = lax.slice(sel_exec, (0,), (n_inner,))
                ctx_s["tiers"] = tiers_inner
                if fv is not None:
                    # rebase block-relative fault steps onto this
                    # sub-block's local scan indices; out-of-range sites
                    # stay disarmed here and fire in their own sub-block
                    ctx_s["fault_vec"] = jnp.where(
                        (fv >= done) & (fv < done + take),
                        fv - done, jnp.int32(DISARMED))
                cell_f, f_cur, m = run_pipe(cell_f, f_cur, take, ctx_s)
                chunks.append(m)
                done += take
            metrics = {k: jnp.concatenate([c[k] for c in chunks])
                       for k in chunks[0]}
            return (cell_f, cell_i, f_cur, metrics,
                    lax.pmax(overflow, AXES))

        def block_sched(cell_f, cell_i, force, sel, n_steps, tiers,
                        tiers_inner):
            return block_sched_impl(cell_f, cell_i, force, sel, None,
                                    n_steps, tiers, tiers_inner)

        def rebin_program(cell_f, cell_i):
            with sc("rebin"):
                new_f, new_i, diag = rebin(cell_f, cell_i, layout, mig_cap)
                with sc("rebin_force"):
                    force, pe = self._carry_pass(new_f[..., :4], new_i)
                    force = jnp.where(new_i[..., 0:1] >= 0, force, 0.0)
            return new_f, new_i, force, diag

        def prune_program(cell_f, cell_i):
            with sc("prune"):
                ext_f = self.plan.fwd_local(cell_f[..., :4])
                ext_i = self.plan.fwd_local(cell_i, wrap_shift=None)
                sel, cum, cum_inner, occ = prune_local(
                    self.pair_schedule, self._trim_ext(ext_f),
                    self._trim_ext(ext_i), self.r_prune,
                    r_inner=self.r_inner)
                # the exec shapes must agree across the SPMD mesh: every
                # domain sizes to the global worst case
                cum = lax.pmax(cum, AXES)
                cum_inner = lax.pmax(cum_inner, AXES)
                occ = lax.pmax(occ, AXES)
            return sel[None, None, None], cum, cum_inner, occ

        # device-local program bodies, exposed for external composition:
        # repro.serve.SimServer wraps these in vmap under its own
        # shard_map to stack independent replicas into one bucketed
        # block program (each vmap lane runs this exact op sequence, so
        # a batched row's trajectory stays bitwise-identical to a solo
        # run of the same engine config)
        self.local_programs = {
            "block": block, "block_sched": block_sched,
            "rebin": rebin_program, "prune": prune_program,
        }

        # overlap_rebin: the nstlist-cadence DLB work (migration gather +
        # occupancy/bbox prune) fused into the block program's final
        # region instead of host-dispatched between blocks.  The seam is
        # barrier-pinned so fusing cannot perturb the step physics — the
        # fused and host-dispatched paths stay bitwise-identical.

        def block_rebin(cell_f, cell_i, force, n_steps):
            cell_f, cell_i, _f_last, metrics = block(cell_f, cell_i, force,
                                                     n_steps)
            cell_f, cell_i = lax.optimization_barrier((cell_f, cell_i))
            with sc("rebin_seam"):
                new_f, new_i, force, diag = rebin_program(cell_f, cell_i)
            return new_f, new_i, force, metrics, diag

        def block_sched_rebin(cell_f, cell_i, force, sel, n_steps, tiers,
                              tiers_inner):
            cell_f, cell_i, _f_last, metrics, ovf = block_sched(
                cell_f, cell_i, force, sel, n_steps, tiers, tiers_inner)
            cell_f, cell_i = lax.optimization_barrier((cell_f, cell_i))
            with sc("rebin_seam"):
                new_f, new_i, force, diag = rebin_program(cell_f, cell_i)
                sel2, cum, cum_inner, occ = prune_program(new_f, new_i)
            return (new_f, new_i, force, metrics, diag, sel2, cum,
                    cum_inner, occ, ovf)

        spec = self._spec
        if self.inject:
            # the fault vector is a small replicated operand — NOT a jit
            # constant — so re-arming between blocks never retraces
            self.block_fn = jax.jit(
                shard_map_norep(
                    block_impl, mesh=self.mesh,
                    in_specs=(spec, spec, spec, P(), None),
                    out_specs=(spec, spec, spec, P()),
                ),
                static_argnums=(4,),
            )
        else:
            self.block_fn = jax.jit(
                shard_map_norep(
                    functools.partial(block),
                    mesh=self.mesh,
                    in_specs=(spec, spec, spec, None),
                    out_specs=(spec, spec, spec, P()),
                ),
                static_argnums=(3,),
            )
        self.rebin_fn = jax.jit(shard_map_norep(
            rebin_program, mesh=self.mesh, in_specs=(spec, spec),
            out_specs=(spec, spec, spec, P())))
        self._force_fn_dense = jax.jit(shard_map_norep(
            lambda f, i: self._force_pass(f[..., :4], i),
            mesh=self.mesh, in_specs=(spec, spec), out_specs=(spec, P())))
        if self.overlap_rebin:
            self.block_rebin_fn = jax.jit(
                shard_map_norep(
                    block_rebin, mesh=self.mesh,
                    in_specs=(spec, spec, spec, None),
                    out_specs=(spec, spec, spec, P(), P()),
                ),
                static_argnums=(3,),
            )
        if self.force_backend != "dense":
            if self.inject:
                self.block_sched_fn = jax.jit(
                    shard_map_norep(
                        block_sched_impl, mesh=self.mesh,
                        in_specs=(spec, spec, spec, spec, P(), None, None,
                                  None),
                        out_specs=(spec, spec, spec, P(), P()),
                    ),
                    static_argnums=(5, 6, 7),
                )
            else:
                self.block_sched_fn = jax.jit(
                    shard_map_norep(
                        block_sched, mesh=self.mesh,
                        in_specs=(spec, spec, spec, spec, None, None, None),
                        out_specs=(spec, spec, spec, P(), P()),
                    ),
                    static_argnums=(4, 5, 6),
                )
            self.prune_fn = jax.jit(shard_map_norep(
                prune_program, mesh=self.mesh, in_specs=(spec, spec),
                out_specs=(spec, P(), P(), P())))
            self._force_fn_sched = jax.jit(
                shard_map_norep(
                    self._force_pass_sched, mesh=self.mesh,
                    in_specs=(spec, spec, spec, None),
                    out_specs=(spec, P()),
                ),
                static_argnums=(3,),
            )
            if self.overlap_rebin:
                self.block_sched_rebin_fn = jax.jit(
                    shard_map_norep(
                        block_sched_rebin, mesh=self.mesh,
                        in_specs=(spec, spec, spec, spec, None, None,
                                  None),
                        out_specs=(spec, spec, spec, P(), P(), spec,
                                   P(), P(), P(), P()),
                    ),
                    static_argnums=(4, 5, 6),
                )

    def force_fn(self, cell_f, cell_i):
        """One force pass (halo fwd -> NB -> halo rev) on global arrays.

        Dispatches to the engine's force backend; the pruned backends use
        the schedule of the most recent rebin (``simulate`` refreshes it),
        falling back to a fresh prune when none exists yet.
        """
        if self.force_backend == "dense":
            return self._force_fn_dense(cell_f, cell_i)
        if self._sched_exec is None:
            self._refresh_schedule(cell_f, cell_i)
        sel, tiers, _tiers_inner = self._sched_exec
        return self._force_fn_sched(cell_f, cell_i, sel, tiers)

    # ---- state init ----------------------------------------------------------

    def bin_host(self, system: MDSystem | None = None):
        """Host-side binning of a system into numpy cell arrays.

        Defaults to the engine's own system; passing another system bins
        it under THIS engine's layout (the SimServer admission path: a
        replica whose box matches the bucket's is binned into the bucket
        shapes before being written into a batch row)."""
        sys, layout = system or self.system, self.layout
        G = layout.global_cells
        K = layout.capacity
        cs = np.asarray(layout.cell_size)
        pos = np.mod(np.asarray(sys.pos, np.float64), sys.box)
        cell3 = np.minimum((pos / cs).astype(np.int64),
                           np.asarray(G) - 1)
        flat = (cell3[:, 0] * G[1] + cell3[:, 1]) * G[2] + cell3[:, 2]
        order = np.argsort(flat, kind="stable")
        sf = flat[order]
        first = np.searchsorted(sf, sf, side="left")
        rank = np.arange(sf.shape[0]) - first
        if np.any(rank >= K):
            raise ValueError("cell capacity overflow at init; raise safety")
        dtype = sys.pos.dtype
        cell_f = np.zeros((G[0], G[1], G[2], K, 7), dtype)
        cell_i = np.full((G[0], G[1], G[2], K, 2), -1, np.int32)
        gz, gy, gx = cell3[order].T
        cell_f[gz, gy, gx, rank, 0:3] = pos[order].astype(dtype)
        cell_f[gz, gy, gx, rank, 3] = np.asarray(sys.charge)[order]
        cell_f[gz, gy, gx, rank, 4:7] = np.asarray(sys.vel)[order]
        cell_i[gz, gy, gx, rank, 0] = np.arange(sys.n_atoms)[order]
        cell_i[gz, gy, gx, rank, 1] = np.asarray(sys.typ)[order]
        return cell_f, cell_i

    def init_state(self):
        """Bin the global system into the stacked global cell arrays."""
        cell_f, cell_i = self.bin_host()
        shard = NamedSharding(self.mesh, self._spec)
        return (jax.device_put(jnp.asarray(cell_f), shard),
                jax.device_put(jnp.asarray(cell_i), shard))

    # ---- drivers ---------------------------------------------------------------

    def _refresh_schedule(self, cell_f, cell_i, disable_inner: bool = False):
        """Re-prune the pair worklist for the next block (nstlist cadence).

        Runs right after ``rebin_fn`` — the same off-hot-path slot as the
        migration/NS program (paper §5.4).  The host reads the global
        per-level pair histograms + max occupancy and buckets them into
        the static tier ladders of the block program.
        """
        if self.force_backend == "dense":
            return None
        with obs_span("prune_dispatch", self.obs):
            sel, cum, cum_inner, occ = self.prune_fn(cell_f, cell_i)
        return self._bucket_exec(sel, cum, cum_inner, occ,
                                 disable_inner=disable_inner)

    def _bucket_exec(self, sel, cum, cum_inner, occ,
                     disable_inner: bool = False):
        """Host half of the prune: read the global histograms and bucket
        them into the static tier ladders of the next block program
        (shared by the host-dispatched and ``overlap_rebin``-fused
        prunes).  ``disable_inner`` is the overflow fallback — one block
        on the outer ladder after a refresh outgrew the inner one."""
        with obs_span("schedule_read", self.obs):
            M = self.pair_schedule.n_pairs
            K = self.layout.capacity
            cum = [int(v) for v in jax.device_get(cum)]
            cum_inner = [int(v) for v in jax.device_get(cum_inner)]
            occ = int(jax.device_get(occ))
            n_keep = cum[0]         # measured survivors (stats stay honest)
            if self.static_ladder:
                # worst-case histogram: all M rows at the deepest level —
                # one (M, K) tier, constant across blocks and replicas
                cum = [M] * len(cum)
            tiers = tier_plan(cum, self.pair_bucket, M, SLOT_QUANTUM, K)
            tiers_inner = ()
            if self.nstprune and not disable_inner:
                # inner ladder: rebin-time inner histogram, safety-
                # margined for drift until the next rebin, never above
                # the outer one
                cum_in = [min(int(math.ceil(ci * self.inner_safety)), co)
                          for ci, co in zip(cum_inner, cum)]
                tiers_inner = tier_plan(cum_in, self.pair_bucket, M,
                                        SLOT_QUANTUM, K)
            # what the old single-rectangle schedule (one global
            # k_exec) would have evaluated — the per-pair-bound gain
            # baseline
            global_kexec = bucket(cum[0], self.pair_bucket, M) * \
                bucket(occ, SLOT_QUANTUM, K) ** 2 if cum[0] else 0
            self._pair_stats = self.pair_schedule.slot_pair_stats(
                tiers=tiers, tiers_inner=tiers_inner, n_keep=n_keep,
                n_inner=cum_inner[0], max_occupancy=occ,
                global_kexec_slot_pairs=global_kexec)
            self._pair_stats.update({
                "force_backend": self.force_backend,
                "nstprune": self.nstprune,
                "inner_radius": self.r_inner,
                "inner_overflow_blocks": self._inner_overflows,
                "inner_disabled": bool(self.nstprune and disable_inner),
            })
            outer_rows = tier_rows(tiers)
            inner_rows = (tier_rows(tiers_inner) if tiers_inner
                          else outer_rows)
            self.sched_history.append((outer_rows, inner_rows))
            self.obs.gauge("md/outer_rows").set(outer_rows)
            self.obs.gauge("md/inner_rows").set(inner_rows)
            self.obs.emit(
                "sched_update", block=len(self.sched_history),
                outer_rows=outer_rows, inner_rows=inner_rows,
                max_occupancy=occ,
                inner_disabled=bool(self.nstprune and disable_inner))
            self._sched_exec = (sel, tiers, tiers_inner)
            return self._sched_exec

    def _note_overflow(self, ovf) -> bool:
        """Record a block's rolling-prune overflow scalar; True if the
        next block must fall back to the outer ladder."""
        if not self.nstprune:
            return False
        with obs_span("overflow_read", self.obs):
            ovf = int(jax.device_get(ovf))
        if ovf == 0:
            return False
        self._inner_overflows += 1
        self.obs.counter("md/inner_overflow_blocks").inc()
        if self._inner_overflows == 1:
            warnings.warn(
                "rolling inner prune overflowed its tier ladder (more "
                "survivors than the rebin-time sizing allowed); falling "
                "back to the outer pair list for the next block — raise "
                "inner_safety to avoid this", RuntimeWarning,
                stacklevel=3)
        return True

    def begin_run(self, state=None, disable_inner: bool = False):
        """Open a block-loop run: bin (or adopt) the state, run the first
        rebin + prune, and return the live :class:`RunState`.

        ``disable_inner=True`` starts the first block on the outer ladder
        (the resume-after-overflow / degraded-restore path)."""
        if state is None:
            cell_f, cell_i = self.init_state()
        else:
            cell_f, cell_i = state
        with obs_span("rebin_dispatch", self.obs):
            cell_f, cell_i, force, diag = self.rebin_fn(cell_f, cell_i)
        sched = self._refresh_schedule(cell_f, cell_i,
                                       disable_inner=disable_inner)
        with obs_span("diag_read", self.obs):
            diag = jax.device_get(diag)
        return RunState(cell_f, cell_i, force, sched,
                        bool(disable_inner), 0, [diag])

    def _fault_operand(self, fault_vec):
        """Normalize a fault vector to the replicated int32 operand the
        injected block programs take (None = every site disarmed)."""
        if fault_vec is None:
            return jnp.full((len(SCAN_FAULT_SITES),), DISARMED, jnp.int32)
        fv = jnp.asarray(fault_vec, jnp.int32)
        if fv.shape != (len(SCAN_FAULT_SITES),):
            raise ValueError(
                f"fault_vec must have shape ({len(SCAN_FAULT_SITES)},) "
                f"— one block-relative step per site in "
                f"{SCAN_FAULT_SITES} — got {fv.shape}")
        return fv

    def run_block(self, rs: RunState, take: int, fuse: bool = False,
                  fault_vec=None, force_overflow: bool = False):
        """Advance one ``take``-step block on a live :class:`RunState`
        (mutated in place); returns the block's device-side metrics.

        ``fault_vec`` arms the scan fault sites of an ``inject=True``
        engine for this block (``ledger.SCAN_FAULT_SITES`` layout,
        block-relative steps, -1 disarmed); ``force_overflow`` feeds the
        overflow monitor a synthetic trip (the forced-inner-ladder-
        overflow fault site — only meaningful on the ``nstprune`` path).
        """
        if (fault_vec is not None or force_overflow) and not self.inject:
            raise ValueError("fault arming requires an inject=True engine")
        sched = rs.sched
        with obs_span("block_dispatch", self.obs, steps=take,
                      fused_rebin=fuse):
            if fuse and sched is None:
                rs.cell_f, rs.cell_i, rs.force, m, diag = \
                    self.block_rebin_fn(rs.cell_f, rs.cell_i, rs.force,
                                        take)
            elif fuse:
                sel, tiers, tiers_inner = sched
                (rs.cell_f, rs.cell_i, rs.force, m, diag, sel2, cum,
                 cum_inner, occ, ovf) = \
                    self.block_sched_rebin_fn(rs.cell_f, rs.cell_i,
                                              rs.force, sel, take, tiers,
                                              tiers_inner)
                rs.sched = self._bucket_exec(
                    sel2, cum, cum_inner, occ,
                    disable_inner=self._note_overflow(ovf))
            elif sched is None:
                if self.inject:
                    rs.cell_f, rs.cell_i, rs.force, m = self.block_fn(
                        rs.cell_f, rs.cell_i, rs.force,
                        self._fault_operand(fault_vec), take)
                else:
                    rs.cell_f, rs.cell_i, rs.force, m = self.block_fn(
                        rs.cell_f, rs.cell_i, rs.force, take)
            else:
                sel, tiers, tiers_inner = sched
                if self.inject:
                    rs.cell_f, rs.cell_i, rs.force, m, ovf = \
                        self.block_sched_fn(
                            rs.cell_f, rs.cell_i, rs.force, sel,
                            self._fault_operand(fault_vec), take, tiers,
                            tiers_inner)
                else:
                    rs.cell_f, rs.cell_i, rs.force, m, ovf = \
                        self.block_sched_fn(rs.cell_f, rs.cell_i,
                                            rs.force, sel, take, tiers,
                                            tiers_inner)
                # read the block's overflow scalar NOW (not at the next
                # boundary) so a final block's overflow is still
                # counted and warned — the monitor has no blind spot
                rs.disable = self._note_overflow(
                    jnp.int32(1) if force_overflow else ovf)
        self.obs.counter("md/blocks").inc()
        self.obs.counter("md/steps").inc(take)
        rs.step += take
        if fuse:
            with obs_span("diag_read", self.obs):
                rs.diags.append(jax.device_get(diag))
        return m

    def advance_schedule(self, rs: RunState):
        """The between-block rebin + prune (host-dispatched path only;
        fused blocks already carried theirs)."""
        old_sched = rs.sched
        with obs_span("rebin_dispatch", self.obs):
            cell_f, cell_i, force, diag = self.rebin_fn(rs.cell_f,
                                                        rs.cell_i)
        rs.sched = self._refresh_schedule(
            cell_f, cell_i,
            disable_inner=old_sched is not None and rs.disable)
        rs.cell_f, rs.cell_i, rs.force = cell_f, cell_i, force
        rs.disable = False
        with obs_span("diag_read", self.obs):
            rs.diags.append(jax.device_get(diag))

    def simulate(self, n_steps: int, state=None, collect=True,
                 on_boundary=None):
        """Run n_steps in nstlist-sized TPU-resident blocks.

        With ``overlap_rebin`` every block that another block follows is
        one fused dispatch (steps + rebin/migration + prune); the final
        block — after which the host path would not rebin either — runs
        the plain block program.  Both paths visit bitwise-identical
        states and the host still reads only the prune histograms (two
        small per-level vectors + occupancy + overflow scalars) per
        block boundary.

        ``on_boundary`` is the block-boundary admission hook: called as
        ``on_boundary(rs)`` at every interior block boundary, BEFORE the
        boundary rebin — the host-visible point the SimServer admits and
        retires replicas at.  The hook may mutate ``rs.cell_f`` /
        ``rs.cell_i`` in place; the boundary rebin that follows
        re-derives the force carry and pair schedule from whatever state
        it finds, so mutated atoms never run under a stale schedule.
        (First-block admission is the ``state`` argument itself.)
        """
        nst = self.system.params.nstlist
        if on_boundary is not None and self.overlap_rebin:
            raise ValueError(
                "on_boundary is incompatible with overlap_rebin: the "
                "fused block carries its own rebin, so a boundary "
                "mutation would run under the already-derived schedule")
        with obs_span("simulate", self.obs, n_steps=n_steps):
            rs = self.begin_run(state)
            all_metrics = []
            while rs.step < n_steps:
                take = min(nst, n_steps - rs.step)
                fuse = self.overlap_rebin and rs.step + take < n_steps
                m = self.run_block(rs, take, fuse=fuse)
                if collect:
                    with obs_span("metrics_read", self.obs):
                        all_metrics.append(jax.device_get(m))
                if not fuse and rs.step < n_steps:
                    if on_boundary is not None:
                        on_boundary(rs)
                    self.advance_schedule(rs)
            metrics = {}
            if collect and all_metrics:
                with obs_span("metrics_read", self.obs):
                    metrics = {k: np.concatenate([np.atleast_1d(m[k])
                                                  for m in all_metrics])
                               for k in all_metrics[0]}
            with obs_span("snapshot", self.obs):
                obs_keys = [k for k in metrics if k.startswith("obs/")]
                if obs_keys:
                    # the traced per-step ledger counters, as one record
                    # the Perfetto exporter turns into counter tracks
                    self.obs.emit("step_counters",
                                  data={k: metrics[k] for k in obs_keys})
                self.obs.snapshot(label="md/simulate", n_steps=n_steps,
                                  backend=self.backend,
                                  pipeline=self.pipeline_mode)
        return (rs.cell_f, rs.cell_i), metrics, rs.diags

    def gather_by_id(self, arrays, cell_i):
        """Host-side: reassemble per-atom arrays ordered by global id."""
        ids = np.asarray(jax.device_get(cell_i))[..., 0].reshape(-1)
        out = []
        for a in arrays:
            flat = np.asarray(jax.device_get(a)).reshape(ids.shape[0], -1)
            dest = np.zeros((self.system.n_atoms, flat.shape[-1]),
                            flat.dtype)
            valid = ids >= 0
            dest[ids[valid]] = flat[valid]
            out.append(dest)
        return out

    # ---- elasticity (rebuild / reshard) -----------------------------------

    def export_atoms(self, state) -> dict:
        """Mesh-independent snapshot of a cell state: per-atom positions
        and velocities in global-id order (the portable half of a
        checkpoint — restorable onto any mesh/layout)."""
        cell_f, cell_i = state
        pos, vel = self.gather_by_id(
            [cell_f[..., :3], cell_f[..., 4:7]], cell_i)
        return {"pos": pos, "vel": vel}

    def rebuild(self, mesh: Mesh = None, system: MDSystem = None,
                **overrides) -> "MDEngine":
        """A fresh engine with this engine's construction parameters,
        selectively overridden.

        Any ``__init__`` keyword can be overridden; additionally
        ``backend="..."`` rewrites the halo spec's backend (the degrade
        ladder's signal→serialized rung).  The caller re-enters via
        :meth:`begin_run` / :meth:`init_state` — compiled programs are
        not carried over.
        """
        kw = dict(self._init_kwargs)
        backend = overrides.pop("backend", None)
        kw.update(overrides)
        if backend is not None:
            base = kw["spec"] if kw["spec"] is not None else \
                HaloSpec(axis_names=AXES, widths=(1, 1, 1))
            kw["spec"] = dataclasses.replace(base, backend=backend)
        return MDEngine(system if system is not None else self.system,
                        mesh if mesh is not None else self.mesh, **kw)

    def reshard(self, mesh: Mesh, state=None, atoms=None,
                **overrides) -> "MDEngine":
        """Elastic reshard: rebuild this engine on a different mesh and
        carry the atoms over (the device-loss shrink path, promoting the
        ``check_elastic.py`` restore-on-smaller-mesh math to runtime).

        Pass either the live cell ``state`` (exported here) or a
        pre-exported ``atoms`` dict (the checkpointed form — the one a
        *lost* device's state is recovered from).  Returns the new
        engine; the caller re-bins with ``begin_run()`` (``init_state``
        re-bins the carried atoms under the new layout/sharding).
        """
        if atoms is None:
            if state is None:
                raise ValueError("reshard needs `state` or `atoms`")
            atoms = self.export_atoms(state)
        dt = self.system.pos.dtype
        system = dataclasses.replace(
            self.system,
            pos=np.asarray(atoms["pos"], dt),
            vel=np.asarray(atoms["vel"], dt))
        return self.rebuild(mesh=mesh, system=system, **overrides)
