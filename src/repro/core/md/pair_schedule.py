"""Pruned cell-pair force schedules: the sparse NB engine (paper §5.4).

The paper's speedups depend on the non-bonded force kernels — the hot
loop — staying saturated while halo communication overlaps (§5.4).
GROMACS gets there with its **dual pair list** (Páll et al. 2020): an
outer list built coarsely at neighbor-search time with the Verlet-buffer
radius, re-pruned cheaply every few steps into an inner list at a tighter
cutoff, executed by batched cluster-pair kernels.  The dense engine path
(:func:`repro.core.md.forces.compute_forces`) ignores all of that: it
evaluates every ``K x K`` slot pair of all 14 eighth-shell zone products
over the full cell grid, padding slots included.

This module is the pair-list analogue for the cell scheme:

* :class:`PairSchedule` — the **static worklist**: all
  ``14 * n_local_cells`` eighth-shell cell pairs of one domain, enumerated
  once per :class:`~repro.core.md.cells.CellLayout` as flat indices into
  the trimmed extended (home + one halo layer) cell array.  This is the
  DD-time coarse list build.

* :func:`prune_local` — the rebin-cadence **outer prune**: runs device-
  local (inside the engine's shard_map) right where ``rebin_fn`` already
  executes, off the hot step path (see
  :mod:`repro.core.md.schedule_opt`).  Pairs are dropped when either cell
  is empty (cell membership is frozen within a block, so this is exact)
  or when the cells' atom bounding boxes are further apart than the prune
  radius (:func:`prune_radius`, the Verlet-buffer analogue: ``r_cut``
  plus twice the expected per-block drift).  Survivors are packed
  front-first **sorted by descending per-pair slot bound** (the
  occupancy level ``ceil(max(count_a, count_b) / SLOT_QUANTUM)``), so
  dense cell pairs land in full batches at the head of the list and the
  shallow/sentinel tail shrinks; the prune reports a cumulative
  per-level histogram that :func:`repro.core.md.schedule_opt.tier_plan`
  turns into a static ladder of ``(n_rows, k_slots)`` tiers — per-pair
  slot bounds replace the old single rectangular ``k_exec``.

* :func:`roll_prune` — the ``nstprune``-cadence **rolling inner prune**
  (GROMACS' dual-cutoff scheme): *inside* the fused block program, the
  outer exec prefix is re-partitioned with current coordinates — pairs
  whose bounding boxes sit beyond :func:`inner_radius` are stably sorted
  behind the survivors (survivors stay in descending-level order, so the
  tier invariant holds) and the force pass evaluates only the
  host-sized inner tier ladder.  ``n_exec`` shrinks between rebins with
  no host round-trip; a dropped pair re-enters on a later refresh
  because every refresh re-examines the full outer prefix.  A refresh
  whose survivors outgrow the inner ladder reports a nonzero overflow
  count (read by the host with the block's other prune scalars), and
  the engine falls back to the outer ladder for the next block.

* :func:`get_force_backend` — a registry of force engines sharing one
  signature:

  - ``"dense"``  — the unchanged 14-zone jnp loop; the **bitwise
    reference** (trajectories are identical to the pre-schedule engine).
  - ``"sparse"`` — jnp evaluation over the pruned worklist only, packed
    per-tier ``(N_t, K_t, 4)`` A/B batches with gather/scatter-add
    epilogues.
  - ``"pallas"`` — the same batches executed by the tuned Pallas
    cluster-pair kernel (:func:`repro.kernels.nonbonded.pair_forces_accum`,
    compiled on a TPU, interpreted on the CPU; a kernel failure raises).
    Both sparse and pallas consume
    the per-pair occupancy counts directly (validity masks are
    ``slot < count`` — binning packs each cell's atoms into a contiguous
    slot prefix).

  Sparse and pallas match dense to tolerance (summation order differs);
  they are *not* bitwise.  Per-tier ``K_t`` (the evaluated slot depth)
  can be much smaller than the layout capacity ``K`` — the 2.2x capacity
  safety padding is what the schedule stops paying for, and the tier
  ladder stops paying the global-max occupancy for mostly-shallow pairs.

The engine threads the block-constant schedule (``pair_sel``, ``tiers``)
through the :class:`~repro.core.pipeline.step_pipeline.StepFns` context,
so both pipeline modes (``off`` / ``double_buffer``) execute the same
pruned worklist.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Callable, Dict, Optional, Sequence, Tuple

import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.core.md.cells import CellLayout, cell_bounds, cell_counts, \
    cell_levels
from repro.core.md.forces import compute_forces, pair_terms
from repro.core.md.schedule_opt import tier_plan, tier_rows, tier_slot_pairs
from repro.core.md.system import ForceField, MDParams

# exec-shape quanta: surviving pair counts bucket to multiples of
# PAIR_BUCKET and slot depths to multiples of SLOT_QUANTUM (matching the
# capacity padding in choose_layout), so the per-block prune produces only
# a handful of distinct compiled block programs
PAIR_BUCKET = 64
SLOT_QUANTUM = 4

_BIG = 1e30  # empty-cell bounding-box sentinel (finite: no inf-inf NaNs)


def n_levels(capacity: int) -> int:
    """Occupancy levels of a layout: ``ceil(capacity / SLOT_QUANTUM)``."""
    return -(-int(capacity) // SLOT_QUANTUM)


# --------------------------------------------------------------------------
# static worklist (built once per layout — the DD-time list build)
# --------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class PairSchedule:
    """Static eighth-shell cell-pair worklist of one domain.

    ``cell_a`` / ``cell_b`` are flat indices into the trimmed extended
    cell array ``(cz+1, cy+1, cx+1)`` reshaped to ``(n_ext_cells, K,
    ...)``; ``same`` flags the self pairs (triangle masking).  Shapes are
    static per layout; the dynamic part (which pairs survive a block) is
    the ``sel`` vector produced by :func:`prune_local` /
    :func:`roll_prune`.
    """

    layout: CellLayout
    cell_a: np.ndarray    # (M,) int32
    cell_b: np.ndarray    # (M,) int32
    same: np.ndarray      # (M,) int32

    @classmethod
    def build(cls, layout: CellLayout) -> "PairSchedule":
        for d in range(3):
            if layout.global_cells[d] < 2:
                raise ValueError(
                    "pair schedules need >= 2 global cells per dim "
                    f"(got {layout.global_cells}): with one global cell a "
                    "halo cell aliases its own periodic image, which only "
                    "the dense path's id mask handles")
        from repro.core.md.forces import stencil_pairs
        cz, cy, cx = layout.cells_per_domain
        ez, ey, ex = cz + 1, cy + 1, cx + 1
        base = np.stack(np.meshgrid(np.arange(cz), np.arange(cy),
                                    np.arange(cx), indexing="ij"),
                        axis=-1).reshape(-1, 3)

        def flat(cells3):
            return ((cells3[:, 0] * ey + cells3[:, 1]) * ex
                    + cells3[:, 2]).astype(np.int32)

        cell_a, cell_b, same = [], [], []
        for a, b in stencil_pairs():
            cell_a.append(flat(base + np.asarray(a)))
            cell_b.append(flat(base + np.asarray(b)))
            same.append(np.full(base.shape[0], int(a == b), np.int32))
        return cls(layout=layout,
                   cell_a=np.concatenate(cell_a),
                   cell_b=np.concatenate(cell_b),
                   same=np.concatenate(same))

    @property
    def n_pairs(self) -> int:
        """Worklist length M = 14 * n_local_cells (the dense pair count)."""
        return int(self.cell_a.shape[0])

    @property
    def n_ext_cells(self) -> int:
        cz, cy, cx = self.layout.cells_per_domain
        return (cz + 1) * (cy + 1) * (cx + 1)

    @property
    def levels(self) -> int:
        """Occupancy-level count of this layout's tier ladders."""
        return n_levels(self.layout.capacity)

    def full_tiers(self, pair_bucket: int):
        """The data-independent worst-case ladder: every worklist row at
        the deepest level, one ``(M, K)`` tier.  Evaluated over
        ``sel = arange(M)`` it covers every eighth-shell pair, so it
        needs no prune (the ``static_ladder`` block ladder and the
        rebin's force carry)."""
        M = self.n_pairs
        return tier_plan([M] * self.levels, pair_bucket, M, SLOT_QUANTUM,
                         self.layout.capacity)

    def dense_slot_pairs(self) -> int:
        """Slot pairs the dense engine evaluates per domain per step."""
        return self.n_pairs * self.layout.capacity ** 2

    def slot_pair_stats(self, tiers: Optional[Sequence] = None,
                        tiers_inner: Optional[Sequence] = None,
                        n_keep: Optional[int] = None,
                        n_inner: Optional[int] = None,
                        max_occupancy: Optional[int] = None,
                        global_kexec_slot_pairs: Optional[int] = None
                        ) -> dict:
        """Evaluated-work accounting for one pruned block (per domain).

        ``tiers`` is the outer ladder, ``tiers_inner`` the rolling-prune
        ladder actually executed between refreshes (when the dual list is
        on).  ``global_kexec_slot_pairs`` is the accounting the old
        single-rectangle schedule (one global ``k_exec``) would have
        reported — kept so the per-pair-bound gain stays visible.
        """
        dense = self.dense_slot_pairs()
        out = {
            "n_pairs_dense": self.n_pairs,
            "k_capacity": self.layout.capacity,
            "dense_slot_pairs": dense,
        }
        if tiers is None:
            out.update({"evaluated_slot_pairs": dense, "prune_ratio": 1.0})
            return out
        outer = tier_slot_pairs(tiers)
        evaluated = tier_slot_pairs(tiers_inner) if tiers_inner else outer
        out.update({
            "n_pairs_exec": tier_rows(tiers),
            "n_pairs_kept": None if n_keep is None else int(n_keep),
            "tiers": [list(t) for t in tiers],
            "tiers_inner": None if not tiers_inner
            else [list(t) for t in tiers_inner],
            "n_pairs_inner": None if n_inner is None else int(n_inner),
            "max_occupancy": None if max_occupancy is None
            else int(max_occupancy),
            "outer_slot_pairs": outer,
            "evaluated_slot_pairs": evaluated,
            "global_kexec_slot_pairs": global_kexec_slot_pairs,
            "prune_ratio": dense / max(evaluated, 1),
        })
        if global_kexec_slot_pairs:
            out["per_pair_bound_gain"] = \
                global_kexec_slot_pairs / max(evaluated, 1)
        return out


def _drift(params: MDParams, steps: int) -> float:
    """Expected 3-sigma thermal drift of one atom over ``steps`` steps."""
    return steps * params.dt * 3.0 * math.sqrt(
        params.temperature / params.mass)


def prune_radius(params: MDParams) -> float:
    """Verlet-buffer analogue for the outer bounding-box prune.

    Bounding boxes are sampled at rebin time and go stale as atoms drift
    during the block, so the prune keeps every pair whose boxes come
    within ``r_cut`` plus twice the expected per-block drift (3-sigma
    thermal velocity over ``nstlist`` steps) — GROMACS' ``r_list``
    buffer, sized for the same cadence.
    """
    return params.ff.r_cut + 2.0 * _drift(params, params.nstlist)


def inner_radius(params: MDParams, nstprune: int) -> float:
    """Inner cutoff of the rolling prune (the dual list's second radius).

    Sized like :func:`prune_radius` but for the ``nstprune`` refresh
    cadence: a pair dropped by a refresh needs more than a 3-sigma drift
    to come within ``r_cut`` before the next refresh re-examines it.
    """
    return params.ff.r_cut + 2.0 * _drift(params, max(int(nstprune), 1))


# --------------------------------------------------------------------------
# rebin-cadence outer prune (device-local, off the hot path)
# --------------------------------------------------------------------------

def _pair_geometry(sched: PairSchedule, ext_f, ext_i, idx):
    """Per-pair (bbox gap^2, same flag, occupancy level) at ``idx`` rows.

    ``idx`` holds worklist rows in ``[0, M]`` (``M`` = sentinel).  The
    level is the per-pair slot bound quantized by ``SLOT_QUANTUM``
    (sentinel rows report level 0).
    """
    M = sched.n_pairs
    ne = sched.n_ext_cells
    counts = cell_counts(ext_i).reshape(ne)
    lvl_cell = cell_levels(counts, SLOT_QUANTUM)
    lo, hi = cell_bounds(ext_f[..., :3], ext_i, big=_BIG)
    lo, hi = lo.reshape(ne, 3), hi.reshape(ne, 3)

    ca = jnp.concatenate([jnp.asarray(sched.cell_a),
                          jnp.asarray([ne], jnp.int32)])[idx]
    cb = jnp.concatenate([jnp.asarray(sched.cell_b),
                          jnp.asarray([ne], jnp.int32)])[idx]
    same = jnp.concatenate([jnp.asarray(sched.same),
                            jnp.asarray([0], jnp.int32)])[idx]
    counts_p = jnp.concatenate([counts, jnp.zeros((1,), counts.dtype)])
    lvl_p = jnp.concatenate([lvl_cell, jnp.zeros((1,), lvl_cell.dtype)])
    gap = jnp.maximum(0.0, jnp.maximum(
        lo[jnp.clip(ca, 0, ne - 1)] - hi[jnp.clip(cb, 0, ne - 1)],
        lo[jnp.clip(cb, 0, ne - 1)] - hi[jnp.clip(ca, 0, ne - 1)]))
    d2 = jnp.sum(gap * gap, axis=-1)
    d2 = jnp.where(idx >= M, jnp.asarray(_BIG, d2.dtype), d2)
    lvl = jnp.maximum(lvl_p[ca], lvl_p[cb])
    return d2, same, lvl, counts_p[ca], counts_p[cb]


def _pack_by_level(keep, lvl, L: int, base=None):
    """Occupancy-sorted packing: kept rows first, by DESCENDING level,
    original order preserved within a level (stable argsort).  Returns
    the permutation and the cumulative per-level histogram ``cum``
    (``cum[l-1]`` = kept rows with level >= ``l``)."""
    n = keep.shape[0]
    key = jnp.where(keep, L - lvl, L + 1).astype(jnp.int32)
    order = jnp.argsort(key, stable=True).astype(jnp.int32)
    hist = jnp.zeros((L + 1,), jnp.int32).at[
        jnp.where(keep, lvl, 0)].add(1, mode="drop")
    cum = jnp.flip(jnp.cumsum(jnp.flip(hist[1:])))
    if base is None:
        base = jnp.arange(n, dtype=jnp.int32)
    return base[order], cum


def prune_local(sched: PairSchedule, ext_f: jnp.ndarray, ext_i: jnp.ndarray,
                r_prune: float, r_inner: Optional[float] = None):
    """Outer prune of the static worklist for one block (in shard_map).

    ``ext_f`` / ``ext_i`` are the TRIMMED extended arrays (home + one halo
    cell layer, the NB stencil's reach).  Returns ``(sel, cum, cum_inner,
    max_occ)``: ``sel`` (M,) int32 holds the surviving worklist rows
    packed first, sorted by descending occupancy level (original order
    within a level), with the sentinel ``M`` in the padding tail;
    ``cum`` / ``cum_inner`` are the cumulative per-level histograms of
    the outer survivors and of the subset also within ``r_inner`` (for
    sizing the rolling prune's ladder — ``r_inner=None`` reports the
    outer histogram twice); ``max_occ`` is the max cell occupancy.  The
    host buckets the histograms into static tier ladders (see
    :func:`repro.core.md.schedule_opt.tier_plan`).
    """
    M = sched.n_pairs
    L = sched.levels
    idx = jnp.arange(M, dtype=jnp.int32)
    d2, same, lvl, cnt_a, cnt_b = _pair_geometry(sched, ext_f, ext_i, idx)
    occupied = (cnt_a > 0) & (cnt_b > 0)
    keep = jnp.where(
        same > 0,
        cnt_a >= 2,                                # self pair: >= 1 real pair
        occupied & (d2 < jnp.asarray(r_prune ** 2, d2.dtype)))
    order, cum = _pack_by_level(keep, lvl, L)
    sel = jnp.where(jnp.arange(M) < cum[0], order, M).astype(jnp.int32)
    if r_inner is None:
        cum_inner = cum
    else:
        keep_in = keep & ((same > 0) |
                          (d2 < jnp.asarray(r_inner ** 2, d2.dtype)))
        _, cum_inner = _pack_by_level(keep_in, lvl, L)
    ne = sched.n_ext_cells
    max_occ = jnp.max(cell_counts(ext_i).reshape(ne)).astype(jnp.int32)
    return sel, cum, cum_inner, max_occ


# --------------------------------------------------------------------------
# nstprune-cadence rolling inner prune (inside the block program)
# --------------------------------------------------------------------------

def roll_prune(sched: PairSchedule, sel: jnp.ndarray, ext_f, ext_i,
               r_inner: float):
    """Re-partition the outer exec prefix with CURRENT coordinates.

    ``sel`` is the packed outer prefix (rows in ``[0, M]``, sentinel
    ``M``).  Pairs whose bounding boxes now sit beyond ``r_inner`` are
    stably sorted behind the survivors; survivors are re-sorted by
    descending occupancy level, so the inner tier ladder's per-pair
    bounds stay valid.  Dropped pairs remain in the list (a later
    refresh re-examines every row, so pairs drifting back in are
    resurrected) — rows past the inner ladder are simply not evaluated,
    and any dropped pair still inside the ladder contributes exactly
    zero force (its bbox gap lower-bounds every atom distance at
    ``r_inner >= r_cut``).

    Returns ``(new_sel, cum_surv)``; ``cum_surv[l-1]`` (survivors with
    level >= ``l``) is compared against the ladder's static row budget
    by the engine's overflow monitor.
    """
    L = sched.levels
    d2, same, lvl, cnt_a, _cnt_b = _pair_geometry(sched, ext_f, ext_i, sel)
    keep = (sel < sched.n_pairs) & \
        ((same > 0) | (d2 < jnp.asarray(r_inner ** 2, d2.dtype)))
    new_sel, cum = _pack_by_level(keep, lvl, L, base=sel)
    return new_sel, cum


# --------------------------------------------------------------------------
# batched execution over the pruned worklist (per-tier)
# --------------------------------------------------------------------------

def _padded_ext(sched: PairSchedule, ext_f, ext_i):
    """Flatten + pad the extended arrays for sentinel-safe pair gathers.

    The sentinel worklist row ``M`` routes padding entries to an extra
    all-empty cell at flat index ``n_ext_cells`` (count 0, types -1,
    coords 0), so no masking branch is needed downstream — the kernels'
    count masks kill padding work and the scatter epilogue accumulates it
    into the sliced-off sentinel row.
    """
    ne = sched.n_ext_cells
    K = ext_f.shape[3]
    f2 = ext_f.reshape(ne, K, ext_f.shape[-1])
    id2 = ext_i[..., 0].reshape(ne, K)
    t2 = ext_i[..., 1].reshape(ne, K)
    typ = jnp.where(id2 >= 0, t2, -1).astype(jnp.int32)
    f2p = jnp.concatenate([f2, jnp.zeros((1,) + f2.shape[1:], f2.dtype)])
    tp = jnp.concatenate([typ, jnp.full((1, K), -1, jnp.int32)])
    counts = cell_counts(ext_i).reshape(ne)
    cp = jnp.concatenate([counts, jnp.zeros((1,), counts.dtype)]) \
        .astype(jnp.int32)
    ca_p = jnp.concatenate([jnp.asarray(sched.cell_a),
                            jnp.asarray([ne], jnp.int32)])
    cb_p = jnp.concatenate([jnp.asarray(sched.cell_b),
                            jnp.asarray([ne], jnp.int32)])
    same_p = jnp.concatenate([jnp.asarray(sched.same),
                              jnp.asarray([0], jnp.int32)])
    return f2p, tp, cp, ca_p, cb_p, same_p


def _gather_tier(padded, sel_t, k_exec: int):
    """Pack one tier's pairs into (N_t, K_t, ...) A/B batches + counts."""
    f2p, tp, cp, ca_p, cb_p, same_p = padded
    ca = ca_p[sel_t]
    cb = cb_p[sel_t]
    same = same_p[sel_t]
    fk = f2p[:, :k_exec]
    tk = tp[:, :k_exec]
    return (fk[ca], fk[cb], tk[ca], tk[cb], same, ca, cb,
            jnp.minimum(cp[ca], k_exec), jnp.minimum(cp[cb], k_exec))


def _pair_forces_jnp(a, b, ta, tb, same, cnt_a, cnt_b, ff: ForceField):
    """jnp twin of the Pallas cluster-pair kernel (one batch).

    Same masks and math as ``kernels.nonbonded._pair_kernel``; validity
    comes from the per-pair occupancy counts (``slot < count`` — binning
    packs atoms into a contiguous slot prefix).  The optimization
    barriers pin the K-wide reductions exactly like the dense path does
    (see forces.py), so sparse trajectories stay bitwise stable across
    halo backends and pipeline modes.
    """
    kk = a.shape[1]
    dtype = a.dtype
    pos_a, q_a = a[..., :3], a[..., 3]
    pos_b, q_b = b[..., :3], b[..., 3]
    dx = pos_a[:, :, None, :] - pos_b[:, None, :, :]
    r2 = jnp.sum(dx * dx, axis=-1)
    iota = jnp.arange(kk, dtype=jnp.int32)[None, :]
    mask = (iota < cnt_a[:, None])[:, :, None] & \
        (iota < cnt_b[:, None])[:, None, :]
    mask &= r2 < jnp.asarray(ff.r_cut ** 2, dtype)
    tri = jnp.triu(jnp.ones((kk, kk), jnp.bool_), k=1)[None]
    mask &= jnp.where(same[:, None, None] > 0, tri,
                      jnp.ones((1, kk, kk), jnp.bool_))

    eps_t = jnp.asarray(ff.eps, dtype)
    sig_t = jnp.asarray(ff.sigma, dtype)
    tai = jnp.clip(ta, 0, eps_t.shape[0] - 1)
    tbi = jnp.clip(tb, 0, eps_t.shape[0] - 1)
    eps = eps_t[tai[:, :, None], tbi[:, None, :]]
    sig = sig_t[tai[:, :, None], tbi[:, None, :]]
    # pin the pair-term operands too: left free, XLA fuses the r2 and
    # gather producers into the pair-term fusion differently in the
    # step pipeline's prologue than in its scan body, and the energy
    # then rounds differently (1 ulp) between pipeline modes
    dx, r2, q_a, q_b, eps, sig, mask = lax.optimization_barrier(
        (dx, r2, q_a, q_b, eps, sig, mask))
    fac, pe = pair_terms(dx, r2, q_a[:, :, None], q_b[:, None, :],
                         eps, sig, ff, mask)
    fvec = lax.optimization_barrier(fac[..., None] * dx)
    fa = lax.optimization_barrier(jnp.sum(fvec, axis=2))
    fb = lax.optimization_barrier(-jnp.sum(fvec, axis=1))
    return fa, fb, lax.optimization_barrier(jnp.sum(pe, axis=(1, 2)))


def _eval_schedule(ext_f, ext_i, layout: CellLayout, ff: ForceField, *,
                   sched: PairSchedule, sel, tiers,
                   use_pallas: bool):
    """Evaluate the tiered worklist: gather -> pair kernel -> scatter-add.

    ``tiers`` is the static ``((n_rows, k_slots), ...)`` ladder, deepest
    first; ``sel`` covers at least the ladder's total rows.  Returns
    ``(F_ext, pe)`` in the same layout as ``compute_forces`` (the trimmed
    extended force array with halo partial sums).  Tier accumulation
    order is fixed by the python loop, so reductions stay deterministic.
    """
    ne = sched.n_ext_cells
    K = ext_f.shape[3]
    padded = _padded_ext(sched, ext_f, ext_i)
    F_acc = jnp.zeros((ne + 1, K, 3), ext_f.dtype)
    pe_total = jnp.zeros((), ext_f.dtype)
    off = 0
    for n_t, k_t in tiers:
        k_t = min(int(k_t), K)
        sel_t = lax.slice(sel, (off,), (off + int(n_t),))
        off += int(n_t)
        a, b, ta, tb, same, ca, cb, cnt_a, cnt_b = _gather_tier(
            padded, sel_t, k_t)
        if use_pallas:
            from repro.kernels import nonbonded
            # the kernel + its scatter-accumulate epilogue; the sentinel
            # row ne absorbs padding entries and is sliced off below
            F, pe_pairs = nonbonded.pair_forces_accum(
                a, b, ta, tb, same, ca, cb, ff, ne + 1,
                cnt_a=cnt_a, cnt_b=cnt_b)
        else:
            fa, fb, pe_pairs = _pair_forces_jnp(a, b, ta, tb, same,
                                                cnt_a, cnt_b, ff)
            F = jnp.zeros((ne + 1, k_t, 3), ext_f.dtype)
            F = F.at[ca].add(fa, mode="drop")
            F = F.at[cb].add(fb, mode="drop")
        F_acc = F_acc.at[:, :k_t].add(F)
        pe_total = pe_total + jnp.sum(pe_pairs)
    F_out = lax.optimization_barrier(F_acc[:ne])
    F_ext = F_out.reshape(ext_f.shape[:3] + (K, 3))
    return F_ext, pe_total


# --------------------------------------------------------------------------
# force-backend registry
# --------------------------------------------------------------------------

def _dense(ext_f, ext_i, layout, ff, **_):
    """The unchanged 14-zone loop: the bitwise trajectory reference."""
    return compute_forces(ext_f, ext_i, layout, ff)


def _norm_tiers(sel, tiers, k_exec):
    """Accept the legacy single-rectangle call shape (``k_exec=`` alone
    means one tier spanning the whole ``sel`` prefix)."""
    if tiers is None:
        if k_exec is None:
            raise ValueError("pruned backends need tiers= (or k_exec=)")
        return ((int(sel.shape[0]), int(k_exec)),)
    return tuple((int(n), int(k)) for n, k in tiers)


def _sparse(ext_f, ext_i, layout, ff, *, sched, sel, tiers=None,
            k_exec=None):
    return _eval_schedule(ext_f, ext_i, layout, ff, sched=sched, sel=sel,
                          tiers=_norm_tiers(sel, tiers, k_exec),
                          use_pallas=False)


def _pallas(ext_f, ext_i, layout, ff, *, sched, sel, tiers=None,
            k_exec=None):
    return _eval_schedule(ext_f, ext_i, layout, ff, sched=sched, sel=sel,
                          tiers=_norm_tiers(sel, tiers, k_exec),
                          use_pallas=True)


ForceBackend = Callable[..., Tuple[jnp.ndarray, jnp.ndarray]]
_FORCE_BACKENDS: Dict[str, ForceBackend] = {}


def register_force_backend(name: str, fn: ForceBackend) -> None:
    """Register a force engine under ``name`` (the config axis value)."""
    _FORCE_BACKENDS[name] = fn


def force_backends() -> Tuple[str, ...]:
    return tuple(sorted(_FORCE_BACKENDS))


def get_force_backend(name: str) -> ForceBackend:
    try:
        return _FORCE_BACKENDS[name]
    except KeyError:
        raise ValueError(
            f"unknown force backend {name!r}; "
            f"available: {force_backends()}") from None


register_force_backend("dense", _dense)
register_force_backend("sparse", _sparse)
register_force_backend("pallas", _pallas)
