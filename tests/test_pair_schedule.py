"""Pair-schedule force engine: worklist/prune invariants + backend parity.

The functional guarantee under test: for any occupancy pattern — empty
cells, capacity-full (overflow-adjacent) cells, random fills — the pruned
``"sparse"`` / ``"pallas"`` backends reproduce the dense 14-zone forces
(and the O(N^2) direct oracle) to dtype-scaled tolerance, i.e. the prune
never drops a contributing pair and padding slots contribute nothing.

The dual pair-list properties ride on top (hypothesis sweeps below):
the outer list is conservative under any bounded drift replay, the
rolling inner prune never drops a pair within the inner radius, the
occupancy-sorted tier packing never truncates a pair's real occupancy,
and every packing is a permutation (no duplicated or lost worklist
rows).
"""
import numpy as np
import pytest

try:
    from hypothesis import given, settings
    from hypothesis import strategies as st
except ImportError:  # property tests skip; hypothesis is a dev extra
    from _hypothesis_stub import given, settings, st

import jax.numpy as jnp
from jax import lax

from repro.core.md import make_grappa_like
from repro.core.md import pair_schedule as psched
from repro.core.md.cells import (
    bin_to_cells,
    cell_bounds,
    cell_counts,
    cell_levels,
    choose_layout,
)
from repro.core.md.forces import compute_forces, stencil_pairs
from repro.core.md.schedule_opt import (
    bucket,
    bucket0,
    tier_cum,
    tier_plan,
    tier_rows,
    tier_slot_pairs,
)
from repro.core.md.system import DEFAULT_FF, MDParams

# tolerance of the sparse/pallas-vs-dense parity, scaled to max |F|:
# identical per-pair math, different summation order (float32)
FORCE_RTOL = 5e-6
PE_RTOL = 5e-6


def periodic_extend(cell_f4, cell_i, box):
    """One-device halo oracle: wrap each dim's first layer to the far side
    (coordinate-shifted), mirroring the engine's fused exchange."""
    ef = np.array(cell_f4)
    ei = np.array(cell_i)
    for d in range(3):
        slab_f = np.take(ef, [0], axis=d).copy()
        slab_valid = np.take(ei, [0], axis=d)[..., 0] >= 0
        slab_f[..., d] = np.where(slab_valid, slab_f[..., d] + box[d], 0.0)
        ef = np.concatenate([ef, slab_f], axis=d)
        ei = np.concatenate([ei, np.take(ei, [0], axis=d)], axis=d)
    return jnp.asarray(ef), jnp.asarray(ei)


def plan_tiers(sched, layout, cum):
    return tier_plan([int(v) for v in cum], psched.PAIR_BUCKET,
                     sched.n_pairs, psched.SLOT_QUANTUM, layout.capacity)


def eval_backends(layout, ext_f, ext_i, ff, params):
    """Dense + pruned-backend forces on the same extended arrays."""
    F_d, pe_d = compute_forces(ext_f, ext_i, layout, ff)
    sched = psched.PairSchedule.build(layout)
    sel, cum, _cum_in, occ = psched.prune_local(
        sched, ext_f, ext_i, psched.prune_radius(params))
    tiers = plan_tiers(sched, layout, cum)
    sel_exec = lax.slice(sel, (0,), (tier_rows(tiers),))
    out = {"dense": (F_d, pe_d),
           "_shapes": (int(cum[0]), tiers, int(occ))}
    for name in ("sparse", "pallas"):
        out[name] = psched.get_force_backend(name)(
            ext_f, ext_i, layout, ff, sched=sched, sel=sel_exec,
            tiers=tiers)
    return out


def assert_parity(out):
    F_d, pe_d = out["dense"]
    scale = max(float(jnp.abs(F_d).max()), 1.0)
    for name in ("sparse", "pallas"):
        F, pe = out[name]
        assert float(jnp.abs(F - F_d).max()) / scale < FORCE_RTOL, name
        assert abs(float(pe - pe_d)) / max(abs(float(pe_d)), 1.0) \
            < PE_RTOL, name


# ---- static worklist ------------------------------------------------------

def test_worklist_is_static_eighth_shell():
    layout = choose_layout((8.0, 8.0, 8.0), (1, 1, 1), 2.6, 400)
    sched = psched.PairSchedule.build(layout)
    ncells = layout.n_local_cells
    assert sched.n_pairs == 14 * ncells == len(stencil_pairs()) * ncells
    ne = sched.n_ext_cells
    assert sched.cell_a.min() >= 0 and sched.cell_a.max() < ne
    assert sched.cell_b.min() >= 0 and sched.cell_b.max() < ne
    assert int(sched.same.sum()) == ncells
    assert np.all(sched.cell_a[sched.same > 0]
                  == sched.cell_b[sched.same > 0])
    assert sched.dense_slot_pairs() == 14 * ncells * layout.capacity ** 2
    assert sched.levels == -(-layout.capacity // psched.SLOT_QUANTUM)


def test_worklist_rejects_single_global_cell():
    layout = choose_layout((3.0, 8.0, 8.0), (1, 1, 1), 2.6, 100)
    assert layout.global_cells[0] == 1
    with pytest.raises(ValueError, match="2 global cells"):
        psched.PairSchedule.build(layout)


def test_engine_degrades_to_dense_on_single_global_cell():
    """Tiny-box regression: the engine must not crash on layouts the
    pair schedule rejects — it degrades to the dense backend (which
    masks self-image pairs by atom id) with a warning, and the rolling
    prune is disabled along with it."""
    from repro.core.md import MDEngine
    from repro.launch.mesh import make_mesh

    sys_ = make_grappa_like(110, seed=3)
    mesh = make_mesh((1, 1, 1), ("z", "y", "x"))
    with pytest.warns(RuntimeWarning, match="degrades to the 'dense'"):
        eng = MDEngine(sys_, mesh, force_backend="sparse", nstprune=5)
    assert min(eng.layout.global_cells) == 1
    assert eng.force_backend == "dense"
    assert eng.nstprune == 0 and eng.pair_schedule is None
    (cf, ci), m, diags = eng.simulate(8)
    assert np.all(np.isfinite(np.asarray(m["pe"])))
    assert eng.pair_stats()["prune_ratio"] == 1.0


def test_bucket_quantization():
    assert bucket(0, 64, 1000) == 64
    assert bucket(65, 64, 1000) == 128
    assert bucket(999, 64, 140) == 140        # capped
    assert bucket(7, 4, 84) == 8
    assert bucket(84, 4, 84) == 84
    assert bucket0(0, 64, 1000) == 0          # empty tiers are dropped
    assert bucket0(1, 64, 1000) == 64


def test_tier_plan_ladder():
    # cum[l-1] = pairs needing level >= l; quantum 4, capacity 12
    tiers = tier_plan([100, 40, 3], 16, 1000, 4, 12)
    assert tiers == ((16, 12), (32, 8), (64, 4))
    assert tier_rows(tiers) == 112            # bucketed cum[0]
    assert tier_slot_pairs(tiers) == 16 * 144 + 32 * 64 + 64 * 16
    # inverse: row budget per level
    assert tier_cum(tiers, 4, 3) == (112, 48, 16)
    # empty levels are dropped, cap respected
    assert tier_plan([10, 0, 0], 16, 1000, 4, 12) == ((16, 4),)
    assert tier_plan([0, 0, 0], 16, 1000, 4, 12) == ()
    assert tier_rows(tier_plan([999, 999, 999], 16, 140, 4, 12)) == 140


def test_cell_counts_and_bounds():
    rng = np.random.RandomState(0)
    pos = rng.uniform(0, 2.0, (2, 3, 5)).astype(np.float32)
    ci = np.full((2, 3, 2), -1, np.int32)
    ci[0, :2, 0] = [4, 9]                       # cell 0: two atoms
    counts = cell_counts(jnp.asarray(ci))
    assert counts.tolist() == [2, 0]
    assert cell_levels(counts, 4).tolist() == [1, 0]
    assert cell_levels(jnp.asarray([5, 4, 13]), 4).tolist() == [2, 1, 4]
    lo, hi = cell_bounds(jnp.asarray(pos[..., :3]), jnp.asarray(ci))
    np.testing.assert_allclose(np.asarray(lo[0]),
                               pos[0, :2, :3].min(axis=0))
    np.testing.assert_allclose(np.asarray(hi[0]),
                               pos[0, :2, :3].max(axis=0))
    assert np.all(np.asarray(lo[1]) > np.asarray(hi[1]))   # empty: inverted


# ---- backend parity on a real system -------------------------------------

@pytest.fixture(scope="module")
def binned_system():
    sys_ = make_grappa_like(420, seed=5)
    layout = choose_layout(sys_.box, (1, 1, 1),
                           sys_.params.ff.r_cut * 1.08, sys_.n_atoms)
    feats_f = np.concatenate([sys_.charge[:, None], sys_.vel], axis=1)
    feats_i = np.stack([np.arange(sys_.n_atoms), sys_.typ],
                       axis=1).astype(np.int32)
    cell_f, cell_i, ovf = bin_to_cells(
        jnp.asarray(sys_.pos), jnp.asarray(feats_f), jnp.asarray(feats_i),
        layout, jnp.zeros(3, jnp.int32))
    assert int(ovf) == 0
    ext_f, ext_i = periodic_extend(np.asarray(cell_f)[..., :4], cell_i,
                                   sys_.box)
    return sys_, layout, ext_f, ext_i


def test_sparse_and_pallas_match_dense(binned_system):
    sys_, layout, ext_f, ext_i = binned_system
    out = eval_backends(layout, ext_f, ext_i, sys_.params.ff, sys_.params)
    assert_parity(out)
    n_keep, tiers, occ = out["_shapes"]
    # the headline claim: pruned work is at least 2x below dense at the
    # default 2.2 capacity safety, and the tier ladder never exceeds the
    # old single-rectangle (global k_exec) accounting
    sched = psched.PairSchedule.build(layout)
    assert tier_slot_pairs(tiers) * 2 <= sched.dense_slot_pairs()
    global_kexec = bucket(n_keep, psched.PAIR_BUCKET, sched.n_pairs) * \
        bucket(occ, psched.SLOT_QUANTUM, layout.capacity) ** 2
    assert tier_slot_pairs(tiers) <= global_kexec


def test_prune_is_conservative(binned_system):
    """Disabling the distance prune (huge radius) must not change forces —
    i.e. the bounded prune only ever removes non-contributing pairs."""
    sys_, layout, ext_f, ext_i = binned_system
    ff = sys_.params.ff
    sched = psched.PairSchedule.build(layout)
    sel_all, cum_all, _, occ = psched.prune_local(sched, ext_f, ext_i,
                                                  r_prune=1e6)
    sel, cum, _, _ = psched.prune_local(sched, ext_f, ext_i,
                                        psched.prune_radius(sys_.params))
    assert int(cum[0]) <= int(cum_all[0])
    k_exec = bucket(int(occ), psched.SLOT_QUANTUM, layout.capacity)
    F_a, pe_a = psched.get_force_backend("sparse")(
        ext_f, ext_i, layout, ff, sched=sched,
        sel=lax.slice(sel_all, (0,), (sched.n_pairs,)), k_exec=k_exec)
    tiers = plan_tiers(sched, layout, cum)
    F_p, pe_p = psched.get_force_backend("sparse")(
        ext_f, ext_i, layout, ff, sched=sched,
        sel=lax.slice(sel, (0,), (tier_rows(tiers),)), tiers=tiers)
    scale = max(float(jnp.abs(F_a).max()), 1.0)
    assert float(jnp.abs(F_a - F_p).max()) / scale < FORCE_RTOL


# ---- crafted occupancies: empty + capacity-full cells --------------------

def test_empty_and_overflow_adjacent_cells():
    """One cell at exactly capacity K, one region fully empty."""
    rng = np.random.RandomState(7)
    box = (10.8, 10.8, 10.8)
    layout = choose_layout(box, (1, 1, 1), 2.7, 120, min_capacity=8)
    cz, cy, cx = layout.cells_per_domain
    K = layout.capacity
    cs = np.asarray(layout.cell_size)

    pos, typ = [], []
    for iz in range(cz):
        for iy in range(cy):
            for ix in range(cx):
                if (iz, iy, ix) == (cz - 1, cy - 1, cx - 1):
                    n = 0                       # fully-empty cell
                elif (iz, iy, ix) == (0, 0, 0):
                    n = K                       # overflow-adjacent: full
                else:
                    # occupied but shallow (one quantum level below the
                    # full cell), so the tier ladder must split
                    n = int(rng.randint(1, max(K // 2, 2)))
                origin = np.asarray([iz, iy, ix]) * cs
                p = origin + rng.uniform(0.05, 0.95, (n, 3)) * cs
                pos.append(p)
                typ.append(rng.randint(0, 2, n))
    pos = np.concatenate(pos).astype(np.float32)
    typ = np.concatenate(typ).astype(np.int32)
    n_atoms = pos.shape[0]
    charge = (rng.uniform(size=n_atoms) - 0.5).astype(np.float32) * 0.5

    feats_f = np.concatenate([charge[:, None],
                              np.zeros((n_atoms, 3), np.float32)], axis=1)
    feats_i = np.stack([np.arange(n_atoms), typ], axis=1).astype(np.int32)
    cell_f, cell_i, ovf = bin_to_cells(
        jnp.asarray(pos), jnp.asarray(feats_f), jnp.asarray(feats_i),
        layout, jnp.zeros(3, jnp.int32))
    assert int(ovf) == 0
    counts = np.asarray(cell_counts(cell_i))
    assert counts[0, 0, 0] == K and counts[-1, -1, -1] == 0

    ext_f, ext_i = periodic_extend(np.asarray(cell_f)[..., :4], cell_i, box)
    params = MDParams(ff=DEFAULT_FF)
    out = eval_backends(layout, ext_f, ext_i, DEFAULT_FF, params)
    assert_parity(out)
    # empty-cell pairs must actually be pruned, and the tier ladder must
    # be heterogeneous: the full cell forces one max-level tier while
    # the shallow cells populate cheaper tiers
    sched = psched.PairSchedule.build(layout)
    _, cum, _, occ = psched.prune_local(sched, ext_f, ext_i,
                                        psched.prune_radius(params))
    assert int(cum[0]) < sched.n_pairs
    assert int(occ) == K                        # the full cell tops a tier
    tiers = plan_tiers(sched, layout, cum)
    assert tiers[0][1] == K                     # deepest tier at capacity
    assert len(tiers) >= 2                      # shallow tiers split off
    assert tier_slot_pairs(tiers) < tier_rows(tiers) * K ** 2


# ---- hypothesis sweep -----------------------------------------------------

@given(n=st.integers(200, 420), seed=st.integers(0, 10_000))
@settings(max_examples=6, deadline=None)
def test_backend_parity_random_systems(n, seed):
    sys_ = make_grappa_like(n, seed=seed)
    layout = choose_layout(sys_.box, (1, 1, 1),
                           sys_.params.ff.r_cut * 1.08, sys_.n_atoms)
    feats_f = np.concatenate([sys_.charge[:, None], sys_.vel], axis=1)
    feats_i = np.stack([np.arange(n), sys_.typ], axis=1).astype(np.int32)
    cell_f, cell_i, ovf = bin_to_cells(
        jnp.asarray(sys_.pos), jnp.asarray(feats_f), jnp.asarray(feats_i),
        layout, jnp.zeros(3, jnp.int32))
    assert int(ovf) == 0
    ext_f, ext_i = periodic_extend(np.asarray(cell_f)[..., :4], cell_i,
                                   sys_.box)
    out = eval_backends(layout, ext_f, ext_i, sys_.params.ff, sys_.params)
    assert_parity(out)


# ---- dual pair-list properties (random occupancy + drift replays) ---------

def _random_binned(n, seed):
    """A binned random system + its periodic extension (numpy views)."""
    sys_ = make_grappa_like(n, seed=seed)
    layout = choose_layout(sys_.box, (1, 1, 1),
                           sys_.params.ff.r_cut * 1.08, sys_.n_atoms)
    feats_f = np.concatenate([sys_.charge[:, None], sys_.vel], axis=1)
    feats_i = np.stack([np.arange(n), sys_.typ], axis=1).astype(np.int32)
    cell_f, cell_i, ovf = bin_to_cells(
        jnp.asarray(sys_.pos), jnp.asarray(feats_f), jnp.asarray(feats_i),
        layout, jnp.zeros(3, jnp.int32))
    assert int(ovf) == 0
    return sys_, layout, np.asarray(cell_f), np.asarray(cell_i)


def _drifted_ext(cell_f, cell_i, box, budget, seed):
    """Displace every occupied slot by a random vector of norm <= budget
    (cell membership frozen — the within-block invariant), re-extend."""
    rng = np.random.RandomState(seed)
    disp = rng.normal(size=cell_f.shape[:-1] + (3,))
    norm = np.linalg.norm(disp, axis=-1, keepdims=True)
    disp = disp / np.maximum(norm, 1e-9) * \
        rng.uniform(0, budget, norm.shape)
    moved = cell_f.copy()
    valid = (cell_i[..., 0] >= 0)[..., None]
    moved[..., :3] = np.where(valid, moved[..., :3] + disp, 0.0)
    return periodic_extend(moved[..., :4], jnp.asarray(cell_i), box)


def _pair_min_dist(sched, ext_f, ext_i):
    """Brute-force per-worklist-pair min atom distance (numpy oracle)."""
    ne = sched.n_ext_cells
    K = np.asarray(ext_f).shape[3]
    f2 = np.asarray(ext_f).reshape(ne, K, -1)[..., :3]
    valid = np.asarray(ext_i)[..., 0].reshape(ne, K) >= 0
    out = np.full(sched.n_pairs, np.inf)
    for p in range(sched.n_pairs):
        a, b, same = sched.cell_a[p], sched.cell_b[p], sched.same[p]
        va, vb = valid[a], valid[b]
        if not va.any() or not vb.any():
            continue
        d = np.linalg.norm(f2[a][va][:, None] - f2[b][vb][None], axis=-1)
        if same:
            if va.sum() < 2:
                continue
            d = d[np.triu_indices(va.sum(), k=1)]
        out[p] = d.min() if d.size else np.inf
    return out


@given(n=st.integers(150, 260), seed=st.integers(0, 1000),
       dseed=st.integers(0, 1000))
@settings(max_examples=4, deadline=None)
def test_outer_list_conservative_under_drift(n, seed, dseed):
    """Any pair within r_cut at ANY bounded-drift replay state before the
    next rebuild must be on the outer list built at rebin time."""
    sys_, layout, cell_f, cell_i = _random_binned(n, seed)
    sched = psched.PairSchedule.build(layout)
    r_outer = psched.prune_radius(sys_.params)
    budget = (r_outer - sys_.params.ff.r_cut) / 2.0   # per-atom drift bound
    ext_f0, ext_i0 = periodic_extend(cell_f[..., :4], jnp.asarray(cell_i),
                                     sys_.box)
    sel, cum, _, _ = psched.prune_local(sched, ext_f0, ext_i0, r_outer)
    kept = set(np.asarray(sel)[:int(cum[0])].tolist())
    ext_fd, ext_id = _drifted_ext(cell_f, cell_i, sys_.box, budget, dseed)
    dmin = _pair_min_dist(sched, ext_fd, ext_id)
    within = np.where(dmin < sys_.params.ff.r_cut)[0]
    missing = [int(p) for p in within if int(p) not in kept]
    assert not missing, f"outer list dropped in-range pairs {missing[:5]}"


@given(n=st.integers(150, 260), seed=st.integers(0, 1000),
       dseed=st.integers(0, 1000))
@settings(max_examples=4, deadline=None)
def test_inner_prune_never_drops_within_inner_radius(n, seed, dseed):
    """After a drift replay, roll_prune's survivor prefix must contain
    every outer pair whose current min atom distance is < r_inner (the
    bbox gap lower-bounds atom distances, so this holds by construction
    — the test pins it against a brute-force oracle)."""
    sys_, layout, cell_f, cell_i = _random_binned(n, seed)
    sched = psched.PairSchedule.build(layout)
    params = sys_.params
    r_outer = psched.prune_radius(params)
    r_inner = psched.inner_radius(params, nstprune=5)
    ext_f0, ext_i0 = periodic_extend(cell_f[..., :4], jnp.asarray(cell_i),
                                     sys_.box)
    sel, cum, _, _ = psched.prune_local(sched, ext_f0, ext_i0, r_outer)
    tiers = plan_tiers(sched, layout, cum)
    sel_exec = lax.slice(sel, (0,), (tier_rows(tiers),))
    budget = (r_outer - params.ff.r_cut) / 2.0
    ext_fd, ext_id = _drifted_ext(cell_f, cell_i, sys_.box, budget, dseed)
    new_sel, cum_s = psched.roll_prune(sched, sel_exec, ext_fd, ext_id,
                                       r_inner)
    survivors = set(np.asarray(new_sel)[:int(cum_s[0])].tolist())
    dmin = _pair_min_dist(sched, ext_fd, ext_id)
    in_prefix = set(np.asarray(sel_exec).tolist())
    for p in np.where(dmin < r_inner)[0]:
        if int(p) in in_prefix:
            assert int(p) in survivors, \
                f"inner prune dropped pair {p} at d={dmin[p]:.3f}"
    # permutation: the refresh reorders, never duplicates or loses rows
    assert sorted(np.asarray(new_sel).tolist()) == \
        sorted(np.asarray(sel_exec).tolist())


@given(n=st.integers(150, 260), seed=st.integers(0, 1000))
@settings(max_examples=4, deadline=None)
def test_per_pair_bounds_and_packing_permutation(n, seed):
    """The occupancy-sorted packing is a permutation of the kept rows,
    and every packed row lands in a tier whose slot depth covers BOTH
    cells' real occupancy (per-pair bounds never truncate)."""
    sys_, layout, cell_f, cell_i = _random_binned(n, seed)
    sched = psched.PairSchedule.build(layout)
    ext_f, ext_i = periodic_extend(cell_f[..., :4], jnp.asarray(cell_i),
                                   sys_.box)
    sel, cum, _, occ = psched.prune_local(sched, ext_f, ext_i,
                                          psched.prune_radius(sys_.params))
    sel_np = np.asarray(sel)
    n_keep = int(cum[0])
    packed, tail = sel_np[:n_keep], sel_np[n_keep:]
    assert np.all(tail == sched.n_pairs)              # sentinel-only tail
    assert len(set(packed.tolist())) == n_keep        # no duplicates
    ne = sched.n_ext_cells
    K = layout.capacity
    counts = np.asarray(cell_counts(ext_i)).reshape(ne)
    tiers = plan_tiers(sched, layout, cum)
    assert tier_rows(tiers) >= n_keep                 # nothing spills
    row = 0
    for n_t, k_t in tiers:
        for r in range(row, row + n_t):
            if r >= n_keep:
                break
            p = int(packed[r])
            bound = max(counts[sched.cell_a[p]], counts[sched.cell_b[p]])
            assert bound <= k_t, (r, p, bound, k_t)
        row += n_t
    # levels are packed descending (dense pairs first, tail shrinks)
    lvls = np.maximum(
        -(-counts[sched.cell_a[packed]] // psched.SLOT_QUANTUM),
        -(-counts[sched.cell_b[packed]] // psched.SLOT_QUANTUM))
    assert np.all(np.diff(lvls) <= 0)
    assert int(occ) == counts.max()


# ---- overlap_rebin: fused rebin/migration/prune invariants ----------------

def test_overlap_rebin_fused_path_matches_host_dispatch():
    """24 steps (nstlist=20: one rebin/migration/prune boundary): fusing
    the DLB work into the block program must (a) reproduce the
    host-dispatched trajectory and migration diagnostics bit for bit,
    (b) hand the next block the exact same pruned schedule, and (c) keep
    the prune conservative across the block boundary — evaluating the
    full unpruned worklist on the final state changes nothing."""
    from repro.core.halo_plan import HaloSpec
    from repro.core.md import MDEngine, make_grappa_like
    from repro.launch.mesh import make_mesh

    sys_ = make_grappa_like(300, seed=9)
    mesh = make_mesh((1, 1, 1), ("z", "y", "x"))
    spec = HaloSpec(axis_names=("z", "y", "x"), widths=(1, 1, 1),
                    backend="fused")
    host = MDEngine(sys_, mesh, spec, force_backend="sparse", nstprune=4)
    fused = MDEngine(sys_, mesh, spec, force_backend="sparse", nstprune=4,
                     overlap_rebin=True)
    (cf_h, ci_h), m_h, d_h = host.simulate(24)
    (cf_f, ci_f), m_f, d_f = fused.simulate(24)

    np.testing.assert_array_equal(np.asarray(cf_f), np.asarray(cf_h))
    np.testing.assert_array_equal(np.asarray(ci_f), np.asarray(ci_h))
    for k in m_h:
        np.testing.assert_array_equal(np.asarray(m_f[k]),
                                      np.asarray(m_h[k]))
    assert len(d_f) == len(d_h)
    for a, b in zip(d_f, d_h):
        for k in b:
            np.testing.assert_array_equal(np.asarray(a[k]),
                                          np.asarray(b[k]))

    # (b) identical post-boundary exec schedule (fused prune == prune_fn)
    sel_h, t_h, ti_h = host._sched_exec
    sel_f, t_f, ti_f = fused._sched_exec
    assert (t_h, ti_h) == (t_f, ti_f)
    np.testing.assert_array_equal(np.asarray(sel_f), np.asarray(sel_h))

    # (c) conservativeness across the boundary: the pruned schedule's
    # forces on the final state match the full unpruned worklist's
    F_pruned, pe_pruned = fused._force_fn_sched(cf_f, ci_f, sel_f, t_f)
    sched = fused.pair_schedule
    k_max = max(k for _, k in t_f)
    F_full, pe_full = fused._force_fn_sched(
        cf_f, ci_f, sel_f, ((sched.n_pairs, k_max),))
    scale = max(float(jnp.abs(F_full).max()), 1.0)
    assert float(jnp.abs(F_pruned - F_full).max()) / scale < FORCE_RTOL
    assert abs(float(pe_pruned - pe_full)) / \
        max(abs(float(pe_full)), 1.0) < PE_RTOL


# ---- sparse forces against the O(N^2) oracle ------------------------------

def test_sparse_engine_matches_direct_oracle():
    from repro.core.halo_plan import HaloSpec
    from repro.core.md import MDEngine, direct_forces_reference
    from repro.launch.mesh import make_mesh

    sys_ = make_grappa_like(300, seed=11)
    mesh = make_mesh((1, 1, 1), ("z", "y", "x"))
    spec = HaloSpec(axis_names=("z", "y", "x"), widths=(1, 1, 1),
                    backend="fused")
    eng = MDEngine(sys_, mesh, spec, force_backend="sparse")
    cf, ci = eng.init_state()
    cf, ci, force, diag = eng.rebin_fn(cf, ci)
    eng._refresh_schedule(cf, ci)
    f_s, pe_s = eng.force_fn(cf, ci)
    f_eng, = eng.gather_by_id([f_s], ci)
    f_ref, _ = direct_forces_reference(sys_.pos, sys_.charge, sys_.typ,
                                       sys_.box, sys_.params.ff)
    scale = np.abs(f_ref).max()
    assert np.abs(f_eng - f_ref).max() / scale < 5e-5
    assert eng.pair_stats()["prune_ratio"] >= 2.0
    # the tier ladder beats (or matches) the single-rectangle schedule
    ps = eng.pair_stats()
    assert ps["evaluated_slot_pairs"] <= ps["global_kexec_slot_pairs"]


# ---- the rebin's force carry: the engine's own backend --------------------

def _carry_engine(force_backend, n=300, seed=11, **kw):
    from repro.core.halo_plan import HaloSpec
    from repro.core.md import MDEngine
    from repro.launch.mesh import make_mesh

    sys_ = make_grappa_like(n, seed=seed)
    spec = HaloSpec(axis_names=("z", "y", "x"), widths=(1, 1, 1),
                    backend="fused")
    return sys_, MDEngine(sys_, make_mesh((1, 1, 1), ("z", "y", "x")), spec,
                          force_backend=force_backend, **kw)


@pytest.mark.parametrize("fb", ["sparse", "pallas"])
def test_pruned_rebin_carry_matches_direct_oracle(fb):
    """The carry of a pruned-backend engine (the full worklist on the
    worst-case ladder, no prune) is the exact force: it matches the
    O(N^2) oracle like the dense carry does, and sums to zero."""
    from repro.core.md import direct_forces_reference

    sys_, eng = _carry_engine(fb)
    cf, ci = eng.init_state()
    cf, ci, force, diag = eng.rebin_fn(cf, ci)
    assert int(np.asarray(diag["bin_overflow"])) == 0
    f_eng, = eng.gather_by_id([force], ci)
    f_ref, _ = direct_forces_reference(sys_.pos, sys_.charge, sys_.typ,
                                       sys_.box, sys_.params.ff)
    scale = np.abs(f_ref).max()
    assert np.abs(f_eng - f_ref).max() / scale < 5e-5
    assert np.abs(f_eng.sum(axis=0)).max() < 1e-3


def test_pruned_engines_never_run_the_dense_pass(monkeypatch):
    """With the dense 14-zone pass made to raise, a pruned engine opens a
    run and crosses two rebins (begin_run and one interior boundary);
    a dense engine, and one the tiny-box path degraded to dense, still
    trace it for their carry."""
    from repro.core.md import engine as engine_mod

    def boom(*args, **kwargs):
        raise RuntimeError("dense 14-zone pass traced")

    monkeypatch.setattr(engine_mod, "compute_forces", boom)
    _, eng = _carry_engine("sparse", n=200, seed=5, static_ladder=True)
    eng.begin_run()
    (cf, ci), m, diags = eng.simulate(40)
    assert len(diags) == 2
    assert np.all(np.isfinite(np.asarray(m["pe"])))

    _, dense = _carry_engine("dense", n=200, seed=5)
    with pytest.warns(RuntimeWarning, match="degrades to the 'dense'"):
        _, degraded = _carry_engine("sparse", n=110, seed=3)
    for e in (dense, degraded):
        cf, ci = e.init_state()
        with pytest.raises(RuntimeError, match="dense 14-zone pass"):
            e.rebin_fn(cf, ci)


def test_dense_rebin_carry_is_the_dense_force_pass():
    """A dense engine's carry is bitwise the standalone dense force pass
    on the rebinned state (empty slots zeroed)."""
    _, eng = _carry_engine("dense")
    cf, ci = eng.init_state()
    cf, ci, force, _ = eng.rebin_fn(cf, ci)
    f_ref, _ = eng._force_fn_dense(cf, ci)
    f_ref = jnp.where(ci[..., 0:1] >= 0, f_ref, 0.0)
    np.testing.assert_array_equal(np.asarray(force), np.asarray(f_ref))


@pytest.mark.parametrize("fb", ["dense", "sparse", "pallas"])
def test_pair_stats_publish_the_carry(fb):
    """``pair_stats()`` and the ``engine_build`` record name the carry's
    evaluator and its slot pairs per domain: the full (M, K) tier for
    the pruned backends, every dense slot pair for ``dense``."""
    from repro.obs import MetricsRegistry

    reg = MetricsRegistry()
    _, eng = _carry_engine(fb, n=200, seed=5, obs=reg)
    ps = eng.pair_stats()
    assert ps["carry_backend"] == fb
    assert ps["carry_slot_pairs"] == ps["dense_slot_pairs"] == \
        14 * eng.layout.n_local_cells * eng.layout.capacity ** 2
    build = [r for r in reg.records if r["kind"] == "engine_build"][0]
    assert build["carry_backend"] == fb
    assert build["carry_slot_pairs"] == ps["carry_slot_pairs"]
    if fb != "dense":
        assert eng._carry_tiers == ((eng.pair_schedule.n_pairs,
                                     eng.layout.capacity),)
        # still published after a prune replaces the block accounting
        eng.begin_run()
        assert eng.pair_stats()["carry_slot_pairs"] == \
            ps["carry_slot_pairs"]
