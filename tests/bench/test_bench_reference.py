"""The benchmark's own reference: the pair list against brute force, the
pair-list forces against the float64 direct sum, the integrator, and the
bfloat16 control being measurably worse than float64."""
import itertools

import numpy as np
import pytest

from benchtools import spec

CFG = dict(spec.resolve_cell(spec.load_benchmark(),
                             "lammps-lj-32k.nve.dd4")["config"])
REF = spec.reference(CFG)


def small(n, seed=3, jitter=0.1):
    """A small fcc start of ``n`` = 4 m^3 atoms; ``jitter`` (sigma) moves
    every atom off its lattice site, where all forces cancel."""
    s = REF.make_system({**CFG, "n_atoms": n}, seed)
    rng = np.random.RandomState(seed)
    s["pos"] = (s["pos"] + rng.uniform(-jitter, jitter, s["pos"].shape)) \
        % s["box"]
    return s


@pytest.mark.parametrize("n,r", [(500, 2.5), (864, 2.5), (864, 3.1)])
def test_pair_list_matches_brute_force(n, r):
    s = small(n)
    pos, box = s["pos"], s["box"]
    i, j = REF.pair_list(pos, box, r)
    want = set()
    for a, b in itertools.combinations(range(n), 2):
        d = pos[a] - pos[b]
        d -= box * np.round(d / box)
        if d @ d < r * r:
            want.add((a, b))
    got = list(zip(i.tolist(), j.tolist()))
    assert len(got) == len(set(got))          # each pair once
    assert set(got) == want


def test_pair_list_forces_match_direct_sum():
    s = small(500)
    F, _ = REF.PairSystem(s["charge"], s["typ"], s["box"], CFG, s["pos"],
                          CFG["r_cut"]).forces(s["pos"])
    Fd = REF.direct_forces_rows(s["pos"], s["charge"], s["typ"], s["box"],
                                CFG, np.arange(500))
    assert np.abs(F - Fd).max() <= 1e-12 * np.abs(Fd).max()


def test_pair_images_stay_right_while_atoms_move():
    """A list built at one position, read after every atom has moved by
    up to half the skin (some across the box edge), gives the direct
    sum's forces there."""
    s = small(864)
    skin = CFG["ref_skin"]
    ps = REF.PairSystem(s["charge"], s["typ"], s["box"], CFG, s["pos"],
                        CFG["r_cut"] + skin)
    rng = np.random.RandomState(5)
    step = rng.normal(size=s["pos"].shape)
    step *= 0.499 * skin / np.linalg.norm(step, axis=1, keepdims=True)
    moved = s["pos"] + step
    F, _ = ps.forces(moved)
    Fd = REF.direct_forces_rows(moved, s["charge"], s["typ"], s["box"], CFG,
                                np.arange(864))
    assert np.abs(F - Fd).max() <= 1e-12 * np.abs(Fd).max()


def test_seeds_above_32_bits_are_reproducible():
    a = REF.make_system({**CFG, "n_atoms": 500}, 2**40 + 3)
    b = REF.make_system({**CFG, "n_atoms": 500}, 2**40 + 3)
    c = REF.make_system({**CFG, "n_atoms": 500}, 2**40 + 4)
    assert np.array_equal(a["vel"], b["vel"])
    assert not np.array_equal(a["vel"], c["vel"])
    assert np.array_equal(a["pos"], c["pos"])      # the lattice is fixed


def test_fcc_start_is_the_published_one():
    """in.lj's start: 4 m^3 atoms at the stated density, no momentum,
    kinetic temperature exactly as stated over 3N - 3 degrees of freedom."""
    n = 4 * 5 ** 3
    s = REF.make_system({**CFG, "n_atoms": n}, 7)
    assert np.isclose(n / np.prod(s["box"]), CFG["density"])
    d = s["pos"][:, None] - s["pos"][None]
    d -= s["box"] * np.round(d / s["box"])
    r = np.sqrt((d ** 2).sum(-1))[np.triu_indices(n, 1)]
    a = (4 / CFG["density"]) ** (1 / 3)
    assert np.isclose(r.min(), a / np.sqrt(2))     # fcc nearest neighbours
    assert np.sum(np.isclose(r, a / np.sqrt(2))) == 6 * n
    assert np.abs(s["vel"].sum(axis=0)).max() < 1e-9
    t = CFG["mass"] * np.sum(s["vel"] ** 2) / (3 * n - 3)
    assert np.isclose(t, CFG["temperature"])
    with pytest.raises(ValueError, match="4 m"):
        REF.make_system({**CFG, "n_atoms": 501}, 7)


def test_verlet_conserves_momentum():
    s = small(864)
    pos, vel, pe = REF.verlet(s["pos"], s["vel"], s["charge"], s["typ"],
                              s["box"], CFG, 20)
    assert pe.shape == (20,)
    assert np.abs(vel.sum(axis=0)).max() < 1e-9


def test_bf16_control_differs_from_float64():
    s = small(500)
    f64, e64 = REF.PairSystem(s["charge"], s["typ"], s["box"], CFG,
                              s["pos"], CFG["r_cut"]).forces(s["pos"])
    f16, e16 = REF.PairSystem(s["charge"], s["typ"], s["box"], CFG,
                              s["pos"], CFG["r_cut"], REF.BF16).forces(s["pos"])
    assert np.abs(f16 - f64).max() > 1e-3 * np.abs(f64).max()
