"""MD engine: single-domain oracle checks in-process; DD checks in subprocess."""
import numpy as np
import pytest

try:
    from hypothesis import given, settings
    from hypothesis import strategies as st
except ImportError:  # property tests skip; hypothesis is a dev extra
    from _hypothesis_stub import given, settings, st

from repro.core.halo_plan import HaloSpec
from repro.core.md import (
    MDEngine,
    direct_forces_reference,
    make_grappa_like,
)
from repro.core.md.forces import stencil_pairs
from repro.launch.mesh import make_mesh


def test_stencil_is_exact_half_shell():
    """14 zone pairs; offsets disjoint; every {-1,0,1}^3 displacement covered
    exactly once (the eighth-shell uniqueness argument)."""
    pairs = stencil_pairs()
    assert len(pairs) == 14
    seen = set()
    for a, b in pairs:
        assert all(x * y == 0 for x, y in zip(a, b))
        d = tuple(bi - ai for ai, bi in zip(a, b))
        assert d not in seen and tuple(-x for x in d) not in seen
        seen.add(d)
    # 13 distinct non-zero displacements + the self pair
    assert len(seen) == 14 and (0, 0, 0) in seen


@pytest.fixture(scope="module")
def small_system():
    return make_grappa_like(300, seed=11)


@pytest.fixture(scope="module")
def single_engine(small_system):
    mesh = make_mesh((1, 1, 1), ("z", "y", "x"))
    spec = HaloSpec(axis_names=("z", "y", "x"), widths=(1, 1, 1),
                    backend="fused")
    return MDEngine(small_system, mesh, spec)


def test_forces_match_direct_oracle(small_system, single_engine):
    eng = single_engine
    cf, ci = eng.init_state()
    cf, ci, force, diag = eng.rebin_fn(cf, ci)
    assert int(np.asarray(diag["bin_overflow"])) == 0
    f_eng, = eng.gather_by_id([force], ci)
    f_ref, _ = direct_forces_reference(
        small_system.pos, small_system.charge, small_system.typ,
        small_system.box, small_system.params.ff)
    scale = np.abs(f_ref).max()
    assert np.abs(f_eng - f_ref).max() / scale < 5e-5


def test_newtons_third_law(small_system, single_engine):
    eng = single_engine
    cf, ci = eng.init_state()
    cf, ci, force, _ = eng.rebin_fn(cf, ci)
    f_eng, = eng.gather_by_id([force], ci)
    assert np.abs(f_eng.sum(axis=0)).max() < 1e-3


def test_short_nve_run_is_stable(small_system, single_engine):
    _, metrics, diags = single_engine.simulate(40)
    E = metrics["pe"] + metrics["ke"]
    assert np.all(np.isfinite(E))
    assert (E.max() - E.min()) / small_system.n_atoms < 5e-3
    assert np.abs(metrics["mom"]).max() < 1e-3
    for d in diags:
        assert int(np.asarray(d["n_atoms"])) == small_system.n_atoms


@given(n=st.integers(120, 300), seed=st.integers(0, 10_000))
@settings(max_examples=8, deadline=None)
def test_system_builder_properties(n, seed):
    sys_ = make_grappa_like(n, seed=seed)
    assert sys_.n_atoms == n
    assert abs(sys_.charge.sum()) < 1e-5          # neutral
    assert np.abs(sys_.vel.mean(axis=0)).max() < 1e-6   # no COM drift
    assert np.all((sys_.pos >= 0) & (sys_.pos < sys_.box))
    assert sys_.params.ff.r_cut < sys_.box.min() / 2


@pytest.mark.dist
def test_dd_equivalence_and_migration(dist):
    out = dist("check_md.py")
    assert "check_md OK" in out


@pytest.mark.dist
def test_nve_float64(dist):
    out = dist("check_md_nve.py")
    assert "check_md_nve OK" in out


def test_direct_forces_rows_matches_reference(small_system):
    """The chunked subset oracle is the full oracle's rows."""
    from repro.core.md import direct_forces_rows
    s = small_system
    f_ref, _ = direct_forces_reference(s.pos, s.charge, s.typ, s.box,
                                       s.params.ff)
    rows = np.random.RandomState(1).choice(s.n_atoms, 37, replace=False)
    f_rows = direct_forces_rows(s.pos, s.charge, s.typ, s.box, s.params.ff,
                                rows, chunk=8)
    np.testing.assert_allclose(f_rows, f_ref[rows], rtol=1e-12, atol=1e-12)
