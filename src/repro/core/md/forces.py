"""Non-bonded forces: LJ + reaction-field over cutoff-sized cell pairs.

Pair assignment follows the neutral-territory eighth-shell rule [Liem'91,
Hess'08]: with one-sided halos (the extended array covers offsets {0, +1}
per dim), every global cell pair within the cutoff stencil is computed by
exactly one domain — the owner of the componentwise-min "base" cell.  Per
base cell that yields 14 interactions: the cell with itself plus 13
unordered pairs of disjoint offsets (a, b) in {0,1}^3 (a AND b == 0, the
classic half stencil re-anchored so only POSITIVE offsets are touched —
which is precisely why the one-directional staged halo suffices).

Periodic images are pre-shifted by the halo exchange (coordShift), so no
minimum-image logic appears here — exactly like GROMACS' shifted halo
coordinates.

``compute_forces`` is the ``"dense"`` entry of the force-backend registry
(:mod:`repro.core.md.pair_schedule`): it evaluates every K x K slot pair
of every zone product and is the bitwise trajectory reference that the
pruned ``"sparse"`` / ``"pallas"`` pair-schedule engines are validated
against.
"""
from __future__ import annotations

import itertools
from typing import List, Tuple

import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.core.md.cells import CellLayout
from repro.core.md.system import ForceField

Offset = Tuple[int, int, int]


def stencil_pairs() -> List[Tuple[Offset, Offset]]:
    """Self pair + the 13 disjoint-offset cell pairs (eighth-shell zones)."""
    offs = list(itertools.product((0, 1), repeat=3))
    pairs: List[Tuple[Offset, Offset]] = [((0, 0, 0), (0, 0, 0))]
    for a, b in itertools.combinations(offs, 2):
        if all(x * y == 0 for x, y in zip(a, b)):
            pairs.append((a, b))
    assert len(pairs) == 14
    return pairs


def _zone(arr, off, shape):
    cz, cy, cx = shape
    return arr[off[0]:off[0] + cz, off[1]:off[1] + cy, off[2]:off[2] + cx]


def pair_terms(dx, r2, qa, qb, eps, sig, ff: ForceField, mask):
    """Per-pair scalar force factor (F = fac * dx) and potential energy.

    Shared by the dense 14-zone loop below and the sparse pair-schedule
    engine (:mod:`repro.core.md.pair_schedule`), so every force backend
    evaluates the identical per-pair math and differs only in which slot
    pairs it touches and in reduction order.
    """
    dtype = dx.dtype
    r2safe = jnp.where(mask, r2, jnp.asarray(1.0, dtype))
    inv_r2 = 1.0 / r2safe
    sr2 = (sig * sig) * inv_r2
    sr6 = sr2 * sr2 * sr2
    sr12 = sr6 * sr6
    # LJ with potential-shift at the cutoff (forces unchanged)
    fac_lj = 24.0 * eps * (2.0 * sr12 - sr6) * inv_r2
    src2 = (sig * sig) / (ff.r_cut * ff.r_cut)
    src6 = src2 * src2 * src2
    e_lj = 4.0 * eps * ((sr12 - sr6) - (src6 * src6 - src6))
    # reaction field with potential shift c_rf
    inv_r = jnp.sqrt(inv_r2)
    qq = qa * qb
    k_rf = jnp.asarray(ff.k_rf, dtype)
    c_rf = jnp.asarray(ff.c_rf, dtype)
    fac_c = qq * (inv_r * inv_r2 - 2.0 * k_rf)
    e_c = qq * (inv_r + k_rf * r2safe - c_rf)
    fac = jnp.where(mask, fac_lj + fac_c, 0.0)
    pe = jnp.where(mask, e_lj + e_c, 0.0)
    return fac, pe


def compute_forces(ext_f, ext_i, layout: CellLayout, ff: ForceField):
    """Forces + potential energy on the extended (home + halo) cell array.

    ext_f: (cz+1, cy+1, cx+1, K, 4) — [x, y, z, charge] halo-shifted coords
    ext_i: (cz+1, cy+1, cx+1, K, 2) — [atom id, type]; id < 0 marks padding
    Returns (F_ext, pe): forces accumulated at BOTH pair members (halo
    members hold partial sums to be returned by the reverse exchange) and
    this domain's share of the potential energy.
    """
    shape = layout.cells_per_domain
    dtype = ext_f.dtype
    eps_t = jnp.asarray(ff.eps, dtype)
    sig_t = jnp.asarray(ff.sigma, dtype)
    rc2 = jnp.asarray(ff.r_cut * ff.r_cut, dtype)
    K = layout.capacity

    F_ext = jnp.zeros(ext_f.shape[:-1] + (3,), dtype)
    pe_total = jnp.zeros((), dtype)
    eye = jnp.eye(K, dtype=bool)
    tri = jnp.triu(jnp.ones((K, K), dtype=bool), k=1)

    for a, b in stencil_pairs():
        A_f, B_f = _zone(ext_f, a, shape), _zone(ext_f, b, shape)
        A_i, B_i = _zone(ext_i, a, shape), _zone(ext_i, b, shape)
        pos_a, q_a = A_f[..., :3], A_f[..., 3]
        pos_b, q_b = B_f[..., :3], B_f[..., 3]
        valid_a, valid_b = A_i[..., 0] >= 0, B_i[..., 0] >= 0
        typ_a = jnp.clip(A_i[..., 1], 0, eps_t.shape[0] - 1)
        typ_b = jnp.clip(B_i[..., 1], 0, eps_t.shape[0] - 1)

        dx = pos_a[..., :, None, :] - pos_b[..., None, :, :]
        r2 = jnp.sum(dx * dx, axis=-1)
        mask = (valid_a[..., :, None] & valid_b[..., None, :]) & (r2 < rc2)
        if a == b:
            mask = mask & tri        # each intra-cell pair once
        else:
            mask = mask & ~(eye & (A_i[..., 0:1] == B_i[..., None, :, 0]))

        eps = eps_t[typ_a[..., :, None], typ_b[..., None, :]]
        sig = sig_t[typ_a[..., :, None], typ_b[..., None, :]]
        fac, pe = pair_terms(dx, r2, q_a[..., :, None], q_b[..., None, :],
                             eps, sig, ff, mask)
        # barriers pin the K-wide pair reductions to standalone, canonical
        # compilations: their partial-sum order must not depend on how the
        # surrounding program (halo backend, step-pipeline schedule) fuses,
        # or different schedules would drift apart at the ulp level
        fvec = lax.optimization_barrier(fac[..., None] * dx)
        fa = lax.optimization_barrier(
            jnp.sum(fvec, axis=-2))          # force on A atoms
        fb = lax.optimization_barrier(
            -jnp.sum(fvec, axis=-3))         # Newton's third law
        cz, cy, cx = shape
        F_ext = F_ext.at[a[0]:a[0] + cz, a[1]:a[1] + cy,
                         a[2]:a[2] + cx].add(fa)
        F_ext = F_ext.at[b[0]:b[0] + cz, b[1]:b[1] + cy,
                         b[2]:b[2] + cx].add(fb)
        pe_total = pe_total + jnp.sum(pe)

    return F_ext, pe_total


# --------------------------------------------------------------------------
# O(N^2) minimum-image oracle (tests only)
# --------------------------------------------------------------------------

def _direct_terms(dx, q_i, q_j, t_i, t_j, ff: ForceField, mask):
    """Float64 pair force factor and energy of minimum-image ``dx``."""
    eps_t = np.asarray(ff.eps, np.float64)
    sig_t = np.asarray(ff.sigma, np.float64)
    r2 = np.sum(dx * dx, axis=-1)
    mask = mask & (r2 < ff.r_cut ** 2)
    r2safe = np.where(mask, r2, 1.0)
    inv_r2 = 1.0 / r2safe
    eps = eps_t[t_i, t_j]
    sig = sig_t[t_i, t_j]
    sr2 = sig * sig * inv_r2
    sr6 = sr2 ** 3
    sr12 = sr6 ** 2
    fac_lj = 24 * eps * (2 * sr12 - sr6) * inv_r2
    src6 = (sig * sig / ff.r_cut ** 2) ** 3
    e_lj = 4 * eps * ((sr12 - sr6) - (src6 ** 2 - src6))
    inv_r = np.sqrt(inv_r2)
    qq = q_i * q_j
    fac_c = qq * (inv_r * inv_r2 - 2 * ff.k_rf)
    e_c = qq * (inv_r + ff.k_rf * r2safe - ff.c_rf)
    return (np.where(mask, fac_lj + fac_c, 0.0),
            np.where(mask, e_lj + e_c, 0.0))


def direct_forces_reference(pos, charge, typ, box, ff: ForceField):
    """Direct-sum reference with minimum image; float64 numpy."""
    pos = np.asarray(pos, np.float64)
    q = np.asarray(charge, np.float64)
    t = np.asarray(typ, np.int64)
    box = np.asarray(box, np.float64)
    n = pos.shape[0]
    dx = pos[:, None, :] - pos[None, :, :]
    dx -= box * np.round(dx / box)
    fac, e = _direct_terms(dx, q[:, None], q[None, :], t[:, None],
                           t[None, :], ff, ~np.eye(n, dtype=bool))
    forces = np.sum(fac[..., None] * dx, axis=1)
    return forces, 0.5 * np.sum(e)


def direct_forces_rows(pos, charge, typ, box, ff: ForceField, rows,
                       chunk: int = 32):
    """Float64 direct-sum forces on the atoms ``rows`` from ALL atoms.

    The same minimum-image math as :func:`direct_forces_reference`,
    evaluated ``chunk`` rows at a time so a subset of a large system can
    be checked in O(chunk * N) memory.
    """
    pos = np.asarray(pos, np.float64)
    q = np.asarray(charge, np.float64)
    t = np.asarray(typ, np.int64)
    box = np.asarray(box, np.float64)
    rows = np.asarray(rows, np.int64)
    out = np.zeros((rows.shape[0], 3))
    for lo in range(0, rows.shape[0], chunk):
        r = rows[lo:lo + chunk]
        dx = pos[r, None, :] - pos[None, :, :]
        dx -= box * np.round(dx / box)
        fac, _ = _direct_terms(dx, q[r, None], q[None, :], t[r, None],
                               t[None, :], ff,
                               r[:, None] != np.arange(pos.shape[0]))
        out[lo:lo + chunk] = np.sum(fac[..., None] * dx, axis=1)
    return out
