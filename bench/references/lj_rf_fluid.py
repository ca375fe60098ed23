"""Plain reference of a Lennard-Jones (+ reaction-field) fluid.

The benchmark's own copy, independent of the program under test: the
system generator (the LAMMPS ``in.lj`` melt: an fcc lattice with uniform
random velocities scaled to the temperature), the float64 minimum-image
direct sum, and a velocity-Verlet integrator over an O(N) pair list.
Nothing here imports the program.

``pair_dtype`` selects the precision of the per-pair arithmetic (from
the pair distance onward).  ``float64`` is the reference; ``bfloat16``
is the lower-precision control that the correctness limits must reject.
Positions, velocities and the per-atom force sums stay in float64 either
way, so the control differs from the reference only in the pair math.
"""
from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor

import ml_dtypes
import numpy as np

BF16 = np.dtype(ml_dtypes.bfloat16)
CHUNK = 1 << 19           # pairs per threaded work item
FCC_BASIS = np.array([[0.0, 0.0, 0.0], [0.5, 0.5, 0.0], [0.5, 0.0, 0.5],
                      [0.0, 0.5, 0.5]])


# --------------------------------------------------------------------------
# system generator
# --------------------------------------------------------------------------

def rng_for(seed: int) -> np.random.RandomState:
    """A generator for any whole-number seed, including ones above 2**32."""
    word = np.random.SeedSequence(int(seed)).generate_state(1)[0]
    return np.random.RandomState(int(word))


def make_system(cfg: dict, seed: int) -> dict:
    """The ``in.lj`` start: ``n_atoms`` = 4 m^3 atoms on an m x m x m fcc
    lattice at ``density``, all of type 0 with charge 0.

    Velocities are uniform random, with zero total momentum, scaled so
    that the kinetic temperature over 3N - 3 degrees of freedom is
    ``temperature`` (LAMMPS' ``velocity create`` defaults).  Only the
    velocities depend on the seed.  Returns float64
    ``box``/``pos``/``vel``/``charge`` and int8 ``typ``.
    """
    n = int(cfg["n_atoms"])
    m = int(round((n / 4) ** (1.0 / 3.0)))
    if 4 * m ** 3 != n:
        raise ValueError(f"n_atoms {n} is not 4 m^3 (an fcc lattice)")
    a = (4.0 / float(cfg["density"])) ** (1.0 / 3.0)
    box = np.full(3, m * a)
    cells = np.stack(np.meshgrid(*[np.arange(m)] * 3, indexing="ij"),
                     axis=-1).reshape(-1, 1, 3)
    pos = ((cells + FCC_BASIS[None]) * a).reshape(-1, 3)
    vel = rng_for(seed).uniform(-0.5, 0.5, (n, 3))
    vel -= vel.mean(axis=0, keepdims=True)
    mass = float(cfg["mass"])
    t_now = mass * np.sum(vel * vel) / (3 * n - 3)
    vel *= np.sqrt(float(cfg["temperature"]) / t_now)
    if float(cfg["r_cut"]) >= box[0] / 2:
        raise ValueError(f"r_cut {cfg['r_cut']} must be < box/2 = "
                         f"{box[0] / 2:.3f}")
    return {"box": box, "pos": pos, "vel": vel, "charge": np.zeros(n),
            "typ": np.zeros(n, np.int8)}


# --------------------------------------------------------------------------
# pair physics
# --------------------------------------------------------------------------

def k_rf(cfg: dict) -> float:
    eps_rf, rc = float(cfg["eps_rf"]), float(cfg["r_cut"])
    if np.isinf(eps_rf):
        return 1.0 / (2.0 * rc ** 3)
    return (eps_rf - 1.0) / (2.0 * eps_rf + 1.0) / rc ** 3


def pair_terms(r2, qq, eps, sig, cfg: dict, pair_dtype=np.float64):
    """Force factor (F_i = fac * dx) and energy of pairs at distance^2 r2.

    LJ with a potential shift at ``r_cut`` plus reaction field with the
    shift ``c_rf``; pairs at or beyond ``r_cut``, and pairs at r2 = 0 (an
    atom with itself), give exactly zero.
    """
    dt = np.dtype(pair_dtype)
    c = dt.type
    rc = float(cfg["r_cut"])
    krf = k_rf(cfg)
    r2, qq, eps, sig = (np.asarray(a).astype(dt) for a in (r2, qq, eps, sig))
    mask = (r2 < c(rc * rc)) & (r2 > c(0.0))
    r2s = np.where(mask, r2, c(1.0))
    inv_r2 = c(1.0) / r2s
    sr2 = sig * sig * inv_r2
    sr6 = sr2 * sr2 * sr2
    sr12 = sr6 * sr6
    fac_lj = c(24.0) * eps * (c(2.0) * sr12 - sr6) * inv_r2
    src2 = (sig * sig) / c(rc * rc)
    src6 = src2 * src2 * src2
    e_lj = c(4.0) * eps * ((sr12 - sr6) - (src6 * src6 - src6))
    inv_r = np.sqrt(inv_r2)
    fac_c = qq * (inv_r * inv_r2 - c(2.0 * krf))
    e_c = qq * (inv_r + c(krf) * r2s - c(1.0 / rc + krf * rc * rc))
    zero = c(0.0)
    return (np.where(mask, fac_lj + fac_c, zero).astype(np.float64),
            np.where(mask, e_lj + e_c, zero).astype(np.float64))


def _min_image(d, box):
    return d - box * np.round(d / box)


def direct_forces_rows(pos, charge, typ, box, cfg: dict, rows, chunk=32):
    """Float64 direct-sum forces on atoms ``rows`` from ALL atoms.

    Minimum image over every atom: O(rows * N), the plainest statement of
    the physics, against which the pair-list path below is tested.
    """
    pos = np.asarray(pos, np.float64)
    q = np.asarray(charge, np.float64)
    t = np.asarray(typ, np.int64)
    box = np.asarray(box, np.float64)
    eps_t = np.asarray(cfg["eps"], np.float64)
    sig_t = np.asarray(cfg["sigma"], np.float64)
    rows = np.asarray(rows, np.int64)
    out = np.zeros((rows.shape[0], 3))
    for lo in range(0, rows.shape[0], chunk):
        r = rows[lo:lo + chunk]
        dx = _min_image(pos[r, None, :] - pos[None, :, :], box)
        fac, _ = pair_terms(np.sum(dx * dx, axis=-1), q[r, None] * q[None, :],
                            eps_t[t[r, None], t[None, :]],
                            sig_t[t[r, None], t[None, :]], cfg)
        out[lo:lo + chunk] = np.sum(fac[..., None] * dx, axis=1)
    return out


# --------------------------------------------------------------------------
# O(N) pair list and the integrator over it
# --------------------------------------------------------------------------

def _pool():
    return ThreadPoolExecutor(max_workers=min(8, os.cpu_count() or 1))


def pair_list(pos, box, r_list: float):
    """Every pair (i < j) closer than ``r_list`` under minimum image.

    A grid of cells at least ``r_list`` wide; the cell itself and 13 of
    its 26 periodic neighbours are visited from each cell, so each
    unordered pair is found once (three or more cells per dimension).
    """
    box = np.asarray(box, np.float64)
    pos = np.mod(np.asarray(pos, np.float64), box)
    nc = np.floor(box / r_list).astype(np.int64)
    if np.any(nc < 3):
        raise ValueError(f"pair_list needs >= 3 cells per dim, got {nc}")
    c3 = np.minimum((pos / (box / nc)).astype(np.int64), nc - 1)
    flat = (c3[:, 0] * nc[1] + c3[:, 1]) * nc[2] + c3[:, 2]
    order = np.argsort(flat, kind="stable")
    n_cells = int(np.prod(nc))
    start = np.searchsorted(flat[order], np.arange(n_cells + 1))
    kmax = int(np.diff(start).max())
    members = np.full((n_cells, kmax), -1, np.int64)
    members[flat[order], np.arange(pos.shape[0]) - start[flat[order]]] = order
    cells = np.stack(np.unravel_index(np.arange(n_cells), tuple(nc)), axis=1)
    offsets = [(0, 0, 0)] + [(a, b, c) for a in (-1, 0, 1) for b in (-1, 0, 1)
                             for c in (-1, 0, 1) if (a, b, c) > (0, 0, 0)]

    def one(off):
        nb = (cells + np.asarray(off)) % nc
        b = members[np.ravel_multi_index(tuple(nb.T), tuple(nc))][:, None, :]
        a = members[:, :, None]
        ok = (a >= 0) & (b >= 0)
        if off == (0, 0, 0):
            ok &= a < b
        ia = np.broadcast_to(a, ok.shape)[ok]
        ib = np.broadcast_to(b, ok.shape)[ok]
        d = _min_image(pos[ia] - pos[ib], box)
        keep = np.einsum("ij,ij->i", d, d) < r_list * r_list
        return np.minimum(ia, ib)[keep], np.maximum(ia, ib)[keep]

    with _pool() as ex:
        parts = list(ex.map(one, offsets))
    return (np.concatenate([p[0] for p in parts]),
            np.concatenate([p[1] for p in parts]))


class PairSystem:
    """Every pair within ``r_list`` of positions ``pos`` and its constants.

    Each pair keeps the periodic image it had at ``pos``.  That image
    stays the minimum one while no atom has moved ``r_list - r_cut`` / 2
    (the integrator rebuilds before then), since ``r_list`` plus that is
    under half the box.
    """

    def __init__(self, charge, typ, box, cfg: dict, pos, r_list: float,
                 pair_dtype=np.float64):
        pos = np.asarray(pos, np.float64)
        self.box = np.asarray(box, np.float64)
        if r_list >= self.box.min() / 4 + float(cfg["r_cut"]) / 2:
            raise ValueError(f"r_list {r_list} too long for box {self.box}")
        i, j = pair_list(pos, self.box, r_list)
        t = np.asarray(typ, np.int64)
        q = np.asarray(charge, np.float64)
        self.i, self.j = i, j
        d = pos[i] - pos[j]
        self.shift = self.box * np.round(d / self.box)
        self.qq = q[i] * q[j]
        self.eps = np.asarray(cfg["eps"], np.float64)[t[i], t[j]]
        self.sig = np.asarray(cfg["sigma"], np.float64)[t[i], t[j]]
        self.cfg = cfg
        self.dtype = pair_dtype
        self.n = q.shape[0]

    def forces(self, pos):
        """Forces (N, 3) and total potential energy at ``pos``."""
        pos = np.asarray(pos, np.float64)

        def chunk(lo):
            sl = slice(lo, lo + CHUNK)
            i, j = self.i[sl], self.j[sl]
            d = pos[i] - pos[j] - self.shift[sl]
            fac, e = pair_terms(np.einsum("ij,ij->i", d, d), self.qq[sl],
                                self.eps[sl], self.sig[sl], self.cfg,
                                self.dtype)
            fv = fac[:, None] * d
            F = np.stack([np.bincount(i, fv[:, k], self.n)
                          - np.bincount(j, fv[:, k], self.n)
                          for k in range(3)], axis=1)
            return F, float(np.sum(e))

        with _pool() as ex:
            parts = list(ex.map(chunk, range(0, self.i.shape[0], CHUNK)))
        return (np.sum([p[0] for p in parts], axis=0),
                float(sum(p[1] for p in parts)))


def verlet(pos, vel, charge, typ, box, cfg: dict, n_steps: int,
           pair_dtype=np.float64):
    """``n_steps`` of velocity Verlet from (pos, vel); float64 state.

    Returns the final positions and velocities and the potential energy
    after each step.  The pair list holds every pair within ``r_cut +
    ref_skin`` and is rebuilt whenever an atom has moved half the skin
    since the last build, so it always holds every pair within ``r_cut``.
    """
    dt, mass = float(cfg["dt"]), float(cfg["mass"])
    skin = float(cfg["ref_skin"])
    pos = np.asarray(pos, np.float64).copy()
    vel = np.asarray(vel, np.float64).copy()

    def build():
        return pos.copy(), PairSystem(charge, typ, box, cfg, pos,
                                      float(cfg["r_cut"]) + skin, pair_dtype)

    built, ps = build()
    F, _ = ps.forces(pos)
    pe = []
    for _ in range(n_steps):
        vel += F * (dt / (2 * mass))
        pos += vel * dt
        if np.max(np.sum((pos - built) ** 2, axis=1)) >= (skin / 2) ** 2:
            built, ps = build()
        F, e = ps.forces(pos)
        vel += F * (dt / (2 * mass))
        pe.append(e)
    return pos, vel, np.asarray(pe)
