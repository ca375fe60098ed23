"""Cluster-pair LJ + reaction-field force kernel (the paper's hot loop).

GROMACS' non-bonded kernels interact i-clusters with j-clusters from the
pair list; our cell scheme (see core/md/forces.py) interacts K-atom cell
pairs across the 14-offset eighth-shell stencil.  This Pallas kernel
computes one batch of cell pairs: given packed A-cells and B-cells
(N, K, 4) [x, y, z, q] plus per-pair type tables, it produces forces on
both sides and the pair potential energy.

TPU adaptation (vs the CUDA cluster kernel): the K x K pair interaction
tile is computed as VPU-vectorized broadcasts in VMEM, ``block`` cell
pairs (a multiple of 8) per grid step; operands are laid out with the
slot axis on lanes and streamed HBM->VMEM through BlockSpecs.  Validated
in interpret mode against ref.py / the engine's jnp path, and compiled
for a v5e at grappa-90k shapes by tests/test_tpu_compile.py.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.md.system import ForceField
from repro.kernels import interpret_mode


def _colify(row, eye):
    """(C, 1, K) lane row -> (C, K, 1) sublane column, exactly: the only
    nonzero term of each diagonal sum is the element itself."""
    full = jnp.broadcast_to(row, eye.shape)
    return jnp.sum(jnp.where(eye, full, jnp.zeros((), row.dtype)), axis=2,
                   keepdims=True)


def _rowify(col, eye):
    """(C, K, 1) sublane column -> (C, 1, K) lane row, exactly."""
    full = jnp.broadcast_to(col, eye.shape)
    return jnp.sum(jnp.where(eye, full, jnp.zeros((), col.dtype)), axis=1,
                   keepdims=True)


def _table(ti, tj, table, dtype):
    """Per-pair entry of a static (T, T) table, as a select chain (the
    vector units have no gather); types are pre-clipped into range."""
    out = None
    for i, row in enumerate(table):
        for j, val in enumerate(row):
            if out is None:
                out = jnp.full(jnp.broadcast_shapes(ti.shape, tj.shape),
                               val, dtype)
            else:
                out = jnp.where((ti == i) & (tj == j), val, out)
    return out


def _pair_kernel(a_ref, b_ref, ta_ref, tb_ref, same_ref, *rest,
                 ff: ForceField, use_counts: bool):
    """One block of C cell pairs, all (C, K, K) slot-pair tiles in VMEM.

    Operands are lane-dense: positions/charges ``(C, 4, K)``, types
    ``(C, 1, K)``, per-pair scalars ``(C, 1, 1)``.  Row i of a tile is an
    A slot (sublanes), column j a B slot (lanes); A's rows are turned
    into columns in-register (:func:`_colify`), and the per-A force sums
    back into rows (:func:`_rowify`), both exactly.
    """
    if use_counts:
        cnta_ref, cntb_ref, fa_ref, fb_ref, pe_ref = rest
    else:
        fa_ref, fb_ref, pe_ref = rest
    a = a_ref[...]                                # (C, 4, K)
    b = b_ref[...]
    C, _, K = a.shape
    dtype = a.dtype
    shape = (C, K, K)
    ii = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    jj = jax.lax.broadcasted_iota(jnp.int32, shape, 2)
    eye = ii == jj
    T = len(ff.eps)
    ta = _colify(ta_ref[...], eye)                          # (C, K, 1)
    tb = tb_ref[...]                                        # (C, 1, K)
    if use_counts:
        # per-pair slot bounds: binning packs each cell's atoms into a
        # contiguous slot prefix, so slot < count IS slot validity
        valid = (ii < cnta_ref[...]) & (jj < cntb_ref[...])
    else:
        valid = (ta >= 0) & (tb >= 0)

    dx = [_colify(a[:, d:d + 1, :], eye) - b[:, d:d + 1, :]
          for d in range(3)]
    r2 = dx[0] * dx[0] + dx[1] * dx[1] + dx[2] * dx[2]
    mask = valid & (r2 < ff.r_cut ** 2)
    # same-cell pairs take the strict upper triangle (each pair once);
    # distinct cells interact fully — slots never alias across cells
    mask &= (same_ref[...] == 0) | (ii < jj)

    r2s = jnp.where(mask, r2, 1.0)
    inv_r2 = 1.0 / r2s
    tai = jnp.clip(ta, 0, T - 1)
    tbi = jnp.clip(tb, 0, T - 1)
    eps = _table(tai, tbi, ff.eps, dtype)
    sig = _table(tai, tbi, ff.sigma, dtype)
    sr2 = sig * sig * inv_r2
    sr6 = sr2 * sr2 * sr2
    sr12 = sr6 * sr6
    fac_lj = 24.0 * eps * (2.0 * sr12 - sr6) * inv_r2
    src2 = sig * sig / (ff.r_cut ** 2)
    src6 = src2 * src2 * src2
    e_lj = 4.0 * eps * ((sr12 - sr6) - (src6 * src6 - src6))
    inv_r = jnp.sqrt(inv_r2)
    qq = _colify(a[:, 3:4, :], eye) * b[:, 3:4, :]
    fac_c = qq * (inv_r * inv_r2 - 2.0 * ff.k_rf)
    e_c = qq * (inv_r + ff.k_rf * r2s - ff.c_rf)
    fac = jnp.where(mask, fac_lj + fac_c, 0.0)
    pe = jnp.where(mask, e_lj + e_c, 0.0)

    for d in range(3):
        fvec = fac * dx[d]
        fa_ref[:, d:d + 1, :] = _rowify(jnp.sum(fvec, axis=2, keepdims=True),
                                        eye)
        fb_ref[:, d:d + 1, :] = -jnp.sum(fvec, axis=1, keepdims=True)
    pe_ref[...] = jnp.sum(jnp.sum(pe, axis=2, keepdims=True), axis=1,
                          keepdims=True)


def _pad_rows(x, n_pad: int, value):
    if n_pad == x.shape[0]:
        return x
    cfg = [(0, n_pad - x.shape[0])] + [(0, 0)] * (x.ndim - 1)
    return jnp.pad(x, cfg, constant_values=value)


def pair_forces(a, b, ta, tb, same, ff: ForceField, block: int = 8,
                interpret: bool | None = None, cnt_a=None, cnt_b=None):
    """Forces + energies for N cell pairs.

    a, b: (N, K, 4) packed [x, y, z, q]; ta, tb: (N, K) atom types with
    -1 padding; same: (N,) nonzero when a pair is a cell with itself
    (triangle masking).  ``cnt_a`` / ``cnt_b`` (N,) int32, when given,
    supply per-pair slot bounds: slot validity becomes ``slot < count``
    (the packed-prefix invariant of ``cells.bin_to_cells``) instead of
    the per-slot type test — the form the tiered pair schedule feeds,
    where the batch K is already the pair's bucketed bound.  Returns
    (fa (N,K,3), fb (N,K,3), pe (N,)).

    ``block`` (a multiple of 8) cell pairs run per grid step; N is padded
    up to a whole number of blocks with empty pairs.
    """
    interpret = interpret_mode(interpret)
    if block % 8:
        raise ValueError(f"block must be a multiple of 8, got {block}")
    N, K, _ = a.shape
    n_pad = -(-N // block) * block
    use_counts = cnt_a is not None

    def lanes(x, fill):                           # (N, K, f) -> (Np, f, K)
        return _pad_rows(jnp.swapaxes(x, 1, 2), n_pad, fill)

    def scalar(x):                                # (N,) -> (Np, 1, 1)
        return _pad_rows(x.astype(jnp.int32).reshape(N, 1, 1), n_pad, 0)

    args = [lanes(a, 0), lanes(b, 0),
            lanes(ta.astype(jnp.int32)[..., None], -1),
            lanes(tb.astype(jnp.int32)[..., None], -1), scalar(same)]
    if use_counts:
        args += [scalar(cnt_a), scalar(cnt_b)]
    spec = lambda r, c: pl.BlockSpec((block, r, c),  # noqa: E731
                                     lambda i: (i, 0, 0))
    in_specs = [spec(4, K), spec(4, K), spec(1, K), spec(1, K), spec(1, 1)]
    if use_counts:
        in_specs += [spec(1, 1), spec(1, 1)]
    fa, fb, pe = pl.pallas_call(
        functools.partial(_pair_kernel, ff=ff, use_counts=use_counts),
        grid=(n_pad // block,),
        in_specs=in_specs,
        out_specs=[spec(3, K), spec(3, K), spec(1, 1)],
        out_shape=[jax.ShapeDtypeStruct((n_pad, 3, K), a.dtype),
                   jax.ShapeDtypeStruct((n_pad, 3, K), a.dtype),
                   jax.ShapeDtypeStruct((n_pad, 1, 1), a.dtype)],
        interpret=interpret,
        name="nb_pair_forces",
    )(*args)
    return (jnp.swapaxes(fa[:N], 1, 2), jnp.swapaxes(fb[:N], 1, 2),
            pe[:N, 0, 0])


# --------------------------------------------------------------------------
# scatter-accumulate epilogue: batched pair forces -> extended force array
# --------------------------------------------------------------------------

def _scatter_accum_kernel(ia_ref, ib_ref, fa_ref, fb_ref, zero_ref, out_ref,
                          acc, sem, *, chunk: int):
    """Grid step c accumulates chunk c's per-pair forces into their cells.

    Cell indices REPEAT across pairs (every base cell anchors 14 stencil
    pairs), so rows are added one pair at a time — a read-modify-write of
    the cell's HBM row through a VMEM accumulator.  The TPU grid is
    sequential, which makes the accumulation deterministic (the analogue
    of GROMACS' per-cluster force reduction order).  ``out`` aliases the
    zero-initialized ``zero_ref``.
    """
    del zero_ref

    def add(cell, rows):
        cp = pltpu.make_async_copy(out_ref.at[cell], acc, sem)
        cp.start()
        cp.wait()
        acc[...] = acc[...] + rows
        cp = pltpu.make_async_copy(acc, out_ref.at[cell], sem)
        cp.start()
        cp.wait()

    def body(i, carry):
        add(ia_ref[0, 0, i], fa_ref[i])
        add(ib_ref[0, 0, i], fb_ref[i])
        return carry

    jax.lax.fori_loop(0, chunk, body, 0)


def scatter_accum(cell_a, cell_b, fa, fb, n_cells: int, chunk: int = 128,
                  interpret: bool | None = None):
    """Pallas epilogue: sum (N, K, 3) pair forces into (n_cells, K, 3).

    ``cell_a`` / ``cell_b`` are per-pair flat cell indices in
    ``[0, n_cells)`` (padding pairs must point at a sentinel row the
    caller slices off).  Duplicate indices accumulate.  Cell rows are
    moved as lane-padded ``(1, K*3)`` rows, the DMA-sliceable view.
    """
    interpret = interpret_mode(interpret)
    N, K, _ = fa.shape
    if N == 0:
        return jnp.zeros((n_cells, K, 3), fa.dtype)
    f = K * 3
    fp = -(-f // 128) * 128
    chunk = max(1, min(chunk, N))
    n_pad = -(-N // chunk) * chunk

    pad = n_pad - N

    def rows(x):
        x = jnp.pad(x.reshape(N, f), ((0, pad), (0, fp - f)))
        return x.reshape(n_pad, 1, fp)

    # padding pairs add zero rows to cell 0: a no-op.  Each grid step's
    # cell indices are one (1, 1, chunk) SMEM block.
    ia = jnp.pad(cell_a.astype(jnp.int32), (0, pad)).reshape(-1, 1, chunk)
    ib = jnp.pad(cell_b.astype(jnp.int32), (0, pad)).reshape(-1, 1, chunk)
    zero = jnp.zeros((n_cells, 1, fp), fa.dtype)
    smem = lambda: pl.BlockSpec((1, 1, chunk),  # noqa: E731
                                lambda c: (c, 0, 0),
                                memory_space=pltpu.SMEM)
    vrows = lambda: pl.BlockSpec((chunk, 1, fp),  # noqa: E731
                                 lambda c: (c, 0, 0))
    out = pl.pallas_call(
        functools.partial(_scatter_accum_kernel, chunk=chunk),
        grid=(n_pad // chunk,),
        in_specs=[smem(), smem(), vrows(), vrows(),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        out_shape=jax.ShapeDtypeStruct((n_cells, 1, fp), fa.dtype),
        scratch_shapes=[pltpu.VMEM((1, fp), fa.dtype),
                        pltpu.SemaphoreType.DMA],
        input_output_aliases={4: 0},
        interpret=interpret,
        name="nb_scatter_accum",
    )(ia, ib, rows(fa), rows(fb), zero)
    return out.reshape(n_cells, fp)[:, :f].reshape(n_cells, K, 3)


def pair_forces_accum(a, b, ta, tb, same, cell_a, cell_b, ff: ForceField,
                      n_cells: int, block: int = 8,
                      interpret: bool | None = None,
                      epilogue: str = "xla", cnt_a=None, cnt_b=None):
    """``pair_forces`` extended with the scatter-accumulate epilogue.

    Computes one batch of cell-pair forces and accumulates both sides
    into a fresh ``(n_cells, K, 3)`` extended force array (plus the
    per-pair energies).  ``cnt_a`` / ``cnt_b`` thread the per-pair slot
    bounds through to the kernel's validity masks (the tiered pair
    schedule's batches are sized per tier, not to one rectangular
    ``K_exec``).  ``epilogue="pallas"`` drives the sequential
    :func:`scatter_accum` kernel — the TPU-native shape of the fused
    NB-force + reduction stage; ``"xla"`` lowers the same accumulation
    as an XLA scatter-add (duplicate-safe, and the faster choice under
    interpret mode on CPU).  Both orders are fixed per compilation.
    """
    fa, fb, pe = pair_forces(a, b, ta, tb, same, ff, block=block,
                             interpret=interpret, cnt_a=cnt_a, cnt_b=cnt_b)
    if epilogue == "pallas":
        F = scatter_accum(cell_a, cell_b, fa, fb, n_cells,
                          interpret=interpret)
    else:
        F = jnp.zeros((n_cells, fa.shape[1], 3), fa.dtype)
        F = F.at[cell_a].add(fa, mode="drop")
        F = F.at[cell_b].add(fb, mode="drop")
    return F, pe
