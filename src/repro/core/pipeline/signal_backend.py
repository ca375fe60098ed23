"""The ``"signal"`` halo backend: device-initiated put-with-signal pulses.

This backend is the end-to-end consumer of the two Pallas kernels that the
paper's GPU-initiated redesign is built from (and that previously had no
production call-site):

* single-pulse dims run :func:`repro.kernels.halo_pack.put_signal` — the
  fused pack + remote put whose receive semaphore *is* the data signal
  (paper Alg. 3/5);
* multi-pulse dims (GROMACS' two-pulse case, ``HaloSpec.pulses``) run
  :func:`repro.kernels.halo_pack.fused_pulses` — one kernel launch per
  dim, with the dependency-partitioned chunk schedule of Alg. 4 chaining
  within-dim pulses through their signal semaphores;
* the reverse (force-return) path runs ``put_signal`` with ``shift=+1``
  (put to the +1 neighbor) feeding ``unpack_add`` — Alg. 6's
  CommUnpackF.

Kernels are compiled on a TPU and interpreted on the CPU
(:func:`repro.kernels.interpret_mode`).  The interpreter emulates remote
DMAs with only one named mesh axis in scope, so on the CPU a multi-axis
call site runs a ppermute oracle with the kernels' exact copy semantics;
that is the only route to the oracle, and on a TPU a kernel failure
raises.  Index maps are static per local shape and cached on the plan,
the analogue of the paper's DD-time index-map build.

Like the other backends this one ships one hop per pulse, so halo widths
must not exceed the local block (``w <= n``, the paper's single-pulse
regime per hop); multi-pulse splits of such widths are fully supported.
"""
from __future__ import annotations

import math
from typing import Tuple

import numpy as np

import jax.numpy as jnp
from jax import lax

from repro.compat import named_axes_in_scope
from repro.core import halo as _halo
from repro.core.halo_plan import PallasBackend, register_backend
from repro.kernels import interpret_mode


class SignalBackend(PallasBackend):
    """Put-with-signal exchange over :mod:`repro.kernels.halo_pack`."""

    name = "signal"
    # pack/put/signal are fused per pulse and phases overlap in hardware:
    # the fused critical-path model describes this backend
    critical_path = "fused"

    # -- transports ----------------------------------------------------------

    def _kernel_ok(self, plan) -> bool:
        """Can the remote-copy kernels run at this call site?

        Interpret mode (CPU validation) can only emulate remote DMAs with
        a single named axis in scope; real TPU lowering has no such limit.
        """
        if not interpret_mode():
            return True
        return len(named_axes_in_scope()) <= 1

    def _put_rows(self, plan, src2d: jnp.ndarray, idx: np.ndarray, d: int,
                  shift: int, wire=None) -> jnp.ndarray:
        """One put-with-signal pulse on packed rows; returns received rows.

        ``wire`` (an fp wire dtype name) fuses quantize-into-pack: the
        VMEM scratch and the remote put are wire-dtyped, so the wire
        format never materializes in HBM — only the received buffer is,
        and the caller casts it back on acquire.
        """
        axis = plan.sched.axis_names[d]
        ring = plan.axis_sizes[d]
        jidx = jnp.asarray(idx)
        if self._kernel_ok(plan):
            from repro.kernels import halo_pack
            return halo_pack.put_signal(src2d, jidx, axis=axis, ring=ring,
                                        shift=shift, wire_dtype=wire)
        rows = jnp.take(src2d, jidx, axis=0)
        if wire is not None:
            rows = rows.astype(jnp.dtype(wire))
        perm = (_halo._perm_fwd(ring) if shift == -1
                else _halo._perm_rev(ring))
        return lax.ppermute(rows, axis, perm)

    def _fused_dim(self, plan, src2d: jnp.ndarray, maps: np.ndarray,
                   d: int) -> jnp.ndarray:
        """All of dim ``d``'s pulses in one fused kernel launch."""
        axis = plan.sched.axis_names[d]
        ring = plan.axis_sizes[d]
        n_local = src2d.shape[0]
        if self._kernel_ok(plan):
            from repro.kernels import halo_pack
            return halo_pack.fused_pulses(src2d, jnp.asarray(maps),
                                          axis=axis, ring=ring,
                                          n_local=n_local)
        # jnp oracle with the kernel's exact semantics: entries >= n_local
        # read the previous pulse's receive buffer (staged forwarding),
        # padding entries produce zero rows, puts become ppermutes.
        n_pulses, M = maps.shape
        perm = _halo._perm_fwd(ring)
        prev = jnp.zeros((M, src2d.shape[-1]), src2d.dtype)
        outs = []
        for p in range(n_pulses):
            idx = jnp.asarray(maps[p])
            valid = idx >= 0
            safe = jnp.maximum(idx, 0)
            local_rows = jnp.take(src2d, jnp.clip(safe, 0, n_local - 1),
                                  axis=0)
            dep_rows = jnp.take(prev, jnp.clip(safe - n_local, 0, M - 1),
                                axis=0)
            rows = jnp.where((safe >= n_local)[:, None], dep_rows,
                             local_rows)
            rows = jnp.where(valid[:, None], rows,
                             jnp.zeros((), rows.dtype))
            prev = lax.ppermute(rows, axis, perm)
            outs.append(prev)
        return jnp.stack(outs)

    # -- per-dim forward index maps (cached on the plan) -------------------

    def _dim_fwd_maps(self, plan, local_shape: Tuple[int, ...]):
        key = ("signal_fwd", local_shape)
        cached = plan._index_maps.get(key)
        if cached is not None:
            return cached
        shape = list(local_shape)
        per_dim = []
        for d in range(plan.spec.ndim):
            pulses = plan.sched.dim_pulses(d)
            w_total = plan.sched.widths[d]
            if w_total == 0:
                per_dim.append(None)
                continue
            if w_total > shape[d]:
                raise NotImplementedError(
                    f"signal backend: dim {d} halo width {w_total} exceeds "
                    f"the local block ({shape[d]}); multi-hop forwarding "
                    "(w > n) is not implemented")
            maps = [self._rows_along(shape, d, p.offset, p.offset + p.width)
                    for p in pulses]
            m_max = max(m.shape[0] for m in maps)
            padded = np.full((len(maps), m_max), -1, np.int32)
            for k, m in enumerate(maps):
                padded[k, :m.shape[0]] = m
            per_dim.append((padded, tuple(m.shape[0] for m in maps)))
            shape[d] += w_total
        plan._index_maps[key] = tuple(per_dim)
        return plan._index_maps[key]

    # -- exchange ----------------------------------------------------------

    def fwd(self, plan, local, wrap_shift):
        sched = plan.sched
        shifter = _halo._Shifter(sched.axis_names, plan.axis_sizes,
                                 wrap_shift)
        nd = plan.spec.ndim
        ext = local
        # single-pulse dims ship put_signal buffers at the coordinate
        # direction's f32 floor (the payload is pre-gridded at the plan
        # seam so the cast is exact); multi-pulse staged forwarding stays
        # dense — the
        # fused kernel forwards received rows without an intermediate
        # decode, which only matches the serialized reference bitwise
        # when no per-hop re-rounding is involved
        wire = plan.wire_pack_dtype(local.dtype)
        per_dim = self._dim_fwd_maps(plan, tuple(local.shape[:nd]))
        for d in range(nd):
            if per_dim[d] is None:
                continue
            padded, counts = per_dim[d]
            pulses = sched.dim_pulses(d)
            shape = ext.shape
            src2d = ext.reshape(math.prod(shape[:d + 1]), -1)
            if len(pulses) == 1:
                recvs = [self._put_rows(plan, src2d, padded[0][:counts[0]],
                                        d, shift=-1, wire=wire)]
            else:
                out = self._fused_dim(plan, src2d, padded, d)
                recvs = [out[k, :counts[k]] for k in range(len(pulses))]
            for pulse, rows in zip(pulses, recvs):
                rows = rows.astype(ext.dtype)    # dequantize-after-receive
                slab = rows.reshape(shape[:d] + (pulse.width,)
                                    + shape[d + 1:])
                ext = jnp.concatenate([ext, shifter(slab, d)], axis=d)
        return ext

    def rev(self, plan, ext):
        sched = plan.sched
        local_shape = self._local_shape(plan, ext)
        _, rev_maps = self._maps(plan, local_shape)
        out = ext
        for pulse, maps in zip(reversed(sched.serialized_order()), rev_maps):
            if maps is None:
                continue
            pack_idx, _add_idx = maps
            d, w, off = pulse.dim, pulse.width, pulse.offset
            shape = out.shape
            n = shape[d] - w
            src2d = out.reshape(math.prod(shape[:d + 1]), -1)
            # fused pack + put to the +1 neighbor: the force-return pulse
            recv_rows = self._put_rows(plan, src2d, pack_idx, d, shift=+1)
            body = lax.slice_in_dim(out, 0, n, axis=d)
            # unpack as a slab accumulate (the canonical CommUnpackF form):
            # a scatter here would hand downstream consumers a gather/
            # scatter layout and perturb how the integrator kick compiles,
            # breaking bitwise agreement with the serialized reference
            slab = recv_rows.reshape(shape[:d] + (w,) + shape[d + 1:])
            out = _halo._add_at(body, d, off, w, slab)
        return out


register_backend("signal", SignalBackend)
