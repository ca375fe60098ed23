"""Plan-based halo exchange: one differentiable object, pluggable backends.

The paper's core design is a *persistent, pre-planned* exchange: pulse
metadata (``PulseData``, ``depOffset``, index maps, signal slots) is built
once at domain-decomposition time and then executed by GPU-initiated
kernels every step.  This module is that construct-once/execute-many seam
for the JAX reproduction:

* :class:`HaloSpec` — frozen, hashable description of the exchange (mesh
  axis names, per-dim halo widths, periodic wrap shifts, dtype / feature
  layout, backend name).

* :class:`HaloPlan` — built via :meth:`HaloPlan.build(spec, mesh)`.  It
  precomputes the :class:`~repro.core.schedule.PulseSchedule`, the per-dim
  ``ppermute`` pairs, region metadata, byte / critical-path statistics
  (:meth:`HaloPlan.stats`, absorbing the old ``exchange_stats``), and — for
  the ``"pallas"`` backend — the static index maps feeding
  :func:`repro.kernels.halo_pack.pack` / ``unpack_add``.

* ``plan.fwd(x)`` / ``plan.rev(ext)`` — shard-mapped coordinate / force
  exchanges over global arrays, plus device-local ``fwd_local`` /
  ``rev_local`` for callers that already sit inside a ``shard_map`` (the
  MD engine's fused step program).

* ``plan.exchange(x)`` — a ``jax.custom_vjp``-registered exchange whose
  adjoint *is* the fused reverse path (paper Alg. 6): ``jax.grad`` through
  a coordinate exchange automatically emits the force-return exchange.

Backends are a registry; ``"serialized"`` and ``"fused"`` wrap the staged
implementations in :mod:`repro.core.halo`, ``"pallas"`` drives the
pack/put kernels of :mod:`repro.kernels.halo_pack` (compiled on a TPU,
interpreted on the CPU; a kernel failure raises).  New backends
(double-buffered, multi-step, NVSHMEM-alike) plug in via
:func:`register_backend`.
"""
from __future__ import annotations

import dataclasses
import math
from dataclasses import dataclass
from typing import Any, Callable, Dict, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from repro.compat import shard_map_norep
from repro.core import halo as _halo
from repro.core import wire as _wire
from repro.core.schedule import PulseSchedule, make_schedule

Region = Tuple[int, ...]

_UNSET = object()


# --------------------------------------------------------------------------
# spec
# --------------------------------------------------------------------------

@dataclass(frozen=True)
class HaloSpec:
    """Frozen description of a halo exchange (hashable, jit-static).

    ``wrap_shift`` is the per-dimension periodic-image shift added to
    feature components when data crosses the periodic boundary (the
    paper's ``coordShift``); stored as a nested tuple so the spec stays
    hashable — ``HaloSpec.with_wrap_shift`` converts from arrays.
    ``dtype``/``feature_elems`` describe the payload layout and feed the
    default byte accounting in :meth:`HaloPlan.stats`.  ``pulses`` is the
    per-dim pulse count (GROMACS' two-pulse case splits a dim's halo across
    two staged pulses); ``None`` means one pulse per dim.

    ``wire_dtype`` compresses the exchanged payload on the wire
    (``None`` = dense; ``"float32"`` / ``"bfloat16"`` / ``"float16"`` =
    cast, ``"int8_ef"`` = error-feedback int8; see
    :mod:`repro.core.wire` for the measured rationale).  Compression is
    direction-asymmetric: the coordinate (forward) exchange has a
    float32 floor — f64 payloads ship f32 coordinates, f32 ships dense —
    while the named format compresses the force-return (reverse)
    exchange, whose quantization error integrates as zero-mean noise.
    Payloads are quantized before send and dequantized after receive,
    the local body never crosses the wire and stays exact, and integer
    payloads (the MD engine's ``cell_i`` index exchange) always ride
    dense.  Plan build rejects formats whose measured NVE drift exceeds
    the dense-f32 bound (:func:`repro.core.wire.gate_wire_config`).
    """

    axis_names: Tuple[str, ...]
    widths: Tuple[int, ...]
    backend: str = "fused"
    wrap_shift: Optional[Tuple[Tuple[float, ...], ...]] = None
    dtype: str = "float32"
    feature_elems: int = 1
    pulses: Optional[Tuple[int, ...]] = None
    wire_dtype: Optional[str] = None

    def __post_init__(self):
        if self.wire_dtype is not None and \
                self.wire_dtype not in _wire.WIRE_DTYPES:
            raise ValueError(
                f"unknown wire_dtype {self.wire_dtype!r}; "
                f"available: {_wire.WIRE_DTYPES} or None")
        object.__setattr__(self, "axis_names", tuple(self.axis_names))
        object.__setattr__(self, "widths",
                           tuple(int(w) for w in self.widths))
        if len(self.axis_names) != len(self.widths):
            raise ValueError("axis_names and widths must have equal length")
        if self.pulses is not None:
            object.__setattr__(self, "pulses",
                               tuple(int(n) for n in self.pulses))
        if self.wrap_shift is not None:
            object.__setattr__(
                self, "wrap_shift",
                tuple(tuple(float(v) for v in row)
                      for row in np.asarray(self.wrap_shift)))

    @property
    def ndim(self) -> int:
        return len(self.axis_names)

    def with_wrap_shift(self, wrap_shift) -> "HaloSpec":
        """Return a copy with ``wrap_shift`` taken from an array-like
        (``__post_init__`` re-normalizes to the hashable nested tuple)."""
        return dataclasses.replace(self, wrap_shift=wrap_shift)

    def wrap_shift_array(self) -> Optional[jnp.ndarray]:
        if self.wrap_shift is None:
            return None
        return jnp.asarray(np.asarray(self.wrap_shift, dtype=self.dtype))


# --------------------------------------------------------------------------
# backend registry
# --------------------------------------------------------------------------

class HaloBackend:
    """Device-local executor: both methods run *inside* a shard_map.

    ``critical_path`` names which of the two chained-bytes models in
    :meth:`HaloPlan.stats` describes this backend's execution —
    ``"serialized"`` for pulse-sequential backends, ``"fused"`` for
    phase-concurrent ones.
    """

    name: str = "?"
    critical_path: str = "serialized"

    def fwd(self, plan: "HaloPlan", local: jnp.ndarray,
            wrap_shift: Optional[jnp.ndarray]) -> jnp.ndarray:
        raise NotImplementedError

    def rev(self, plan: "HaloPlan", ext: jnp.ndarray) -> jnp.ndarray:
        raise NotImplementedError

    def _local_shape(self, plan: "HaloPlan", ext: jnp.ndarray) -> Tuple[int, ...]:
        return tuple(ext.shape[d] - plan.spec.widths[d]
                     for d in range(plan.spec.ndim))


class SerializedBackend(HaloBackend):
    """CPU-initiated MPI baseline: one full slab per pulse, sequential."""

    name = "serialized"

    def fwd(self, plan, local, wrap_shift):
        return _halo.exchange_fwd_serialized(local, plan.sched,
                                             plan.axis_sizes, wrap_shift)

    def rev(self, plan, ext):
        return _halo.exchange_rev_serialized(ext, plan.sched,
                                             plan.axis_sizes)


class FusedBackend(HaloBackend):
    """GPU-initiated fused redesign: dependency-partitioned phases."""

    name = "fused"
    critical_path = "fused"

    def fwd(self, plan, local, wrap_shift):
        return _halo.exchange_fwd_fused(local, plan.sched, plan.axis_sizes,
                                        wrap_shift)

    def rev(self, plan, ext):
        return _halo.exchange_rev_fused(ext, plan.sched, plan.axis_sizes,
                                        self._local_shape(plan, ext))


class PallasBackend(HaloBackend):
    """Pack/unpack through the Pallas kernels of ``kernels.halo_pack``.

    Realizes each pulse as pack (device-initiated gather into a contiguous
    send buffer, paper Alg. 3 line 7) -> ``ppermute`` (the put) ->
    concat / scatter-add (the unpack).  Index maps are static per local
    shape and cached on the plan — the analogue of the paper's DD-time
    index-map build.  Pulses execute in serialized (forwarding-chained)
    order, so the serialized critical-path model applies.
    """

    name = "pallas"
    critical_path = "serialized"

    # -- kernel dispatch ---------------------------------------------------

    def _pack(self, plan, src2d: jnp.ndarray, idx: np.ndarray,
              wire: Optional[str] = None) -> jnp.ndarray:
        """Pack rows into the send buffer, optionally quantizing into the
        wire dtype inside the kernel (fused quantize-into-pack: the wire
        format never materializes in HBM — only the packed send buffer
        and the received rows are wire-dtyped)."""
        from repro.kernels import halo_pack
        return halo_pack.pack(src2d, jnp.asarray(idx), wire_dtype=wire)

    def _unpack_add(self, plan, dst2d: jnp.ndarray, idx: np.ndarray,
                    rows: jnp.ndarray) -> jnp.ndarray:
        from repro.kernels import halo_pack
        return halo_pack.unpack_add(dst2d, jnp.asarray(idx), rows)

    # -- static index maps (built once per local shape, cached) ------------

    @staticmethod
    def _rows_along(shape: Sequence[int], d: int, lo: int, hi: int
                    ) -> np.ndarray:
        """Row ids of ``reshape(prod(shape[:d+1]), -1)`` whose coordinate
        along axis ``d`` lies in ``[lo, hi)``."""
        n_rows = int(np.prod(shape[:d + 1], dtype=np.int64))
        coord = np.arange(n_rows, dtype=np.int64) % shape[d]
        return np.nonzero((coord >= lo) & (coord < hi))[0].astype(np.int32)

    def _maps(self, plan, local_shape: Tuple[int, ...]):
        cached = plan._index_maps.get(local_shape)
        if cached is not None:
            return cached
        fwd_maps, rev_maps = [], []
        shape = list(local_shape)
        for pulse in plan.sched.serialized_order():
            d, w, off = pulse.dim, pulse.width, pulse.offset
            if w:
                fwd_maps.append(self._rows_along(shape, d, off, off + w))
                shape[d] += w
            else:
                fwd_maps.append(None)
        for pulse in reversed(plan.sched.serialized_order()):
            d, w, off = pulse.dim, pulse.width, pulse.offset
            if w:
                n = shape[d] - w
                pack_idx = self._rows_along(shape, d, n, shape[d])
                shape[d] = n
                add_idx = self._rows_along(shape, d, off, off + w)
                rev_maps.append((pack_idx, add_idx))
            else:
                rev_maps.append(None)
        plan._index_maps[local_shape] = (tuple(fwd_maps), tuple(rev_maps))
        return plan._index_maps[local_shape]

    # -- exchange ----------------------------------------------------------

    def fwd(self, plan, local, wrap_shift):
        sched = plan.sched
        shifter = _halo._Shifter(sched.axis_names, plan.axis_sizes,
                                 wrap_shift)
        nd = plan.spec.ndim
        local_shape = tuple(local.shape[:nd])
        fwd_maps, _ = self._maps(plan, local_shape)
        # the coordinate direction's f32 floor ships f32 send buffers for
        # wide payloads (pack casts, receive side casts back before the
        # wrap shift); the payload is already wire-gridded at the plan
        # seam so the cast is exact and results stay bitwise-identical
        # to the serialized reference
        wire = plan.wire_pack_dtype(local.dtype)
        ext = local
        for pulse, idx in zip(sched.serialized_order(), fwd_maps):
            if idx is None:
                continue
            d, w = pulse.dim, pulse.width
            shape = ext.shape
            src2d = ext.reshape(math.prod(shape[:d + 1]), -1)
            slab = self._pack(plan, src2d, idx, wire).reshape(
                shape[:d] + (w,) + shape[d + 1:])
            recv = lax.ppermute(slab, sched.axis_names[d], plan.fwd_perms[d])
            recv = recv.astype(local.dtype)       # dequantize-after-receive
            recv = shifter(recv, d)
            ext = jnp.concatenate([ext, recv], axis=d)
        return ext

    def rev(self, plan, ext):
        sched = plan.sched
        nd = plan.spec.ndim
        local_shape = self._local_shape(plan, ext)
        _, rev_maps = self._maps(plan, local_shape)
        out = ext
        for pulse, maps in zip(reversed(sched.serialized_order()), rev_maps):
            if maps is None:
                continue
            pack_idx, add_idx = maps
            d, w = pulse.dim, pulse.width
            shape = out.shape
            n = shape[d] - w
            src2d = out.reshape(math.prod(shape[:d + 1]), -1)
            halo_rows = self._pack(plan, src2d, pack_idx)
            slab = halo_rows.reshape(shape[:d] + (w,) + shape[d + 1:])
            recv = lax.ppermute(slab, sched.axis_names[d], plan.rev_perms[d])
            body = lax.slice_in_dim(out, 0, n, axis=d)
            bshape = body.shape
            body2d = body.reshape(math.prod(bshape[:d + 1]), -1)
            rows = recv.reshape(add_idx.shape[0], -1)
            body2d = self._unpack_add(plan, body2d, add_idx, rows)
            out = body2d.reshape(bshape)
        return out


_BACKENDS: Dict[str, Callable[[], HaloBackend]] = {}


def register_backend(name: str, factory: Callable[[], HaloBackend]) -> None:
    """Register a halo backend under ``name`` (the config axis value)."""
    _BACKENDS[name] = factory


def available_backends() -> Tuple[str, ...]:
    return tuple(sorted(_BACKENDS))


def get_backend(name: str) -> HaloBackend:
    try:
        return _BACKENDS[name]()
    except KeyError:
        raise ValueError(
            f"unknown halo backend {name!r}; "
            f"available: {available_backends()}") from None


register_backend("serialized", SerializedBackend)
register_backend("fused", FusedBackend)
register_backend("pallas", PallasBackend)


# --------------------------------------------------------------------------
# byte / critical-path accounting (absorbs the old halo.exchange_stats)
# --------------------------------------------------------------------------

# default link model for the latency term in HaloPlan.stats: an
# InfiniBand-class inter-node hop (~1.5 us) at NVLink/ICI-class payload
# bandwidth; both are per-call configurable
DEFAULT_LINK_LATENCY_S = 1.5e-6
DEFAULT_BANDWIDTH_BPS = 5.0e10


def compute_exchange_stats(sched: PulseSchedule,
                           local_shape: Sequence[int],
                           itemsize: int,
                           feature_elems: int = 1) -> dict:
    """Bytes moved per phase/pulse and the two critical-path models.

    Both designs move the same regions, hence the single ``total_bytes``.
    The serialized design chains every pulse's full (forwarding-inclusive)
    slab, so its critical path *is* the total; the fused design overlaps
    each phase's transfers, chaining only ``max`` bytes per phase.

    ``exchanged_cells`` is the exchanged region volume in *cells* — the
    payload-independent first-class quantity every byte field is derived
    from (``total_bytes = exchanged_cells * feature_elems * itemsize``).
    Callers accounting side-channel payloads with different itemsizes
    (index exchanges, wire formats) must scale from ``exchanged_cells``,
    never back-derive volume from a byte total.
    """
    ndim = sched.ndim
    widths = sched.widths

    def vol_cells(region: Region) -> int:
        v = 1
        for d in range(ndim):
            v *= widths[d] if d in region else local_shape[d]
        return v

    def vol(region: Region) -> int:
        return vol_cells(region) * feature_elems * itemsize

    ser_pulse_bytes = []
    shape = list(local_shape)
    for pulse in sched.serialized_order():
        d = pulse.dim
        slab = 1
        for k in range(ndim):
            slab *= pulse.width if k == d else shape[k]
        ser_pulse_bytes.append(slab * feature_elems * itemsize)
        shape[d] += pulse.width

    fused_phases = []
    for phase in sched.forward_phases():
        fused_phases.append({
            "regions": [{"dims": r, "bytes": vol(r)} for r in phase],
            "phase_bytes": sum(vol(r) for r in phase),
            "phase_critical_bytes": max((vol(r) for r in phase), default=0),
        })

    cells = sum(vol_cells(r) for phase in sched.forward_phases()
                for r in phase)
    total = sum(p["phase_bytes"] for p in fused_phases)
    assert total == cells * feature_elems * itemsize
    assert total == sum(ser_pulse_bytes), "slab/region accounting mismatch"
    return {
        "exchanged_cells": cells,
        "total_bytes": total,
        "serialized_pulse_bytes": ser_pulse_bytes,
        # fully sequential: the chained bytes are all of them
        "serialized_critical_bytes": sum(ser_pulse_bytes),
        "fused_phases": fused_phases,
        "fused_critical_bytes": sum(p["phase_critical_bytes"]
                                    for p in fused_phases),
        "dependent_fraction": sched.dependent_fraction(local_shape),
    }


def latency_model(stats: dict,
                  link_latency_s: float = DEFAULT_LINK_LATENCY_S,
                  bandwidth_Bps: float = DEFAULT_BANDWIDTH_BPS) -> dict:
    """alpha-beta time model for one exchange direction (paper §6.2).

    The serialized (CPU-initiated) design pays one link latency per
    *message* — pulses are strictly chained, so each of its messages adds
    ``alpha + bytes / BW`` to the critical path.  The fused GPU-initiated
    design issues every message of a phase concurrently (put-with-signal,
    no host round-trip), so a phase costs one ``alpha`` plus its chained
    (max-transfer) bytes.  In the strong-scaling limit (bytes -> 0) the
    ratio approaches ``n_messages / n_phases`` — the paper's small-domain
    regime, where GROMACS' two-pulse dims make the serialized path pay
    twice the latency per dim.
    """
    ser_msgs = [b for b in stats["serialized_pulse_bytes"] if b > 0]
    phases = [p for p in stats["fused_phases"] if p["phase_bytes"] > 0]
    serialized_s = sum(link_latency_s + b / bandwidth_Bps for b in ser_msgs)
    fused_s = sum(link_latency_s + p["phase_critical_bytes"] / bandwidth_Bps
                  for p in phases)
    return {
        "link_latency_s": link_latency_s,
        "bandwidth_Bps": bandwidth_Bps,
        "serialized_messages": len(ser_msgs),
        "fused_phase_messages": [len(p["regions"]) for p in phases],
        "serialized_time_s": serialized_s,
        "fused_time_s": fused_s,
        "fused_speedup": serialized_s / fused_s if fused_s else 1.0,
    }


def overlap_model(stats: dict, critical_path: str,
                  pipeline: str = "off", depth: int = 2) -> dict:
    """Per-step exposed-vs-overlapped communication under a step pipeline.

    ``exposed_phases_per_step`` counts the communication stages left on a
    step's critical path (per the backend's ``critical_path`` model: pulses
    when serialized, phases when fused), for both exchange directions.
    ``pipeline="double_buffer"`` overlaps the whole force-return exchange
    of step ``N`` with step ``N+1``'s forward half, so the reverse bytes
    count as overlapped (the drain of the final step is amortized over
    the block).  A ``depth``-deep window (ring of ``depth`` extended-force
    slots, ``depth - 1`` steps resident per fused program region) further
    amortizes the *forward* stages: the coordinate sends of an in-window
    step overlap the force compute of up to ``depth - 2`` older resident
    steps, leaving ``1 / (depth - 1)`` of the forward stages exposed per
    step — monotone decreasing in ``depth``, the paper's deeper-overlap
    limit where only one exchange per window stays on the critical path.

    Like the alpha-beta :func:`latency_model`, this is an *analytic*
    model of what signal-coordinated hardware can hide, not a property
    of the emulated schedule: the CPU pipeline pins each step with
    barriers to guarantee bitwise conformance, so the depth axis is
    measurable here but its predicted win must be validated on a real
    mesh (see the ROADMAP open item).
    """
    if critical_path == "serialized":
        stages = len([b for b in stats["serialized_pulse_bytes"] if b > 0])
    else:
        stages = len([p for p in stats["fused_phases"]
                      if p["phase_bytes"] > 0])
    if pipeline == "double_buffer":
        if depth < 2:
            raise ValueError("double_buffer overlap model needs depth >= 2")
        window = depth - 1                     # steps in flight per region
        exposed = stages / window              # exposed forward fraction
        overlapped_stages = 2 * stages - exposed
        # the whole reverse exchange plus the hidden forward fraction
        overlapped_bytes = int(round(
            stats["total_bytes"] * (2 - 1 / window)))
    else:
        depth = 1
        exposed = 2 * stages                   # forward + reverse chained
        overlapped_bytes = 0
        overlapped_stages = 0
    return {
        "pipeline": pipeline,
        "depth": depth,
        "exposed_phases_per_step": exposed,
        "overlapped_phases_per_step": overlapped_stages,
        "overlapped_bytes_per_step": overlapped_bytes,
        # both directions move the same regions
        "exchanged_bytes_per_step": 2 * stats["total_bytes"],
    }


# --------------------------------------------------------------------------
# plan
# --------------------------------------------------------------------------

class HaloPlan:
    """Construct-once / execute-many halo exchange bound to a mesh.

    Build with :meth:`HaloPlan.build`; execute with :meth:`fwd` /
    :meth:`rev` / :meth:`exchange` (global arrays) or :meth:`fwd_local` /
    :meth:`rev_local` (inside an enclosing ``shard_map``).
    """

    def __init__(self, spec: HaloSpec, mesh: Mesh, verify: str = "error"):
        for a in spec.axis_names:
            if a not in mesh.shape:
                raise ValueError(f"mesh has no axis {a!r}; "
                                 f"mesh axes: {tuple(mesh.shape)}")
        self.spec = spec
        self.mesh = mesh
        self.backend = get_backend(spec.backend)
        # wire-format acceptance gate first: a compressed-payload config
        # whose measured NVE drift exceeds the dense-f32 bound is rejected
        # here (verify="warn"/"off" is the PR 6 escape-hatch convention)
        self.wire = _wire.make_codec(spec.wire_dtype)
        self.wire_drift = _wire.gate_wire_config(spec.wire_dtype, verify)
        # config check next: nonsense (widths, pulses) combinations fail
        # here with an actionable message instead of deep in tracing
        from repro.analysis.schedule_verifier import check_halo_config
        self.sched: PulseSchedule = check_halo_config(
            spec.axis_names, spec.widths, spec.pulses)
        self.axis_sizes: Tuple[int, ...] = tuple(
            int(mesh.shape[a]) for a in spec.axis_names)
        # per-dim ppermute pairs, precomputed once (the plan's PulseData)
        self.fwd_perms = tuple(_halo._perm_fwd(n) for n in self.axis_sizes)
        self.rev_perms = tuple(_halo._perm_rev(n) for n in self.axis_sizes)
        self.partition_spec = P(*spec.axis_names)
        self._wrap = spec.wrap_shift_array()
        self._index_maps: Dict[Tuple[int, ...], Any] = {}
        self._stats_cache: Dict[Tuple, dict] = {}
        self._exchange = self._make_exchange()

    # -- construction ------------------------------------------------------

    @classmethod
    def build(cls, spec: HaloSpec, mesh: Mesh,
              verify: str = "error") -> "HaloPlan":
        return cls(spec, mesh, verify=verify)

    # -- introspection -----------------------------------------------------

    @property
    def regions(self) -> Tuple[Region, ...]:
        return self.sched.regions()

    @property
    def forward_phases(self):
        return self.sched.forward_phases()

    @property
    def reverse_phases(self):
        return self.sched.reverse_phases()

    def extended_shape(self, local_shape: Sequence[int]) -> Tuple[int, ...]:
        """Per-device extended-block shape for a given local block shape."""
        out = list(local_shape)
        for d, w in enumerate(self.spec.widths):
            out[d] += w
        return tuple(out)

    def stats(self, local_shape: Sequence[int],
              itemsize: Optional[int] = None,
              feature_elems: Optional[int] = None,
              pipeline: str = "off", depth: int = 2,
              link_latency_s: float = DEFAULT_LINK_LATENCY_S,
              bandwidth_Bps: float = DEFAULT_BANDWIDTH_BPS,
              index_elems: int = 0, index_itemsize: int = 4,
              occupancy: Optional[float] = None) -> dict:
        """Canonical byte/critical-path stats for this plan's schedule.

        Defaults derive from the spec's dtype / feature layout; results are
        cached per argument tuple.  On top of the byte accounting this
        reports a configurable alpha-beta ``latency`` model (per-message
        link latency + bytes/bandwidth — see :func:`latency_model`) and the
        step-``pipeline`` overlap model (``exposed_phases_per_step`` /
        ``overlapped_bytes_per_step`` under ``"off"`` or
        ``"double_buffer"`` at in-flight window ``depth`` — see
        :func:`overlap_model`).

        ``index_elems`` accounts side-channel *index* payloads the
        canonical float accounting excludes (the MD engine's ``(K, 2)``
        int32 ``cell_i`` exchange: ``index_elems=2 * K``), reported as
        ``bytes_index`` over the same exchanged regions.  ``occupancy``
        (fraction of payload elements carrying real data — for MD, atoms
        per capacity slot) yields ``useful_bytes``: the padded capacity
        slots are exchanged but carry nothing.
        """
        if itemsize is None:
            itemsize = int(np.dtype(self.spec.dtype).itemsize)
        if feature_elems is None:
            feature_elems = self.spec.feature_elems
        key = (tuple(local_shape), itemsize, feature_elems, pipeline,
               depth, link_latency_s, bandwidth_Bps, index_elems,
               index_itemsize, occupancy)
        if key not in self._stats_cache:
            stats = dict(compute_exchange_stats(
                self.sched, tuple(local_shape), itemsize, feature_elems))
            # every byte field derives from the first-class exchanged
            # region volume in cells — NOT back-derived from total_bytes,
            # which silently mis-scales once payload and index itemsizes
            # diverge (e.g. feature_elems=0 index-only accounting, or
            # wire formats whose itemsize differs from the payload's)
            cells = stats["exchanged_cells"]
            stats["bytes_index"] = cells * index_elems * index_itemsize
            stats["occupancy"] = occupancy
            stats["useful_bytes"] = (
                None if occupancy is None
                else int(round(stats["total_bytes"] * occupancy)))
            # wire-format accounting, per direction: coordinates (fwd)
            # ride at the float32 floor, the force return (rev) at the
            # named format (int8 adds one 4-byte scale per serialized
            # message).  ``wire_bytes`` covers BOTH directions of one
            # step against ``2 * total_bytes`` dense.
            wire = self.wire
            stats["wire_dtype"] = self.spec.wire_dtype
            stats["wire_itemsize_fwd"] = (
                itemsize if wire is None
                else wire.fwd_itemsize(self.spec.dtype))
            stats["wire_itemsize_rev"] = (itemsize if wire is None
                                          else wire.wire_itemsize)
            stats["wire_itemsize"] = stats["wire_itemsize_rev"]
            n_msgs = len([b for b in stats["serialized_pulse_bytes"]
                          if b > 0])
            scale_overhead = (0 if wire is None or wire.is_float
                              else 4 * n_msgs)
            stats["wire_bytes_fwd"] = (cells * feature_elems
                                       * stats["wire_itemsize_fwd"])
            stats["wire_bytes_rev"] = (cells * feature_elems
                                       * stats["wire_itemsize_rev"]
                                       + scale_overhead)
            stats["wire_bytes"] = (stats["wire_bytes_fwd"]
                                   + stats["wire_bytes_rev"])
            stats["wire_reduction"] = (
                2 * stats["total_bytes"] / stats["wire_bytes"]
                if stats["wire_bytes"] else 1.0)
            stats["latency"] = latency_model(stats, link_latency_s,
                                             bandwidth_Bps)
            if wire is not None:
                # the predicted win: the same alpha-beta model at the
                # per-direction mean wire itemsize — latency terms
                # unchanged, bandwidth terms scaled by the byte cut
                mean_itemsize = (stats["wire_itemsize_fwd"]
                                 + stats["wire_itemsize_rev"]) / 2
                wstats = compute_exchange_stats(
                    self.sched, tuple(local_shape),
                    mean_itemsize, feature_elems)
                lat_w = latency_model(wstats, link_latency_s,
                                      bandwidth_Bps)
                lat_w["wire_speedup_fused"] = (
                    stats["latency"]["fused_time_s"] / lat_w["fused_time_s"]
                    if lat_w["fused_time_s"] else 1.0)
                lat_w["wire_speedup_serialized"] = (
                    stats["latency"]["serialized_time_s"]
                    / lat_w["serialized_time_s"]
                    if lat_w["serialized_time_s"] else 1.0)
                stats["latency_wire"] = lat_w
            overlap = overlap_model(stats, self.backend.critical_path,
                                    pipeline, depth)
            stats["overlap"] = overlap
            stats["exposed_phases_per_step"] = \
                overlap["exposed_phases_per_step"]
            stats["overlapped_bytes_per_step"] = \
                overlap["overlapped_bytes_per_step"]
            self._stats_cache[key] = stats
        return self._stats_cache[key]

    def publish_stats(self, registry, local_shape: Sequence[int],
                      **kw) -> dict:
        """:meth:`stats`, also published as a ``halo_stats`` record.

        The registry stays out of the stats cache key: this is a separate
        method so ``stats`` callers keep their memoization while emitters
        (engine build, benchmarks) push the same dict — plus the backend's
        critical-path model — into a
        :class:`~repro.obs.registry.MetricsRegistry`.
        """
        stats = self.stats(local_shape, **kw)
        registry.emit("halo_stats", backend=self.spec.backend,
                      critical_path=self.backend.critical_path,
                      local_shape=tuple(local_shape), data=stats)
        return stats

    # -- device-local execution (inside an enclosing shard_map) ------------

    def _resolve_shift(self, wrap_shift):
        if wrap_shift is _UNSET:
            wrap_shift = self._wrap
        if wrap_shift is None:
            return None
        return jnp.asarray(wrap_shift)

    def _wire_active(self, x: jnp.ndarray) -> bool:
        """Wire compression applies to floating payloads only: integer
        side channels (the MD engine's ``cell_i`` exchange) ride dense."""
        return self.wire is not None and \
            jnp.issubdtype(x.dtype, jnp.floating)

    def wire_pack_dtype(self, dtype) -> Optional[str]:
        """Wire dtype for fused quantize-into-pack kernels on the
        coordinate (forward) direction: the float32 floor — f64 payloads
        pack/put f32 rows, narrower payloads pack dense.  (The named
        format compresses only the force-return direction, whose
        accumulated sums the kernels never re-round.)"""
        if self.wire is None:
            return None
        if not jnp.issubdtype(jnp.dtype(dtype), jnp.floating):
            return None
        return self.wire.fwd_wire_dtype(dtype)

    def _body_idx(self, local_shape: Sequence[int]) -> Tuple[slice, ...]:
        """Index of the local body inside an extended block (halos are
        appended at the high end of each decomposed dim)."""
        return tuple(slice(0, int(n)) for n in local_shape)

    def fwd_local(self, local: jnp.ndarray, wrap_shift=_UNSET) -> jnp.ndarray:
        """Coordinate exchange on one device's block (needs shard_map).

        With ``spec.wire_dtype`` set the payload is wire-gridded at the
        coordinate direction's float32 floor before the sends and the
        exact local body spliced back afterwards: received halo data is
        wire-lossy, local data never is.  Payloads already at or below
        the floor ride dense (the coordinate cast would be an identity).
        """
        shift = self._resolve_shift(wrap_shift)
        if not self._wire_active(local) or \
                self.wire.fwd_wire_dtype(local.dtype) is None:
            return self.backend.fwd(self, local, shift)
        q = self.wire.fwd_roundtrip(local)
        ext = self.backend.fwd(self, q, shift)
        body = self._body_idx(local.shape[:self.spec.ndim])
        return ext.at[body].set(local)

    def rev_local(self, ext: jnp.ndarray) -> jnp.ndarray:
        """Force-return exchange on one device's extended block.

        The adjoint direction compresses symmetrically: halo-region force
        contributions are wire-quantized before the return puts, the body
        (never transmitted) stays exact.
        """
        if not self._wire_active(ext):
            return self.backend.rev(self, ext)
        return self.backend.rev(self, self._rev_wire(ext, None)[0])

    def rev_local_ef(self, ext: jnp.ndarray, ef: jnp.ndarray
                     ) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """:meth:`rev_local` with error-feedback state (ext-shaped)."""
        q, new_ef = self._rev_wire(ext, ef)
        return self.backend.rev(self, q), new_ef

    def rev_local_raw(self, ext: jnp.ndarray) -> jnp.ndarray:
        """Reverse exchange with NO wire seam — for callers that already
        hold a wire-gridded extended buffer (the pipeline's slot ring
        decodes at drain time; re-quantizing would double-apply EF)."""
        return self.backend.rev(self, ext)

    def _rev_wire(self, ext, ef):
        q, new_ef = self.wire.roundtrip(ext, ef)
        body = self._body_idx(tuple(
            ext.shape[d] - self.spec.widths[d]
            for d in range(self.spec.ndim)))
        return q.at[body].set(ext[body]), new_ef

    # -- wire-format slot-ring codec (pipeline extended-force buffers) -----

    def wire_encode_ext(self, F_ext: jnp.ndarray,
                        ef: Optional[jnp.ndarray] = None):
        """Encode an extended-force buffer into wire-format ring parts.

        Returns ``(parts, new_ef)`` where ``parts`` is a tuple of arrays
        to store in the pipeline's slot ring: the wire-dtyped buffer
        (+ scale for int8) plus the exact f32/f64 body — so in-flight
        force windows are HBM-resident in wire format while the local
        body keeps full precision.  ``wire_decode_ext`` inverts it; the
        composition equals :meth:`_rev_wire`'s quantize-and-splice
        bitwise, which keeps ``off`` == ``double_buffer`` conformance.
        """
        parts, new_ef = self.wire.encode(F_ext, ef)
        body = self._body_idx(tuple(
            F_ext.shape[d] - self.spec.widths[d]
            for d in range(self.spec.ndim)))
        return parts + (F_ext[body],), new_ef

    def wire_decode_ext(self, parts, dtype) -> jnp.ndarray:
        """Decode slot-ring parts back to the wire-gridded extended-force
        buffer with the exact body spliced in (drain side)."""
        wire_parts, bodyv = parts[:-1], parts[-1]
        F = self.wire.decode(wire_parts, dtype)
        body = self._body_idx(bodyv.shape[:self.spec.ndim])
        return F.at[body].set(bodyv)

    # -- global execution (plan applies the shard_map) ---------------------

    def _shard(self, body):
        spec = self.partition_spec
        return shard_map_norep(body, mesh=self.mesh, in_specs=spec,
                               out_specs=spec)

    def fwd(self, x: jax.Array, wrap_shift=_UNSET) -> jax.Array:
        """Shard-mapped coordinate exchange over ``mesh``.

        ``x`` is sharded over the spec's axis names on its leading dims;
        the result re-stacks the per-device extended blocks (global shape
        grows by ``size_d * w_d`` per dim).
        """
        shift = self._resolve_shift(wrap_shift)
        return self._shard(lambda lo: self.fwd_local(lo, shift))(x)

    def rev(self, ext: jax.Array) -> jax.Array:
        """Shard-mapped force-return exchange (adjoint of :meth:`fwd`)."""
        return self._shard(lambda e: self.rev_local(e))(ext)

    def exchange(self, x: jax.Array) -> jax.Array:
        """Differentiable exchange: the VJP *is* the reverse exchange.

        ``jax.grad`` through ``plan.exchange`` emits this plan's fused
        (or backend-selected) force-return path instead of XLA's
        transpose of the forward collectives — paper Alg. 6 as an
        autodiff rule.
        """
        return self._exchange(x)

    def _make_exchange(self):
        @jax.custom_vjp
        def exchange(x):
            return self.fwd(x)

        def exchange_fwd(x):
            # the exchange is affine in x (wrap shifts are constants), so
            # no residuals are needed: the VJP is the exact linear adjoint
            return self.fwd(x), None

        def exchange_bwd(_, g):
            return (self.rev(g),)

        exchange.defvjp(exchange_fwd, exchange_bwd)
        return exchange

    def __repr__(self):
        return (f"HaloPlan(backend={self.spec.backend!r}, "
                f"axes={self.spec.axis_names}, widths={self.spec.widths}, "
                f"mesh={dict(self.mesh.shape)})")


# the pipeline subsystem's put-with-signal backend registers itself on
# import; the cycle is benign (it only references names defined above)
import repro.core.pipeline.signal_backend  # noqa: E402,F401
