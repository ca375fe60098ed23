"""Fused pack + device-initiated remote put with signal (paper Alg. 3/4/5).

TPU mapping of the paper's NVSHMEM kernels:

  * ``nvshmem_put_signal_nbi`` / TMA remote store  ->
        ``pltpu.make_async_remote_copy`` — TPU RDMA is *natively*
        put-with-signal: the receiver's ``recv_sem`` IS the signal, and
        ``wait_recv`` is the acquire side (paper's acquire_wait on
        ctx.signal[p]).
  * warp-level pack/transmit pipelining (Alg. 3 line 7)  ->
        chunk-grained DMA issue: each packed chunk's remote copy starts as
        soon as that chunk is gathered, while the next chunk packs.
  * depOffset dependency partitioning (Alg. 4)  ->
        chunks whose index-map entries reference the previous pulse's halo
        slots wait on THAT pulse's completion token only; independent
        chunks are packed and transmitted immediately.

Memory layout.  Index maps are scalar-prefetched into SMEM
(``PrefetchScalarGridSpec``); the row arrays stay in HBM (``pl.ANY``) and
are only ever touched by DMA: each selected row is copied into a VMEM
staging buffer, where the vector units may read it.  Rows are viewed as
``(P, 1, F)`` with ``F`` padded to a multiple of 128 lanes, because a DMA
slice must be tile-aligned and the leading dim of that view is untiled.
Negative index entries are padding and produce zero rows.

Interpret mode follows :func:`repro.kernels.interpret_mode`: the Pallas
interpreter on the CPU, compiled Mosaic kernels on a TPU.  Compiled
kernels (and Pallas' TPU interpreter) address remote peers by mesh
coordinates after a barrier handshake; the plain interpreter emulates
remote copies along one named axis only, by logical id.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import interpret_mode

_LANE = 128
# VMEM bytes one staging buffer may take; chunks shrink to fit wide rows
_STAGE_BYTES = 1 << 20


# --------------------------------------------------------------------------
# shared helpers
# --------------------------------------------------------------------------

def _lane_pad(f: int) -> int:
    return -(-f // _LANE) * _LANE


def _as_rows(x: jax.Array) -> jax.Array:
    """(P, F) -> (P, 1, Fp): the DMA-sliceable row view."""
    n, f = x.shape
    fp = _lane_pad(f)
    if fp != f:
        x = jnp.pad(x, ((0, 0), (0, fp - f)))
    return x.reshape(n, 1, fp)


def _chunking(m: int, f: int, itemsize: int, chunk: int):
    """Rows per grid step and the padded row count (a chunk multiple)."""
    fit = max(1, _STAGE_BYTES // (_lane_pad(f) * itemsize))
    chunk = max(1, min(chunk, m, fit))
    return chunk, -(-m // chunk) * chunk


def _pad_index(idx: jax.Array, m_pad: int) -> jax.Array:
    """Pad the trailing axis with -1 (padding rows come out zero)."""
    pad = m_pad - idx.shape[-1]
    if pad == 0:
        return idx.astype(jnp.int32)
    cfg = [(0, 0)] * (idx.ndim - 1) + [(0, pad)]
    return jnp.pad(idx.astype(jnp.int32), cfg, constant_values=-1)


def _gather_rows(read, base, chunk: int, src_of, buf, sem):
    """``buf[i] = row(read(base + i))`` for one chunk, by DMA.

    ``read(r)`` is the index-map entry of packed row ``r``; ``src_of(j)``
    returns ``(pred, ref_row)`` pairs naming the HBM row that entry ``j``
    selects under each predicate (the predicates are disjoint).  Entries
    matching no predicate are padding: their row is zeroed in VMEM.  All
    copies of the chunk are started before any is waited on.
    """
    def start(i, carry):
        j = read(base + i)
        hit = jnp.zeros((), jnp.bool_)
        for pred, row in src_of(j):
            hit = hit | pred

            @pl.when(pred)
            def _(row=row):
                pltpu.make_async_copy(row, buf.at[i], sem).start()

        @pl.when(jnp.logical_not(hit))
        def _():
            buf[i] = jnp.zeros(buf.shape[1:], buf.dtype)
        return carry

    lax.fori_loop(0, chunk, start, 0)

    def wait(i, carry):
        @pl.when(read(base + i) >= 0)
        def _():
            # a wait only needs the destination size and the semaphore
            pltpu.make_async_copy(buf.at[i], buf.at[i], sem).wait()
        return carry

    lax.fori_loop(0, chunk, wait, 0)


def _peer(axis: str, index: jax.Array, mesh_ids: bool) -> dict:
    """Device-id keywords naming ring position ``index`` along ``axis``.

    With ``mesh_ids`` the peer is addressed by mesh coordinates, the other
    mesh axes kept at this device's own (the dict form); the plain
    interpreter only emulates one named axis and takes the logical id.
    """
    if mesh_ids:
        return {"device_id": {axis: index},
                "device_id_type": pltpu.DeviceIdType.MESH}
    return {"device_id": index, "device_id_type": pltpu.DeviceIdType.LOGICAL}


def _barrier(axis: str, source: jax.Array):
    """Tell the device that writes into us that our buffers are live,
    and wait for the device we write into to say the same."""
    sem = pltpu.get_barrier_semaphore()
    pltpu.semaphore_signal(sem, 1, **_peer(axis, source, True))
    pltpu.semaphore_wait(sem, 1)


def _mesh_ids(interpret) -> bool:
    """Peers by mesh coordinates + barrier: all but the plain interpreter."""
    return interpret is not True


def _remote_params(interpret):
    return (pltpu.CompilerParams(collective_id=0) if _mesh_ids(interpret)
            else None)


# --------------------------------------------------------------------------
# 1. pack kernel: gather rows by index map into a contiguous send buffer
# --------------------------------------------------------------------------

def _pack_kernel(idx_ref, src_ref, out_ref, buf, sem, *, chunk: int):
    """Grid step c packs chunk c: out[c*C:(c+1)*C] = src[idx[c*C:...]].

    When the output is wire-dtyped (compressed halo payloads) the
    gathered rows are quantized in VMEM before the store: quantize fuses
    into pack, so only the packed send buffer is compressed.
    """
    base = pl.program_id(0) * chunk
    _gather_rows(lambda r: idx_ref[r], base, chunk,
                 lambda j: [(j >= 0, src_ref.at[j])], buf, sem)
    out_ref[...] = buf[...].astype(out_ref.dtype)


def pack(src: jax.Array, index_map: jax.Array, chunk: int = 128,
         interpret: bool | None = None, wire_dtype=None) -> jax.Array:
    """Pack rows of ``src`` (P, F) selected by ``index_map`` (M,).

    ``wire_dtype`` (e.g. ``"bfloat16"``) returns the packed buffer in
    that dtype with the cast fused into the gather (quantize-into-pack).
    """
    interpret = interpret_mode(interpret)
    M = index_map.shape[0]
    F = src.shape[-1]
    out_dtype = src.dtype if wire_dtype is None else jnp.dtype(wire_dtype)
    chunk, m_pad = _chunking(M, F, src.dtype.itemsize, chunk)
    fp = _lane_pad(F)
    out = pl.pallas_call(
        functools.partial(_pack_kernel, chunk=chunk),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(m_pad // chunk,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((chunk, 1, fp), lambda c, idx: (c, 0, 0)),
            scratch_shapes=[pltpu.VMEM((chunk, 1, fp), src.dtype),
                            pltpu.SemaphoreType.DMA]),
        out_shape=jax.ShapeDtypeStruct((m_pad, 1, fp), out_dtype),
        interpret=interpret,
        name="halo_pack",
    )(_pad_index(index_map, m_pad), _as_rows(src))
    return out.reshape(m_pad, fp)[:M, :F]


# --------------------------------------------------------------------------
# 1b. unpack kernel: scatter-add received rows back by index map
# --------------------------------------------------------------------------

def _unpack_add_kernel(idx_ref, rows_ref, dst_ref, out_ref, buf, sem, *,
                       chunk: int):
    """Grid step c: out[idx[c*C:(c+1)*C]] += rows[c*C:(c+1)*C].

    The reverse-path unpack (paper's CommUnpackF): received force rows are
    accumulated into the destination selected by the index map.  ``out``
    aliases ``dst``; indices must be unique within the map (halo-plan
    index maps are collision-free by construction), so a chunk's rows
    are gathered, added and scattered back as one batch.
    """
    del dst_ref                                   # aliased by out_ref
    base = pl.program_id(0) * chunk
    read = lambda r: idx_ref[r]                   # noqa: E731
    _gather_rows(read, base, chunk,
                 lambda j: [(j >= 0, out_ref.at[j])], buf, sem)
    buf[...] = buf[...] + rows_ref[...].astype(buf.dtype)

    def start(i, carry):
        j = read(base + i)

        @pl.when(j >= 0)
        def _():
            pltpu.make_async_copy(buf.at[i], out_ref.at[j], sem).start()
        return carry

    lax.fori_loop(0, chunk, start, 0)

    def wait(i, carry):
        @pl.when(read(base + i) >= 0)
        def _():
            pltpu.make_async_copy(buf.at[i], buf.at[i], sem).wait()
        return carry

    lax.fori_loop(0, chunk, wait, 0)


def unpack_add(dst: jax.Array, index_map: jax.Array, rows: jax.Array,
               chunk: int = 128, interpret: bool | None = None) -> jax.Array:
    """Scatter-add ``rows`` (M, F) into ``dst`` (P, F) at ``index_map``."""
    interpret = interpret_mode(interpret)
    M = index_map.shape[0]
    P_, F = dst.shape
    chunk, m_pad = _chunking(M, F, dst.dtype.itemsize, chunk)
    fp = _lane_pad(F)
    rows = rows.astype(dst.dtype)
    if m_pad != M:
        rows = jnp.pad(rows, ((0, m_pad - M), (0, 0)))
    out = pl.pallas_call(
        functools.partial(_unpack_add_kernel, chunk=chunk),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(m_pad // chunk,),
            in_specs=[pl.BlockSpec((chunk, 1, fp), lambda c, idx: (c, 0, 0)),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            scratch_shapes=[pltpu.VMEM((chunk, 1, fp), dst.dtype),
                            pltpu.SemaphoreType.DMA]),
        out_shape=jax.ShapeDtypeStruct((P_, 1, fp), dst.dtype),
        # operand 0 is the scalar-prefetched index map
        input_output_aliases={2: 0},
        interpret=interpret,
        name="halo_unpack_add",
    )(_pad_index(index_map, m_pad), _as_rows(rows), _as_rows(dst))
    return out.reshape(P_, fp)[:, :F]


# --------------------------------------------------------------------------
# 2. put-with-signal: pack + remote copy to a ring neighbor
# --------------------------------------------------------------------------

def _put_signal_kernel(idx_ref, src_ref, out_ref, *scratch, chunk: int,
                       axis: str, ring: int, shift: int, mesh_ids: bool):
    """One pulse of a ring halo exchange, chunk-pipelined.

    Packs chunk c into VMEM, then starts the remote copy into the
    receiver's out buffer (fused pack+comm+notify); the wait drains the
    receive (the signal acquire).  ``shift`` is the ring offset of the
    put target: -1 for the coordinate (forward) halo (send to -1,
    receive from +1), +1 for the force-return (reverse) path.

    When the out buffer is wire-dtyped (compressed halo payloads) the
    quantizing cast happens in VMEM between gather and put, so both the
    staging buffer and the remote DMA move wire-sized rows.
    """
    if len(scratch) == 5:
        buf, wbuf, sem, send_sem, recv_sem = scratch
    else:
        (buf, sem, send_sem, recv_sem), wbuf = scratch, None
    c = pl.program_id(0)
    my = lax.axis_index(axis)
    target = lax.rem(my + ring + shift, ring)
    if mesh_ids:
        pl.when(c == 0)(lambda: _barrier(axis, lax.rem(my + ring - shift,
                                                         ring)))
    base = c * chunk
    _gather_rows(lambda r: idx_ref[r], base, chunk,
                 lambda j: [(j >= 0, src_ref.at[j])], buf, sem)
    send = buf
    if wbuf is not None:
        wbuf[...] = buf[...].astype(wbuf.dtype)
        send = wbuf
    copy = pltpu.make_async_remote_copy(
        src_ref=send, dst_ref=out_ref.at[pl.ds(base, chunk)],
        send_sem=send_sem, recv_sem=recv_sem,
        **_peer(axis, target, mesh_ids))
    copy.start()
    copy.wait()                                   # drain send+recv signals


def put_signal(src: jax.Array, index_map: jax.Array, axis: str, ring: int,
               chunk: int = 128, interpret: bool | None = None,
               shift: int = -1, wire_dtype=None) -> jax.Array:
    """Device-initiated halo put: returns this device's RECEIVED buffer.

    Must run inside shard_map over ``axis`` (ring size ``ring``).
    ``shift=-1`` puts to the -1 neighbor (coordinate halo, receive from
    +1); ``shift=+1`` puts to the +1 neighbor (force-return path).
    ``wire_dtype`` (e.g. ``"bfloat16"``) makes the put and the returned
    receive buffer wire-dtyped (quantize fused into pack).
    """
    interpret = interpret_mode(interpret)
    M = index_map.shape[0]
    F = src.shape[-1]
    out_dtype = src.dtype if wire_dtype is None else jnp.dtype(wire_dtype)
    if interpret is False and out_dtype.itemsize != 4:
        # a (1, F) row of a packed 16/8-bit array is not a whole tile, so
        # its remote DMA cannot be sliced; the engine's puts ship f32
        raise NotImplementedError(
            f"put_signal: compiled puts move 32-bit rows, got {out_dtype}")
    chunk, m_pad = _chunking(M, F, src.dtype.itemsize, chunk)
    fp = _lane_pad(F)
    scratch = [pltpu.VMEM((chunk, 1, fp), src.dtype)]
    if out_dtype != src.dtype:
        scratch.append(pltpu.VMEM((chunk, 1, fp), out_dtype))
    scratch += [pltpu.SemaphoreType.DMA] * 3
    out = pl.pallas_call(
        functools.partial(_put_signal_kernel, chunk=chunk, axis=axis,
                          ring=ring, shift=shift,
                          mesh_ids=_mesh_ids(interpret)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(m_pad // chunk,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            scratch_shapes=scratch),
        out_shape=jax.ShapeDtypeStruct((m_pad, 1, fp), out_dtype),
        compiler_params=_remote_params(interpret),
        interpret=interpret,
        name="halo_put_signal",
    )(_pad_index(index_map, m_pad), _as_rows(src))
    return out.reshape(m_pad, fp)[:M, :F]


# --------------------------------------------------------------------------
# 3. fused two-pulse exchange with dependency partitioning (Alg. 3+4)
# --------------------------------------------------------------------------

def _fused_pulses_kernel(idx_ref, dep_ref, ndep_ref, src_ref, out_ref, buf,
                         sem, send_sem, recv_sem, dep_sem, *, chunk: int,
                         axis: str, ring: int, n_pulses: int,
                         n_local: int, mesh_ids: bool):
    """Grid (pulse, chunk).  Pulse p's index entries < n_local gather from
    local data (independent — packed/sent immediately); entries >= n_local
    reference pulse p-1's receive buffer (dependent — the chunk first
    acquires p-1's dependency token).  This is Alg. 4's depOffset split
    with the signal wait fused into the same kernel (Alg. 5): the remote
    copy's recv semaphore is the data signal, dep_sem carries the
    last-completing-chunk release notification to the next pulse.
    ``dep_ref[p, c]`` flags dependent chunks and ``ndep_ref[p]`` counts
    them (both precomputed from the static index maps).

    Staged forwarding reads pulse p-1's receive buffer verbatim, so wire
    compression of this kernel would re-round at every hop; multi-pulse
    dims therefore always ship dense (see SignalBackend.fwd).
    """
    p = pl.program_id(0)
    c = pl.program_id(1)
    n_chunks = pl.num_programs(1)
    my = lax.axis_index(axis)
    target = lax.rem(my + ring - 1, ring)
    if mesh_ids:
        pl.when((p == 0) & (c == 0))(
            lambda: _barrier(axis, lax.rem(my + 1, ring)))

    # dependent chunks acquire the previous pulse's completion token;
    # independent chunks proceed immediately (the fused-design payoff).
    @pl.when(jnp.logical_and(p > 0, dep_ref[p, c] > 0))
    def _():
        pltpu.semaphore_wait(dep_sem, 1)

    prev = jnp.maximum(p - 1, 0)
    base = c * chunk
    _gather_rows(
        lambda r: idx_ref[p, r], base, chunk,
        lambda j: [((j >= 0) & (j < n_local),
                    src_ref.at[jnp.minimum(j, n_local - 1)]),
                   (j >= n_local,
                    out_ref.at[prev, jnp.maximum(j - n_local, 0)])],
        buf, sem)

    copy = pltpu.make_async_remote_copy(
        src_ref=buf, dst_ref=out_ref.at[p, pl.ds(base, chunk)],
        send_sem=send_sem, recv_sem=recv_sem,
        **_peer(axis, target, mesh_ids))
    copy.start()
    copy.wait()

    # last-completing chunk of pulse p releases exactly one token per
    # dependent chunk of pulse p+1 (paper Alg. 5: only the last block
    # emits the release, keeping signal traffic minimal)
    @pl.when(jnp.logical_and(c == n_chunks - 1, p < n_pulses - 1))
    def _():
        pltpu.semaphore_signal(dep_sem,
                               ndep_ref[jnp.minimum(p + 1, n_pulses - 1)])


def fused_pulses(src: jax.Array, index_maps: jax.Array, axis: str,
                 ring: int, n_local: int, chunk: int = 64,
                 interpret: bool | None = None) -> jax.Array:
    """Fused multi-pulse staged exchange along one ring axis.

    src: (P, F) local rows; index_maps: (n_pulses, M) with entries in
    [0, n_local) selecting local rows and [n_local, n_local+M) selecting
    rows of the previous pulse's receive buffer (staged forwarding).
    Returns (n_pulses, M, F): this device's receive buffers.
    """
    interpret = interpret_mode(interpret)
    n_pulses, M = index_maps.shape
    F = src.shape[-1]
    chunk, m_pad = _chunking(M, F, src.dtype.itemsize, chunk)
    n_chunks = m_pad // chunk
    fp = _lane_pad(F)
    maps = _pad_index(index_maps, m_pad)
    dep = jnp.any((maps >= n_local).reshape(n_pulses, n_chunks, chunk),
                  axis=-1).astype(jnp.int32)
    ndep = jnp.sum(dep, axis=-1).astype(jnp.int32)
    out = pl.pallas_call(
        functools.partial(_fused_pulses_kernel, chunk=chunk, axis=axis,
                          ring=ring, n_pulses=n_pulses, n_local=n_local,
                          mesh_ids=_mesh_ids(interpret)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(n_pulses, n_chunks),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            scratch_shapes=[pltpu.VMEM((chunk, 1, fp), src.dtype),
                            pltpu.SemaphoreType.DMA,
                            pltpu.SemaphoreType.DMA,
                            pltpu.SemaphoreType.DMA,
                            pltpu.SemaphoreType.REGULAR]),
        out_shape=jax.ShapeDtypeStruct((n_pulses, m_pad, 1, fp), src.dtype),
        compiler_params=_remote_params(interpret),
        interpret=interpret,
        name="halo_fused_pulses",
    )(maps, dep, ndep, _as_rows(src))
    return out.reshape(n_pulses, m_pad, fp)[:, :M, :F]
