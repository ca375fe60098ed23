"""Jitted public wrappers around the Pallas kernels.

``interpret=None`` resolves through :func:`repro.kernels.interpret_mode`:
the Pallas interpreter on the CPU, compiled kernels on a TPU.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.md.system import ForceField
from repro.kernels import flash_attention as _fa
from repro.kernels import halo_pack as _hp
from repro.kernels import nonbonded as _nb


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def pack(src, index_map, chunk: int = 128, interpret: bool | None = None):
    return _hp.pack(src, index_map, chunk=chunk, interpret=interpret)


def put_signal(src, index_map, *, axis: str, ring: int, chunk: int = 128,
               interpret: bool | None = None):
    """Must be called inside shard_map over ``axis``."""
    return _hp.put_signal(src, index_map, axis, ring, chunk=chunk,
                          interpret=interpret)


def fused_pulses(src, index_maps, *, axis: str, ring: int, n_local: int,
                 chunk: int = 64, interpret: bool | None = None):
    """Fused dependency-partitioned multi-pulse exchange (shard_map)."""
    return _hp.fused_pulses(src, index_maps, axis, ring, n_local,
                            chunk=chunk, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("ff", "block", "interpret"))
def pair_forces(a, b, ta, tb, same, ff: ForceField, block: int = 8,
                interpret: bool | None = None):
    return _nb.pair_forces(a, b, ta, tb, same, ff, block=block,
                           interpret=interpret)


@functools.partial(jax.jit,
                   static_argnames=("causal", "bq", "bk", "interpret"))
def flash_attention(q, k, v, causal: bool = True, bq: int = 128,
                    bk: int = 256, interpret: bool | None = None):
    return _fa.flash_attention(q, k, v, causal=causal, bq=bq, bk=bk,
                               interpret=interpret)
