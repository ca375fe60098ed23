"""Small helpers over the installed JAX (0.9) that several modules share.

``shard_map_norep`` is ``jax.shard_map`` with the replication check off,
and ``named_axes_in_scope`` reads the mesh axes bound by enclosing
shard_maps while tracing.
"""
from __future__ import annotations

import jax
from jax._src import core as _core


def shard_map_norep(f, *, mesh, in_specs, out_specs):
    """shard_map with replication checking off.

    Pallas calls have no replication rule, so bodies that may invoke them
    (the halo-plan backends) disable the check.
    """
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def named_axes_in_scope() -> tuple:
    """Mesh axis names bound by enclosing shard_maps at trace time.

    Used by the ``"signal"`` halo backend: the Pallas *interpret-mode*
    remote-DMA emulation only supports a single named axis in scope
    (``dma_start_p`` discharge), so multi-axis callers run the ppermute
    oracle on CPU.
    """
    return tuple(n for n in _core.get_axis_env().axis_sizes
                 if n is not None)
