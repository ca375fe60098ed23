"""Mesh construction for single-pod / multi-pod production runs.

``make_production_mesh`` is a FUNCTION (not a module constant) so importing
this module never touches jax device state; the dry-run launcher sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any jax
import and only then builds the mesh.
"""
from __future__ import annotations

from typing import Sequence

import math
from typing import Sequence

import numpy as np

import jax
from jax.sharding import AxisType, Mesh



def make_mesh(shape: Sequence[int], axis_names: Sequence[str]) -> Mesh:
    """Mesh over the first prod(shape) devices, Auto axis types.

    Unlike ``jax.make_mesh`` this tolerates a device count larger than the
    mesh (the dry-run forces 512 host devices but the single-pod mesh uses
    256; tests use subsets of 8).
    """
    n = math.prod(shape)
    devs = jax.devices()
    if len(devs) < n:
        raise ValueError(f"need {n} devices, have {len(devs)}")
    arr = np.asarray(devs[:n]).reshape(tuple(shape))
    return Mesh(arr, tuple(axis_names),
                axis_types=(AxisType.Auto,) * len(axis_names))


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    """The graded production mesh: 16x16 per pod, 2 pods multi-pod.

    Axes: ``data`` carries DP/FSDP/CP, ``model`` carries TP/EP, ``pod`` is
    the DCN dimension (slow links; collectives over it are coarsened and
    optionally compressed — the TPU analogue of the paper's
    NVLink-vs-InfiniBand transport adaptivity).
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_md_mesh(n_devices: int | None = None, max_dims: int = 3) -> Mesh:
    """Factor the device count into a (Z, Y, X)-style DD mesh for MD.

    Mirrors GROMACS' automatic 1D -> 2D -> 3D domain-decomposition switch as
    rank count grows (paper §6.3): factors are peeled greedily so e.g.
    8 -> (2,2,2), 16 -> (4,2,2), 256 -> (8,8,4), 512 -> (16,8,4).
    """
    if n_devices is None:
        n_devices = len(jax.devices())
    dims = [1] * max_dims
    remaining = n_devices
    i = 0
    while remaining > 1:
        # peel the smallest prime factor onto the next axis (round robin)
        for f in range(2, remaining + 1):
            if remaining % f == 0:
                dims[i % max_dims] *= f
                remaining //= f
                break
        i += 1
    dims.sort(reverse=True)
    # Always return all three axes (sizes may be 1): the MD cell grid is 3-D
    # regardless of DD dimensionality, and size-1 axes degrade gracefully to
    # periodic self-exchange inside the halo code.
    return make_mesh(tuple(dims), ("z", "y", "x"))
