"""Pallas kernels of the halo exchange and the non-bonded force pass.

Every kernel wrapper takes ``interpret=None`` and resolves it through
:func:`interpret_mode`, the one place interpret mode is decided: the
Pallas interpreter runs only when JAX's default backend is the CPU.  On
a TPU the kernels are always compiled, and a kernel that fails to
compile raises instead of falling back to a jnp oracle.
"""
from __future__ import annotations

import jax


def interpret_mode(interpret=None):
    """Resolve a kernel's ``interpret`` argument.

    ``None`` (every production call site) derives it from the platform:
    true only on the CPU backend.  An explicit value is passed through:
    ``False`` lets a test compile a kernel for a described TPU from a CPU
    host, and a ``pltpu.InterpretParams`` runs the kernel in Pallas' TPU
    interpreter, which emulates DMAs, semaphores and remote copies across
    every mesh axis the way the chip executes them.
    """
    if interpret is None:
        return jax.default_backend() == "cpu"
    return interpret
