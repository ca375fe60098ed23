"""Faults planted under the timed path, to show that ``correct`` sees them.

Each is a context manager that patches the program while it is open; an
engine built inside it compiles the broken path.  Used by the CPU tests
(``tests/bench/test_bench_run.py``) and by ``calibrate.py --fault`` for
readings on the chip.

* ``state_unchanged``: ``simulate`` hands back the state it was given;
* ``half_of_pairs``: the NB kernel runs half of each tier's pair rows;
* ``no_halo_exchange``: the forward halo delivers nothing (padding where
  the neighbours' cells, or the periodic images on one chip, would be);
* ``answer_altered``: ``simulate`` moves one atom by 0.01 sigma in x.
"""
from __future__ import annotations

import contextlib
from unittest import mock

import jax.numpy as jnp
import numpy as np


def _first_live(cell_i):
    ids = np.asarray(cell_i)[..., 0]
    return tuple(int(v) for v in np.argwhere(ids >= 0)[0])


def _wrap_simulate(after):
    from repro.core.md import MDEngine
    orig = MDEngine.simulate

    def simulate(self, n_steps, state=None, **kw):
        out, m, d = orig(self, n_steps, state=state, **kw)
        return after(state, out), m, d
    return mock.patch.object(MDEngine, "simulate", simulate)


def state_unchanged():
    return _wrap_simulate(lambda state, out: out if state is None else state)


def answer_altered():
    def alter(state, out):
        cf, ci = out
        return cf.at[_first_live(ci) + (0,)].add(1e-2), ci
    return _wrap_simulate(alter)


def half_of_pairs():
    from repro.core.md import pair_schedule
    orig = pair_schedule._FORCE_BACKENDS["pallas"]

    def half(ext_f, ext_i, layout, ff, *, sched, sel, tiers, **kw):
        tiers = tuple((max(1, n // 2), k) for n, k in tiers)
        return orig(ext_f, ext_i, layout, ff, sched=sched, sel=sel,
                    tiers=tiers, **kw)
    return mock.patch.dict(pair_schedule._FORCE_BACKENDS, {"pallas": half})


def no_halo_exchange():
    from repro.core.halo_plan import HaloPlan
    orig = HaloPlan.fwd_local

    def fwd_local(self, x, *a, **kw):
        ext = orig(self, x, *a, **kw)
        n = x.shape[:3]
        keep = jnp.zeros(ext.shape[:3], bool).at[:n[0], :n[1], :n[2]].set(
            True)[..., None, None]
        fill = -1 if jnp.issubdtype(ext.dtype, jnp.integer) else 0
        return jnp.where(keep, ext, jnp.asarray(fill, ext.dtype))
    return mock.patch.object(HaloPlan, "fwd_local", fwd_local)


FAULTS = {f.__name__: f for f in (state_unchanged, half_of_pairs,
                                  no_halo_exchange, answer_altered)}


@contextlib.contextmanager
def planted(name: str):
    """The program with fault ``name`` planted, while the block is open."""
    with FAULTS[name]():
        yield
