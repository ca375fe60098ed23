"""GROMACS-style MD substrate (the paper's application domain)."""
from repro.core.md.cells import CellLayout, choose_layout
from repro.core.md.engine import MDEngine
from repro.core.md.forces import (
    compute_forces,
    direct_forces_reference,
    direct_forces_rows,
)
from repro.core.md.pair_schedule import (
    PairSchedule,
    force_backends,
    get_force_backend,
    register_force_backend,
)
from repro.core.md.system import (
    DEFAULT_FF,
    GRAPPA_SIZES,
    ForceField,
    MDParams,
    MDSystem,
    make_grappa_like,
)

__all__ = [
    "CellLayout", "choose_layout", "MDEngine", "compute_forces",
    "direct_forces_reference", "direct_forces_rows", "ForceField", "MDParams", "MDSystem",
    "make_grappa_like", "GRAPPA_SIZES", "DEFAULT_FF", "PairSchedule",
    "force_backends", "get_force_backend", "register_force_backend",
]
