"""Lennard-Jones + reaction-field force field (``force_field: "lj_rf"``).

Reads the configuration's per-type-pair ``eps`` and ``sigma`` tables,
``r_cut`` and the reaction field's dielectric ``eps_rf`` (infinite for
a conducting boundary; with no charges the term is zero).
"""


def build(config: dict):
    from repro.core.md.system import ForceField
    return ForceField(eps=tuple(map(tuple, config["eps"])),
                      sigma=tuple(map(tuple, config["sigma"])),
                      r_cut=float(config["r_cut"]),
                      eps_rf=float(config["eps_rf"]))
