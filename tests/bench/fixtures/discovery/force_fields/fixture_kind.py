"""A force field added as a new file: it hands back what it was given."""


def build(config):
    return ("fixture kind", config["r_cut"])
