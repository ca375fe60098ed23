"""Share of the traced window in which no operation ran on a device, for
the idlest device of the cell (%)."""


def read(ctx):
    r = ctx.reduced
    if r is None or not r.devices:
        return None
    return 100.0 * max(1.0 - r.busy_ns(d) / r.window_ns for d in r.devices)
