"""Device time of the rebin program per block boundary (ms).

Operations under the ``obs.rebin`` scope (binning and migration) and
under ``obs.rebin_force`` inside it (the velocity-Verlet force carry),
as a union of intervals per device, over the block boundaries of the
traced window, on the device where it is largest.
"""
PHASES = ("rebin", "rebin_force")


def read(ctx):
    r = ctx.reduced
    if r is None:
        return None
    per = [r.scope_ns(d, PHASES) / len(g)
           for d, g in ((d, r.block_gaps_ns(d)) for d in r.devices) if g]
    t = max(per, default=0.0)
    return t / 1e6 if t > 0 else None
