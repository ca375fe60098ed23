"""Device time of the rebin program's force carry per block boundary
(ms): operations under the ``obs.rebin_force`` scope, the force pass
that the rebin recomputes for velocity Verlet, on the device where it is
largest."""
PHASES = ("rebin_force",)


def read(ctx):
    r = ctx.reduced
    if r is None:
        return None
    per = [r.scope_ns(d, PHASES) / len(g)
           for d, g in ((d, r.block_gaps_ns(d)) for d in r.devices) if g]
    t = max(per, default=0.0)
    return t / 1e6 if t > 0 else None
