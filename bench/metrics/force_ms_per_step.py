"""Device time of the non-bonded force phase per MD step (ms): operations
under the ``obs.force`` scope, on the device where it is largest."""
import trace_reduce


def read(ctx):
    r = ctx.reduced
    if r is None or not r.devices:
        return None
    t = max(r.scope_ns(d, trace_reduce.FORCE) for d in r.devices)
    return t / 1e6 / ctx.steps if t > 0 else None
