"""Device time of the halo exchange phases per MD step (ms).

Operations under the ``obs.pack_send``, ``obs.fwd_*`` and ``obs.rev_*``
scopes, as a union of intervals per device, on the device where it is
largest, over the steps of the window.
"""
import trace_reduce


def read(ctx):
    r = ctx.reduced
    if r is None or not r.devices:
        return None
    t = max(r.scope_ns(d, trace_reduce.HALO) for d in r.devices)
    return t / 1e6 / ctx.steps if t > 0 else None
