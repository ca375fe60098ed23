"""Exposed halo time per MD step (ms): the part of the halo phases'
device intervals in which no other operation runs on that device, on the
device where it is largest."""
import trace_reduce


def read(ctx):
    r = ctx.reduced
    if r is None or not r.devices:
        return None
    t = max(r.exposed_ns(d, trace_reduce.HALO) for d in r.devices)
    return t / 1e6 / ctx.steps if t > 0 else None
