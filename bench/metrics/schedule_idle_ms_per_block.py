"""Device idle time per block boundary whose innermost host span is the
engine's ``obs.schedule_read`` (ms), on the device where it is largest:
the prune histogram reads and the tier bucketing that the next block
program waits for."""
import host_idle


def read(ctx):
    return host_idle.idle_ms_per_block(ctx.reduced, host_idle.host_spans(),
                                       "obs.schedule_read")
