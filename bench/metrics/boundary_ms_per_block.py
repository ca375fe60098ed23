"""Device-clock gap between consecutive block programs (ms per block).

From the end of one block program's last device operation to the start
of the next block program, averaged over the window's boundaries and
taken on the device where it is longest: the rebin and prune programs,
the host's histogram read and everything else between two blocks.
"""


def read(ctx):
    r = ctx.reduced
    if r is None:
        return None
    gaps = [g for g in (r.block_gaps_ns(d) for d in r.devices) if g]
    if not gaps:
        return None
    return max(sum(g) / len(g) for g in gaps) / 1e6
