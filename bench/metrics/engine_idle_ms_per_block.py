"""Device idle time per block boundary whose host side lies inside the
engine's ``obs.simulate`` span (ms), on the device where it is largest:
the time the chip waits while ``MDEngine.simulate`` runs host code."""
import host_idle


def read(ctx):
    spans = [s for s in host_idle.host_spans(ctx) if s[2] == "obs.simulate"]
    return host_idle.idle_ms_per_block(ctx.reduced, spans, "obs.simulate")
