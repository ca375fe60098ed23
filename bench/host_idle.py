"""Device idle time attributed to what the host was doing in it.

The program's own host spans (``repro.obs.span``) are JAX profiler
annotations named ``obs.<span>`` on the trace's host process, on the
device ops' clock.  :func:`trace_reduce.from_events` keeps them with the
rest of the run's trace, in the one parse of it, as the reduced trace's
``spans``; :func:`trace_reduce.idle_by_span` cuts each idle interval of
a device at the spans' edges and gives each piece to the innermost span
that covers it.
"""
from __future__ import annotations

import trace_reduce
from trace_reduce import idle_by_span


def idle_intervals(r, d) -> list:
    """Device ``d``'s idle intervals in the traced window of ``r``."""
    busy = trace_reduce.union((s, e) for s, e, *_ in r.ops[d])
    return trace_reduce.gaps(busy, r.t0, r.t1)


def host_spans(ctx) -> list:
    """The ``obs.*`` host spans of the run's reduced trace (empty
    without one)."""
    return list(ctx.reduced.spans) if ctx.reduced is not None else []


def idle_ms_per_block(r, spans, name: str):
    """Idle ms per block boundary under ``name`` (innermost among
    ``spans``), on the device where it is largest; ``None`` where the
    trace has no such span or no boundary."""
    if r is None or not any(n == name for _s, _e, n in spans):
        return None
    per = []
    for d in r.devices:
        n = len(r.block_gaps_ns(d))
        if n:
            got = idle_by_span(idle_intervals(r, d), spans).get(name, 0)
            per.append(got / n)
    return max(per) / 1e6 if per else None
