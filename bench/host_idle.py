"""Device idle time attributed to what the host was doing in it.

The program's own host spans (``repro.obs.span``) are JAX profiler
annotations named ``obs.<span>`` on the trace's host process, on the
device ops' clock.  :func:`idle_by_span` cuts each idle interval of a
device at the spans' edges and gives each piece to the innermost span
that covers it: the one that started last (of two that started
together, the one that ends first).  It is one sorted sweep over the
idle intervals and the spans together.

:func:`host_spans` reads those spans from the trace that the harness
wrote for this run, which it finds in this process's temporary directory
(``harness.run_cell`` keeps it there, as ``bench-trace-*``, until the
readers are done).
"""
from __future__ import annotations

import functools
import gzip
import heapq
import json
import tempfile
from pathlib import Path

import trace_reduce

PREFIX = "obs."


def idle_by_span(idle, spans) -> dict:
    """``{span name: ns}`` of the merged, sorted ``idle`` intervals under
    the innermost of ``spans`` (``(start, end, name)``); the pieces under
    no span go to ``None``."""
    spans = sorted(spans)
    out, active, j = {}, [], 0
    for lo, hi in idle:
        cur = lo
        while cur < hi:
            while j < len(spans) and spans[j][0] <= cur:
                s, e, name = spans[j]
                heapq.heappush(active, (-s, e, name))
                j += 1
            while active and active[0][1] <= cur:
                heapq.heappop(active)
            nxt = hi
            if j < len(spans):
                nxt = min(nxt, spans[j][0])
            owner = None
            if active:
                owner = active[0][2]
                nxt = min(nxt, active[0][1])
            out[owner] = out.get(owner, 0) + (nxt - cur)
            cur = nxt
    return out


def idle_intervals(r, d) -> list:
    """Device ``d``'s idle intervals in the traced window of ``r``."""
    busy = trace_reduce.union((s, e) for s, e, *_ in r.ops[d])
    return trace_reduce.gaps(busy, r.t0, r.t1)


def spans_from_events(events) -> list:
    """``(start_ns, end_ns, name)`` of the host's ``obs.*`` annotations
    among Chrome-trace events."""
    hosts = {e["pid"] for e in events if e.get("ph") == "M"
             and e.get("name") == "process_name"
             and e["args"]["name"].startswith("/host:")}
    out = []
    for e in events:
        if (e.get("ph") == "X" and e.get("pid") in hosts
                and e.get("name", "").startswith(PREFIX)):
            s = int(round(float(e["ts"]) * 1e3))
            out.append((s, s + int(round(float(e.get("dur", 0.0)) * 1e3)),
                        e["name"]))
    return out


def trace_file():
    """The newest ``*.trace.json.gz`` under this process's
    ``bench-trace-*`` directories, or ``None``."""
    files = sorted(Path(tempfile.gettempdir()).glob(
        "bench-trace-*/**/*.trace.json.gz"), key=lambda p: p.stat().st_mtime)
    return files[-1] if files else None


@functools.lru_cache(maxsize=1)
def _read(path: str, mtime: float) -> tuple:
    with gzip.open(path, "rt") as fh:
        return tuple(spans_from_events(json.load(fh)["traceEvents"]))


def host_spans() -> list:
    """The ``obs.*`` host spans of this run's trace (empty without one);
    read once for all the readers of a run."""
    path = trace_file()
    if path is None:
        return []
    return list(_read(str(path), path.stat().st_mtime))


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
