"""Reduce a JAX profiler trace to per-device intervals and phase times.

Reads the ``*.trace.json.gz`` that ``jax.profiler`` writes beside its
``*.xplane.pb`` (Chrome-trace JSON, read with ``gzip`` and ``json``).
Each device is a process ``/device:TPU:<n>`` whose ``XLA Ops`` thread
holds one event per device operation (a ``while`` op spans its body's
ops), ``Async XLA Ops`` the asynchronous copies and collectives in
flight, and ``XLA Modules`` one event per program execution.  The host
process ``/host:CPU`` holds the harness's ``bench.*`` annotations and the
program's own ``obs.*`` host spans (``repro.obs.span``), on the same
clock.

An operation is attributed to a step-pipeline phase by the innermost
``obs.<phase>`` named scope in its ``tf_op`` scope path; an operation
with no such scope is ``other``.
"""
from __future__ import annotations

import gzip
import heapq
import json
import re
from pathlib import Path

HALO = ("pack_send", "fwd_release", "fwd_acquire",
        "rev_release", "rev_return", "rev_acquire")
FORCE = ("force",)
OTHER = "other"
BLOCK_MODULE = re.compile(r"block")
_SCOPE = re.compile(r"obs\.([A-Za-z_]+)")
_DEVICE = re.compile(r"^/device:(TPU|GPU):(\d+)$")


# --------------------------------------------------------------------------
# interval arithmetic (nanoseconds; half-open [start, end))
# --------------------------------------------------------------------------

def union(intervals):
    """Merged, sorted, non-overlapping cover of ``intervals``."""
    out = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def length(merged) -> float:
    return float(sum(e - s for s, e in merged))


def subtract(a, b) -> list:
    """The parts of merged ``a`` not covered by merged ``b``."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def gaps(merged, lo, hi):
    """Uncovered stretches of [lo, hi) between merged intervals."""
    return subtract([(lo, hi)], merged)


def clip(items, lo, hi):
    """``(start, end, *rest)`` items cut to [lo, hi); empty ones dropped."""
    return [(max(s, lo), min(e, hi), *rest) for s, e, *rest in items
            if e > lo and s < hi]


def leaves(ops):
    """The operations that contain no other operation of their line."""
    ops = sorted(ops, key=lambda o: (o[0], -o[1]))
    return [op for op, nxt in zip(ops, ops[1:] + [None])
            if nxt is None or not (nxt[0] < op[1] and nxt[1] <= op[1])]


def idle_by_span(idle, spans) -> dict:
    """``{span name: ns}`` of the merged, sorted ``idle`` intervals under
    the innermost of ``spans`` (``(start, end, name)``): each interval is
    cut at the spans' edges and each piece goes to the span that covers
    it and started last (of two that started together, the one that ends
    first); the pieces under no span go to ``None``.  One sorted sweep
    over the intervals and the spans together."""
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


def phase_of(text: str) -> str:
    """Innermost ``obs.<phase>`` scope named in ``text``, else ``other``."""
    found = _SCOPE.findall(text or "")
    return found[-1] if found else OTHER


# --------------------------------------------------------------------------
# the reduced trace
# --------------------------------------------------------------------------

class Reduced:
    """Device operations, program executions and host annotations of the
    traced window, on one clock.

    ``ops[d]``: the leaf operations of device ``d`` as ``(start_ns,
    end_ns, name, phase)``, where ``name`` is the op's scope path;
    ``async_ops[d]``: its asynchronous operations, alike;
    ``modules[d]``: ``(start_ns, end_ns, program_name)``;
    ``host``: the harness's ``bench.*`` annotations as ``(start_ns,
    end_ns, name)``; ``spans``: the program's ``obs.*`` host spans, alike.

    The traced window ``[t0, t1)`` is the host's ``bench.window``, cut to
    the block programs that every device's trace holds: from the latest
    first block-program start to the earliest last block-program end.
    The profiler keeps a bounded number of trace buffers per chip, and a
    long window of short steps overflows them, so a device's trace can
    stop before the window does; the cut keeps every reading to the part
    that all devices hold, in whole block periods.  ``blocks`` is the
    number of block programs in it (0 where the trace has none).
    Device items are clipped to ``[t0, t1)``.
    """

    def __init__(self, ops, async_ops, modules, host, t0, t1, spans=()):
        runs = [sorted((s, e) for s, e, n in clip(v, t0, t1)
                       if BLOCK_MODULE.search(n))
                for v in modules.values()]
        runs = [r for r in runs if r]
        if runs and max(r[0][0] for r in runs) < min(r[-1][1] for r in runs):
            t0 = max(r[0][0] for r in runs)
            t1 = min(r[-1][1] for r in runs)
        self.t0, self.t1 = t0, t1
        self.blocks = min((sum(1 for s, e in r if t0 <= (s + e) / 2 < t1)
                           for r in runs), default=0)
        self.ops = {d: clip(leaves(v), t0, t1) for d, v in ops.items()}
        self.async_ops = {d: clip(v, t0, t1) for d, v in async_ops.items()}
        self.modules = {d: clip(v, t0, t1) for d, v in modules.items()}
        self.host = host
        self.spans = list(spans)
        self.devices = sorted(self.ops)

    @property
    def window_ns(self) -> float:
        return float(self.t1 - self.t0)

    @property
    def window_s(self) -> float:
        return self.window_ns / 1e9

    def _cover(self, items, phases=None, exclude=False):
        return union((s, e) for s, e, _n, p in items
                     if phases is None or ((p in phases) != exclude))

    def busy_ns(self, d) -> float:
        """Time in which an operation ran on device ``d``."""
        return length(self._cover(self.ops[d]))

    def busy_s(self) -> float:
        """Busy seconds averaged over the devices."""
        if not self.devices:
            return 0.0
        return sum(self.busy_ns(d) for d in self.devices) / len(
            self.devices) / 1e9

    def _phase_cover(self, d, phases):
        return union(self._cover(self.ops[d], phases)
                     + self._cover(self.async_ops.get(d, []), phases))

    def scope_ns(self, d, phases) -> float:
        """Time in which an operation of ``phases`` ran or was in flight."""
        return length(self._phase_cover(d, phases))

    def exposed_ns(self, d, phases) -> float:
        """The part of :meth:`scope_ns` in which no other op runs."""
        return length(subtract(self._phase_cover(d, phases),
                               self._cover(self.ops[d], phases,
                                           exclude=True)))

    def block_gaps_ns(self, d) -> list:
        """Gaps between consecutive block-program executions on ``d``."""
        blocks = sorted((s, e) for s, e, n in self.modules.get(d, [])
                        if BLOCK_MODULE.search(n))
        return [b[0] - a[1] for a, b in zip(blocks, blocks[1:])
                if b[0] > a[1]]

    def breakdown(self, top: int = 10) -> dict:
        """The ops that took most device time (by scope path, summed over
        devices, seconds), and the longest idle gaps of device 0, each
        labelled by :meth:`gap_label`."""
        total = {}
        for d in self.devices:
            for s, e, n, _p in self.ops[d]:
                total[n] = total.get(n, 0.0) + (e - s) / 1e9
        ops = sorted(total.items(), key=lambda kv: -kv[1])[:top]
        idle = []
        if self.devices:
            busy = self._cover(self.ops[self.devices[0]])
            longest = sorted(gaps(busy, self.t0, self.t1),
                             key=lambda g: g[0] - g[1])[:top]
            idle = [[self.gap_label(s, e), (e - s) / 1e9]
                    for s, e in longest]
        return {"device_ops": [[n, v] for n, v in ops], "idle_gaps": idle}

    def gap_label(self, s, e) -> str:
        """The ``obs.*`` host span that is innermost over most of [s, e)
        (:func:`idle_by_span`); where none is, the ``bench.*`` annotation
        of :meth:`host_label`."""
        owners = idle_by_span([(s, e)], self.spans)
        owners.pop(None, None)
        if owners:
            return max(owners, key=owners.get)
        return self.host_label(s, e)

    def host_label(self, s, e) -> str:
        """The innermost ``bench.*`` annotation that overlaps [s, e) most."""
        best, best_key = "host", (0, 0)
        for hs, he, name in self.host:
            ov = min(e, he) - max(s, hs)
            if ov <= 0:
                continue
            key = (ov, -(he - hs))        # most overlap, then innermost
            if key > best_key:
                best, best_key = name, key
        return best


# --------------------------------------------------------------------------
# reading a trace
# --------------------------------------------------------------------------

def from_events(events) -> Reduced:
    """Build a :class:`Reduced` from Chrome-trace events: ``M`` events
    name processes and threads, ``X`` events carry ``ts`` and ``dur`` in
    microseconds and ``args``.  The one parse of a run's trace: the host
    spans that the readers attribute idle time to come from here too."""
    proc, thread = {}, {}
    for e in events:
        if e.get("ph") == "M" and e.get("name") == "process_name":
            proc[e["pid"]] = e["args"]["name"]
        elif e.get("ph") == "M" and e.get("name") == "thread_name":
            thread[(e["pid"], e["tid"])] = e["args"]["name"]
    ops, async_ops, modules, host, spans = {}, {}, {}, [], []
    for e in events:
        if e.get("ph") != "X":
            continue
        pname = proc.get(e["pid"], "")
        s = int(round(float(e["ts"]) * 1e3))
        t = s + int(round(float(e.get("dur", 0.0)) * 1e3))
        dev = _DEVICE.match(pname)
        if dev:
            d = int(dev.group(2))
            line = thread.get((e["pid"], e.get("tid")), "")
            if line in ("XLA Ops", "Async XLA Ops"):
                tf_op = e.get("args", {}).get("tf_op", "")
                op = (s, t, tf_op.rstrip(":") or e["name"], phase_of(tf_op))
                dest = ops if line == "XLA Ops" else async_ops
                dest.setdefault(d, []).append(op)
            elif line == "XLA Modules":
                modules.setdefault(d, []).append((s, t, e["name"]))
        elif pname.startswith("/host:") and e["name"].startswith("bench."):
            host.append((s, t, e["name"]))
        elif pname.startswith("/host:") and e["name"].startswith("obs."):
            spans.append((s, t, e["name"]))
    windows = [(s, t) for s, t, n in host if n == "bench.window"]
    if not windows:
        raise ValueError("trace has no bench.window annotation")
    return Reduced(ops, async_ops, modules, host, *windows[0], spans=spans)


def load(trace_dir) -> Reduced:
    """The reduced trace of the newest ``*.trace.json.gz`` under
    ``trace_dir``."""
    files = sorted(Path(trace_dir).rglob("*.trace.json.gz"),
                   key=lambda p: p.stat().st_mtime)
    if not files:
        raise FileNotFoundError(f"no *.trace.json.gz under {trace_dir}")
    with gzip.open(files[-1], "rt") as fh:
        return from_events(json.load(fh)["traceEvents"])
