"""Chrome/Perfetto ``trace.json`` export of a metrics JSONL.

Renders a metrics JSONL (written by :class:`~repro.obs.registry.
MetricsRegistry`) as a Chrome trace-event file with one process lane,
**pid 0 — measured**:

* every host-side ``span`` record becomes a duration event (one thread
  row per span name, wall-clock placement);
* every ``snapshot`` record's counters/gauges become counter tracks;
* the ``obs/*`` per-step ledger counters of each ``step_counters``
  record become counter tracks, their steps spread evenly over the time
  since the previous such record (the steps carry no timestamps of
  their own).

Open the output at https://ui.perfetto.dev (or ``chrome://tracing``).
Device time is not here: the JAX profiler's own trace holds it, with
the same spans as ``obs.<name>`` host annotations beside the device ops.
"""
from __future__ import annotations

import json
from typing import Dict, List, Optional

from repro.obs.registry import iter_kind, load_jsonl  # noqa: F401

_US = 1e6   # trace-event timestamps are microseconds


def _meta(pid: int, name: str, tid: Optional[int] = None,
          tname: Optional[str] = None) -> List[dict]:
    evs = [{"ph": "M", "pid": pid, "tid": 0, "name": "process_name",
            "args": {"name": name}}]
    if tid is not None:
        evs.append({"ph": "M", "pid": pid, "tid": tid,
                    "name": "thread_name", "args": {"name": tname}})
    return evs


def _measured_events(records: List[dict]) -> List[dict]:
    spans = iter_kind(records, "span")
    snaps = iter_kind(records, "snapshot")
    steps = iter_kind(records, "step_counters")
    events: List[dict] = _meta(0, "measured (host spans)")
    if not spans and not snaps and not steps:
        return events
    starts = [r["t"] - r.get("dur", 0.0) for r in spans] + \
             [r["t"] for r in snaps + steps]
    t_base = min(starts)
    tids = {name: i + 1
            for i, name in enumerate(sorted({r["name"] for r in spans}))}
    for name, tid in tids.items():
        events += _meta(0, "", tid=tid, tname=f"span:{name}")[1:]
    for rec in spans:
        dur = float(rec.get("dur", 0.0))
        args = {k: v for k, v in rec.items()
                if k not in ("kind", "t", "t0", "name", "dur")}
        events.append({
            "ph": "X", "pid": 0, "tid": tids[rec["name"]],
            "name": rec["name"],
            "ts": (rec["t"] - dur - t_base) * _US,
            "dur": max(dur * _US, 0.01),
            "args": args,
        })
    for rec in snaps:
        ts = (rec["t"] - t_base) * _US
        for mname, m in sorted(rec.get("metrics", {}).items()):
            val = m.get("value")
            if isinstance(val, dict):       # histogram state -> mean track
                val = val.get("mean")
            if isinstance(val, (int, float)):
                events.append({"ph": "C", "pid": 0, "tid": 0, "name": mname,
                               "ts": ts, "args": {mname: val}})
    t_prev = t_base
    for rec in steps:
        counters = rec.get("data", {})
        n = max((len(v) for v in counters.values()), default=0)
        for mname, vals in sorted(counters.items()):
            for i, v in enumerate(vals):
                ts = t_prev + (rec["t"] - t_prev) * i / n
                events.append({"ph": "C", "pid": 0, "tid": 0, "name": mname,
                               "ts": (ts - t_base) * _US,
                               "args": {mname: v}})
        t_prev = rec["t"]
    return events


def to_trace(records: List[dict]) -> dict:
    """Build the Chrome trace-event document from registry records."""
    events = _measured_events(records)
    other: Dict[str, object] = {"generator": "python -m repro.obs",
                                "n_records": len(records)}
    halos = iter_kind(records, "halo_stats")
    if halos:
        other["backend"] = halos[-1].get("backend")
    return {"traceEvents": events, "displayTimeUnit": "ms",
            "otherData": other}


def export_trace(jsonl_path, out_path) -> dict:
    """JSONL in, ``trace.json`` out; returns the trace document."""
    trace = to_trace(load_jsonl(jsonl_path))
    with open(out_path, "w") as fh:
        json.dump(trace, fh, indent=1, sort_keys=True)
    return trace
