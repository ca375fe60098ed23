"""The trace reduction: interval arithmetic, scope attribution, idle
share, exposed-halo overlap and block-boundary gaps, on small traces
whose answers are known."""
import json

import pytest

from benchtools import FIXTURES, trace_reduce as tr


def trace(device_ops, modules=(), host=(), async_ops=(), n_devices=1):
    """Chrome-trace events: ``device_ops`` as (device, name, start_us,
    dur_us, tf_op), ``modules`` as (device, name, start, dur), ``host``
    as (name, start, dur)."""
    ev = [{"ph": "M", "pid": 900, "name": "process_name",
           "args": {"name": "/host:CPU"}}]
    for d in range(n_devices):
        ev.append({"ph": "M", "pid": d, "name": "process_name",
                   "args": {"name": f"/device:TPU:{d}"}})
        for tid, line in ((2, "XLA Modules"), (3, "XLA Ops"),
                          (4, "Async XLA Ops")):
            ev.append({"ph": "M", "pid": d, "tid": tid, "name": "thread_name",
                       "args": {"name": line}})
    for tid, items in ((3, device_ops), (4, async_ops)):
        for d, name, s, dur, tf in items:
            ev.append({"ph": "X", "pid": d, "tid": tid, "name": name,
                       "ts": s, "dur": dur, "args": {"tf_op": tf}})
    for d, name, s, dur in modules:
        ev.append({"ph": "X", "pid": d, "tid": 2, "name": name, "ts": s,
                   "dur": dur})
    for name, s, dur in host:
        ev.append({"ph": "X", "pid": 900, "tid": 1, "name": name, "ts": s,
                   "dur": dur})
    return ev


def test_union_subtract_gaps():
    assert tr.union([(5, 7), (0, 2), (1, 3), (7, 8), (9, 9)]) == \
        [(0, 3), (5, 8)]
    assert tr.subtract([(0, 10)], [(2, 3), (5, 12)]) == [(0, 2), (3, 5)]
    assert tr.subtract([(0, 4), (6, 9)], [(3, 7)]) == [(0, 3), (7, 9)]
    assert tr.gaps([(2, 3), (5, 6)], 0, 8) == [(0, 2), (3, 5), (6, 8)]
    assert tr.length([(0, 3), (5, 8)]) == 6


@pytest.mark.parametrize("text,phase", [
    ("tf_op=jit(block)/while/body/obs.force/mul", "force"),
    ("long_name=%fusion = f32[] ... op_name=\"jit(x)/obs.rev_release/"
     "obs.rev_return/ppermute\"", "rev_return"),
    ("tf_op=jit(do_rebin)/sort", "other"),
    ("", "other"),
])
def test_phase_is_the_innermost_obs_scope(text, phase):
    assert tr.phase_of(text) == phase


def synthetic():
    """Window [0.1, 1.1) us on two devices (times below in ns).

    Device 0: a ``while`` op [100, 500) around a halo op [100, 200), a
    force op [150, 400) overlapping it and a halo op [400, 500) alone;
    rebin [520, 680); a second block's force op [700, 1000); an async
    halo collective in flight [1000, 1050) with nothing beside it.
    Block programs [100, 500) and [700, 1050).  Device 1: one force op
    [100, 300).
    """
    us = 1e-3
    ops = [
        (0, "while.1", 100 * us, 400 * us, ""),
        (0, "fusion.1", 100 * us, 100 * us, "jit(b)/obs.pack_send/x:"),
        (0, "pallas_nb", 150 * us, 250 * us, "jit(b)/obs.force/y:"),
        (0, "cp.2", 400 * us, 100 * us, "jit(b)/obs.rev_return/z:"),
        (0, "sort.3", 520 * us, 160 * us, "jit(do_rebin)/sort:"),
        (0, "fusion.4", 700 * us, 300 * us, "jit(b)/obs.force/w:"),
        (0, "late", 1100 * us, 50 * us, "outside the window"),
        (1, "pallas_nb", 100 * us, 200 * us, "obs.force/pallas_call:"),
    ]
    return tr.from_events(trace(
        ops, n_devices=2,
        async_ops=[(0, "cp-start", 1000 * us, 50 * us,
                    "jit(b)/obs.pack_send/collective-permute:")],
        modules=[(0, "jit_block_sched(1)", 100 * us, 400 * us),
                 (0, "jit_do_rebin(2)", 520 * us, 160 * us),
                 (0, "jit_block_sched(1)", 700 * us, 350 * us)],
        host=[("bench.window", 100 * us, 1000 * us),
              ("bench.simulate", 100 * us, 480 * us),
              ("bench.simulate", 580 * us, 520 * us), ("other", 0, 5)]))


def test_window_devices_and_busy():
    r = synthetic()
    # the host window [100, 1100) cut to the block programs [100, 1050)
    assert (r.t0, r.t1) == (100, 1050) and r.window_s == 950e-9
    assert r.blocks == 2
    assert r.devices == [0, 1]
    assert r.busy_ns(0) == 400 + 160 + 300       # the while op is no leaf
    assert r.busy_ns(1) == 200
    assert r.busy_s() == pytest.approx((860 + 200) / 2 / 1e9)


def test_scope_time_and_exposed_halo():
    r = synthetic()
    assert r.scope_ns(0, tr.HALO) == 200 + 50     # async op in flight
    assert r.scope_ns(0, tr.FORCE) == 250 + 300
    # [100,150), [400,500) and the async [1000,1050) have nothing beside
    assert r.exposed_ns(0, tr.HALO) == 50 + 100 + 50
    assert r.scope_ns(1, tr.HALO) == 0


def test_block_boundary_gaps():
    r = synthetic()
    assert r.block_gaps_ns(0) == [200]
    assert r.block_gaps_ns(1) == []


def test_breakdown_ops_and_labelled_idle_gaps():
    b = synthetic().breakdown()
    names = [n for n, _ in b["device_ops"]]
    assert names[0] == "jit(b)/obs.force/w"       # 300 ns
    assert "late" not in names and "while.1" not in names
    gaps = dict((n, v) for n, v in b["idle_gaps"])
    # device 0 idle [500,520) and [680,700) under the simulate calls,
    # and [1000,1050) under the second call
    assert sorted(v for _n, v in b["idle_gaps"]) == \
        pytest.approx([20e-9, 20e-9, 50e-9])
    assert set(gaps) == {"bench.simulate"}


def test_window_is_cut_to_what_every_device_trace_holds():
    """Device 1's trace stops after its second block program, as an
    overflowed trace buffer leaves it: every reading keeps to the block
    periods both devices hold, and counts the blocks in them."""
    us = 1e-3
    ops, modules = [], []
    for d in (0, 1):
        for k in range(4 if d == 0 else 2):
            b = 100 + 300 * k
            modules.append((d, "jit_block_sched(1)", b * us, 200 * us))
            ops.append((d, "pallas_nb", b * us, 200 * us,
                        "jit(b)/obs.force/y:"))
            ops.append((d, "rebin", (b + 220) * us, 60 * us,
                        "jit(do_rebin)/mul:"))
    r = tr.from_events(trace(ops, modules=modules, n_devices=2,
                             host=[("bench.window", 50 * us, 1300 * us)]))
    assert (r.t0, r.t1) == (100, 600) and r.blocks == 2
    # two force ops and one boundary's rebin in [100, 600) on each device
    assert r.busy_ns(0) == r.busy_ns(1) == 200 + 60 + 200
    assert r.scope_ns(1, tr.FORCE) == 400
    assert r.block_gaps_ns(0) == r.block_gaps_ns(1) == [100]


def test_no_window_annotation_is_an_error():
    with pytest.raises(ValueError):
        tr.from_events(trace([], host=[("x", 0, 1)]))


RECORDED = FIXTURES / "trace_90k_boundary.json"


def raster(intervals, lo, hi):
    """Brute-force cover at 1 us resolution (independent of union())."""
    import numpy as np
    m = np.zeros(int((hi - lo) // 1000) + 2, bool)
    for s, e in intervals:
        m[int((s - lo) // 1000):int((e - lo) // 1000)] = True
    return int(m.sum()) * 1000


def test_recorded_trace_reduces_as_brute_force_says():
    """A cut of a real one-chip grappa-90k trace (see its ``source``):
    the end of one block program, the rebin and prune programs' edges and
    the start of the next block."""
    events = json.loads(RECORDED.read_text())["traceEvents"]
    r = tr.from_events(events)
    assert r.devices == [0]
    raw = [e for e in events if e.get("ph") == "X" and e["pid"] == 3
           and e["tid"] == 3]
    n_while = sum(1 for e in raw if e["name"].startswith("while"))
    assert n_while > 0 and len(r.ops[0]) <= len(raw) - n_while
    # leaves of one line do not overlap: their durations add up to busy
    total = sum(e - s for s, e, *_ in r.ops[0])
    assert abs(total - r.busy_ns(0)) <= 1e-6 * r.window_ns
    phases = {p for *_x, p in r.ops[0]}
    assert {"force", "rev_return", "integrate_begin", "other"} <= phases
    busy = raster([(s, e) for s, e, *_ in r.ops[0]], r.t0, r.t1)
    assert abs(r.busy_ns(0) - busy) <= 2000 * len(r.ops[0])
    force = raster([(s, e) for s, e, _n, p in r.ops[0] if p == "force"],
                   r.t0, r.t1)
    assert abs(r.scope_ns(0, tr.FORCE) - force) <= 2000 * len(r.ops[0])
    assert 0 < r.exposed_ns(0, tr.HALO) <= r.scope_ns(0, tr.HALO)
    mods = sorted((e["ts"], e["dur"], e["name"]) for e in events
                  if e.get("ph") == "X" and e["tid"] == 2)
    blocks = [m for m in mods if "block" in m[2]]
    gap = (blocks[1][0] - (blocks[0][0] + blocks[0][1])) * 1e3
    assert r.block_gaps_ns(0) == [pytest.approx(gap, abs=2)]
    # the boundary holds the rebin and prune programs
    inner = [m for m in mods if "block" not in m[2]]
    assert all(blocks[0][0] + blocks[0][1] <= m[0] < blocks[1][0]
               for m in inner)
    idle = r.breakdown()["idle_gaps"]
    assert idle and all(v > 0 for _n, v in idle)
