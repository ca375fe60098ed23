"""The block-boundary metrics: device time under the rebin and prune
programs' ``obs.*`` scopes, and device idle time attributed to the
engine's ``obs.*`` host spans, on small traces whose answers are known;
and the recorded trace, on which every earlier reading stays as it was."""
import json
import tempfile
from types import SimpleNamespace

import numpy as np
import pytest

from benchtools import FIXTURES, spec, trace_reduce as tr

import host_idle

NEW = ("rebin_ms_per_block", "rebin_force_ms_per_block",
       "prune_ms_per_block", "engine_idle_ms_per_block",
       "schedule_idle_ms_per_block")


def events(device_ops, async_ops, modules, host):
    """Chrome-trace events of one device; times in ns (written in us)."""
    us = 1e-3
    ev = [{"ph": "M", "pid": 900, "name": "process_name",
           "args": {"name": "/host:CPU"}},
          {"ph": "M", "pid": 0, "name": "process_name",
           "args": {"name": "/device:TPU:0"}}]
    for tid, line in ((2, "XLA Modules"), (3, "XLA Ops"),
                      (4, "Async XLA Ops")):
        ev.append({"ph": "M", "pid": 0, "tid": tid, "name": "thread_name",
                   "args": {"name": line}})
    for tid, items in ((3, device_ops), (4, async_ops)):
        for s, e, tf in items:
            ev.append({"ph": "X", "pid": 0, "tid": tid, "name": "op",
                       "ts": s * us, "dur": (e - s) * us,
                       "args": {"tf_op": tf}})
    for s, e, name in modules:
        ev.append({"ph": "X", "pid": 0, "tid": 2, "name": name,
                   "ts": s * us, "dur": (e - s) * us})
    for s, e, name in host:
        ev.append({"ph": "X", "pid": 900, "tid": 1, "name": name,
                   "ts": s * us, "dur": (e - s) * us})
    return ev


# Three block programs, so two block boundaries, on one device (ns):
#   [100, 300) block; rebin [350, 550), prune [560, 640); [700, 900) block;
#   rebin [950, 1000); [1050, 1100) block.
# Busy: the XLA ops below; idle: [300,350) [500,560) [600,610) [640,700)
# [900,950) [1000,1050) = 280 ns.  The rebin's halo copy [480, 530) is
# in flight under obs.rebin_force but is no XLA op.
OPS = [
    (100, 300, "jit(block_sched)/while/body/obs.force/nb:"),
    (350, 400, "jit(rebin_program)/obs.rebin/sort:"),
    (400, 500, "jit(rebin_program)/obs.rebin/obs.rebin_force/mul:"),
    (560, 600, "jit(prune_program)/obs.prune/gather:"),
    (610, 640, "jit(prune_program)/obs.prune/scatter:"),
    (700, 900, "jit(block_sched)/while/body/obs.force/nb:"),
    (950, 1000, "jit(rebin_program)/obs.rebin/sort:"),
    (1050, 1100, "jit(block_sched)/while/body/obs.force/nb:"),
]
ASYNC = [(480, 530,
          "jit(rebin_program)/obs.rebin/obs.rebin_force/ppermute:")]
MODULES = [(100, 300, "jit_block_sched(1)"),
           (350, 550, "jit_rebin_program(2)"),
           (560, 640, "jit_prune_program(3)"),
           (700, 900, "jit_block_sched(1)"),
           (950, 1000, "jit_rebin_program(2)"),
           (1050, 1100, "jit_block_sched(1)")]
# Nested engine spans under the first simulate call; a span of another
# thread that partly overlaps the second call's start; idle [690, 700)
# under no span.
HOST = [
    (100, 1100, "bench.window"), (290, 690, "bench.simulate"),
    (290, 690, "obs.simulate"),
    (300, 320, "obs.rebin_dispatch"), (320, 330, "obs.prune_dispatch"),
    (330, 600, "obs.schedule_read"), (600, 620, "obs.diag_read"),
    (620, 640, "obs.block_dispatch"), (640, 690, "obs.metrics_read"),
    (890, 930, "obs.stray"),
    (920, 1100, "obs.simulate"), (940, 1000, "obs.snapshot"),
]
IDLE_BY_SPAN = {"obs.rebin_dispatch": 20, "obs.prune_dispatch": 10,
                "obs.schedule_read": 20 + 60, "obs.diag_read": 10,
                "obs.metrics_read": 50, None: 10, "obs.stray": 20,
                "obs.simulate": 10 + 10 + 50, "obs.snapshot": 10}


@pytest.fixture
def traced():
    """The reader context of a run whose trace is the synthetic one: the
    readers take the host spans from its reduced trace."""
    r = tr.from_events(events(OPS, ASYNC, MODULES, HOST))
    return SimpleNamespace(reduced=r, steps=3 * 20, blocks=r.blocks)


def test_window_and_boundaries_of_the_synthetic_trace(traced):
    r = traced.reduced
    assert (r.t0, r.t1, r.blocks) == (100, 1100, 3)
    assert r.block_gaps_ns(0) == [400, 150]
    assert tr.length(host_idle.idle_intervals(r, 0)) == 280


def test_rebin_and_prune_scope_metrics(traced):
    # rebin [350, 530) + [950, 1000); force carry [400, 530) with the copy
    # in flight; prune [560, 600) + [610, 640): over 2 boundaries
    got = {m: spec.metric_reader(m)(traced) for m in NEW[:3]}
    assert got == {"rebin_ms_per_block": pytest.approx(230 / 2 / 1e6),
                   "rebin_force_ms_per_block": pytest.approx(130 / 2 / 1e6),
                   "prune_ms_per_block": pytest.approx(70 / 2 / 1e6)}


def test_idle_goes_to_the_innermost_host_span(traced):
    spans = host_idle.host_spans(traced)
    assert {n for _s, _e, n in spans} == {n for _s, _e, n in HOST
                                          if n.startswith("obs.")}
    idle = host_idle.idle_intervals(traced.reduced, 0)
    assert host_idle.idle_by_span(idle, spans) == IDLE_BY_SPAN


def test_engine_and_schedule_idle_metrics(traced):
    # inside obs.simulate: [300,350) [500,560) [600,610) [640,690) under
    # the first call, [920,950) [1000,1050) under the second
    engine = spec.metric_reader("engine_idle_ms_per_block")(traced)
    sched = spec.metric_reader("schedule_idle_ms_per_block")(traced)
    assert engine == pytest.approx((50 + 60 + 10 + 50 + 30 + 50) / 2 / 1e6)
    assert sched == pytest.approx(80 / 2 / 1e6)


@pytest.mark.parametrize("spans,want", [
    ([], {None: 10}),
    ([(0, 5, "a")], {None: 10}),                       # ends before
    ([(0, 20, "outer"), (2, 8, "inner")],              # nested
     {"outer": 10}),
    ([(0, 20, "outer"), (12, 14, "inner")],
     {"outer": 8, "inner": 2}),
    ([(12, 30, "late"), (0, 15, "early")],             # partial overlap
     {"early": 2, "late": 8}),
    ([(12, 30, "b"), (0, 11, "a")],                    # gap under none
     {"a": 1, None: 1, "b": 8}),
    ([(10, 20, "short"), (10, 30, "long")],            # same start
     {"short": 10}),
])
def test_idle_by_span_cases(spans, want):
    got = host_idle.idle_by_span([(10, 20)], spans)
    assert {k: v for k, v in got.items() if v} == want


def test_idle_by_span_matches_brute_force():
    """Against a per-nanosecond reading of the same rule."""
    rng = np.random.default_rng(7)
    spans = []
    for k in range(40):
        s = int(rng.integers(0, 900))
        spans.append((s, s + int(rng.integers(1, 200)), f"s{k}"))
    busy = tr.union((int(s), int(s) + int(d)) for s, d in
                    zip(rng.integers(0, 1000, 60), rng.integers(1, 40, 60)))
    idle = tr.gaps(busy, 0, 1000)
    want = {}
    for lo, hi in idle:
        for t in range(lo, hi):
            cover = [(-s, e, n) for s, e, n in spans if s <= t < e]
            owner = min(cover)[2] if cover else None
            want[owner] = want.get(owner, 0) + 1
    assert host_idle.idle_by_span(idle, spans) == want


def test_no_trace_and_no_spans_read_nothing():
    assert host_idle.host_spans(SimpleNamespace(reduced=None)) == []
    r = tr.from_events(events(OPS, ASYNC, MODULES, HOST[:2]))
    ctx = SimpleNamespace(reduced=r, steps=60, blocks=3)
    assert host_idle.host_spans(ctx) == []
    for m in NEW[3:]:
        assert spec.metric_reader(m)(ctx) is None
    assert host_idle.idle_ms_per_block(None, HOST, "obs.simulate") is None


@pytest.mark.parametrize("text,phase", [
    ("jit(rebin_program)/obs.rebin/obs.rebin_force/mul", "rebin_force"),
    ("jit(block_sched_rebin)/obs.rebin_seam/obs.rebin/sort", "rebin"),
    ("jit(block_sched_rebin)/obs.rebin_seam/obs.prune/gather", "prune"),
    ("jit(block_sched)/obs.force/nb_pair_forces/pallas_call", "force"),
])
def test_the_innermost_scope_names_the_phase(text, phase):
    assert tr.phase_of(text) == phase


# --------------------------------------------------------------------------
# the recorded one-chip trace: earlier readings pinned
# --------------------------------------------------------------------------

RECORDED = FIXTURES / "trace_90k_boundary.json"
PINNED = {
    "device_idle_share": 98.55041644806442,
    "compiles_in_window": 0,
    "boundary_ms_per_block": 2663.466594,
    "halo_ms_per_step": 0.0004759,
    "halo_exposed_ms_per_step": 0.00047585,
    "force_ms_per_step": 0.09107364999999999,
    "nb_pair_efficiency": 1.375,
}
PINNED_BREAKDOWN = {
    "device_ops": [
        ["while.46", 0.025096008],
        ["jit(do_prune)/gather", 0.003446331],
        ["jit(block_sched)/while/body/closed_call/obs.force/gather",
         0.001693669],
        ["jit(do_rebin)/scatter-add", 0.001628805],
        ["jit(do_rebin)/gather", 0.0013927100000000001],
        ["jit(do_rebin)/jit(_where)/select_n", 0.000929825],
        ["jit(do_rebin)/scatter", 0.000768569],
        ["jit(do_prune)/scatter-add", 0.000713731],
        ["jit(do_rebin)/slice", 0.00041054000000000004],
        ["jit(do_rebin)/reshape", 0.000268661]],
    "idle_gaps": [
        ["bench.window", 2.621493083], ["bench.window", 0.003636641],
        ["bench.window", 0.002192479], ["bench.window", 0.001437235],
        ["bench.window", 2.177e-05], ["bench.window", 1.7577e-05],
        ["bench.window", 3.51e-07], ["bench.window", 1.7e-08],
        ["bench.window", 5e-09], ["bench.window", 3e-09]],
}


def test_recorded_trace_readings_are_pinned(tmp_path, monkeypatch):
    """The seven earlier metrics and the breakdown read the recorded
    trace as they did before the boundary metrics came; the new ones
    find nothing there (it predates the scopes and host spans), as on a
    program without them."""
    monkeypatch.setattr(tempfile, "tempdir", str(tmp_path))
    r = tr.from_events(json.loads(RECORDED.read_text())["traceEvents"])
    ref = SimpleNamespace(
        pair_list=lambda pos, box, r_cut: (np.zeros(55), np.zeros(55)))
    ctx = SimpleNamespace(reduced=r, steps=20, blocks=1, compiles=0,
                          pair_stats={"evaluated_slot_pairs": 4000},
                          n_domains=1, final_pos=None, box=None,
                          config={"r_cut": 2.5}, ref=ref)
    assert {m: spec.metric_reader(m)(ctx) for m in PINNED} == PINNED
    assert r.breakdown() == PINNED_BREAKDOWN
    assert {m: spec.metric_reader(m)(ctx) for m in NEW} == \
        dict.fromkeys(NEW)
