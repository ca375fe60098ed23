"""StepPipeline subsystem: ledger, overlap schedules, signal backend, MD.

The pipeline's conformance bar is a single parametrized MATRIX — backend
x pipeline mode x halo width x window depth — every cell of which must be
bitwise-identical to the serialized/off reference (replacing the old
hand-enumerated per-case tests, which could not keep up with the
multiplicative axis growth).  Single-device (periodic self-exchange)
cells run in-process; the multi-device versions live in
tests/dist/check_halo.py / check_md.py.
"""
import functools

import numpy as np
import pytest

try:
    from hypothesis import given, settings
    from hypothesis import strategies as st
except ImportError:  # property tests skip; hypothesis is a dev extra
    from _hypothesis_stub import given, settings, st

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.compat import shard_map_norep
from repro.core.halo_plan import HaloPlan, HaloSpec
from repro.core.pipeline import (
    PIPELINE_MODES,
    SignalLedger,
    StepFns,
    StepPipeline,
)
from repro.core.schedule import make_schedule, split_width
from repro.launch.mesh import make_mesh


# --------------------------------------------------------------------------
# width>1 multi-pulse schedules
# --------------------------------------------------------------------------

def test_split_width_balanced():
    assert split_width(2, 2) == (1, 1)
    assert split_width(5, 2) == (3, 2)
    assert split_width(3, 3) == (1, 1, 1)


def test_multi_pulse_schedule_offsets_tile_the_halo():
    sched = make_schedule(("z", "y"), (3, 2), pulses_per_dim=(2, 2))
    assert sched.total_pulses == 4
    for d, w in enumerate(sched.widths):
        pulses = sched.dim_pulses(d)
        assert [p.offset for p in pulses] == \
            [sum(q.width for q in pulses[:k]) for k in range(len(pulses))]
        assert sum(p.width for p in pulses) == w
    # global order still concatenates dims Z -> Y
    assert [p.dim for p in sched.serialized_order()] == [0, 0, 1, 1]


def test_multi_pulse_schedule_validation():
    with pytest.raises(ValueError, match="cannot split"):
        make_schedule(("z",), (1,), pulses_per_dim=(2,))
    with pytest.raises(ValueError, match="at least one pulse"):
        make_schedule(("z",), (2,), pulses_per_dim=(0,))
    # width-0 dims degrade to a single no-op pulse
    sched = make_schedule(("z", "y"), (2, 0), pulses_per_dim=(2, 2))
    assert len(sched.dim_pulses(1)) == 1


@pytest.mark.parametrize("backend",
                         ("serialized", "fused", "pallas", "signal"))
def test_width2_two_pulse_bitwise_identical(backend):
    """Width-2 halos, one- vs two-pulse schedules: same bytes, same bits,
    across all four backends (single-device periodic self-exchange; the
    8-device version is in tests/dist/check_halo.py)."""
    mesh = make_mesh((1,), ("z",))
    rng = np.random.RandomState(3)
    x = jnp.asarray(rng.randn(6, 5).astype(np.float32))
    shift = np.zeros((1, 5))
    shift[0, 0] = 17.0
    ref = np.asarray(HaloPlan.build(
        HaloSpec(("z",), (2,), backend="serialized", wrap_shift=shift),
        mesh).fwd(x))
    for pulses in (None, (2,)):
        plan = HaloPlan.build(
            HaloSpec(("z",), (2,), backend=backend, wrap_shift=shift,
                     pulses=pulses), mesh)
        np.testing.assert_array_equal(np.asarray(plan.fwd(x)), ref)


@pytest.mark.parametrize("backend",
                         ("serialized", "fused", "pallas", "signal"))
def test_width2_two_pulse_adjoint(backend):
    mesh = make_mesh((1,), ("z",))
    plan = HaloPlan.build(
        HaloSpec(("z",), (2,), backend=backend, pulses=(2,)), mesh)
    rng = np.random.RandomState(4)
    x = jnp.asarray(rng.randn(6, 4).astype(np.float32))
    y = jnp.asarray(rng.randn(8, 4).astype(np.float32))
    lhs = float(jnp.vdot(plan.fwd(x), y))
    rhs = float(jnp.vdot(x, plan.rev(y)))
    assert abs(lhs - rhs) <= 1e-5 * max(abs(lhs), 1.0)


# --------------------------------------------------------------------------
# signal ledger
# --------------------------------------------------------------------------

def test_ledger_release_acquire_balance():
    led = SignalLedger(depth=2, n_pulses=3)
    st = led.init()
    st = led.release(st, "fwd", 0)
    st = led.acquire(st, "fwd", 0)
    st = led.release(st, "rev", 1)
    assert bool(led.consistent(st))
    s = led.summary(st)
    assert s["fwd"] == {"released": 3, "acquired": 3}
    assert s["rev"] == {"released": 3, "acquired": 0}
    assert int(led.outstanding(st).sum()) == 3


def test_ledger_detects_unreleased_acquire():
    led = SignalLedger(depth=2, n_pulses=1)
    st = led.acquire(led.init(), "rev", 0)
    assert not bool(led.consistent(st))


def test_ledger_slot_parity_is_traceable():
    led = SignalLedger(depth=2, n_pulses=2)

    def f(k):
        return led.release(led.init(), "fwd", k % 2).released

    out = jax.jit(f)(jnp.int32(3))          # slot 1
    assert int(out[led.slot("fwd", 1, 0)]) == 1
    assert int(out[led.slot("fwd", 0, 0)]) == 0


def test_ledger_detects_slot_clobber():
    """A second release onto a still-outstanding slot is the buffer
    overwrite the depth-d ring exists to prevent."""
    led = SignalLedger(depth=2, n_pulses=1)
    st_ = led.release(led.init(), "rev", 0)
    assert bool(led.window_safe(st_))
    st_ = led.release(st_, "rev", 0)         # slot 0 never acquired
    assert not bool(led.window_safe(st_))
    assert int(st_.clobbers.sum()) == 1
    # acquire-then-release is the legal reuse and adds no clobber
    st2 = led.release(led.init(), "rev", 0)
    st2 = led.acquire(st2, "rev", 0)
    st2 = led.release(st2, "rev", 0)
    assert bool(led.window_safe(st2))


def _replay_window_schedule(led, depth, n_steps, watch):
    """Replay the deep-window pipeline's exact ledger transition sequence
    (prologue, skew-one steps with release-at-fill, epilogue drain),
    calling ``watch`` after every transition."""
    st_ = led.init()
    st_ = watch(led.release(st_, "fwd", 0))
    st_ = watch(led.acquire(st_, "fwd", 0))
    st_ = watch(led.release(st_, "rev", 0))
    for k in range(1, n_steps):
        st_ = watch(led.acquire(st_, "rev", k - 1))
        st_ = watch(led.release(st_, "fwd", k))
        st_ = watch(led.acquire(st_, "fwd", k))
        st_ = watch(led.release(st_, "rev", k))
    return watch(led.acquire(st_, "rev", n_steps - 1))


@given(depth=st.integers(2, 6), n_steps=st.integers(1, 16),
       n_pulses=st.integers(1, 3))
@settings(max_examples=25, deadline=None)
def test_ledger_window_replay_properties(depth, n_steps, n_pulses):
    """For random (depth, n_steps): no acquire ever observes a slot
    before its release, counters are monotone non-decreasing, and the
    drain epilogue leaves zero in-flight slots and zero clobbers."""
    led = SignalLedger(depth=depth, n_pulses=n_pulses)
    seen = {"released": None, "acquired": None}

    def watch(st_):
        assert bool(led.consistent(st_))              # causal at all times
        assert bool(led.window_safe(st_))             # ring never clobbers
        # skew-one window: at most one kind's pulses in flight at once
        assert int(led.in_flight(st_)) <= n_pulses
        for name in seen:                             # monotone counters
            cur = np.asarray(getattr(st_, name))
            assert np.all(cur >= 0)
            if seen[name] is not None:
                assert np.all(cur >= seen[name])
            seen[name] = cur
        return st_

    st_ = _replay_window_schedule(led, depth, n_steps, watch)
    assert bool(led.drained(st_))                     # epilogue drains all
    assert int(led.in_flight(st_)) == 0
    s = led.summary(st_)
    # summary totals sum over the per-pulse counters: every step releases
    # and acquires each of its n_pulses pulses once
    n_ops = n_steps * n_pulses
    assert s["fwd"]["released"] == s["fwd"]["acquired"] == n_ops
    assert s["rev"]["released"] == s["rev"]["acquired"] == n_ops


@given(depth=st.integers(2, 4), extra=st.integers(1, 4))
@settings(max_examples=10, deadline=None)
def test_ledger_overdeep_window_is_flagged(depth, extra):
    """Keeping more than ``depth`` deposits in flight MUST trip the
    clobber monitor: releases wrap the ring onto unacquired slots."""
    led = SignalLedger(depth=depth, n_pulses=1)
    st_ = led.init()
    for k in range(depth + extra):                    # no acquires at all
        st_ = led.release(st_, "rev", k)
    assert not bool(led.window_safe(st_))
    assert bool(led.consistent(st_))                  # still causal


# --------------------------------------------------------------------------
# cross-backend conformance matrix: every (backend, mode, width, depth)
# cell must reproduce the serialized/off reference bit for bit
# --------------------------------------------------------------------------

MATRIX_BACKENDS = ("serialized", "fused", "pallas", "signal")
MATRIX_MODES = ("off", "double_buffer")
MATRIX_WIDTHS = (1, 2)
MATRIX_DEPTHS = (2, 3, 4)
MATRIX_STEPS = 8     # 7 post-prologue steps: exercises rem != 0 at span 2/3

MATRIX = [(b, m, w, d)
          for b in MATRIX_BACKENDS
          for m in MATRIX_MODES
          for w in MATRIX_WIDTHS
          for d in MATRIX_DEPTHS]


def _toy_fns():
    def begin(state, f, ctx):
        state = state + 0.1 * f
        return state, state.sum(), state

    def force(ext, ctx):
        F = jnp.tanh(ext) * ctx
        return F, {"pe": jnp.sum(F)}

    def finish(state, aux, f, ctx):
        state = state + 0.01 * f + 1e-3 * aux
        return state, f, {"ke": jnp.sum(state)}

    return StepFns(begin=begin, force=force, finish=finish)


@functools.lru_cache(maxsize=None)
def _run_cell(backend, mode, width, depth, n_steps=MATRIX_STEPS):
    """One matrix cell (cached: ``off`` collapses the depth axis, and
    reference cells are shared by every comparison against them)."""
    if mode == "off":
        depth = 2        # the serialized chain has no ring to deepen
    mesh = make_mesh((1,), ("z",))
    plan = HaloPlan.build(HaloSpec(("z",), (width,), backend=backend),
                          mesh)
    pipe = StepPipeline.build(plan, _toy_fns(), mode=mode, depth=depth)
    x0 = jnp.asarray(np.random.RandomState(0).randn(6, 4)
                     .astype(np.float32))

    def run(state, f):
        return pipe.run_local(state, f, n_steps, jnp.float32(0.5))

    fn = shard_map_norep(run, mesh=mesh, in_specs=(P(), P()),
                         out_specs=(P(), P(), P(), P()))
    state, f, metrics, led = jax.jit(fn)(x0, jnp.zeros_like(x0))
    return (np.asarray(state), np.asarray(f),
            {k: np.asarray(v) for k, v in metrics.items()},
            pipe.ledger.summary(jax.device_get(led)))


@pytest.mark.parametrize(
    "backend,mode,width,depth", MATRIX,
    ids=[f"{b}-{m}-w{w}-d{d}" for b, m, w, d in MATRIX])
def test_conformance_matrix(backend, mode, width, depth):
    """Bitwise trajectory identity of every cell vs serialized/off, plus
    the ledger conservation laws (balanced, causal, clobber-free,
    drained) the hardware signal flags would enforce."""
    ref = _run_cell("serialized", "off", width, 2)
    got = _run_cell(backend, mode, width, depth)
    np.testing.assert_array_equal(got[0], ref[0])
    np.testing.assert_array_equal(got[1], ref[1])
    for k in ref[2]:
        assert ref[2][k].shape[0] == MATRIX_STEPS
        np.testing.assert_array_equal(got[2][k], ref[2][k])
    summary = got[3]
    assert summary["consistent"] and summary["window_safe"]
    assert summary["in_flight"] == 0 and summary["clobbers"] == 0
    for kind in ("fwd", "rev"):
        assert summary[kind]["released"] == MATRIX_STEPS
        assert summary[kind]["acquired"] == MATRIX_STEPS


@pytest.mark.parametrize("n_steps", (1, 2, 3))
@pytest.mark.parametrize("depth", (3, 4))
def test_deep_window_short_blocks(depth, n_steps):
    """Blocks shorter than the window: the whole run is prologue +
    epilogue drain loop (n_full = 0), which must still match ``off``."""
    ref = _run_cell("signal", "off", 1, 2, n_steps=n_steps)
    got = _run_cell("signal", "double_buffer", 1, depth, n_steps=n_steps)
    np.testing.assert_array_equal(got[0], ref[0])
    np.testing.assert_array_equal(got[1], ref[1])
    for k in ref[2]:
        np.testing.assert_array_equal(got[2][k], ref[2][k])
    assert got[3]["in_flight"] == 0 and got[3]["window_safe"]


def test_pipeline_rejects_bad_mode_and_depth():
    mesh = make_mesh((1,), ("z",))
    plan = HaloPlan.build(HaloSpec(("z",), (1,)), mesh)
    with pytest.raises(ValueError, match="unknown pipeline mode"):
        StepPipeline.build(plan, _toy_fns(), mode="triple")
    with pytest.raises(ValueError, match="depth >= 2"):
        StepPipeline.build(plan, _toy_fns(), mode="double_buffer",
                           depth=1)
    # "off" has no ring: depth is normalized away, not an error
    assert StepPipeline.build(plan, _toy_fns(), mode="off",
                              depth=7).depth == 1


# --------------------------------------------------------------------------
# wire-dtype cells: compressed halo payloads (HaloSpec.wire_dtype) must
# preserve the off == double_buffer bitwise conformance per wire format
# — fills encode once per step at the same cadence serial mode
# quantizes, drains decode + splice, so regrouping steps across scan
# iterations cannot re-round.  (float32 payloads here: the force-return
# carries the named format; the f64 coordinate floor is covered by the
# NVE harness and tests/dist/check_halo.py.)
# --------------------------------------------------------------------------

WIRE_MATRIX = [(wd, b, m, d)
               for wd in ("bfloat16", "float16", "int8_ef")
               for b in ("fused", "signal")
               for (m, d) in (("double_buffer", 2), ("double_buffer", 3))]


@functools.lru_cache(maxsize=None)
def _run_wire_cell(wire, backend, mode, depth, n_steps=MATRIX_STEPS):
    mesh = make_mesh((1,), ("z",))
    plan = HaloPlan.build(HaloSpec(("z",), (1,), backend=backend,
                                   wire_dtype=wire), mesh)
    pipe = StepPipeline.build(plan, _toy_fns(), mode=mode, depth=depth)
    x0 = jnp.asarray(np.random.RandomState(0).randn(6, 4)
                     .astype(np.float32))

    def run(state, f):
        return pipe.run_local(state, f, n_steps, jnp.float32(0.5))

    fn = shard_map_norep(run, mesh=mesh, in_specs=(P(), P()),
                         out_specs=(P(), P(), P(), P()))
    state, f, metrics, _ = jax.jit(fn)(x0, jnp.zeros_like(x0))
    return (np.asarray(state), np.asarray(f),
            {k: np.asarray(v) for k, v in metrics.items()})


@pytest.mark.parametrize("wire,backend,mode,depth", WIRE_MATRIX,
                         ids=[f"{wd}-{b}-{m}-d{d}"
                              for wd, b, m, d in WIRE_MATRIX])
def test_wire_conformance_matrix(wire, backend, mode, depth):
    ref = _run_wire_cell(wire, "serialized", "off", 2)
    got = _run_wire_cell(wire, backend, mode, depth)
    np.testing.assert_array_equal(got[0], ref[0])
    np.testing.assert_array_equal(got[1], ref[1])
    for k in ref[2]:
        np.testing.assert_array_equal(got[2][k], ref[2][k])


def test_wire_none_trace_unchanged():
    """wire_dtype=None must be bitwise-identical to the pre-wire
    program (the dense path's selection happens in python, so the
    traced computation is operand-for-operand the same)."""
    ref = _run_cell("fused", "double_buffer", 1, 3)
    got = _run_wire_cell(None, "fused", "double_buffer", 3)
    np.testing.assert_array_equal(got[0], ref[0])
    np.testing.assert_array_equal(got[1], ref[1])


def test_wire_compression_is_live():
    """bf16 force-return must actually perturb the trajectory relative
    to dense (guards against the wire path silently short-circuiting)."""
    dense = _run_wire_cell(None, "fused", "off", 2)
    comp = _run_wire_cell("bfloat16", "fused", "off", 2)
    d = np.abs(dense[0] - comp[0]).max()
    assert 0 < d < 1e-1, d


# --------------------------------------------------------------------------
# overlap + latency stats (plan-level, the ROADMAP items)
# --------------------------------------------------------------------------

def test_double_buffer_exposes_strictly_fewer_phases():
    mesh = make_mesh((1, 1, 1), ("z", "y", "x"))
    for backend in ("serialized", "fused", "pallas", "signal"):
        plan = HaloPlan.build(
            HaloSpec(("z", "y", "x"), (1, 1, 1), backend=backend), mesh)
        off = plan.stats((8, 8, 8), pipeline="off")
        db = plan.stats((8, 8, 8), pipeline="double_buffer")
        assert db["exposed_phases_per_step"] < \
            off["exposed_phases_per_step"]
        assert off["overlapped_bytes_per_step"] == 0
        assert db["overlapped_bytes_per_step"] == db["total_bytes"]


def test_overlap_model_depth_sweep_is_monotone():
    """Deeper in-flight windows expose strictly fewer phases per step and
    hide strictly more bytes, for every backend's critical-path model."""
    mesh = make_mesh((1, 1, 1), ("z", "y", "x"))
    for backend in ("serialized", "fused", "pallas", "signal"):
        plan = HaloPlan.build(
            HaloSpec(("z", "y", "x"), (1, 1, 1), backend=backend), mesh)
        cells = [plan.stats((8, 8, 8), pipeline="double_buffer", depth=d)
                 for d in (2, 3, 4, 5)]
        exposed = [c["exposed_phases_per_step"] for c in cells]
        hidden = [c["overlapped_bytes_per_step"] for c in cells]
        assert exposed == sorted(exposed, reverse=True)
        assert len(set(exposed)) == len(exposed)      # strictly decreasing
        assert hidden == sorted(hidden)
        assert all(c["overlap"]["depth"] == d
                   for c, d in zip(cells, (2, 3, 4, 5)))
        # depth 2 reproduces the legacy double-buffer accounting
        assert cells[0]["overlapped_bytes_per_step"] == \
            cells[0]["total_bytes"]
        # hidden bytes never exceed what is exchanged
        assert all(h < c["overlap"]["exchanged_bytes_per_step"]
                   for h, c in zip(hidden, cells))
    with pytest.raises(ValueError, match="depth >= 2"):
        plan.stats((8, 8, 8), pipeline="double_buffer", depth=1)


def test_latency_model_two_pulse_small_domain_regime():
    """Strong-scaling limit: with two pulses per dim the serialized path
    pays twice the per-message latency; the fused (put-with-signal) path
    still pays one latency per phase — the paper's crossover driver."""
    mesh = make_mesh((1, 1, 1), ("z", "y", "x"))
    plan = HaloPlan.build(
        HaloSpec(("z", "y", "x"), (2, 2, 2), pulses=(2, 2, 2)), mesh)
    lat = plan.stats((4, 4, 4))["latency"]
    assert lat["serialized_messages"] == 6
    assert len(lat["fused_phase_messages"]) == 3
    assert lat["serialized_time_s"] > lat["fused_time_s"]
    # tiny domains: latency-dominated, speedup approaches 6/3
    tiny = HaloPlan.build(
        HaloSpec(("z", "y", "x"), (2, 2, 2), pulses=(2, 2, 2)), mesh) \
        .stats((2, 2, 2), bandwidth_Bps=1e15)
    assert tiny["latency"]["fused_speedup"] == pytest.approx(2.0, rel=1e-3)


def test_stats_latency_configurable():
    mesh = make_mesh((1,), ("z",))
    plan = HaloPlan.build(HaloSpec(("z",), (1,)), mesh)
    fast = plan.stats((8,), link_latency_s=1e-9)["latency"]
    slow = plan.stats((8,), link_latency_s=1e-3)["latency"]
    assert slow["serialized_time_s"] > fast["serialized_time_s"]


# --------------------------------------------------------------------------
# MD engine through the pipeline (single device; 8-device in tests/dist)
# --------------------------------------------------------------------------

@pytest.mark.parametrize("pipeline", PIPELINE_MODES)
def test_md_engine_pipeline_bitwise(pipeline):
    from repro.core.md import MDEngine, make_grappa_like

    sys_ = make_grappa_like(200, seed=5)
    mesh = make_mesh((1, 1, 1), ("z", "y", "x"))
    spec = HaloSpec(("z", "y", "x"), (1, 1, 1), backend="serialized")
    ref_eng = MDEngine(sys_, mesh, spec)
    (cf_ref, _), m_ref, _ = ref_eng.simulate(12)

    eng = MDEngine(sys_, mesh,
                   HaloSpec(("z", "y", "x"), (1, 1, 1), backend="signal"),
                   pipeline=pipeline)
    (cf, _), m, _ = eng.simulate(12)
    np.testing.assert_array_equal(np.asarray(jax.device_get(cf)),
                                  np.asarray(jax.device_get(cf_ref)))
    for k in m_ref:
        np.testing.assert_array_equal(np.asarray(m[k]),
                                      np.asarray(m_ref[k]))


def test_md_engine_overlap_stats_and_validation():
    from repro.core.md import MDEngine, make_grappa_like

    sys_ = make_grappa_like(200, seed=5)
    mesh = make_mesh((1, 1, 1), ("z", "y", "x"))
    with pytest.raises(ValueError, match="unknown pipeline"):
        MDEngine(sys_, mesh, pipeline="buffered")
    with pytest.raises(ValueError, match="pipeline_depth must be >= 2"):
        MDEngine(sys_, mesh, pipeline="double_buffer", pipeline_depth=1)
    with pytest.raises(ValueError, match="widths must be >= 1"):
        MDEngine(sys_, mesh, HaloSpec(("z", "y", "x"), (1, 0, 1)))
    eng = MDEngine(sys_, mesh, pipeline="double_buffer")
    ov = eng.overlap_stats()
    assert ov["pipeline"] == "double_buffer"
    assert ov["overlapped_bytes_per_step"] > 0
    deep = MDEngine(sys_, mesh, pipeline="double_buffer",
                    pipeline_depth=4)
    assert deep.pipeline.depth == 4
    assert deep.overlap_stats()["depth"] == 4
    assert deep.overlap_stats()["exposed_phases_per_step"] < \
        ov["exposed_phases_per_step"]


# --------------------------------------------------------------------------
# prune axis: the conformance matrix extended over the dual pair list.
# For a FIXED prune schedule (nstprune setting), every pipeline mode /
# depth / rebin-fusion cell must be bitwise-identical — the rolling
# prune's sub-block refreshes ride the same block-constant ctx contract
# as the static schedule, so software pipelining cannot perturb them.
# --------------------------------------------------------------------------

PRUNE_MATRIX = [(nstprune, mode, depth, ovr)
                for nstprune in (0, 4)
                for (mode, depth, ovr) in (
                    ("off", 2, False),          # the reference cell
                    ("double_buffer", 2, False),
                    ("double_buffer", 3, False),
                    ("off", 2, True),           # overlap_rebin fused
                    ("double_buffer", 3, True),
                )]


@functools.lru_cache(maxsize=None)
def _run_md_prune_cell(nstprune, mode, depth, ovr, n_steps=24):
    from repro.core.md import MDEngine, make_grappa_like

    sys_ = make_grappa_like(200, seed=5)
    mesh = make_mesh((1, 1, 1), ("z", "y", "x"))
    eng = MDEngine(sys_, mesh,
                   HaloSpec(("z", "y", "x"), (1, 1, 1), backend="signal"),
                   pipeline=mode, pipeline_depth=depth, overlap_rebin=ovr,
                   force_backend="sparse", nstprune=nstprune)
    (cf, ci), m, diags = eng.simulate(n_steps)
    sel, tiers, tiers_inner = eng._sched_exec
    return (np.asarray(jax.device_get(cf)), np.asarray(jax.device_get(ci)),
            {k: np.asarray(v) for k, v in m.items()},
            [{k: np.asarray(v) for k, v in d.items()} for d in diags],
            (np.asarray(jax.device_get(sel)), tiers, tiers_inner),
            eng.pair_stats())


@pytest.mark.parametrize(
    "nstprune,mode,depth,ovr", PRUNE_MATRIX,
    ids=[f"np{p}-{m}-d{d}" + ("-ovr" if o else "")
         for p, m, d, o in PRUNE_MATRIX])
def test_prune_conformance_matrix(nstprune, mode, depth, ovr):
    """Sparse trajectories are bitwise-identical across pipeline modes
    and the fused/host-dispatched rebin paths for a fixed nstprune, and
    every cell hands the next block the identical post-prune exec
    schedule (same packed sel, same tier ladders)."""
    ref = _run_md_prune_cell(nstprune, "off", 2, False)
    got = _run_md_prune_cell(nstprune, mode, depth, ovr)
    np.testing.assert_array_equal(got[0], ref[0])
    np.testing.assert_array_equal(got[1], ref[1])
    for k in ref[2]:
        np.testing.assert_array_equal(got[2][k], ref[2][k])
    assert len(got[3]) == len(ref[3])            # same rebin cadence
    for gd, rd in zip(got[3], ref[3]):
        for k in rd:
            np.testing.assert_array_equal(gd[k], rd[k])
    sel_g, tiers_g, inner_g = got[4]
    sel_r, tiers_r, inner_r = ref[4]
    assert (tiers_g, inner_g) == (tiers_r, inner_r)
    np.testing.assert_array_equal(sel_g, sel_r)
    ps = got[5]
    assert ps["nstprune"] == nstprune
    assert ps["inner_overflow_blocks"] == 0


def test_md_engine_deep_window_and_overlap_rebin_bitwise():
    """24 steps (one rebin/migration boundary at nstlist=20): deep
    windows and the fused rebin path must all reproduce the
    host-dispatched serialized/off trajectory bit for bit."""
    from repro.core.md import MDEngine, make_grappa_like

    sys_ = make_grappa_like(200, seed=5)
    mesh = make_mesh((1, 1, 1), ("z", "y", "x"))
    spec = HaloSpec(("z", "y", "x"), (1, 1, 1), backend="serialized")
    ref_eng = MDEngine(sys_, mesh, spec)
    (cf_ref, ci_ref), m_ref, diags_ref = ref_eng.simulate(24)

    cases = [
        dict(pipeline="double_buffer", pipeline_depth=3),
        dict(pipeline="off", overlap_rebin=True),
        dict(pipeline="double_buffer", pipeline_depth=4,
             overlap_rebin=True),
    ]
    for kw in cases:
        eng = MDEngine(
            sys_, mesh,
            HaloSpec(("z", "y", "x"), (1, 1, 1), backend="signal"), **kw)
        (cf, ci), m, diags = eng.simulate(24)
        np.testing.assert_array_equal(np.asarray(jax.device_get(cf)),
                                      np.asarray(jax.device_get(cf_ref)))
        np.testing.assert_array_equal(np.asarray(jax.device_get(ci)),
                                      np.asarray(jax.device_get(ci_ref)))
        for k in m_ref:
            np.testing.assert_array_equal(np.asarray(m[k]),
                                          np.asarray(m_ref[k]))
        assert len(diags) == len(diags_ref)          # same rebin cadence
        for got_d, ref_d in zip(diags, diags_ref):
            for k in ref_d:
                np.testing.assert_array_equal(np.asarray(got_d[k]),
                                              np.asarray(ref_d[k]))
