"""Observability subsystem: registry, tracing neutrality, export, gate.

Four contracts:

1. the metrics registry's instruments/records are typed, JSON-safe and
   round-trip through JSONL;
2. every engine layer that returns a stats dict also publishes it as a
   structured record with a stable schema (the emitter tests);
3. tracing is barrier-neutral — a tracer-enabled pipeline/engine cell is
   bitwise-identical to its untraced twin across the full backend x mode
   x depth matrix, the ``obs/*`` outputs being strictly additive;
4. the Perfetto exporter is deterministic against a golden fixture and
   the perf gate separates exact / rel-tol / timing drift classes.
"""
import functools
import json

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.compat import shard_map_norep
from repro.core.halo_plan import HaloPlan, HaloSpec
from repro.core.pipeline import SignalLedger, StepFns, StepPipeline
from repro.launch.mesh import make_mesh
from repro.obs import (
    DEFAULT_GATE,
    KEY_FIELDS,
    SCHEMA_VERSION,
    MetricsRegistry,
    NULL_TRACER,
    PhaseTracer,
    cell_key,
    compare_bench,
    default_registry,
    export_trace,
    is_obs_metric,
    iter_kind,
    jsonsafe,
    load_jsonl,
    span,
    strip_obs_metrics,
    time_fn,
    to_trace,
)
from repro.obs.__main__ import main as obs_main
from pathlib import Path

FIXTURES = Path(__file__).parent / "fixtures" / "obs"


# --------------------------------------------------------------------------
# registry
# --------------------------------------------------------------------------

def test_registry_instruments_typed_and_get_or_create():
    reg = MetricsRegistry()
    c = reg.counter("md/steps")
    assert reg.counter("md/steps") is c
    c.inc(3)
    c.inc()
    with pytest.raises(ValueError, match="cannot decrease"):
        c.inc(-1)
    reg.gauge("md/occ").set(0.75)
    h = reg.histogram("span/x")
    for v in (3.0, 1.0, 2.0):
        h.observe(v)
    m = reg.metrics()
    assert m["md/steps"] == 4
    assert m["md/occ"] == 0.75
    assert m["span/x"]["count"] == 3
    assert m["span/x"]["min"] == 1.0 and m["span/x"]["max"] == 3.0
    assert m["span/x"]["p50"] == 2.0
    with pytest.raises(ValueError, match="is a counter"):
        reg.gauge("md/steps")


def test_registry_emit_is_jsonsafe_and_ordered():
    reg = MetricsRegistry()
    reg.emit("halo_stats", backend="signal",
             data={"bytes": np.int64(4096), "occ": np.float32(0.5),
                   "dd": (2, 2, 2)})
    reg.emit("pair_stats", ratio=jnp.float32(3.0))
    kinds = [r["kind"] for r in reg.records]
    assert kinds == ["halo_stats", "pair_stats"]
    rec = reg.records[0]
    assert rec["data"] == {"bytes": 4096, "occ": 0.5, "dd": [2, 2, 2]}
    assert isinstance(rec["t"], float)
    json.dumps(reg.records)          # everything emitted is serializable


def test_registry_snapshot_and_jsonl_roundtrip(tmp_path):
    reg = MetricsRegistry()
    reg.counter("md/blocks").inc(2)
    reg.gauge("md/rows").set(112)
    reg.snapshot(label="md/simulate", n_steps=8)
    p = tmp_path / "m.jsonl"
    assert reg.to_jsonl(p) == 1
    back = load_jsonl(p)
    assert back == reg.records
    snap = iter_kind(back, "snapshot")[0]
    assert snap["label"] == "md/simulate" and snap["n_steps"] == 8
    assert snap["metrics"]["md/blocks"] == {"kind": "counter", "value": 2}
    assert snap["metrics"]["md/rows"] == {"kind": "gauge", "value": 112.0}


def test_histogram_state_is_cheap_and_unchanged():
    """``state()`` reads a sorted store: after 50,000 observations it
    takes under 1 ms and gives what sorting every value gives."""
    import time
    vals = np.random.default_rng(3).exponential(size=50_000).tolist()
    h = MetricsRegistry().histogram("span/x")
    for v in vals:
        h.observe(v)
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        got = h.state()
        best = min(best, time.perf_counter() - t0)
    assert best < 1e-3
    vs, n = sorted(vals), len(vals)
    assert {k: got[k] for k in ("count", "min", "max", "p50", "p95")} == \
        {"count": n, "min": vs[0], "max": vs[-1], "p50": vs[n // 2],
         "p95": vs[(19 * n) // 20]}
    assert got["sum"] == pytest.approx(sum(vs), rel=1e-12)
    assert got["mean"] == pytest.approx(sum(vs) / n, rel=1e-12)


def test_default_registry_is_a_singleton():
    assert default_registry() is default_registry()


def test_jsonsafe_falls_back_to_repr():
    class Opaque:
        def __repr__(self):
            return "<opaque>"
    assert jsonsafe({"x": Opaque()}) == {"x": "<opaque>"}


# --------------------------------------------------------------------------
# host-side spans / timers
# --------------------------------------------------------------------------

def test_span_records_duration_and_syncs():
    reg = MetricsRegistry()
    with span("work", reg, steps=4) as sp:
        y = sp.sync(jnp.arange(8) * 2)
    assert sp.dur > 0.0
    assert int(y[-1]) == 14
    rec = iter_kind(reg.records, "span")[0]
    assert rec["name"] == "work" and rec["steps"] == 4
    assert rec["dur"] == sp.dur
    assert reg.metrics()["span/work"]["count"] == 1


def test_time_fn_medians_and_emits():
    reg = MetricsRegistry()
    res = time_fn(lambda: jnp.ones(4).sum(), warmup=1, iters=5,
                  name="toy", registry=reg)
    assert len(res.times) == 5
    assert res.best <= res.median <= max(res.times)
    rec = iter_kind(reg.records, "timing")[0]
    assert rec["name"] == "toy" and rec["iters"] == 5


# --------------------------------------------------------------------------
# engine emitters: every stats dict has a structured twin
# --------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _md_run(trace: bool):
    from repro.core.md import MDEngine, make_grappa_like

    reg = MetricsRegistry()
    eng = MDEngine(make_grappa_like(200, seed=5),
                   make_mesh((1, 1, 1), ("z", "y", "x")),
                   HaloSpec(("z", "y", "x"), (1, 1, 1), backend="signal"),
                   pipeline="double_buffer", pipeline_depth=3,
                   force_backend="sparse", nstprune=4,
                   obs=reg, trace=trace)
    (cf, ci), metrics, _ = eng.simulate(12)
    eng.halo_stats()
    eng.pair_stats()
    eng.overlap_stats()
    return reg, np.asarray(cf), {k: np.asarray(v)
                                 for k, v in metrics.items()}


def test_engine_publishes_structured_records():
    reg, _, _ = _md_run(True)
    kinds = {r["kind"] for r in reg.records}
    assert {"engine_build", "sched_update", "span", "step_counters",
            "snapshot", "halo_stats", "pair_stats",
            "overlap_model"} <= kinds

    build = iter_kind(reg.records, "engine_build")[0]
    assert build["backend"] == "signal"
    assert build["pipeline"] == "double_buffer"
    assert build["pipeline_depth"] == 3 and build["nstprune"] == 4

    halo = iter_kind(reg.records, "halo_stats")[-1]
    assert halo["critical_path"] in ("serialized", "fused")
    assert {"latency", "overlap"} <= set(halo["data"])
    ov = halo["data"]["overlap"]
    assert ov["depth"] == 3 and ov["pipeline"] == "double_buffer"

    pair = iter_kind(reg.records, "pair_stats")[-1]
    assert pair["data"]["prune_ratio"] >= 1.0

    sched = iter_kind(reg.records, "sched_update")[0]
    assert sched["outer_rows"] > 0

    steps = iter_kind(reg.records, "step_counters")[-1]
    assert all(k.startswith("obs/") for k in steps["data"])
    assert all(len(v) == 12 for v in steps["data"].values())

    snap = iter_kind(reg.records, "snapshot")[-1]
    vals = snap["metrics"]
    assert vals["md/steps"]["value"] == 12
    assert "span/block_dispatch" in vals
    # pair_stats() runs after the simulate snapshot: gauge is live-only
    assert reg.metrics()["md/prune_ratio"] >= 1.0
    json.dumps(reg.records)


def test_engine_programs_carry_scopes_and_kernel_names():
    """The rebin and prune programs carry their ``obs.*`` scopes and the
    block program the NB kernel's name, where a profiler trace reads
    them (the lowered text's locations hold the scope paths)."""
    from repro.core.md import MDEngine, make_grappa_like

    eng = MDEngine(make_grappa_like(200, seed=5),
                   make_mesh((1, 1, 1), ("z", "y", "x")),
                   force_backend="pallas", static_ladder=True)
    cf, ci = eng.init_state()

    def text(fn, *args):
        return fn.lower(*args).as_text(debug_info=True)

    rebin = text(eng.rebin_fn, cf, ci)
    assert "obs.rebin/" in rebin and "obs.rebin/obs.rebin_force/" in rebin
    assert "obs.prune/" in text(eng.prune_fn, cf, ci)
    rs = eng.begin_run((cf, ci))
    sel, tiers, tiers_inner = rs.sched
    block = text(eng.block_sched_fn, rs.cell_f, rs.cell_i, rs.force, sel,
                 4, tiers, tiers_inner)
    assert "obs.force/nb_pair_forces/" in block


def test_simulate_host_spans_reach_the_profiler_trace(dist):
    """On 4 virtual CPU devices, under ``jax.profiler.trace``, the
    engine's spans are ``obs.*`` host events nested in ``obs.simulate``."""
    out = dist("check_obs_spans.py", devices=4, timeout=300)
    assert "check_obs_spans OK" in out
    names = set(out.splitlines()[-2].split())
    assert {"obs.simulate", "obs.schedule_read", "obs.metrics_read",
            "obs.rebin_dispatch", "obs.prune_dispatch",
            "obs.block_dispatch", "obs.diag_read", "obs.snapshot"} <= names


def test_ledger_summary_publishes_gauges():
    led = SignalLedger(depth=2, n_pulses=3)
    st = led.init()
    st = led.release(st, "fwd", 0)
    st = led.acquire(st, "fwd", 0)
    reg = MetricsRegistry()
    out = led.summary(st, registry=reg)
    assert out["fwd"]["released"] == 3
    m = reg.metrics()
    assert m["ledger/fwd_released"] == 3
    assert m["ledger/in_flight"] == 0
    rec = iter_kind(reg.records, "ledger_summary")[0]
    assert rec["data"] == out


# --------------------------------------------------------------------------
# tracing neutrality: obs on == obs off, bitwise, across the matrix
# --------------------------------------------------------------------------

TRACE_MATRIX = [(b, m, d)
                for b in ("serialized", "fused", "pallas", "signal")
                for m in ("off", "double_buffer")
                for d in (2, 3, 4)]


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
def _trace_cell(backend, mode, depth, traced, n_steps=8):
    if mode == "off":
        depth = 2
    mesh = make_mesh((1,), ("z",))
    plan = HaloPlan.build(HaloSpec(("z",), (1,), backend=backend), mesh)
    tracer = PhaseTracer(enabled=True) if traced else NULL_TRACER
    pipe = StepPipeline.build(plan, _toy_fns(), mode=mode, depth=depth,
                              tracer=tracer)
    x0 = jnp.asarray(np.random.RandomState(0).randn(6, 4)
                     .astype(np.float32))

    def run(state, f):
        return pipe.run_local(state, f, n_steps, jnp.float32(0.5))

    fn = shard_map_norep(run, mesh=mesh, in_specs=(P(), P()),
                         out_specs=(P(), P(), P(), P()))
    state, f, metrics, led = jax.jit(fn)(x0, jnp.zeros_like(x0))
    return (np.asarray(state), np.asarray(f),
            {k: np.asarray(v) for k, v in metrics.items()},
            pipe.ledger.summary(led))


@pytest.mark.parametrize("backend,mode,depth", TRACE_MATRIX,
                         ids=[f"{b}-{m}-d{d}" for b, m, d in TRACE_MATRIX])
def test_tracing_is_bitwise_neutral(backend, mode, depth):
    """A tracer-enabled cell must equal its untraced twin bit for bit;
    the obs/* outputs are additive (full-length per-step counters)."""
    ref = _trace_cell(backend, mode, depth, False)
    got = _trace_cell(backend, mode, depth, True)
    np.testing.assert_array_equal(got[0], ref[0])
    np.testing.assert_array_equal(got[1], ref[1])
    assert strip_obs_metrics(got[2]).keys() == ref[2].keys()
    for k in ref[2]:
        np.testing.assert_array_equal(got[2][k], ref[2][k])
    obs_keys = [k for k in got[2] if is_obs_metric(k)]
    assert sorted(obs_keys) == ["obs/acquired", "obs/clobbers",
                                "obs/in_flight", "obs/released"]
    for k in obs_keys:
        assert got[2][k].shape[0] == 8
        assert got[2][k].dtype == np.int32
    assert got[3]["consistent"] and got[3]["clobbers"] == 0
    assert int(got[2]["obs/clobbers"][-1]) == 0


def test_md_engine_tracing_is_bitwise_neutral():
    """The full MD engine (signal + deep window + rolling prune), traced
    vs untraced: identical trajectory and physics metrics."""
    _, cf_ref, m_ref = _md_run(False)
    _, cf, m = _md_run(True)
    np.testing.assert_array_equal(cf, cf_ref)
    assert strip_obs_metrics(m).keys() == m_ref.keys()
    for k in m_ref:
        np.testing.assert_array_equal(m[k], m_ref[k])
    obs = {k: v for k, v in m.items() if is_obs_metric(k)}
    assert obs and all(v.shape[0] == 12 for v in obs.values())


# --------------------------------------------------------------------------
# Perfetto export (golden file)
# --------------------------------------------------------------------------

def test_perfetto_export_matches_golden(tmp_path):
    out = tmp_path / "trace.json"
    trace = export_trace(FIXTURES / "sample.jsonl", out)
    golden = json.loads((FIXTURES / "trace_golden.json").read_text())
    assert json.loads(out.read_text()) == golden
    assert trace == golden


def test_perfetto_trace_structure():
    trace = to_trace(load_jsonl(FIXTURES / "sample.jsonl"))
    evs = trace["traceEvents"]
    assert {e["pid"] for e in evs} == {0}              # the measured lane
    for e in evs:
        assert e["ph"] in ("M", "X", "C")
        if e["ph"] == "X":
            assert e["dur"] > 0 and e["ts"] >= 0
    names = {e["name"] for e in evs if e["ph"] == "X"}
    assert names == {"rebin_dispatch", "block_dispatch"}
    # the 8 recorded steps' ledger counters, spread evenly up to the
    # record's time (0.8 s after the first span's start)
    steps = [e for e in evs
             if e["ph"] == "C" and e["name"] == "obs/in_flight"]
    assert [e["args"]["obs/in_flight"] for e in steps] == \
        [1, 2, 2, 2, 2, 2, 2, 1]
    assert [e["ts"] for e in steps] == \
        pytest.approx([k * 0.1e6 for k in range(8)])
    counters = {e["name"] for e in evs if e["ph"] == "C"}
    assert {"obs/in_flight", "obs/clobbers", "md/steps"} <= counters
    assert trace["otherData"]["backend"] == "signal"


def test_perfetto_export_from_live_registry(tmp_path):
    reg, _, _ = _md_run(True)
    p = tmp_path / "live.jsonl"
    reg.to_jsonl(p)
    trace = export_trace(p, tmp_path / "trace.json")
    evs = trace["traceEvents"]
    assert {e["pid"] for e in evs} == {0}
    spans = {e["name"] for e in evs if e["ph"] == "X"}
    assert {"simulate", "schedule_read", "metrics_read"} <= spans
    counters = {e["name"] for e in evs if e["ph"] == "C"}
    assert {"obs/in_flight", "obs/clobbers"} <= counters
    json.dumps(trace)


# --------------------------------------------------------------------------
# perf-trajectory gate
# --------------------------------------------------------------------------

def _bench(**over):
    cell = {"mode": "signal", "pipeline": "double_buffer",
            "pipeline_depth": 3, "devices": 1, "n_atoms": 600,
            "force_backend": "sparse", "nstprune": 4,
            "exposed_phases": 2.0, "overlapped_bytes": 4096,
            "exchanged_bytes": 6144, "halo_total_bytes": 8192,
            "dd": [1, 1, 1], "prune_ratio": 3.5,
            "evaluated_slot_pairs_per_step": 1000,
            "modeled_speedup": 2.5, "ms_per_step": 10.0,
            "ms_force_pass": 6.0}
    cell.update(over)
    return {"suite": "pipeline", "schema_version": SCHEMA_VERSION,
            "gate": DEFAULT_GATE, "cells": [cell]}


def test_gate_passes_identical_and_jittered_runs():
    base = _bench()
    assert compare_bench(base, base) == []
    # timing jitter inside the factor + tiny float drift: still green
    cur = _bench(ms_per_step=19.0, prune_ratio=3.51)
    assert compare_bench(base, cur) == []
    # timing *improvement* never fails (upper bound only)
    assert compare_bench(base, _bench(ms_per_step=0.1)) == []


def test_gate_fails_on_semantic_drift():
    base = _bench()
    probs = compare_bench(base, _bench(exposed_phases=4.0))
    assert len(probs) == 1 and "exposed_phases" in probs[0]
    assert "exact" in probs[0]
    probs = compare_bench(base, _bench(prune_ratio=5.0))
    assert len(probs) == 1 and "prune_ratio" in probs[0]
    probs = compare_bench(base, _bench(ms_per_step=150.0))
    assert len(probs) == 1 and "regression" in probs[0]


def test_gate_fails_on_cell_and_schema_mismatch():
    base = _bench()
    probs = compare_bench(base, _bench(pipeline_depth=4))
    assert any("missing from current" in p for p in probs)
    assert any("not in baseline" in p for p in probs)
    cur = dict(base, schema_version=SCHEMA_VERSION + 1)
    probs = compare_bench(base, cur)
    assert probs == [f"schema_version drift: baseline {SCHEMA_VERSION} "
                     f"vs current {SCHEMA_VERSION + 1}"]


def test_cell_key_covers_identity_fields():
    assert len(cell_key(_bench()["cells"][0])) == len(KEY_FIELDS)


def test_checked_in_baseline_gates_itself():
    """The committed BENCH_pipeline.json must be self-consistent (schema
    version, unique cell keys, green against itself)."""
    path = Path(__file__).parents[1] / "results" / "BENCH_pipeline.json"
    doc = json.loads(path.read_text())
    assert doc["schema_version"] == SCHEMA_VERSION
    assert doc["cells"]
    assert compare_bench(doc, doc) == []


# --------------------------------------------------------------------------
# CLI
# --------------------------------------------------------------------------

def test_cli_export_default_subcommand(tmp_path, capsys):
    out = tmp_path / "t.json"
    rc = obs_main([str(FIXTURES / "sample.jsonl"), "--out", str(out)])
    assert rc == 0
    assert "wrote" in capsys.readouterr().out
    assert json.loads(out.read_text())["traceEvents"]


def test_cli_gate_exit_codes(tmp_path, capsys):
    base = tmp_path / "base.json"
    good = tmp_path / "good.json"
    bad = tmp_path / "bad.json"
    base.write_text(json.dumps(_bench()))
    good.write_text(json.dumps(_bench(ms_per_step=12.0)))
    bad.write_text(json.dumps(_bench(overlapped_bytes=1)))
    assert obs_main(["gate", "--baseline", str(base),
                     "--current", str(good)]) == 0
    assert "0 finding(s)" in capsys.readouterr().out
    assert obs_main(["gate", "--baseline", str(base),
                     "--current", str(bad)]) == 1
    assert "overlapped_bytes" in capsys.readouterr().out
