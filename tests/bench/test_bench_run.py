"""Whole runs of a small copy of each committed cell on the CPU.

The harness's look for a chip is skipped; everything else runs: set-up,
the window through ``MDEngine.simulate``, the readings and the
comparison with the reference, at the committed limits.  A sound run is
``correct``; each fault planted underneath the timed path makes it
not ``correct``; and the result line has the keys the driver reads, with
the compared numbers last.
"""
import json
import subprocess
import sys

import jax
import numpy as np
import pytest

from benchtools import BENCH, ROOT, faults, harness, run_small, small_cell

SOUND = {}


def sound():
    if not SOUND:
        SOUND["out"] = run_small(small_cell())
    return SOUND["out"]


def test_sound_run_is_correct_and_has_the_result_schema():
    out = sound()
    assert list(out)[:3] == ["correct", "attempted", "failed"]
    assert list(out)[-1] == "checks"            # compared numbers come last
    assert out["correct"] is True, out["checks"]
    assert out["attempted"] >= 1 and out["failed"] == 0
    assert set(out["metrics"]) == {"us_per_step", "setup_s"}
    assert all(m["value"] > 0 for m in out["metrics"].values())
    assert out["metrics"]["us_per_step"]["unit"] == "us/step"
    dev = out["device"]
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(dev)
    for name, c in out["checks"].items():
        assert set(c) == {"value", "limit"}, name
    json.dumps(out)


@pytest.mark.parametrize("fault", sorted(faults.FAULTS))
def test_fault_under_the_timed_path_is_not_correct(fault):
    with faults.planted(fault):
        out = run_small(small_cell())
    assert out["correct"] is False, out["checks"]


def test_checked_blocks_are_consecutive_and_drawn_from_the_seed():
    for n_calls, n_checked in ((1, 2), (2, 2), (7, 2), (7, 3), (5, 5)):
        seen = set()
        for seed in (0, 1, 2**31 + 17, 2**40, 5, 6, 7, 8):
            ks = harness.compared_calls(seed, n_calls, n_checked)
            assert len(ks) == min(n_checked, n_calls)
            assert list(ks) == list(range(ks[0], ks[0] + len(ks)))
            assert 0 <= ks[0] and ks[-1] < n_calls
            assert ks == harness.compared_calls(seed, n_calls, n_checked)
            seen.add(ks[0])
        if n_calls > n_checked:               # the seed moves the start
            assert len(seen) > 1


def test_a_fault_in_a_later_checked_block_is_not_correct(monkeypatch):
    """The reference is chained over every checked block, so a fault in
    the second one -- after a block that is sound -- is caught."""
    from unittest import mock
    from repro.core.md import MDEngine
    cell = small_cell()                    # one warm-up call
    orig = MDEngine.simulate
    count = {"n": 0}

    def simulate(self, n_steps, state=None, **kw):
        out, m, d = orig(self, n_steps, state=state, **kw)
        count["n"] += 1
        if count["n"] == 3:                     # the second window call
            cf, ci = out
            out = cf.at[faults._first_live(ci) + (0,)].add(1e-2), ci
        return out, m, d
    monkeypatch.setattr(harness, "compared_calls",
                        lambda seed, n, c: range(0, min(c, n)))
    with mock.patch.object(MDEngine, "simulate", simulate):
        out = harness.run_cell(cell, 3, 0.0, False, jax.devices(), 0.0)
    assert out["attempted"] == 2
    assert out["correct"] is False, out["checks"]
    assert out["checks"]["pos_err"]["value"] > 5e-3


def test_a_number_that_is_not_finite_is_never_dropped():
    box = np.full(3, 10.0)
    good = {"pos": np.ones((4, 3)), "vel": np.ones((4, 3)),
            "pe": np.zeros(20)}
    bad = dict(good, vel=np.full((4, 3), np.nan))
    nums = harness.compare_chain(box, [good, bad], [good, good])
    assert nums["vel_err"] == float("inf") and nums["pos_err"] == 0.0
    nums = harness.compare_chain(box, [bad, good], [good, good])
    assert nums["vel_err"] == float("inf")


def test_a_state_lost_before_the_window_is_not_correct():
    """A program whose state is no longer finite when the compared
    blocks begin: the reference is not run, every gap reads infinite
    and the result line names it."""
    with faults._wrap_simulate(
            lambda state, out: (out[0] * np.nan, out[1])):
        out = run_small(small_cell())
    assert out["correct"] is False
    assert {out["checks"][k]["value"] for k in
            ("pos_err", "vel_err", "pe_err")} == {"inf"}
    json.loads(json.dumps(out), parse_constant=lambda c: 1 / 0)


def test_bf16_control_in_the_programs_place_is_not_correct():
    """The control at test size: the reference with bfloat16 pair math
    fails the committed limits that the program passes."""
    cell = small_cell()
    cfg = cell["config"]
    ref = harness.spec.reference(cfg)
    arrays = ref.make_system(cfg, 11)
    start = {"pos": arrays["pos"], "vel": arrays["vel"]}
    want = harness.reference_chain(ref, cfg, arrays, start, 20, 2)
    control = harness.reference_chain(ref, cfg, arrays, start, 20, 2,
                                      ref.BF16)
    nums = harness.compare_chain(arrays["box"], control, want)
    limits = cfg["limits"]
    assert any(nums[k] > limits[k] for k in nums), (nums, limits)


def test_no_accelerator_no_result():
    """On a host where JAX finds only the CPU the run fails and prints
    no result line."""
    env = {"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin",
           "HOME": str(ROOT)}
    proc = subprocess.run(
        [sys.executable, str(BENCH / "run.py"), "--workload",
         "lammps-lj-32k.nve.dd4", "--seed", "1", "--seconds", "1",
         "--trace", "0"],
        capture_output=True, text=True, timeout=120, cwd=ROOT, env=env)
    assert proc.returncode != 0
    assert "{" not in proc.stdout
    assert "no accelerator" in proc.stderr


def test_too_few_chips_is_refused(monkeypatch):
    import jax

    class Dev:
        platform, device_kind = "tpu", "TPU v5 lite"
    monkeypatch.setattr(jax, "devices", lambda *a: [Dev()])
    with pytest.raises(SystemExit, match="needs 4 chips"):
        harness.require_accelerator(4)


BOUNDARY = ("rebin_ms_per_block", "rebin_force_ms_per_block",
            "prune_ms_per_block", "engine_idle_ms_per_block",
            "schedule_idle_ms_per_block")
CELLS = [w["name"] for w in harness.spec.load_benchmark()["workloads"]]


@pytest.mark.parametrize("workload", CELLS)
def test_traced_run_reports_per_layer_metrics(workload, monkeypatch):
    """With the profiler on, the result carries the cell's per-layer
    metrics that find something to read (the CPU trace has no TPU
    device, so the device-trace metrics are left out, never zero),
    ``busy_s``/``window_s`` and a breakdown; ``correct`` means the same.
    Every cell lists the block-boundary metrics, and the run parses its
    trace once: the readers take the host spans from the reduced trace."""
    import gzip
    opened = []
    gzip_open = gzip.open

    def counting_open(path, mode="rb", *a, **kw):
        if str(path).endswith(".trace.json.gz") and "r" in mode:
            opened.append(path)
        return gzip_open(path, mode, *a, **kw)
    monkeypatch.setattr(gzip, "open", counting_open)
    cell = small_cell(workload)
    listed = {m["name"] for m in cell["per_layer"]}
    assert set(BOUNDARY) <= listed
    out = run_small(cell, trace=True)
    assert len(opened) == 1
    assert set(out["metrics"]) <= listed
    assert {m["name"] for m in cell["per_layer"]
            if m["source"] == "program_counter"} <= set(out["metrics"])
    assert out["correct"] is True, out["checks"]
    assert list(out)[-1] == "checks"
    assert out["metrics"]["compiles_in_window"]["value"] == 0
    assert 0 < out["metrics"]["nb_pair_efficiency"]["value"] < 100
    assert "device_idle_share" not in out["metrics"]
    assert out["device"]["window_s"] > 0
    assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}
