"""The harness finds configurations, traffic mixes, per-layer metric
readers and references by name, so a new one is a new file."""
import json

import pytest

from benchtools import BENCH, FIXTURES, ROOT, spec

FIX = FIXTURES / "discovery"


def test_fixture_entries_are_found_by_name():
    bench = json.loads((FIX / "BENCHMARK.json").read_text())
    cell = spec.resolve_cell(bench, "fixture-cfg.fixture_mix", root=FIX,
                             bench_dir=FIX)
    assert cell["config"]["name"] == "fixture-cfg"
    assert cell["config"]["eps_rf"] == float("inf")
    assert cell["traffic"]["blocks_per_call"] == 3
    assert [m["name"] for m in cell["end_to_end"]] == ["us_per_step",
                                                      "setup_s"]
    assert [m["name"] for m in cell["per_layer"]] == ["fixture.metric"]
    read = spec.metric_reader("fixture.metric", bench_dir=FIX)

    class Ctx:
        blocks = 7
    assert read(Ctx) == 7
    assert spec.reference(cell["config"], bench_dir=FIX).NAME == \
        "fixture reference"


def test_metric_listed_for_other_cells_is_left_out():
    bench = json.loads((FIX / "BENCHMARK.json").read_text())
    cell = spec.resolve_cell(bench, "fixture-cfg.other", root=FIX,
                             bench_dir=FIX)
    assert cell["per_layer"] == []


def test_unknown_names_are_refused():
    bench = json.loads((FIX / "BENCHMARK.json").read_text())
    with pytest.raises(SystemExit):
        spec.resolve_cell(bench, "no-such-cell", root=FIX, bench_dir=FIX)
    with pytest.raises(SystemExit):
        spec.metric_reader("no_such_metric", bench_dir=FIX)


@pytest.mark.parametrize("cell", [w["name"] for w in
                                  spec.load_benchmark()["workloads"]])
def test_every_committed_cell_resolves(cell):
    """Each cell of BENCHMARK.json has its configuration, traffic, a
    reference, limits and a reader for every per-layer metric it lists."""
    resolved = spec.resolve_cell(spec.load_benchmark(), cell)
    cfg = resolved["config"]
    assert cfg["limits"]["atoms_missing"] == 0
    assert cfg["limits"]["atoms_dropped"] == 0
    assert callable(spec.reference(cfg).verlet)
    for m in resolved["per_layer"]:
        assert callable(spec.metric_reader(m["name"]))
    assert (ROOT / "bench" / "traffic"
            / f"{resolved['cell']['traffic']}.json").is_file()
    assert BENCH.is_dir()
