"""Find a cell's configuration, traffic mix and metric readers by name.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric lives in a file of its own, so adding one adds a file:

* a configuration is the JSON file named by its ``configs`` entry in
  ``BENCHMARK.json``, with its plain reference at
  ``references/<reference>.py`` beside this module;
* a traffic mix is ``traffic/<name>.json``;
* a per-layer metric is ``metrics/<name>.py``, a module with
  ``read(ctx)`` that returns a number, or ``None`` where it finds
  nothing to read.
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent


def load_benchmark(root: Path = ROOT) -> dict:
    path = Path(root) / "BENCHMARK.json"
    if not path.is_file():
        raise SystemExit(f"bench: no BENCHMARK.json at {path}")
    return json.loads(path.read_text())


def _by_name(entries, name: str, kind: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise SystemExit(f"bench: no {kind} named {name!r}; known: "
                     f"{[e['name'] for e in entries]}")


def _json(path: Path) -> dict:
    return json.loads(Path(path).read_text())


def resolve_cell(bench: dict, workload: str, root: Path = ROOT,
                 bench_dir: Path = BENCH_DIR) -> dict:
    """Everything one cell runs with: its entry, configuration, traffic
    and the end-to-end and per-layer metrics that it reports."""
    cell = _by_name(bench["workloads"], workload, "workload")
    cfg_entry = _by_name(bench["configs"], cell["config"], "configuration")
    config = _json(Path(root) / cfg_entry["file"])
    # JSON has no infinity: a configuration spells it "inf"
    if isinstance(config.get("eps_rf"), str):
        config["eps_rf"] = float(config["eps_rf"])
    traffic = _json(Path(bench_dir) / "traffic" / f"{cell['traffic']}.json")

    def applies(m):
        return "workloads" not in m or workload in m["workloads"]

    return {
        "cell": cell,
        "config": config,
        "traffic": traffic,
        "end_to_end": [m for m in bench["end_to_end"] if applies(m)],
        "per_layer": [m for m in bench["per_layer"] if applies(m)],
    }


def load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or not path.is_file():
        raise SystemExit(f"bench: no module at {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_reader(name: str, bench_dir: Path = BENCH_DIR):
    """``read`` of ``metrics/<name>.py`` (dots in a name become ``_``)."""
    mod = load_module(Path(bench_dir) / "metrics"
                      / f"{name.replace('.', '_')}.py", f"bench_metric_{name}")
    return mod.read


def reference(config: dict, bench_dir: Path = BENCH_DIR):
    """The configuration's plain reference module."""
    name = config["reference"]
    return load_module(Path(bench_dir) / "references" / f"{name}.py",
                       f"bench_reference_{name}")
