"""Find a cell's configuration, force field, traffic mix and metric
readers by name.

Everything that belongs to one configuration, one force field, one
traffic mix or one per-layer metric lives in a file of its own, so
adding one adds a file:

* a configuration is the JSON file named by its ``configs`` entry in
  ``BENCHMARK.json``, with its plain reference at
  ``references/<reference>.py`` beside this module;
* a force field is ``force_fields/<kind>.py``, a module with
  ``build(config)`` that returns the program's force field for a
  configuration whose ``force_field`` names that kind (there is no
  default kind);
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
    config.update({k: float(v) for k, v in config.items()
                   if v in ("inf", "-inf")})
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
        known = sorted(p.stem for p in Path(path).parent.glob("*.py"))
        raise SystemExit(f"bench: no module at {path}; known: {known}")
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


def force_field(config: dict, bench_dir: Path = BENCH_DIR):
    """The program's force field for ``config``: ``build(config)`` of
    ``force_fields/<kind>.py``, the kind that its ``force_field`` names."""
    if "force_field" not in config:
        raise SystemExit(f"bench: configuration {config.get('name')!r} "
                         f"names no force_field")
    kind = config["force_field"]
    mod = load_module(Path(bench_dir) / "force_fields" / f"{kind}.py",
                      f"bench_force_field_{kind}")
    return mod.build(config)
