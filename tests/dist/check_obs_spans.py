"""MDEngine.simulate on 4 virtual CPU devices under the JAX profiler: the
engine's host spans reach the profiler trace as ``obs.<span>`` host
events, nested under ``obs.simulate``.

Prints the sorted ``obs.*`` host event names and ``check_obs_spans OK``.
"""
import gzip
import json
import sys
import tempfile
from pathlib import Path

import jax

from repro.core.md import MDEngine, make_grappa_like
from repro.launch.mesh import make_md_mesh


def main():
    assert len(jax.devices()) >= 4, "need 4 virtual devices"
    eng = MDEngine(make_grappa_like(900, seed=3), make_md_mesh(4),
                   force_backend="sparse", static_ladder=True)
    state, _m, _d = eng.simulate(4)           # compiles outside the trace
    with tempfile.TemporaryDirectory() as out:
        with jax.profiler.trace(out):
            jax.block_until_ready(eng.simulate(4, state=state)[0])
        path = next(Path(out).rglob("*.trace.json.gz"))
        with gzip.open(path, "rt") as fh:
            events = json.load(fh)["traceEvents"]
    spans = [(round(e["ts"] * 1e3), round((e["ts"] + e["dur"]) * 1e3),
              e["name"]) for e in events
             if e.get("ph") == "X" and e["name"].startswith("obs.")]
    (sim,) = [s for s in spans if s[2] == "obs.simulate"]
    for s, e, name in spans:            # ns; 1 ns of rounding allowed
        assert sim[0] - 1 <= s and e <= sim[1] + 1, name
    print(" ".join(sorted({name for _s, _e, name in spans})))
    print("check_obs_spans OK")


if __name__ == "__main__":
    sys.exit(main())
