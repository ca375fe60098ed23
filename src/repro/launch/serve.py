"""Serving launcher: batched LM waves, or continuous MD batching.

  PYTHONPATH=src python -m repro.launch.serve --arch qwen3-1.7b --reduced \
      --requests 16 --batch 4 --new-tokens 16

  PYTHONPATH=src python -m repro.launch.serve --md \
      --replicas 16 --atoms 200 --steps 40 --backend dense
"""
from __future__ import annotations

import argparse

import numpy as np

import jax

from repro.configs import get_config
from repro.launch.mesh import make_mesh
from repro.models import build_model
from repro.parallel.sharding import ShardingCtx
from repro.runtime.serve_loop import BatchServer, Request, throughput_stats


def main_md(args):
    """Continuous batching of MD replicas (the SimServer subsystem)."""
    from repro.core.md.domain import AXES
    from repro.core.md.system import make_grappa_like
    from repro.launch.mesh import make_mesh as mk
    from repro.serve import BucketLadder, SimServer

    mesh = mk((1, 1, 1), AXES)
    ladder = BucketLadder()
    server = SimServer(mesh, ladder, block_steps=args.nstlist,
                       engine_kwargs={"force_backend": args.backend})
    bucket = ladder.atom_bucket_for(args.atoms)
    handles = [server.submit(
        make_grappa_like(args.atoms, seed=i, nstlist=args.nstlist,
                         box_atoms=bucket), args.steps)
        for i in range(args.replicas)]
    server.drain()
    stats = server.stats()
    print(f"served {stats['replicas_done']} replicas "
          f"({stats['useful_steps']} useful steps) in "
          f"{stats['wall_s']:.3f}s -> {stats['replicas_per_s']:.2f} "
          f"replicas/s; {stats['compiles']} compiles over shapes "
          f"{stats['shapes_touched']}; step latency "
          f"p50={stats['step_latency_p50_ms']:.3f}ms "
          f"p99={stats['step_latency_p99_ms']:.3f}ms")
    assert all(h.status == "done" for h in handles)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=12)
    ap.add_argument("--new-tokens", type=int, default=16)
    ap.add_argument("--max-len", type=int, default=64)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--md", action="store_true",
                    help="serve MD replicas (SimServer) instead of LM waves")
    ap.add_argument("--replicas", type=int, default=8)
    ap.add_argument("--atoms", type=int, default=200)
    ap.add_argument("--steps", type=int, default=40)
    ap.add_argument("--nstlist", type=int, default=10)
    ap.add_argument("--backend", default="dense",
                    choices=("dense", "sparse"))
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    if args.md:
        return main_md(args)
    if args.arch is None:
        ap.error("--arch is required unless --md")

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduce()
    mesh = make_mesh((1, 1), ("data", "model"))
    ctx = ShardingCtx(mesh=mesh, batch_axes=("data",))
    model = build_model(cfg, ctx)
    params = model.init(jax.random.PRNGKey(0))
    server = BatchServer(model, params, batch_size=args.batch,
                         max_len=args.max_len,
                         temperature=args.temperature)

    rng = np.random.RandomState(0)
    pending = [Request(prompt=rng.randint(0, cfg.vocab,
                                          size=(args.prompt_len,))
                       .astype(np.int32),
                       max_new_tokens=args.new_tokens)
               for _ in range(args.requests)]
    done = []
    wave = 0
    while pending:
        take, pending = pending[:args.batch], pending[args.batch:]
        out = server.serve_wave(take)
        stats = throughput_stats(out)
        print(f"wave {wave}: {len(take)} requests, "
              f"{stats['tokens']} tokens, {stats['tok_per_s']:.1f} tok/s")
        done.extend(out)
        wave += 1
    print(f"served {len(done)} requests; sample output: "
          f"{done[0].out_tokens.tolist()}")


if __name__ == "__main__":
    main()
