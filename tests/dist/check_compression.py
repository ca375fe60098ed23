"""8-virtual-device check: error-feedback compressed pod reductions.

The cross-pod (DCN) analogue of the paper's transport adaptivity
(optim/compression.py): int8 and top-k reductions with error feedback
must converge to the uncompressed mean over steps, and with mode=None
``compressed_pod_mean`` must equal the plain pmean exactly.

  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      PYTHONPATH=src python tests/dist/check_compression.py
"""
import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.launch.mesh import make_mesh
from repro.optim.compression import compressed_pod_mean, ef_init


def main():
    assert len(jax.devices()) >= 8, "need 8 virtual devices"
    mesh = make_mesh((8,), ("pod",))
    rng = np.random.RandomState(0)
    # per-pod gradients: shared signal + pod-dependent noise
    base = rng.randn(64, 8).astype(np.float32)
    noise = rng.randn(8, 64, 8).astype(np.float32) * 0.1
    gstack = jnp.asarray(base[None] + noise)            # (pods, ...)
    g_true = np.asarray(jnp.mean(gstack, axis=0))

    def reduce_step(g, e, mode):
        params = {"w": g}
        ef = {"w": e}
        out, ef = compressed_pod_mean(params, ef, mode, axis="pod",
                                      topk_frac=0.25)
        return out["w"], ef["w"]

    for mode in (None, "int8", "topk"):
        fn = jax.shard_map(functools.partial(reduce_step, mode=mode),
                       mesh=mesh, in_specs=(P("pod"), P("pod")),
                       out_specs=(P("pod"), P("pod")), check_vma=False)
        gshard = gstack.reshape(8 * 64, 8)
        e = jnp.asarray(ef_init({"w": np.zeros((64, 8), np.float32)})["w"])
        eshard = jnp.tile(e, (8, 1))

        if mode is None:
            out, _ = fn(gshard, eshard)
            out = np.asarray(out).reshape(8, 64, 8)
            for p in range(8):
                assert np.allclose(out[p], g_true, atol=1e-6)
            print("mode=None: matches plain pmean")
            continue

        # EF accumulation over repeated steps of the same gradient: the
        # compressed running sum must converge to the true mean
        acc = np.zeros_like(g_true)
        eshard_cur = eshard
        steps = 50
        for _ in range(steps):
            out, enew = fn(gshard, eshard_cur)
            acc += np.asarray(out).reshape(8, 64, 8)[0]
            eshard_cur = enew
        rel = np.abs(acc / steps - g_true).max() / np.abs(g_true).max()
        assert rel < 0.05, (mode, rel)
        print(f"mode={mode}: EF-compressed mean rel err {rel:.3f} "
              f"after {steps} steps")

    print("check_compression OK")


if __name__ == "__main__":
    main()
