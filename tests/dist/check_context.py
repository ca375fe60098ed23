"""8-virtual-device check: ring attention schedules + distributed decode.

The LM-side instance of the halo problem (parallel/context.py): the
serialized and fused KV-pulse schedules must agree with each other and
with single-device full attention; distributed decode over a seq-sharded
cache must match the full-cache reference.

  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      PYTHONPATH=src python tests/dist/check_context.py
"""
import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.launch.mesh import make_mesh
from repro.parallel.context import (
    distributed_decode,
    ring_attention_sharded,
)


def full_attention_reference(q, k, v, causal=True):
    B, L, H, hd = q.shape
    logits = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                        k.astype(jnp.float32)) * hd ** -0.5
    if causal:
        mask = jnp.arange(L)[:, None] >= jnp.arange(L)[None, :]
        logits = jnp.where(mask[None, None], logits, -1e30)
    p = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32))
    return out.astype(v.dtype)


def main():
    assert len(jax.devices()) >= 8, "need 8 virtual devices"
    mesh = make_mesh((8,), ("seq",))
    rng = np.random.RandomState(0)
    B, L, H, hd = 2, 64, 4, 16
    q = jnp.asarray(rng.randn(B, L, H, hd).astype(np.float32) * 0.3)
    k = jnp.asarray(rng.randn(B, L, H, hd).astype(np.float32) * 0.3)
    v = jnp.asarray(rng.randn(B, L, H, hd).astype(np.float32))

    ref = np.asarray(full_attention_reference(q, k, v))
    outs = {}
    for mode in ("serialized", "fused"):
        out = np.asarray(ring_attention_sharded(q, k, v, mesh, "seq",
                                                mode=mode))
        err = np.abs(out - ref).max() / np.abs(ref).max()
        assert err < 1e-5, (mode, err)
        outs[mode] = out
        print(f"ring_attention[{mode}]: rel err vs full attention "
              f"{err:.2e}")
    # the two schedules compute identical online-softmax merges
    assert np.array_equal(outs["serialized"], outs["fused"]), \
        "fused and serialized ring schedules disagree"
    print("fused == serialized bitwise")

    # ---- distributed decode over the seq-sharded cache -----------------
    cache_len = jnp.asarray([L, L // 2])
    q1 = jnp.asarray(rng.randn(B, 1, H, hd).astype(np.float32) * 0.3)
    S_loc = L // 8

    def decode_local(q1, k_shard, v_shard, cache_len):
        off = jax.lax.axis_index("seq") * S_loc
        return distributed_decode(q1, k_shard, v_shard, cache_len, "seq",
                                  off)

    fn = jax.shard_map(functools.partial(decode_local), mesh=mesh,
                   in_specs=(P(), P(None, "seq"), P(None, "seq"), P()),
                   out_specs=P(), check_vma=False)
    got = np.asarray(fn(q1, k, v, cache_len))

    # reference: full attention of the single token over the valid cache
    logits = jnp.einsum("bqhd,bkhd->bhqk", q1.astype(jnp.float32),
                        k.astype(jnp.float32)) * hd ** -0.5
    valid = jnp.arange(L)[None] < cache_len[:, None]
    logits = jnp.where(valid[:, None, None], logits, -1e30)
    p = jax.nn.softmax(logits, axis=-1)
    ref1 = np.asarray(jnp.einsum("bhqk,bkhd->bqhd", p,
                                 v.astype(jnp.float32)))
    err = np.abs(got - ref1).max() / np.abs(ref1).max()
    assert err < 1e-5, err
    print(f"distributed_decode: rel err {err:.2e}")

    print("check_context OK")


if __name__ == "__main__":
    main()
