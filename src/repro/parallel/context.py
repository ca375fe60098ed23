"""Context parallelism: ring attention with fused (overlapped) KV pulses.

Long-context attention with the sequence sharded across a mesh axis is the
LM-side instance of the paper's halo problem: every query shard needs every
KV shard, and the KV blocks travel the ring exactly like DD pulses.

Two schedules, mirroring core/halo.py:

  * ``serialized`` — MPI-flavored: compute on the resident KV block, THEN
    rotate (an ``optimization_barrier`` forces the compute->comm ordering a
    host-driven schedule would impose).
  * ``fused``      — GPU/TPU-initiated flavor: the ppermute for step k+1 is
    issued concurrently with step k's attention compute (independent ops,
    XLA overlaps the collective-permute-start with the einsums) — the
    paper's pack/transmit/compute pipelining applied to KV pulses.

Both produce bitwise-comparable results (online-softmax merge), tested in
tests/dist/check_context.py.  Distributed decode (one query token against a
seq-sharded cache) degenerates to per-shard flash decode + a single psum
LSE merge — the 1-pulse case — used by the long_500k cells.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple


import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

NEG_INF = -1e30


def _block_attn(q, k, v, q_pos, k_pos, causal: bool):
    """Masked attention on one (q-shard, kv-block) pair; f32 partials.

    q: (B, Lq, H, hd); k/v: (B, Lk, H, hd).  Returns (o, m, l) partials
    for online-softmax merging.
    """
    hd = q.shape[-1]
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * hd ** -0.5
    if causal:
        mask = q_pos[:, None] >= k_pos[None, :]
        logits = jnp.where(mask[None, None], logits, NEG_INF)
    m = jnp.max(logits, axis=-1)                       # (B, H, Lq)
    p = jnp.exp(logits - m[..., None])
    l = jnp.sum(p, axis=-1)
    o = jnp.einsum("bhqk,bkhd->bhqd", p.astype(v.dtype), v,
                   preferred_element_type=jnp.float32)
    return o, m, l


def _merge(acc, new):
    o1, m1, l1 = acc
    o2, m2, l2 = new
    m = jnp.maximum(m1, m2)
    c1 = jnp.exp(m1 - m)
    c2 = jnp.exp(m2 - m)
    return (o1 * c1[..., None] + o2 * c2[..., None], m,
            l1 * c1 + l2 * c2)


def ring_attention(q, k, v, axis: str, ring: int, *, causal: bool = True,
                   mode: str = "fused"):
    """Sequence-sharded attention; call inside shard_map over ``axis``.

    q/k/v: (B, L_loc, H, hd) — this shard's slice of the sequence.
    Shard i holds positions [i*L_loc, (i+1)*L_loc).
    """
    B, L, H, hd = q.shape
    my = lax.axis_index(axis)
    qf = q.astype(jnp.float32)
    q_pos = my * L + jnp.arange(L)

    o0 = jnp.zeros((B, H, L, hd), jnp.float32)
    m0 = jnp.full((B, H, L), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, H, L), jnp.float32)
    perm = [(j, (j + 1) % ring) for j in range(ring)]

    acc = (o0, m0, l0)
    kv = (k, v)
    for step in range(ring):
        src = jnp.mod(my - step, ring)                 # owner of this block
        k_pos = src * L + jnp.arange(L)
        if mode == "fused" and step < ring - 1:
            # issue the next pulse BEFORE computing: the permute and the
            # einsums are independent, so XLA overlaps them (the paper's
            # fused pack+comm || compute)
            kv_next = jax.tree.map(
                lambda x: lax.ppermute(x, axis, perm), kv)
            part = _block_attn(qf, kv[0], kv[1], q_pos, k_pos, causal)
            acc = _merge(acc, part)
            kv = kv_next
        else:
            part = _block_attn(qf, kv[0], kv[1], q_pos, k_pos, causal)
            acc = _merge(acc, part)
            if step < ring - 1:
                # serialized: comm strictly AFTER compute, like a
                # host-driven schedule waiting on the kernel
                gate, _ = lax.optimization_barrier((part[1], kv))
                kv = jax.tree.map(
                    lambda x: lax.ppermute(x, axis, perm), kv)

    o, m, l = acc
    out = o / jnp.maximum(l, 1e-30)[..., None]
    return out.transpose(0, 2, 1, 3).astype(v.dtype)   # (B, L, H, hd)


def ring_attention_sharded(q, k, v, mesh: Mesh, axis: str, *,
                           causal: bool = True, mode: str = "fused"):
    """shard_map wrapper: q/k/v (B, L, H, hd) sharded on L over ``axis``."""
    ring = mesh.shape[axis]
    fn = jax.shard_map(
        functools.partial(ring_attention, axis=axis, ring=ring,
                          causal=causal, mode=mode),
        mesh=mesh,
        in_specs=(P(None, axis), P(None, axis), P(None, axis)),
        out_specs=P(None, axis),
        check_vma=False)
    return fn(q, k, v)


def distributed_decode(q, k_shard, v_shard, cache_len, axis: str,
                       shard_offset):
    """One-token decode over a seq-sharded cache: per-shard flash decode +
    LSE merge via psum — the degenerate single-pulse halo (call inside
    shard_map over ``axis``).

    q: (B, 1, H, hd) replicated; k/v_shard: (B, S_loc, HK, hd);
    shard_offset: this shard's global start position.
    """
    B, _, H, hd = q.shape
    S, HK = k_shard.shape[1], k_shard.shape[2]
    G = H // HK
    qf = (q.astype(jnp.float32).reshape(B, HK, G, hd) * hd ** -0.5) \
        .astype(k_shard.dtype)
    logits = jnp.einsum("bhgd,bkhd->bhgk", qf, k_shard,
                        preferred_element_type=jnp.float32)
    pos = shard_offset + jnp.arange(S)
    valid = pos[None] < jnp.reshape(cache_len, (-1, 1))
    logits = jnp.where(valid[:, None, None], logits, NEG_INF)
    m = jnp.max(logits, axis=-1)
    m_g = lax.pmax(m, axis)
    p = jnp.exp(logits - m_g[..., None])
    l = lax.psum(jnp.sum(p, axis=-1), axis)
    o = jnp.einsum("bhgk,bkhd->bhgd", p.astype(v_shard.dtype), v_shard,
                   preferred_element_type=jnp.float32)
    o = lax.psum(o, axis)
    out = o / jnp.maximum(l, 1e-30)[..., None]
    return out.reshape(B, 1, H, hd).astype(v_shard.dtype)
