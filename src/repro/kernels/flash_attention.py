"""Flash attention (GQA, causal) with explicit VMEM tiling.

Grid (batch*kv_head, q_blocks, kv_blocks); the kv dimension is the
innermost (sequential on TPU), so the online-softmax running max/denom/
accumulator persist in VMEM scratch across kv steps and the output block
is written once on the last kv step.  Q/K/V blocks stream HBM->VMEM via
BlockSpecs; block sizes default to MXU-aligned 128/256.

Causal blocks fully above the diagonal are skipped with ``pl.when``
(no compute; the fetch is already pipelined).  Matches the pure-jnp
``blocked_attention`` in models/attention.py; ref.py holds the oracle.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import interpret_mode

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_sc, l_sc, acc_sc,
                  *, bq: int, bk: int, causal: bool, scale: float):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _():
        m_sc[...] = jnp.full_like(m_sc, NEG_INF)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)

    if causal:
        # skip blocks fully above the diagonal (no overlap)
        run = qi * bq + bq - 1 >= ki * bk
    else:
        run = jnp.bool_(True)

    @pl.when(run)
    def _():
        q = q_ref[0].astype(jnp.float32) * scale       # (bq, G, hd)
        k = k_ref[0]                                   # (bk, hd)
        logits = jax.lax.dot_general(
            q.astype(k.dtype), k,
            (((2,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)        # (bq, G, bk)
        if causal:
            qpos = qi * bq + jax.lax.broadcasted_iota(
                jnp.int32, (bq, 1, bk), 0)
            kpos = ki * bk + jax.lax.broadcasted_iota(
                jnp.int32, (bq, 1, bk), 2)
            logits = jnp.where(qpos >= kpos, logits, NEG_INF)
        m_prev = m_sc[...]
        m_new = jnp.maximum(m_prev, jnp.max(logits, axis=-1))
        p = jnp.exp(logits - m_new[..., None])
        corr = jnp.exp(m_prev - m_new)
        l_sc[...] = l_sc[...] * corr + jnp.sum(p, axis=-1)
        m_sc[...] = m_new
        v = v_ref[0]                                   # (bk, hd)
        pv = jax.lax.dot_general(
            p.astype(v.dtype), v,
            (((2,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)        # (bq, G, hd)
        acc_sc[...] = acc_sc[...] * corr[..., None] + pv

    @pl.when(ki == nk - 1)
    def _():
        out = acc_sc[...] / jnp.maximum(l_sc[...], 1e-30)[..., None]
        o_ref[0] = out.astype(o_ref.dtype)


def flash_attention(q, k, v, *, causal: bool = True, bq: int = 128,
                    bk: int = 256, interpret: bool | None = None):
    """q: (BH, L, G, hd) grouped queries; k, v: (BH, S, hd).

    BH = batch * kv_heads (flattened); G = q heads per kv head.
    Returns (BH, L, G, hd).
    """
    BH, L, G, hd = q.shape
    S = k.shape[1]
    bq = min(bq, L)
    while L % bq:
        bq -= 1
    bk = min(bk, S)
    while S % bk:
        bk -= 1
    grid = (BH, L // bq, S // bk)
    scale = hd ** -0.5

    kern = functools.partial(_flash_kernel, bq=bq, bk=bk, causal=causal,
                             scale=scale)
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, G, hd), lambda b, i, j: (b, i, 0, 0)),
            pl.BlockSpec((1, bk, hd), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bk, hd), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, G, hd), lambda b, i, j: (b, i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, L, G, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, G), jnp.float32),
            pltpu.VMEM((bq, G), jnp.float32),
            pltpu.VMEM((bq, G, hd), jnp.float32),
        ],
        interpret=interpret_mode(interpret),
    )(q, k, v)
