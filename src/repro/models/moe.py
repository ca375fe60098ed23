"""Mixture-of-Experts with expert parallelism over the model axis.

The paper's dependency-partitioning insight (pack & send independent data
immediately, wait only for what truly depends on earlier communication) is
applied to EP dispatch: tokens routed to experts resident on the local
model rank are computed IMMEDIATELY and never enter the all-to-all; only
remote tokens ride the collective.  XLA can then overlap the remote
all-to-all with the local expert FFN — the EP analogue of overlapping the
pulse-0 transfer with local force computation.

Dispatch paths:
  * ``dense``       — every expert on every token (reference oracle; also
                      the fallback when n_experts isn't divisible by TP)
  * ``serialized``  — all tokens through one all-to-all (MPI-flavored
                      baseline: local tokens also wait for the collective)
  * ``fused``       — local-first dependency-partitioned dispatch (ours)
Decode/small-batch uses a replicated-dispatch path (tokens replicated over
the model axis, experts local, outputs psum'd) — no all-to-all at all.

The EP region is a FULLY-MANUAL shard_map over every mesh axis (partial-
auto shard_map nested in scan+remat trips an XLA-CPU partitioner crash,
"Invalid binary instruction opcode copy").  Under FSDP the expert weights
are additionally tensor-parallel over the data axis (2-D expert sharding:
EP x expert-TP), so e.g. llama4's 400B of experts store at
params/(16*16) per device with no weight gathering — the hidden dim is
contracted locally and partial outputs psum over 'data'.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.configs.base import ArchConfig, MoECfg
from repro.models.layers import ParamDef, ParamDefs, mlp_defs, mlp_fwd
from repro.parallel.sharding import ShardingCtx


def moe_defs(cfg: ArchConfig) -> ParamDefs:
    m = cfg.moe
    d = cfg.d_model
    defs: ParamDefs = {
        "router": ParamDef((d, m.n_experts), "small_normal"),
        "w_gate": ParamDef((m.n_experts, d, m.d_expert), tp_dim=0),
        "w_up": ParamDef((m.n_experts, d, m.d_expert), tp_dim=0),
        "w_down": ParamDef((m.n_experts, m.d_expert, d), tp_dim=0),
    }
    if m.shared_expert:
        defs["shared"] = mlp_defs(d, m.d_expert, "swiglu", False)
    return defs


def expert_specs(cfg: ArchConfig, ctx: ShardingCtx):
    """PartitionSpecs for expert weights: EP over model (+TP over data)."""
    t = ctx.fsdp_axis  # 2-D expert sharding only when FSDP is on
    if cfg.moe.n_experts % ctx.tp != 0:
        return {"router": P(), "w_gate": P(), "w_up": P(), "w_down": P()}
    return {
        "router": P(),
        "w_gate": P(ctx.model_axis, None, t),
        "w_up": P(ctx.model_axis, None, t),
        "w_down": P(ctx.model_axis, t, None),
    }


def stacked_expert_specs(cfg: ArchConfig, ctx: ShardingCtx):
    """expert_specs with the layer-stack dim prepended (scan-stacked)."""
    return {k: P(*((None,) + tuple(v)))
            for k, v in expert_specs(cfg, ctx).items()}


def _route(x2d, router_w, m: MoECfg):
    """Top-k routing (select-then-softmax) + aux losses, in f32."""
    logits = (x2d.astype(jnp.float32) @ router_w.astype(jnp.float32))
    gates_full = jax.nn.softmax(logits, axis=-1)
    top_g, top_e = lax.top_k(logits, m.top_k)
    top_g = jax.nn.softmax(top_g, axis=-1)
    # switch-style load-balance loss + router z-loss
    T = x2d.shape[0]
    density = jnp.mean(gates_full, axis=0)
    counts = jnp.zeros((m.n_experts,), jnp.float32).at[top_e.reshape(-1)] \
        .add(1.0, mode="drop") / (T * m.top_k)
    lb_loss = m.n_experts * jnp.sum(density * counts)
    z_loss = jnp.mean(jax.scipy.special.logsumexp(logits, axis=-1) ** 2)
    return top_e, top_g, {"moe_lb": lb_loss, "moe_z": z_loss}


def _expert_ffn(wg, wu, wd, xe, mlp_type: str, tp_axis: Optional[str]):
    """Batched expert MLP: xe (E_loc, C', d) -> (E_loc, C', d).

    With ``tp_axis`` the hidden dim of wg/wu (and the contraction dim of
    wd) is sharded over that axis; partial outputs are psum'd.
    """
    if mlp_type == "swiglu":
        h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xe, wg)) * \
            jnp.einsum("ecd,edf->ecf", xe, wu)
    else:
        h = jax.nn.gelu(jnp.einsum("ecd,edf->ecf", xe, wu))
    y = jnp.einsum("ecf,efd->ecd", h, wd)
    if tp_axis is not None:
        y = lax.psum(y, tp_axis)
    return y


def _dispatch_tables(top_e, top_g, n_experts: int, capacity: int):
    """Sort-based dispatch: slot assignment with capacity dropping."""
    T, K = top_e.shape
    flat_e = top_e.reshape(-1)
    order = jnp.argsort(flat_e, stable=True)
    sorted_e = flat_e[order]
    first = jnp.searchsorted(sorted_e, sorted_e, side="left")
    rank_sorted = jnp.arange(T * K) - first
    rank = jnp.zeros((T * K,), jnp.int32).at[order] \
        .set(rank_sorted.astype(jnp.int32))
    keep = rank < capacity
    slot = jnp.where(keep, flat_e * capacity + rank, n_experts * capacity)
    return slot, keep


def _scatter_tokens(x2d, slot, keep, n_experts, capacity, K):
    T, d = x2d.shape
    buf = jnp.zeros((n_experts * capacity + 1, d), x2d.dtype)
    src = jnp.repeat(x2d, K, axis=0)
    slot = jnp.minimum(slot, n_experts * capacity)
    buf = buf.at[slot].add(jnp.where(keep[:, None], src, 0.0),
                           mode="drop")
    return buf[:-1].reshape(n_experts, capacity, d)


def _gather_outputs(out_buf, slot, keep, gates, T, K):
    d = out_buf.shape[-1]
    flat = jnp.concatenate(
        [out_buf.reshape(-1, d), jnp.zeros((1, d), out_buf.dtype)])
    per_assign = flat[jnp.minimum(slot, flat.shape[0] - 1)]
    per_assign = per_assign * (keep * gates.reshape(-1)).astype(
        per_assign.dtype)[:, None]
    return per_assign.reshape(T, K, d).sum(axis=1)


def moe_fwd(p, x, cfg: ArchConfig, ctx: ShardingCtx,
            dispatch: str = "fused"):
    """MoE FFN layer.  x: (B, L, d).  Returns (out, aux_losses)."""
    m = cfg.moe
    B, L, d = x.shape
    tp = ctx.tp

    if m.n_experts % tp != 0 and dispatch != "dense":
        # experts not shardable over TP (tiny smoke configs): dense oracle
        dispatch = "dense"

    if dispatch == "dense":
        x2d = x.reshape(-1, d)
        top_e, top_g, aux = _route(x2d, p["router"], m)
        outs = jnp.zeros_like(x2d)
        for e in range(m.n_experts):          # reference oracle (tiny cfgs)
            pe = {k: p[k][e] for k in ("w_gate", "w_up", "w_down")}
            if cfg.mlp_type == "swiglu":
                h = jax.nn.silu(x2d @ pe["w_gate"]) * (x2d @ pe["w_up"])
            else:
                h = jax.nn.gelu(x2d @ pe["w_up"])
            oe = h @ pe["w_down"]
            w = jnp.sum(jnp.where(top_e == e, top_g, 0.0),
                        axis=-1).astype(oe.dtype)
            outs = outs + oe * w[:, None]
        out = outs.reshape(B, L, d)
    else:
        tokens_per_rank = (B * L * max(ctx.dp, 1)) // max(ctx.dp, 1) // tp
        b_loc = B // max(ctx.dp, 1)
        if (b_loc * L) % tp == 0 and (b_loc * L) // tp >= 1 and L > 1:
            out, aux = _moe_manual(p, x, cfg, ctx, dispatch, ep=True)
        else:
            out, aux = _moe_manual(p, x, cfg, ctx, dispatch, ep=False)

    if m.shared_expert:
        out = out + mlp_fwd(p["shared"], x, "swiglu")
    return out, aux


def _moe_manual(p, x, cfg: ArchConfig, ctx: ShardingCtx, dispatch: str,
                ep: bool):
    """Fully-manual shard_map EP dispatch (all mesh axes manual)."""
    m = cfg.moe
    B, L, d = x.shape
    tp = ctx.tp
    e_loc = m.n_experts // tp
    exp_tp = ctx.fsdp_axis        # 2-D expert sharding axis (or None)
    bspec = ctx.batch_spec()
    model = ctx.model_axis
    all_axes = tuple(ctx.mesh.axis_names)

    def body(x_loc, router, wg, wu, wd):
        my = lax.axis_index(model)
        x2d = x_loc.reshape(-1, d)
        Ttot = x2d.shape[0]

        if ep:
            T = Ttot // tp
            x_my = lax.dynamic_slice_in_dim(x2d, my * T, T, axis=0)
            top_e, top_g, aux = _route(x_my, router, m)
            cap = _capacity(T, m, m.n_experts)
            slot, keep = _dispatch_tables(top_e, top_g, m.n_experts, cap)
            buf = _scatter_tokens(x_my, slot, keep, m.n_experts, cap,
                                  m.top_k)

            if dispatch == "fused":
                # paper technique: local-first dependency partitioning —
                # my experts' tokens never enter the all-to-all.
                e0 = my * e_loc
                local_buf = lax.dynamic_slice_in_dim(buf, e0, e_loc, 0)
                remote_buf = lax.dynamic_update_slice_in_dim(
                    buf, jnp.zeros_like(local_buf), e0, 0)
                shuf = _a2a_fwd(remote_buf, tp, e_loc, model)
                local_out = _expert_ffn(wg, wu, wd, local_buf,
                                        cfg.mlp_type, exp_tp)
                remote_out = _expert_ffn(wg, wu, wd, shuf,
                                         cfg.mlp_type, exp_tp)
                back = _a2a_bwd(remote_out, tp, e_loc, model)
                back = lax.dynamic_update_slice_in_dim(
                    back, local_out +
                    lax.dynamic_slice_in_dim(back, e0, e_loc, 0), e0, 0)
                out_buf = back
            else:
                shuf = _a2a_fwd(buf, tp, e_loc, model)
                eout = _expert_ffn(wg, wu, wd, shuf, cfg.mlp_type, exp_tp)
                out_buf = _a2a_bwd(eout, tp, e_loc, model)

            out_my = _gather_outputs(out_buf, slot, keep, top_g, T,
                                     m.top_k)
            out = jnp.zeros((Ttot, d), out_my.dtype)
            out = lax.dynamic_update_slice_in_dim(out, out_my, my * T, 0)
            out = lax.psum(out, model)
        else:
            # replicated dispatch (decode / tiny token counts): every model
            # rank routes all tokens, computes its local experts, psum.
            top_e, top_g, aux = _route(x2d, router, m)
            cap = _capacity(Ttot, m, m.n_experts)
            e0 = my * e_loc
            rel = top_e - e0
            mine = (rel >= 0) & (rel < e_loc)
            slot, keep = _dispatch_tables(
                jnp.where(mine, rel, e_loc), top_g, e_loc, cap)
            keep = keep & mine.reshape(-1)
            buf = _scatter_tokens(x2d, slot, keep, e_loc, cap, m.top_k)
            out_buf = _expert_ffn(wg, wu, wd, buf, cfg.mlp_type, exp_tp)
            out = _gather_outputs(out_buf, slot, keep, top_g, Ttot,
                                  m.top_k)
            out = lax.psum(out, model)

        aux = {k: lax.pmean(v, all_axes) for k, v in aux.items()}
        return out.reshape(x_loc.shape), aux

    es = expert_specs(cfg, ctx)
    fn = jax.shard_map(
        body, mesh=ctx.mesh,
        in_specs=(P(bspec), es["router"], es["w_gate"], es["w_up"],
                  es["w_down"]),
        out_specs=(P(bspec), P()),
        check_vma=False)
    return fn(x, p["router"], p["w_gate"], p["w_up"], p["w_down"])


def _a2a_fwd(buf, tp, e_loc, axis):
    """(E, C, d) on every rank -> (E_loc, tp*C, d) on the expert's owner."""
    E, C, d = buf.shape
    b = buf.reshape(tp, e_loc, C, d)
    shuf = lax.all_to_all(b, axis, split_axis=0, concat_axis=0, tiled=False)
    return jnp.moveaxis(shuf, 0, 1).reshape(e_loc, tp * C, d)


def _a2a_bwd(out, tp, e_loc, axis):
    """(E_loc, tp*C, d) -> (E, C, d) back on the token's source rank."""
    e_loc_, TC, d = out.shape
    C = TC // tp
    b = jnp.moveaxis(out.reshape(e_loc_, tp, C, d), 1, 0)
    shuf = lax.all_to_all(b, axis, split_axis=0, concat_axis=0, tiled=False)
    return shuf.reshape(tp * e_loc_, C, d)


def _capacity(tokens: int, m: MoECfg, n_experts: int) -> int:
    c = int(tokens * m.top_k * m.capacity_factor / n_experts) + 1
    return max(4, ((c + 3) // 4) * 4)
