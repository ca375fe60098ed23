"""Step builders: jitted train / prefill / decode programs per (arch, shape).

This is the glue the dry-run, trainer and server all share:
  * ShardingCtx construction per shape (DP/FSDP/TP/CP axes),
  * input_specs() — ShapeDtypeStruct stand-ins for every model input,
  * make_train_step / make_prefill_step / make_decode_step with
    in/out shardings and donation wired for memory fit.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ArchConfig, ShapeCfg
from repro.models.registry import build_model
from repro.optim import adamw
from repro.optim.compression import compressed_pod_mean, ef_init
from repro.parallel.sharding import ShardingCtx

# FSDP when bf16 weights / TP-shard would exceed this per device
FSDP_BYTES_THRESHOLD = 2e9


def param_count(cfg: ArchConfig) -> int:
    """Analytic parameter count (exact for our param defs)."""
    from repro.models.layers import _flatten  # noqa
    model = build_model(cfg, _dummy_ctx())
    flat = _flatten(model.defs)
    return sum(int(np.prod(d.shape)) for d in flat.values())


def active_param_count(cfg: ArchConfig) -> int:
    """Per-token active params (MoE: top_k of n_experts per MoE layer)."""
    total = param_count(cfg)
    if cfg.moe is None:
        return total
    m = cfg.moe
    moe_layers = sum(1 for s in cfg.pattern_unit if s.moe) * cfg.n_units
    per_expert = 3 * cfg.d_model * m.d_expert
    inactive = moe_layers * per_expert * (m.n_experts - m.top_k)
    return total - inactive


def _dummy_ctx() -> ShardingCtx:
    from repro.launch.mesh import make_mesh
    return ShardingCtx(mesh=make_mesh((1, 1), ("data", "model")),
                       batch_axes=("data",))


def make_ctx(cfg: ArchConfig, shape: ShapeCfg, mesh: Mesh,
             fsdp: Optional[bool] = None) -> ShardingCtx:
    """Sharding context for one (arch, shape, mesh) cell."""
    axes = list(mesh.axis_names)
    batch_axes = tuple(a for a in axes if a in ("pod", "data"))
    dp = math.prod(mesh.shape[a] for a in batch_axes)
    seq_axes: Tuple[str, ...] = ()
    if shape.global_batch % dp != 0 or shape.global_batch < dp:
        # batch can't cover DP (long_500k B=1): context-shard the sequence
        batch_axes = ()
        seq_axes = tuple(a for a in axes if a in ("pod", "data"))
    if fsdp is None:
        n = param_count(cfg)
        fsdp = (2 * n / mesh.shape["model"]) > FSDP_BYTES_THRESHOLD
    fsdp_axis = "data" if (fsdp and "data" in axes) else None
    return ShardingCtx(mesh=mesh, batch_axes=batch_axes,
                       fsdp_axis=fsdp_axis, seq_axes=seq_axes)


def auto_microbatches(cfg: ArchConfig, shape: ShapeCfg, ctx: ShardingCtx,
                      budget_bytes: float = 4e9) -> int:
    """Grad-accumulation factor so saved layer inputs fit the budget."""
    dp = max(ctx.dp, 1)
    b_loc = max(shape.global_batch // dp, 1)
    per_mb = b_loc * shape.seq_len * cfg.d_model * 2 * cfg.n_layers
    mb = 1
    while per_mb / mb > budget_bytes and mb < b_loc:
        mb *= 2
    while b_loc % mb:
        mb //= 2
    return max(mb, 1)


# --------------------------------------------------------------------------
# input specs (ShapeDtypeStruct stand-ins; no allocation)
# --------------------------------------------------------------------------

def batch_shapes(cfg: ArchConfig, shape: ShapeCfg) -> Dict[str, Any]:
    B, L = shape.global_batch, shape.seq_len
    if shape.kind == "train":
        text = L
        out = {}
        if cfg.prefix_tokens:
            text = L - cfg.prefix_tokens
            out["prefix_embeds"] = jax.ShapeDtypeStruct(
                (B, cfg.prefix_tokens, cfg.d_model), jnp.bfloat16)
        if cfg.is_encdec:
            out["frames"] = jax.ShapeDtypeStruct(
                (B, cfg.encoder_seq, cfg.d_model), jnp.bfloat16)
        out["tokens"] = jax.ShapeDtypeStruct((B, text + 1), jnp.int32)
        return out
    if shape.kind == "prefill":
        text = L
        out = {}
        if cfg.prefix_tokens:
            text = L - cfg.prefix_tokens
            out["prefix_embeds"] = jax.ShapeDtypeStruct(
                (B, cfg.prefix_tokens, cfg.d_model), jnp.bfloat16)
        if cfg.is_encdec:
            out["frames"] = jax.ShapeDtypeStruct(
                (B, cfg.encoder_seq, cfg.d_model), jnp.bfloat16)
        out["tokens"] = jax.ShapeDtypeStruct((B, text), jnp.int32)
        return out
    # decode: one token + cache index
    return {"tokens": jax.ShapeDtypeStruct((B, 1), jnp.int32),
            "pos": jax.ShapeDtypeStruct((), jnp.int32)}


def batch_specs(cfg: ArchConfig, shape: ShapeCfg, ctx: ShardingCtx):
    b = ctx.batch_spec()
    shapes = batch_shapes(cfg, shape)
    specs = {}
    for k, v in shapes.items():
        if k == "pos":
            specs[k] = P()
        elif k == "tokens":
            specs[k] = P(b, None)
        else:
            specs[k] = P(b, None, None)
    return shapes, specs


def input_specs(cfg: ArchConfig, shape: ShapeCfg, ctx: ShardingCtx):
    """All abstract inputs for the cell's step program, with shardings."""
    shapes, specs = batch_specs(cfg, shape, ctx)
    model = build_model(cfg, ctx)
    out = {"batch": (shapes, specs)}
    if shape.kind == "decode":
        cache = model.cache_shapes(shape.global_batch, shape.seq_len)
        out["cache"] = (cache, model.cache_specs())
    return out


# --------------------------------------------------------------------------
# step builders
# --------------------------------------------------------------------------

def _shardings(ctx: ShardingCtx, spec_tree):
    return jax.tree.map(lambda s: NamedSharding(ctx.mesh, s), spec_tree,
                        is_leaf=lambda x: isinstance(x, P))


@dataclasses.dataclass
class TrainProgram:
    step_fn: Any            # jitted (params, opt, batch) -> (params, opt, metrics)
    model: Any
    ctx: ShardingCtx
    param_shardings: Any
    opt_shardings: Any
    batch_shardings: Any
    abstract_params: Any
    abstract_opt: Any
    microbatches: int


def make_train_step(cfg: ArchConfig, shape: ShapeCfg, ctx: ShardingCtx,
                    ocfg: Optional[adamw.AdamWConfig] = None,
                    microbatches: Optional[int] = None,
                    pod_compress: Optional[str] = None,
                    moe_dispatch: str = "fused",
                    zero2: bool = False,
                    donate: bool = True) -> TrainProgram:
    """``zero2``: constrain gradients to the ZeRO-sharded layout before the
    optimizer, turning the data-axis gradient all-reduce into a
    reduce-scatter (each device only reduces the shard its optimizer
    states own); GSPMD all-gathers the updated params afterwards in bf16.
    """
    ocfg = ocfg or adamw.AdamWConfig()
    has_pod_pre = "pod" in ctx.mesh.axis_names and pod_compress is not None
    if has_pod_pre:
        # the grad computation runs inside a shard_map MANUAL over 'pod';
        # activation constraints inside must not name the manual axis
        ctx = dataclasses.replace(
            ctx, batch_axes=tuple(a for a in ctx.batch_axes
                                  if a != "pod"))
    model = build_model(cfg, ctx, moe_dispatch=moe_dispatch)
    mb = microbatches or auto_microbatches(cfg, shape, ctx)
    b_shapes, b_specs = batch_specs(cfg, shape, ctx)

    has_pod = "pod" in ctx.mesh.axis_names and pod_compress is not None

    grad_specs = None
    if zero2:
        grad_specs = adamw.zero1_specs(model.specs(), model.abstract(),
                                       ctx)["m"]

    def shard_grads(g):
        if grad_specs is None:
            return g
        return jax.tree.map(
            lambda x, sp: lax.with_sharding_constraint(
                x, NamedSharding(ctx.mesh, sp)),
            g, grad_specs, is_leaf=lambda x: not isinstance(x, dict))

    def grads_of(params, batch):
        def loss(p, b):
            l, m = model.loss_fn(p, b)
            return l, m
        if mb == 1:
            (l, m), g = jax.value_and_grad(loss, has_aux=True)(params, batch)
            return g, l, m
        split = jax.tree.map(
            lambda x: x.reshape((mb, x.shape[0] // mb) + x.shape[1:]), batch)

        def body(carry, mb_batch):
            gacc, lacc = carry
            (l, m), g = jax.value_and_grad(loss, has_aux=True)(params,
                                                               mb_batch)
            gacc = jax.tree.map(lambda a, b: a + b.astype(a.dtype), gacc, g)
            # under zero2 the f32 accumulator stays ZeRO-sharded: each
            # microbatch's grads reduce-scatter into it instead of living
            # replicated (accumulator bytes /dp)
            gacc = shard_grads(gacc)
            return (gacc, lacc + l), m

        g0 = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
        g0 = shard_grads(g0)
        (g, lsum), ms = lax.scan(body, (g0, jnp.zeros((), jnp.float32)),
                                 split)
        g = jax.tree.map(lambda x: x / mb, g)
        m = jax.tree.map(lambda x: x[-1], ms)
        return g, lsum / mb, m

    if has_pod:
        # manual over the pod axis: per-pod grads -> compressed DCN
        # reduction with error feedback (see optim/compression.py)
        def train_step(params, opt_state, ef, batch):
            def pod_body(params, batch, ef):
                g, l, m = grads_of(params, batch)
                g, ef = compressed_pod_mean(g, ef, pod_compress)
                l = lax.pmean(l, "pod")
                return g, ef, l, m
            g, ef, l, m = jax.shard_map(
                pod_body, mesh=ctx.mesh,
                in_specs=(P(), P("pod"), P()),
                out_specs=(P(), P(), P(), P()),
                axis_names={"pod"}, check_vma=False)(params, batch, ef)
            g = shard_grads(g)
            params, opt_state, om = adamw.update(ocfg, params, g, opt_state)
            m = dict(m, loss=l, **om)
            return params, opt_state, ef, m
    else:
        def train_step(params, opt_state, batch):
            g, l, m = grads_of(params, batch)
            g = shard_grads(g)
            params, opt_state, om = adamw.update(ocfg, params, g, opt_state)
            m = dict(m, loss=l, **om)
            return params, opt_state, m

    p_specs = model.specs()
    p_shard = _shardings(ctx, p_specs)
    o_specs = adamw.zero1_specs(p_specs, model.abstract(), ctx)
    o_shard = _shardings(ctx, o_specs)
    b_shard = _shardings(ctx, b_specs)
    in_sh = (p_shard, o_shard) + ((p_shard,) if has_pod else ()) + (b_shard,)
    out_sh = (p_shard, o_shard) + ((p_shard,) if has_pod else ()) + \
        (NamedSharding(ctx.mesh, P()),)
    donate_n = (0, 1, 2) if has_pod else (0, 1)
    fn = jax.jit(train_step, in_shardings=in_sh, out_shardings=out_sh,
                 donate_argnums=donate_n if donate else ())
    return TrainProgram(step_fn=fn, model=model, ctx=ctx,
                        param_shardings=p_shard, opt_shardings=o_shard,
                        batch_shardings=b_shard,
                        abstract_params=model.abstract(),
                        abstract_opt=adamw.abstract_state(model.abstract()),
                        microbatches=mb)


def make_prefill_step(cfg: ArchConfig, shape: ShapeCfg, ctx: ShardingCtx,
                      moe_dispatch: str = "fused"):
    # inference serves bf16 weights: FSDP gathers then move bf16, not f32
    cfg = dataclasses.replace(cfg, param_dtype="bfloat16")
    model = build_model(cfg, ctx, moe_dispatch=moe_dispatch)
    b_shapes, b_specs = batch_specs(cfg, shape, ctx)

    def prefill(params, batch):
        logits, _ = model.prefill(params, batch)
        return logits

    p_shard = _shardings(ctx, model.specs())
    b_shard = _shardings(ctx, b_specs)
    fn = jax.jit(prefill, in_shardings=(p_shard, b_shard),
                 out_shardings=NamedSharding(
                     ctx.mesh, P(ctx.batch_spec(), None)))
    return fn, model, (p_shard, b_shard)


def make_decode_step(cfg: ArchConfig, shape: ShapeCfg, ctx: ShardingCtx,
                     moe_dispatch: str = "fused", donate: bool = True):
    # inference serves bf16 weights: FSDP gathers then move bf16, not f32
    cfg = dataclasses.replace(cfg, param_dtype="bfloat16")
    model = build_model(cfg, ctx, moe_dispatch=moe_dispatch)
    b = ctx.batch_spec()

    def decode(params, token, pos, cache):
        logits, new_cache = model.decode_step(params, token, pos, cache)
        return logits, new_cache

    p_shard = _shardings(ctx, model.specs())
    c_shard = _shardings(ctx, model.cache_specs())
    tok_shard = NamedSharding(ctx.mesh, P(b, None))
    pos_shard = NamedSharding(ctx.mesh, P())
    fn = jax.jit(
        decode,
        in_shardings=(p_shard, tok_shard, pos_shard, c_shard),
        out_shardings=(NamedSharding(ctx.mesh, P(b, None)), c_shard),
        donate_argnums=(3,) if donate else ())
    return fn, model, (p_shard, tok_shard, pos_shard, c_shard)
