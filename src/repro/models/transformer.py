"""Generic layer / period / model assembly.

A *layer* = pre-norm mixer (+ residual) then optional pre-norm FFN
(+ residual).  A *period* is the arch's repeating heterogeneous block list
(configs.base.ArchConfig.period); the model is a scan over period instances.
The pipeline runtime reuses ``period_forward`` / ``period_decode`` as its
per-stage unit, so single-device and pipelined execution share all math.
"""
from __future__ import annotations

from typing import Any, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import (
    ArchConfig,
    LayerSpec,
    ATTN,
    MAMBA,
    MLSTM,
    SLSTM,
    DENSE_FF,
    MOE_FF,
    NO_FF,
    GLOBAL_WINDOW,
)
from repro.models import attention, mamba, mlp, moe, xlstm
from repro.models.common import ParallelCtx, LOCAL_CTX, block_norm, init_norm
import dataclasses as _dc


def _repl_ctx(ctx: ParallelCtx) -> ParallelCtx:
    """xLSTM mixers run TP-replicated (core.sharding.xlstm_pspecs): their
    outputs are already complete per lane, so the row-parallel psum hook must
    be identity for them."""
    if ctx.tp_size == 1:
        return ctx
    return _dc.replace(ctx, psum_tp=lambda x: x)


# ------------------------------------------------------------------ parameters
def init_layer_params(key, cfg: ArchConfig, spec: LayerSpec, dtype,
                      n_experts_local: Optional[int] = None) -> dict:
    k1, k2 = jax.random.split(key)
    p: dict = {"norm1": init_norm(cfg.d_model, dtype)}
    if spec.mixer == ATTN:
        p["mixer"] = attention.init_attn_params(k1, cfg, dtype)
    elif spec.mixer == MAMBA:
        p["mixer"] = mamba.init_mamba_params(k1, cfg, dtype)
    elif spec.mixer == MLSTM:
        p["mixer"] = xlstm.init_mlstm_params(k1, cfg, dtype)
    elif spec.mixer == SLSTM:
        p["mixer"] = xlstm.init_slstm_params(k1, cfg, dtype,
                                             inner_ff=spec.ff == NO_FF)
    if spec.ff != NO_FF:
        p["norm2"] = init_norm(cfg.d_model, dtype)
        if spec.ff == DENSE_FF:
            p["ff"] = mlp.init_mlp_params(k2, cfg, dtype)
        else:
            p["ff"] = moe.init_moe_params(k2, cfg, dtype, n_experts_local)
    return p


# --------------------------------------------------------------------- forward
def layer_forward(
    p: dict,
    x: jax.Array,
    active,
    *,
    cfg: ArchConfig,
    spec: LayerSpec,
    positions: jax.Array,
    ctx: ParallelCtx = LOCAL_CTX,
    use_pallas: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """One layer.  ``active`` (bool scalar) masks padding layers to identity.
    Returns (x, aux_loss)."""
    h = block_norm(x, p["norm1"], cfg)
    if spec.mixer == ATTN:
        mix = attention.attn_forward(
            p["mixer"], h, cfg=cfg, spec=spec, positions=positions, ctx=ctx,
            use_pallas=use_pallas,
        )
    elif spec.mixer == MAMBA:
        mix = mamba.mamba_forward(p["mixer"], h, cfg=cfg, ctx=ctx)
    elif spec.mixer == MLSTM:
        mix = xlstm.mlstm_forward(p["mixer"], h, cfg=cfg, ctx=_repl_ctx(ctx))
    elif spec.mixer == SLSTM:
        mix = xlstm.slstm_forward(p["mixer"], h, cfg=cfg, ctx=_repl_ctx(ctx))
    else:  # pragma: no cover
        raise ValueError(spec.mixer)
    gate = jnp.asarray(active, x.dtype)
    x = x + gate * mix
    aux = jnp.zeros((), jnp.float32)
    if spec.ff != NO_FF:
        h = block_norm(x, p["norm2"], cfg)
        if spec.ff == DENSE_FF:
            ff = mlp.mlp_forward(p["ff"], h, ctx=ctx, use_pallas=use_pallas,
                                 act=cfg.ff_act)
        else:
            ff, aux = moe.moe_forward(p["ff"], h, cfg=cfg, ctx=ctx)
            aux = aux * jnp.asarray(active, jnp.float32)
        x = x + gate * ff
    return x, aux


def period_forward(
    period_params,      # tuple over period positions, leaves for ONE instance
    x: jax.Array,
    active,             # bool [period_len]
    *,
    cfg: ArchConfig,
    positions: jax.Array,
    ctx: ParallelCtx = LOCAL_CTX,
    use_pallas: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    aux = jnp.zeros((), jnp.float32)
    for j, spec in enumerate(cfg.period):
        x, a = layer_forward(
            period_params[j], x, active[j],
            cfg=cfg, spec=spec, positions=positions, ctx=ctx, use_pallas=use_pallas,
        )
        aux = aux + a
    return x, aux


# ---------------------------------------------------------------------- decode
def layer_decode(p, x, cache, active, *, cfg, spec, ctx=LOCAL_CTX,
                 use_pallas=False):
    h = block_norm(x, p["norm1"], cfg)
    if spec.mixer == ATTN:
        mix, new_cache = attention.attn_decode(
            p["mixer"], h, cache, cfg=cfg, spec=spec, ctx=ctx,
            use_pallas=use_pallas,
        )
    elif spec.mixer == MAMBA:
        mix, new_cache = mamba.mamba_decode(p["mixer"], h, cache, cfg=cfg, ctx=ctx)
    elif spec.mixer == MLSTM:
        mix, new_cache = xlstm.mlstm_decode(p["mixer"], h, cache, cfg=cfg, ctx=_repl_ctx(ctx))
    elif spec.mixer == SLSTM:
        mix, new_cache = xlstm.slstm_decode(p["mixer"], h, cache, cfg=cfg, ctx=_repl_ctx(ctx))
    else:  # pragma: no cover
        raise ValueError(spec.mixer)
    gate = jnp.asarray(active, x.dtype)
    x = x + gate * mix
    new_cache = jax.tree.map(
        lambda new, old: jnp.where(active, new, old), new_cache, cache
    )
    if spec.ff != NO_FF:
        h = block_norm(x, p["norm2"], cfg)
        if spec.ff == DENSE_FF:
            ff = mlp.mlp_forward(p["ff"], h, ctx=ctx, act=cfg.ff_act)
        else:
            ff, _ = moe.moe_forward(p["ff"], h, cfg=cfg, ctx=ctx)
        x = x + gate * ff
    return x, new_cache


def period_decode(period_params, x, caches, active, *, cfg, ctx=LOCAL_CTX,
                  use_pallas=False):
    new_caches = []
    for j, spec in enumerate(cfg.period):
        x, c = layer_decode(
            period_params[j], x, caches[j], active[j], cfg=cfg, spec=spec,
            ctx=ctx, use_pallas=use_pallas,
        )
        new_caches.append(c)
    return x, tuple(new_caches)


def layer_prefill(p, x, active, *, cfg, spec, positions, ctx=LOCAL_CTX, capacity=None):
    """Forward + cache construction (serving prefill)."""
    h = block_norm(x, p["norm1"], cfg)
    if spec.mixer == ATTN:
        mix, cache = attention.attn_prefill(
            p["mixer"], h, cfg=cfg, spec=spec, positions=positions, ctx=ctx,
            capacity=capacity,
        )
    elif spec.mixer == MAMBA:
        mix, cache = mamba.mamba_forward(p["mixer"], h, cfg=cfg, ctx=ctx, return_state=True)
    elif spec.mixer == MLSTM:
        mix, cache = xlstm.mlstm_forward(p["mixer"], h, cfg=cfg, ctx=_repl_ctx(ctx), return_state=True)
    elif spec.mixer == SLSTM:
        mix, cache = xlstm.slstm_forward(p["mixer"], h, cfg=cfg, ctx=_repl_ctx(ctx), return_state=True)
    else:  # pragma: no cover
        raise ValueError(spec.mixer)
    gate = jnp.asarray(active, x.dtype)
    x = x + gate * mix
    if spec.ff != NO_FF:
        h = block_norm(x, p["norm2"], cfg)
        if spec.ff == DENSE_FF:
            ff = mlp.mlp_forward(p["ff"], h, ctx=ctx, act=cfg.ff_act)
        else:
            ff, _ = moe.moe_forward(p["ff"], h, cfg=cfg, ctx=ctx)
        x = x + gate * ff
    return x, cache


def period_prefill(period_params, x, active, *, cfg, positions, ctx=LOCAL_CTX, capacity=None):
    caches = []
    for j, spec in enumerate(cfg.period):
        x, c = layer_prefill(
            period_params[j], x, active[j], cfg=cfg, spec=spec, positions=positions,
            ctx=ctx, capacity=capacity,
        )
        caches.append(c)
    return x, tuple(caches)
