"""Model construction and single-device entry points.

Parameter layout: ``params['layers']`` is a tuple over period positions; each
leaf is stacked over *period instances* on axis 0, so a scan over instances
runs the whole network.  ``active_mask(cfg)`` marks padding layers (truncated
final period, and the pipeline's stage padding) to identity.

The pipeline runtime (repro.core.pipeline) consumes the same layout, with the
instance axis re-chunked onto the mesh's model axis.
"""
from __future__ import annotations

from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import (
    ArchConfig,
    ATTN,
    MAMBA,
    MLSTM,
    SLSTM,
    GLOBAL_WINDOW,
)
from repro.models import attention, mamba, xlstm
from repro.models.common import (
    ParallelCtx,
    LOCAL_CTX,
    dense_init,
    block_norm,
    init_norm,
    softmax_cross_entropy,
    vocab_parallel_cross_entropy,
)
from repro.models.transformer import (
    init_layer_params,
    period_decode,
    period_forward,
    period_prefill,
)


def _dtype(cfg: ArchConfig):
    return jnp.dtype(cfg.param_dtype)


def active_mask(cfg: ArchConfig, n_instances: Optional[int] = None) -> np.ndarray:
    """bool [n_instances, period_len]: layer (p, j) is a real layer."""
    P = n_instances if n_instances is not None else cfg.n_periods
    idx = np.arange(P * cfg.period_len).reshape(P, cfg.period_len)
    return idx < cfg.n_layers


def init_params(
    cfg: ArchConfig,
    key,
    n_instances: Optional[int] = None,
    n_experts_local: Optional[int] = None,
) -> dict:
    """Stacked parameters.  ``n_instances`` >= cfg.n_periods adds pipeline
    padding instances (their weights exist but are masked to identity)."""
    dtype = _dtype(cfg)
    P = n_instances if n_instances is not None else cfg.n_periods
    k_embed, k_head, k_layers = jax.random.split(key, 3)

    def init_instance(k):
        ks = jax.random.split(k, cfg.period_len)
        return tuple(
            init_layer_params(ks[j], cfg, cfg.period[j], dtype, n_experts_local)
            for j in range(cfg.period_len)
        )

    layer_keys = jax.random.split(k_layers, P)
    stacked = jax.vmap(init_instance)(layer_keys)
    params = {
        "embed": dense_init(k_embed, (cfg.vocab_size, cfg.d_model), dtype),
        "final_norm": init_norm(cfg.d_model, dtype),
        "layers": stacked,
    }
    if not cfg.tie_embeddings:
        params["head"] = dense_init(k_head, (cfg.vocab_size, cfg.d_model), dtype)
    return params


def embed_inputs(cfg: ArchConfig, params, batch: dict) -> jax.Array:
    """Token/frame/VLM embedding -> [B, S, d]."""
    if cfg.frontend == "audio":
        h = batch["frames"].astype(_dtype(cfg))  # precomputed frame embeddings
    else:
        h = params["embed"][batch["tokens"]]
        if cfg.frontend == "vision":
            n_img = cfg.n_frontend_tokens
            img = batch["image_embeds"].astype(h.dtype)  # [B, n_img, d]
            h = jnp.concatenate([img, h[:, n_img:]], axis=1)
    return h


def _logits(cfg: ArchConfig, params, h: jax.Array) -> jax.Array:
    head = params["embed"] if cfg.tie_embeddings else params["head"]
    return h @ head.T


# ------------------------------------------------------------------- training
def forward(
    cfg: ArchConfig,
    params,
    batch: dict,
    *,
    ctx: ParallelCtx = LOCAL_CTX,
    use_pallas: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """Full forward -> (hidden [B,S,d], aux scalar)."""
    h = embed_inputs(cfg, params, batch)
    S = h.shape[1]
    positions = jnp.arange(S, dtype=jnp.int32)
    mask = jnp.asarray(active_mask(cfg))

    def body(x, scanned):
        period_params, act = scanned
        x, aux = period_forward(
            period_params, x, act, cfg=cfg, positions=positions, ctx=ctx,
            use_pallas=use_pallas,
        )
        return x, aux

    h, auxs = jax.lax.scan(body, h, (params["layers"], mask))
    h = block_norm(h, params["final_norm"], cfg)
    return h, jnp.sum(auxs)


def loss_fn(
    cfg: ArchConfig,
    params,
    batch: dict,
    *,
    ctx: ParallelCtx = LOCAL_CTX,
    use_pallas: bool = False,
) -> Tuple[jax.Array, dict]:
    """Mean next-token (decoder) or masked-prediction (encoder) CE loss."""
    h, aux = forward(cfg, params, batch, ctx=ctx, use_pallas=use_pallas)
    logits = _logits(cfg, params, h)
    labels = batch["labels"]
    if cfg.causal and not cfg.is_encoder:
        logits = logits[:, :-1]
        labels = labels[:, 1:]
    ce = softmax_cross_entropy(logits, labels)
    loss = jnp.mean(ce)
    total = loss + aux
    return total, {"ce": loss, "aux": aux}


# -------------------------------------------------------------------- serving
def init_decode_caches(
    cfg: ArchConfig,
    batch: int,
    s_ctx: int,
    *,
    seq_shards: int = 1,
    dtype=None,
):
    """Cache pytree: tuple over period positions; leaves stacked [P, ...]."""
    dtype = dtype or _dtype(cfg)
    P = cfg.n_periods

    def one(spec):
        if spec.mixer == ATTN:
            cap = attention.cache_capacity(spec, s_ctx, seq_shards if spec.window == GLOBAL_WINDOW else 1)
            c = attention.init_kv_cache(batch, cfg.n_kv_heads, cap, cfg.hd, dtype)
        elif spec.mixer == MAMBA:
            c = mamba.init_mamba_cache(batch, cfg, cfg.mamba.d_inner(cfg.d_model), dtype)
        elif spec.mixer == MLSTM:
            di = int(cfg.d_model * cfg.xlstm.m_proj_factor)
            c = xlstm.init_mlstm_cache(batch, cfg, di, cfg.n_heads, dtype)
        elif spec.mixer == SLSTM:
            c = xlstm.init_slstm_cache(batch, cfg, dtype)
        else:  # pragma: no cover
            raise ValueError(spec.mixer)
        return jax.tree.map(lambda a: jnp.broadcast_to(a, (P, *a.shape)), c)

    return tuple(one(spec) for spec in cfg.period)


def decode_step(
    cfg: ArchConfig,
    params,
    caches,
    tokens: jax.Array,  # [B, 1] int32
    *,
    ctx: ParallelCtx = LOCAL_CTX,
    use_pallas: bool = False,
):
    """One-token decode -> (logits [B,1,V], new caches)."""
    h = params["embed"][tokens]
    mask = jnp.asarray(active_mask(cfg))

    def body(x, scanned):
        period_params, cache, act = scanned
        x, new_cache = period_decode(period_params, x, cache, act, cfg=cfg,
                                     ctx=ctx, use_pallas=use_pallas)
        return x, new_cache

    h, new_caches = jax.lax.scan(body, h, (params["layers"], caches, mask))
    h = block_norm(h, params["final_norm"], cfg)
    return _logits(cfg, params, h), new_caches


def prefill(
    cfg: ArchConfig,
    params,
    batch: dict,
    *,
    ctx: ParallelCtx = LOCAL_CTX,
    capacity: int | None = None,
):
    """Prefill -> (last-position logits [B,1,V], caches)."""
    h = embed_inputs(cfg, params, batch)
    S = h.shape[1]
    positions = jnp.arange(S, dtype=jnp.int32)
    mask = jnp.asarray(active_mask(cfg))

    def body(x, scanned):
        period_params, act = scanned
        x, caches = period_prefill(
            period_params, x, act, cfg=cfg, positions=positions, ctx=ctx,
            capacity=capacity,
        )
        return x, caches

    h, caches = jax.lax.scan(body, h, (params["layers"], mask))
    h_last = block_norm(h[:, -1:], params["final_norm"], cfg)
    return _logits(cfg, params, h_last), caches
