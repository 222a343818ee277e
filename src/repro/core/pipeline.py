"""GPipe-style pipeline parallelism inside shard_map — the paper's §3.2
training pipeline mapped onto the TPU mesh.

The 'model' mesh axis factors into (stages x tensor).  Parameters arrive
pre-laid-out (core.sharding): every layer leaf is [1, ppstage, *sliced] per
device.  A lax.scan over T = mu + stages - 1 *ticks* moves micro-batches
through the stages with lax.ppermute — communication is a pipeline stage
overlapped with compute, exactly the paper's scheduling policy (its
upload/download stages become the permute).  jax.grad through the scan yields
the reversed backward pipeline automatically (the vjp of ppermute is the
opposite permute), i.e. GPipe's synchronous fill/drain.

All functions here execute INSIDE shard_map.
"""
from __future__ import annotations

import functools
from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.base import ArchConfig, ATTN, MAMBA, MLSTM, SLSTM, GLOBAL_WINDOW
from repro.core import collectives as cc
from repro.core.plan import PipelinePlan
from repro.models import attention, mamba, xlstm
from repro.models.common import ParallelCtx, block_norm
from repro.models.transformer import period_decode, period_forward, period_prefill

CE_CHUNK = 512


# ------------------------------------------------------------------- contexts
def make_ctx(plan: PipelinePlan, *, has_pod: bool = False) -> ParallelCtx:
    """Collective hooks for model code, bound to the mesh axes."""
    tp = plan.tensor
    groups = cc.tp_groups(plan.stages, tp) if tp > 1 else None

    def psum_tp(x):
        if tp == 1:
            return x
        return lax.psum(x, "model", axis_index_groups=groups)

    ep_fwd = ep_bwd = None
    if plan.ep > 1:
        def ep_fwd(x):  # [E, C, d] -> [E/ep, C*ep, d]
            return lax.all_to_all(x, "data", split_axis=0, concat_axis=1, tiled=True)

        def ep_bwd(x):
            return lax.all_to_all(x, "data", split_axis=1, concat_axis=0, tiled=True)

    psum_seq = pmax_seq = None
    seq_index = 0
    if plan.seq_shards > 1:
        seq_axes = ("pod", "data") if plan.pods > 1 else ("data",)
        psum_seq = lambda x: lax.psum(x, seq_axes)
        pmax_seq = lambda x: lax.pmax(x, seq_axes)
        seq_index = lax.axis_index("data")
        if plan.pods > 1:
            seq_index = lax.axis_index("pod") * plan.data + seq_index

    return ParallelCtx(
        tp_size=tp,
        dp_size=plan.data,
        seq_shards=plan.seq_shards,
        psum_tp=psum_tp,
        ep_all_to_all=ep_fwd,
        ep_all_to_all_back=ep_bwd,
        psum_seq=psum_seq or (lambda x: x),
        pmax_seq=pmax_seq,
        seq_index=seq_index,
    )


def stage_index(plan: PipelinePlan):
    return lax.axis_index("model") // plan.tensor


def _unbox(params_local):
    """Strip the leading model-axis dim (always 1 per device)."""
    return jax.tree.map(lambda a: a[0] if a.ndim >= 1 and a.shape[0] == 1 else a,
                        params_local)


def _get_mb(tree, i, mb: int, axis: int = 0):
    return jax.tree.map(lambda a: lax.dynamic_slice_in_dim(a, i * mb, mb, axis=axis), tree)


def _embed(cfg: ArchConfig, params, batch_mb) -> jax.Array:
    dtype = jnp.dtype(cfg.param_dtype)
    if cfg.frontend == "audio":
        return batch_mb["frames"].astype(dtype)
    h = params["embed"][batch_mb["tokens"]]
    if cfg.frontend == "vision":
        n_img = cfg.n_frontend_tokens
        img = batch_mb["image_embeds"].astype(h.dtype)
        h = jnp.concatenate([img, h[:, n_img:]], axis=1)
    return h


def _chunked_ce(h: jax.Array, head_w: jax.Array, labels: jax.Array,
                shift: bool, tp: int = 1, tp_index=0) -> jax.Array:
    """Mean CE without materializing full [S, V] logits.  h [mb,S,d].

    With tensor parallelism the sequence chunks are partitioned round-robin
    over the tp lanes (lane t takes chunks with index % tp == t), so the loss
    — and hence the gradient seeds — are computed exactly once per data shard.
    Sum over lanes == full mean CE.
    """
    if shift:
        h = h[:, :-1]
        labels = labels[:, 1:]
    mb, S, d = h.shape
    C = min(CE_CHUNK, S)
    pad = (-S) % C
    if pad:
        h = jnp.pad(h, ((0, 0), (0, pad), (0, 0)))
        labels = jnp.pad(labels, ((0, 0), (0, pad)))
    w = (jnp.arange(S + pad) < S).astype(jnp.float32)
    nch = (S + pad) // C
    hc = h.reshape(mb, nch, C, d).swapaxes(0, 1)
    lc = labels.reshape(mb, nch, C).swapaxes(0, 1)
    wc = w.reshape(nch, C)
    if tp > 1:
        lane = (jnp.arange(nch, dtype=jnp.int32) % tp) == tp_index
        wc = wc * lane[:, None].astype(jnp.float32)

    def body(acc, xs):
        hcb, lcb, wcb = xs
        logits = (hcb @ head_w.T).astype(jnp.float32)
        logz = jax.scipy.special.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, lcb[..., None], axis=-1)[..., 0]
        return acc + jnp.sum((logz - gold) * wcb[None]), None

    total, _ = lax.scan(jax.checkpoint(body), jnp.zeros((), jnp.float32), (hc, lc, wc))
    return total / (mb * S)


# ------------------------------------------------------------------- training
def pipeline_train_loss(
    cfg: ArchConfig,
    plan: PipelinePlan,
    params_local,
    mask_local,            # [ppstage, period_len] bool (already unboxed)
    batch_local,           # leaves [B_local, ...]
    *,
    has_pod: bool = False,
    use_pallas: bool = False,
) -> Tuple[jax.Array, dict]:
    """Differentiable global-mean loss (executed inside shard_map)."""
    S_eff, tp, mu = plan.stages, plan.tensor, plan.microbatches
    ctx = make_ctx(plan, has_pod=has_pod)
    stage = stage_index(plan)
    is_first = stage == 0
    is_last = stage == S_eff - 1
    layers = params_local["layers"]

    some_leaf = jax.tree.leaves(batch_local)[0]
    B_local = some_leaf.shape[0]
    assert B_local % mu == 0, (B_local, mu)
    mb = B_local // mu
    seq = batch_local["labels"].shape[1]
    positions = jnp.arange(seq, dtype=jnp.int32)
    d = cfg.d_model
    dtype = jnp.dtype(cfg.param_dtype)
    head_w = params_local["embed"] if cfg.tie_embeddings else params_local["head"]
    shift = cfg.causal and not cfg.is_encoder

    def stage_compute(x):
        def per_inst(x, xs):
            inst_params, act_row = xs
            x, aux = period_forward(
                inst_params, x, act_row, cfg=cfg, positions=positions, ctx=ctx,
                use_pallas=use_pallas,
            )
            return x, aux

        body = jax.checkpoint(per_inst) if plan.remat == "layer" else per_inst
        x, auxs = lax.scan(body, x, (layers, mask_local))
        return x, jnp.sum(auxs)

    def tick(carry, t):
        act, loss_sum, aux_sum = carry
        in_idx = jnp.clip(t, 0, mu - 1)
        batch_mb = _get_mb(batch_local, in_idx, mb)
        x = lax.cond(
            is_first,
            lambda: _embed(cfg, params_local, batch_mb).astype(dtype),
            lambda: act,
        )
        x, aux = stage_compute(x)
        out_idx = t - (S_eff - 1)
        valid_out = jnp.logical_and(out_idx >= 0, out_idx < mu)
        valid_compute = jnp.logical_and(t - stage >= 0, t - stage < mu)

        def ce_fn():
            lab = _get_mb(batch_local, jnp.clip(out_idx, 0, mu - 1), mb)["labels"]
            hn = block_norm(x, params_local["final_norm"], cfg)
            return _chunked_ce(hn, head_w, lab, shift, tp=tp,
                               tp_index=lax.axis_index("model") % tp)

        ce = lax.cond(jnp.logical_and(is_last, valid_out), ce_fn,
                      lambda: jnp.zeros((), jnp.float32))
        loss_sum = loss_sum + ce
        aux_sum = aux_sum + jnp.where(valid_compute, aux, 0.0)
        act_next = lax.ppermute(x, "model", cc.pipeline_perm(S_eff, tp))
        return (act_next, loss_sum, aux_sum), None

    T = mu + S_eff - 1
    act0 = jnp.zeros((mb, seq, d), dtype)
    z = jnp.zeros((), jnp.float32)
    tick_fn = jax.checkpoint(tick) if plan.remat in ("tick", "layer") else tick
    (act, loss_sum, aux_sum), _ = lax.scan(tick_fn, (act0, z, z), jnp.arange(T))

    # Differentiate the LOCAL lane loss only — no psum in the grad path.
    # Under check_vma=False the transpose of psum is psum, so seeding a
    # replicated (psum'ed) loss on every device over-counts gradients by the
    # device count.  CE chunks are lane-partitioned (sum over lanes == full
    # CE); aux is computed redundantly per lane, hence the extra /tp.
    dp_norm = mu * plan.data * plan.pods
    ce_local = loss_sum / dp_norm
    aux_local = aux_sum / (dp_norm * tp)
    total_local = ce_local + aux_local

    axes = ("pod", "data", "model") if has_pod else ("data", "model")
    ce_mean = lax.psum(lax.stop_gradient(ce_local), axes)
    aux_mean = lax.psum(lax.stop_gradient(aux_local), axes)
    metrics = {"ce": ce_mean, "aux": aux_mean, "loss": ce_mean + aux_mean}
    return total_local, metrics


# -------------------------------------------------------------------- serving
def pipeline_decode_step(
    cfg: ArchConfig,
    plan: PipelinePlan,
    params_local,
    mask_local,
    caches_local,          # leaves [ppstage, B_local, ...]
    tokens_local,          # [B_local, 1] int32
    *,
    has_pod: bool = False,
):
    """One decode tick for B_local sequences, pipelined over micro-batches.
    Returns (logits [B_local, 1, V], new caches)."""
    S_eff, tp, mu = plan.stages, plan.tensor, plan.microbatches
    ctx = make_ctx(plan, has_pod=has_pod)
    stage = stage_index(plan)
    is_first = stage == 0
    is_last = stage == S_eff - 1
    layers = params_local["layers"]
    B_local = tokens_local.shape[0]
    assert B_local % mu == 0
    mb = B_local // mu
    d = cfg.d_model
    dtype = jnp.dtype(cfg.param_dtype)
    head_w = params_local["embed"] if cfg.tie_embeddings else params_local["head"]
    V = head_w.shape[0]

    def tick(carry, t):
        act, caches, logits_buf = carry
        # stage s processes micro-batch (t - s) at tick t
        my_idx = jnp.clip(t - stage, 0, mu - 1)
        valid = jnp.logical_and(t - stage >= 0, t - stage < mu)
        x = lax.cond(
            is_first,
            lambda: params_local["embed"][
                _get_mb({"t": tokens_local}, my_idx, mb)["t"]
            ].astype(dtype),
            lambda: act,
        )
        mb_caches = jax.tree.map(
            lambda a: lax.dynamic_slice_in_dim(a, my_idx * mb, mb, axis=1), caches
        )

        def per_inst(x, xs):
            inst_params, inst_caches, act_row = xs
            x, new_c = period_decode(inst_params, x, inst_caches, act_row, cfg=cfg, ctx=ctx)
            return x, new_c

        x, new_mb_caches = lax.scan(per_inst, x, (layers, mb_caches, mask_local))
        new_mb_caches = jax.tree.map(
            lambda new, old: jnp.where(valid, new, old), new_mb_caches, mb_caches
        )
        caches = jax.tree.map(
            lambda full, mbv: lax.dynamic_update_slice_in_dim(full, mbv, my_idx * mb, axis=1),
            caches,
            new_mb_caches,
        )

        def logit_fn():
            hn = block_norm(x, params_local["final_norm"], cfg)
            return (hn @ head_w.T).astype(jnp.float32)

        out_idx = jnp.clip(t - (S_eff - 1), 0, mu - 1)
        valid_out = jnp.logical_and(t - (S_eff - 1) >= 0, t - (S_eff - 1) < mu)
        lg = lax.cond(jnp.logical_and(is_last, valid_out), logit_fn,
                      lambda: jnp.zeros((mb, 1, V), jnp.float32))
        logits_buf = lax.cond(
            valid_out,
            lambda: lax.dynamic_update_slice_in_dim(logits_buf, lg, out_idx * mb, axis=0),
            lambda: logits_buf,
        )
        act_next = lax.ppermute(x, "model", cc.pipeline_perm(S_eff, tp))
        return (act_next, caches, logits_buf), None

    T = mu + S_eff - 1
    act0 = jnp.zeros((mb, 1, d), dtype)
    logits0 = jnp.zeros((B_local, 1, V), jnp.float32)
    (_, new_caches, logits), _ = lax.scan(tick, (act0, caches_local, logits0), jnp.arange(T))
    # broadcast logits from the last stage to everyone (cheap: [B,1,V])
    logits = lax.psum(logits, "model") / tp
    return logits, new_caches


def pipeline_prefill(
    cfg: ArchConfig,
    plan: PipelinePlan,
    params_local,
    mask_local,
    batch_local,
    *,
    capacity: Optional[int] = None,
    has_pod: bool = False,
):
    """Pipelined prefill: returns (last-position logits [B_local,1,V], caches
    with leaves [ppstage, B_local, ...])."""
    assert plan.seq_shards == 1, (
        "seq-sharded (long-context) serving is decode-only; prefill a "
        "sharded cache by resharding an unsharded prefill (DESIGN.md)"
    )
    S_eff, tp, mu = plan.stages, plan.tensor, plan.microbatches
    ctx = make_ctx(plan, has_pod=has_pod)
    stage = stage_index(plan)
    is_first = stage == 0
    is_last = stage == S_eff - 1
    layers = params_local["layers"]
    some_leaf = jax.tree.leaves(batch_local)[0]
    B_local = some_leaf.shape[0]
    assert B_local % mu == 0
    mb = B_local // mu
    seq = (batch_local["frames"] if cfg.frontend == "audio" else batch_local["tokens"]).shape[1]
    positions = jnp.arange(seq, dtype=jnp.int32)
    d = cfg.d_model
    dtype = jnp.dtype(cfg.param_dtype)
    head_w = params_local["embed"] if cfg.tie_embeddings else params_local["head"]
    V = head_w.shape[0]

    # allocate full-stage cache buffers [ppstage, B_local, ...]
    cap = capacity if capacity is not None else seq
    cache_buf = _abstract_stage_caches(cfg, plan, B_local, cap, dtype)

    def tick(carry, t):
        act, caches, logits_buf = carry
        my_idx = jnp.clip(t - stage, 0, mu - 1)
        valid = jnp.logical_and(t - stage >= 0, t - stage < mu)
        batch_mb = _get_mb(batch_local, my_idx, mb)
        x = lax.cond(
            is_first,
            lambda: _embed(cfg, params_local, batch_mb).astype(dtype),
            lambda: act,
        )

        def per_inst(x, xs):
            inst_params, act_row = xs
            x, cs = period_prefill(
                inst_params, x, act_row, cfg=cfg, positions=positions, ctx=ctx,
                capacity=cap,
            )
            return x, cs

        x, mb_caches = lax.scan(per_inst, x, (layers, mask_local))
        caches = jax.tree.map(
            lambda full, mbv: lax.cond(
                valid,
                lambda: lax.dynamic_update_slice_in_dim(
                    full, mbv.astype(full.dtype), my_idx * mb, axis=1
                ),
                lambda: full,
            ),
            caches,
            mb_caches,
        )

        def logit_fn():
            hn = block_norm(x[:, -1:], params_local["final_norm"], cfg)
            return (hn @ head_w.T).astype(jnp.float32)

        out_idx = jnp.clip(t - (S_eff - 1), 0, mu - 1)
        valid_out = jnp.logical_and(t - (S_eff - 1) >= 0, t - (S_eff - 1) < mu)
        lg = lax.cond(jnp.logical_and(is_last, valid_out), logit_fn,
                      lambda: jnp.zeros((mb, 1, V), jnp.float32))
        logits_buf = lax.cond(
            valid_out,
            lambda: lax.dynamic_update_slice_in_dim(logits_buf, lg, out_idx * mb, axis=0),
            lambda: logits_buf,
        )
        act_next = lax.ppermute(x, "model", cc.pipeline_perm(S_eff, tp))
        return (act_next, caches, logits_buf), None

    T = mu + S_eff - 1
    act0 = jnp.zeros((mb, seq, d), dtype)
    logits0 = jnp.zeros((B_local, 1, V), jnp.float32)
    (_, caches, logits), _ = lax.scan(tick, (act0, cache_buf, logits0), jnp.arange(T))
    logits = lax.psum(logits, "model") / tp
    return logits, caches


def _abstract_stage_caches(cfg: ArchConfig, plan: PipelinePlan, B_local: int,
                           s_ctx: int, dtype):
    """Zero-init per-stage cache buffers [ppstage, B_local, ...] with
    tp-sliced kv heads / d_inner.  Matches the leaves period_decode expects."""
    tp = plan.tensor
    kv_local = max(1, cfg.n_kv_heads // tp) if tp > 1 else cfg.n_kv_heads

    def one(spec):
        if spec.mixer == ATTN:
            capn = attention.cache_capacity(
                spec, s_ctx, plan.seq_shards if spec.window == GLOBAL_WINDOW else 1
            )
            c = attention.init_kv_cache(B_local, kv_local, capn, cfg.hd, dtype)
        elif spec.mixer == MAMBA:
            di = cfg.mamba.d_inner(cfg.d_model) // tp
            c = mamba.init_mamba_cache(B_local, cfg, di, dtype)
        elif spec.mixer == MLSTM:
            di = int(cfg.d_model * cfg.xlstm.m_proj_factor)  # tp-replicated
            c = xlstm.init_mlstm_cache(B_local, cfg, di, cfg.n_heads, dtype)
        elif spec.mixer == SLSTM:
            c = xlstm.init_slstm_cache(B_local, cfg, dtype)
        else:  # pragma: no cover
            raise ValueError(spec.mixer)
        return jax.tree.map(
            lambda a: jnp.zeros((plan.ppstage, *a.shape), a.dtype), c
        )

    return tuple(one(spec) for spec in cfg.period)
