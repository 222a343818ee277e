"""Parameter layout for the pipelined mesh.

Global layout of every layer leaf: ``[model_axis, ppstage, *sliced_dims]``
where index ``m = stage*tp + t`` holds (pipeline stage ``stage``, tensor slice
``t``).  ``PartitionSpec('model', ...)`` then gives each device exactly its
stage's tp-slice.  MoE expert leaves carry an extra 'data'-sharded expert dim
(expert parallelism).  Embedding / head / final norm are replicated.

``TPSpec`` annotations mirror the init_* param structures:
  repl          — copied across tp members
  slice(dim)    — dim divided contiguously by tp (column/row parallel)
  heads(dim,hd) — dim is heads*hd; sliced by whole heads, and *replicated*
                  when there are fewer KV heads than tp members (GQA)
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

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
)
from repro.core.plan import PipelinePlan


@dataclass(frozen=True)
class TPSpec:
    mode: str = "repl"            # repl | slice | heads
    dim: int = -1                 # sliced dim (negative = from the end)
    unit: int = 1                 # head_dim for mode="heads"
    heads: int = 0                # total heads for mode="heads"
    ep: bool = False              # expert dim 0 sharded over 'data'
    # gradient sync over tp members required (kv replication / full repl):
    sync_tp: bool = False

    def local_dim_size(self, full: int, tp: int) -> int:
        if self.mode == "repl":
            return full
        if self.mode == "slice":
            assert full % tp == 0, (full, tp)
            return full // tp
        # heads
        if self.heads >= tp:
            assert self.heads % tp == 0
            return (self.heads // tp) * self.unit
        return self.unit  # one (replicated) kv head per member


REPL = TPSpec("repl", sync_tp=True)


def attn_pspecs(cfg: ArchConfig, replicate: bool = False) -> dict:
    if replicate:
        keys = ["wq", "wk", "wv", "wo"] + (["bq", "bk", "bv"] if cfg.qkv_bias else [])
        keys += ["q_norm", "k_norm"] if cfg.qk_norm else []
        return {k: REPL for k in keys}
    hd = cfg.hd
    kvh = TPSpec("heads", -1, hd, cfg.n_kv_heads, sync_tp=True)
    p = {
        "wq": TPSpec("slice", -1),
        "wk": kvh,
        "wv": kvh,
        "wo": TPSpec("slice", 0),
    }
    if cfg.qkv_bias:
        p["bq"] = TPSpec("slice", 0)
        p["bk"] = dataclasses.replace(kvh, dim=0)
        p["bv"] = dataclasses.replace(kvh, dim=0)
    if cfg.qk_norm:
        p["q_norm"] = REPL
        p["k_norm"] = REPL
    return p


def mlp_pspecs(cfg: ArchConfig) -> dict:
    return {
        "w_gate": TPSpec("slice", 1),
        "w_up": TPSpec("slice", 1),
        "w_down": TPSpec("slice", 0),
    }


def moe_pspecs(cfg: ArchConfig) -> dict:
    return {
        "router": REPL,
        "w_gate": TPSpec("slice", 2, ep=True),
        "w_up": TPSpec("slice", 2, ep=True),
        "w_down": TPSpec("slice", 1, ep=True),
    }


def mamba_pspecs(cfg: ArchConfig) -> dict:
    return {
        "w_in_x": TPSpec("slice", 1),
        "w_in_z": TPSpec("slice", 1),
        "conv_w": TPSpec("slice", 1),
        "conv_b": TPSpec("slice", 0),
        "w_xproj": TPSpec("slice", 0),
        "w_dt": TPSpec("slice", 1),
        "b_dt": TPSpec("slice", 0),
        "A_log": TPSpec("slice", 0),
        "D": TPSpec("slice", 0),
        "w_out": TPSpec("slice", 0),
    }


def xlstm_pspecs(cfg: ArchConfig, spec: LayerSpec) -> dict:
    # Recurrent matrices couple the full width: run TP-replicated (DESIGN.md).
    paper = cfg.xlstm.block == "paper"
    if spec.mixer == MLSTM:
        keys = ["w_up", "w_z", "conv_w", "conv_b", "wq", "wk", "wv",
                "w_if", "b_i", "b_f", "out_norm", "w_down"]
        if paper:
            keys.append("skip")
    else:
        keys = ["w_gates", "r_gates", "b_gates", "out_norm"]
        if paper:
            keys += ["conv_w", "conv_b"]
        if spec.ff == NO_FF:      # the mixer carries its own FFN
            keys += ["w_up_ff", "w_down_ff"]
    return {k: REPL for k in keys}


def layer_pspecs(cfg: ArchConfig, spec: LayerSpec) -> dict:
    p: dict = {"norm1": REPL}
    if spec.mixer == ATTN:
        p["mixer"] = attn_pspecs(cfg)
    elif spec.mixer == MAMBA:
        p["mixer"] = mamba_pspecs(cfg)
    else:
        p["mixer"] = xlstm_pspecs(cfg, spec)
    if spec.ff != NO_FF:
        p["norm2"] = REPL
        p["ff"] = mlp_pspecs(cfg) if spec.ff == DENSE_FF else moe_pspecs(cfg)
    return p


def model_pspecs(cfg: ArchConfig) -> dict:
    """TPSpec pytree matching registry.init_params structure."""
    out = {
        "embed": REPL,
        "final_norm": REPL,
        "layers": tuple(layer_pspecs(cfg, s) for s in cfg.period),
    }
    if not cfg.tie_embeddings:
        out["head"] = REPL
    return out


# ----------------------------------------------------------------- layout ops
def _slice_bounds(ts: TPSpec, full: int, tp: int, t: int) -> tuple[int, int]:
    """start, size of member t's slice of a dim of length ``full``."""
    if ts.mode == "slice":
        sz = full // tp
        return t * sz, sz
    # heads
    if ts.heads >= tp:
        per = ts.heads // tp
        return t * per * ts.unit, per * ts.unit
    # replicate kv heads: member t uses head index t * heads // tp
    h = t * ts.heads // tp
    return h * ts.unit, ts.unit


def layout_leaf(leaf: jax.Array, ts: TPSpec, plan: PipelinePlan) -> jax.Array:
    """[n_periods, *dims] -> [model_axis, ppstage, *tp_sliced_dims]."""
    S, tp = plan.stages, plan.tensor
    P_have = leaf.shape[0]
    pad = plan.n_instances - P_have
    if pad:
        leaf = jnp.concatenate(
            [leaf, jnp.zeros((pad, *leaf.shape[1:]), leaf.dtype)], axis=0
        )
    leaf = leaf.reshape(S, plan.ppstage, *leaf.shape[1:])
    if ts.mode == "repl" or tp == 1:
        out = jnp.broadcast_to(leaf[:, None], (S, tp, *leaf.shape[1:]))
    else:
        dim = ts.dim % (leaf.ndim - 2) + 2  # map leaf-relative dim to padded array
        full = leaf.shape[dim]
        slices = []
        for t in range(tp):
            st, sz = _slice_bounds(ts, full, tp, t)
            slices.append(jax.lax.slice_in_dim(leaf, st, st + sz, axis=dim))
        out = jnp.stack(slices, axis=1)  # [S, tp, ppstage, ...sliced]
    return out.reshape(S * tp, *out.shape[2:])


def leaf_partition_spec(ts: TPSpec, ndim_layout: int, plan: PipelinePlan) -> P:
    """PartitionSpec for a laid-out leaf [model, ppstage, *dims]."""
    axes: list = ["model"] + [None] * (ndim_layout - 1)
    if ts.ep and plan.ep > 1:
        axes[2] = "data"  # expert dim (dim 0 of the original leaf)
    return P(*axes)


def to_pipeline_layout(cfg: ArchConfig, plan: PipelinePlan, params: dict) -> dict:
    specs = model_pspecs(cfg)
    layers = jax.tree.map(
        lambda leaf, ts: layout_leaf(leaf, ts, plan),
        params["layers"],
        specs["layers"],
        is_leaf=lambda x: isinstance(x, TPSpec),
    )
    out = dict(params)
    out["layers"] = layers
    return out


def pipeline_param_specs(cfg: ArchConfig, plan: PipelinePlan) -> dict:
    """PartitionSpec pytree for laid-out params (replicated leaves -> P())."""
    specs = model_pspecs(cfg)

    def layer_spec(ts: TPSpec, leaf_shape_len: int):
        return leaf_partition_spec(ts, leaf_shape_len, plan)

    # need leaf ndim: build from abstract shapes
    shapes = abstract_layout_shapes(cfg, plan)
    layers = jax.tree.map(
        lambda sds, ts: layer_spec(ts, len(sds.shape)),
        shapes["layers"],
        specs["layers"],
        is_leaf=lambda x: isinstance(x, TPSpec),
    )
    out = {"embed": P(), "final_norm": P(), "layers": layers}
    if not cfg.tie_embeddings:
        out["head"] = P()
    return out


def abstract_layout_shapes(cfg: ArchConfig, plan: PipelinePlan) -> dict:
    """ShapeDtypeStructs of laid-out params WITHOUT materializing anything."""
    from repro.models.registry import init_params

    base = jax.eval_shape(
        lambda k: init_params(cfg, k), jax.ShapeDtypeStruct((2,), jnp.uint32)
    )
    specs = model_pspecs(cfg)

    def lay(sds, ts: TPSpec):
        S, tp = plan.stages, plan.tensor
        dims = list(sds.shape[1:])
        if ts.mode != "repl" and tp > 1:
            d = ts.dim % len(dims)
            dims[d] = ts.local_dim_size(dims[d], tp)
        return jax.ShapeDtypeStruct((S * tp, plan.ppstage, *dims), sds.dtype)

    layers = jax.tree.map(
        lay, base["layers"], specs["layers"], is_leaf=lambda x: isinstance(x, TPSpec)
    )
    out = {"embed": base["embed"], "final_norm": base["final_norm"], "layers": layers}
    if not cfg.tie_embeddings:
        out["head"] = base["head"]
    return out


def abstract_params(cfg: ArchConfig, plan: PipelinePlan, mesh) -> dict:
    """Abstract laid-out params with NamedShardings attached (dry-run)."""
    shapes = abstract_layout_shapes(cfg, plan)
    pspecs = pipeline_param_specs(cfg, plan)
    return jax.tree.map(
        lambda sds, ps: jax.ShapeDtypeStruct(
            sds.shape, sds.dtype, sharding=NamedSharding(mesh, ps)
        ),
        shapes,
        pspecs,
        is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct) or isinstance(x, P),
    )


@dataclass(frozen=True)
class GradSync:
    data_rs: bool = True       # reduce-scatter over 'data' (False for EP leaves)
    tp_mode: str = "none"      # none | all (replicated) | kvshare (GQA kv repl)


def grad_sync_specs(cfg: ArchConfig, plan: PipelinePlan) -> dict:
    """Per-leaf sync requirements for the update step (see train.train_step)."""
    specs = model_pspecs(cfg)

    def sync(ts: TPSpec) -> GradSync:
        tp_mode = "none"
        if plan.tensor > 1:
            if ts.mode == "repl":
                tp_mode = "all"
            elif ts.mode == "heads" and ts.heads < plan.tensor:
                tp_mode = "kvshare"
        data_rs = not (ts.ep and plan.ep > 1)
        return GradSync(data_rs=data_rs, tp_mode=tp_mode)

    return jax.tree.map(sync, specs, is_leaf=lambda x: isinstance(x, TPSpec))


def layer_mask_array(cfg: ArchConfig, plan: PipelinePlan) -> np.ndarray:
    """[model_axis, ppstage, period_len] bool — real (non-padding) layers."""
    S, tp = plan.stages, plan.tensor
    idx = np.arange(plan.n_instances * cfg.period_len).reshape(
        S, plan.ppstage, cfg.period_len
    )
    mask = idx < cfg.n_layers
    return np.broadcast_to(mask[:, None], (S, tp, plan.ppstage, cfg.period_len)).reshape(
        S * tp, plan.ppstage, cfg.period_len
    )
