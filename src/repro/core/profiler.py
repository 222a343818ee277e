"""Model Profiler (paper Fig 2, startup component ③).

On the real system this profiles layers on functions of every memory class;
offline we synthesize the same per-layer tables analytically: FLOPs-derived
compute times under the platform's memory->vCPU scaling, plus parameter /
activation / boundary sizes.  Includes the paper's four evaluation models
(Table 1) and a bridge from our ArchConfigs so the serverless planner can
plan any assigned architecture.
"""
from __future__ import annotations

import numpy as np

from repro.configs.base import ArchConfig, DENSE_FF, MOE_FF, ATTN
from repro.core.partition import LayerProfile, ModelProfile
from repro.serverless.platform import MB, GB, Platform

F32 = 4  # training payloads are fp32 on CPU serverless


def _times(platform: Platform, fwd_flops: float):
    fwd = tuple(platform.compute_time(fwd_flops, m) for m in platform.memory_options)
    bwd = tuple(2.0 * t for t in fwd)
    return fwd, bwd


def _layer(platform, name, params_b, act_b, out_b, grad_b, fwd_flops):
    fwd, bwd = _times(platform, fwd_flops)
    return LayerProfile(
        name=name, param_bytes=params_b, act_bytes=act_b, out_bytes=out_b,
        grad_out_bytes=grad_b, fwd_time=fwd, bwd_time=bwd,
    )


# ----------------------------------------------------------- paper's models
# Table 1: (param_MB, act_MB_per_sample); FLOPs calibrated so AmoebaNet-D36
# computation matches Fig 1(a) (~6 s/iteration).
_PAPER_MODELS = {
    "resnet101": dict(params=170 * MB, act=198 * MB, n_layers=35, kind="cnn"),
    "amoebanet-d18": dict(params=476 * MB, act=432 * MB, n_layers=20, kind="cnn"),
    "amoebanet-d36": dict(params=900 * MB, act=697 * MB, n_layers=38, kind="cnn"),
    "bert-large": dict(params=1153 * MB, act=263 * MB, n_layers=26, kind="bert"),
}
_CNN_FLOPS_PER_PARAM_SAMPLE = 240.0   # conv spatial reuse
_BERT_FLOPS_PER_PARAM_SAMPLE = 256.0  # 2 * seq(128)


def paper_model_profile(name: str, platform: Platform,
                        micro_batch: int = 4) -> ModelProfile:
    spec = _PAPER_MODELS[name]
    L = spec["n_layers"]
    P_total, A_total = spec["params"], spec["act"]
    if spec["kind"] == "cnn":
        # params grow with depth, activations shrink (stride-2 reductions)
        depth = np.arange(L)
        pw = np.exp(depth / L * 1.6)          # ~5x growth first->last
        aw = np.exp(-depth / L * 2.2)         # ~9x shrink
        kf = _CNN_FLOPS_PER_PARAM_SAMPLE
    else:
        # embedding-heavy first layer, uniform encoder blocks
        pw = np.ones(L)
        pw[0] = 3.0
        pw[-1] = 0.3
        aw = np.ones(L)
        kf = _BERT_FLOPS_PER_PARAM_SAMPLE
    pw = pw / pw.sum()
    aw = aw / aw.sum()
    layers = []
    for i in range(L):
        p_b = P_total * pw[i]
        a_b = A_total * aw[i] * micro_batch
        out_b = a_b * 0.5                      # boundary tensor ~ half the act
        flops = kf * (p_b / F32) * micro_batch
        if spec["kind"] == "cnn" and i == 0:
            flops *= 3.0                       # stem convs are FLOP-heavy
        layers.append(_layer(platform, f"L{i}", p_b, a_b, out_b, out_b, flops))
    return ModelProfile(name=name, layers=tuple(layers))


# -------------------------------------------------- assigned-arch bridge
def arch_model_profile(cfg: ArchConfig, platform: Platform, *, seq: int = 512,
                       micro_batch: int = 1) -> ModelProfile:
    """Layer table for one of the assigned architectures (fp32 serverless)."""
    d = cfg.d_model
    layers = []
    act_per_layer = 6 * seq * d * F32 * micro_batch  # residual+mixer+ff buffers
    out_b = seq * d * F32 * micro_batch
    # embedding "layer"
    emb_b = cfg.vocab_size * d * F32
    layers.append(_layer(platform, "embed", emb_b, out_b, out_b, out_b,
                         2 * seq * d * micro_batch))
    n_emb_tables = 1 if cfg.tie_embeddings else 2
    per_layer_params = max(
        0.0, cfg.param_count() * F32 - n_emb_tables * emb_b) / cfg.n_layers
    for i in range(cfg.n_layers):
        spec = cfg.layer_spec(i)
        p_b = per_layer_params
        flops_params = p_b / F32
        if spec.ff == MOE_FF and cfg.moe is not None:
            # only top_k experts touched per token
            frac = cfg.active_param_count() / cfg.param_count()
            flops_params *= frac
        flops = 6 * flops_params * seq * micro_batch / 3  # fwd ~ 2*N*D
        layers.append(_layer(platform, f"layer{i}", p_b, act_per_layer, out_b,
                             out_b, flops))
    # lm head
    layers.append(_layer(platform, "head", emb_b, out_b, out_b, out_b,
                         2 * cfg.vocab_size * d * seq * micro_batch / 1000))
    return ModelProfile(name=cfg.name, layers=tuple(layers))


# ------------------------------------------------------- unified resolution
def resolve_profile(model: str, platform: Platform, *, seq=None,
                    micro_batch=None) -> ModelProfile:
    """One front door from a model id to its layer profile.

    Accepts the paper's Table 1 models and every arch spelling
    ``repro.configs.resolve_arch`` reads (``<arch>``, ``<arch>@reduced[<L>]``,
    ``<arch>@depth<L>``), which is what the numeric emulation mode records
    (so its saved plans replay too); ``None`` keeps each family's own
    default (paper: micro_batch=4; arch: seq=512, micro_batch=1).  This is
    the resolution path ``DeploymentPlan.resolve`` replays, so the recorded
    ``profile_args`` must reproduce the profile the plan was solved
    against."""
    from repro.configs import resolve_arch

    if model in _PAPER_MODELS:
        return paper_model_profile(model, platform,
                                   micro_batch=4 if micro_batch is None else micro_batch)
    try:
        cfg = resolve_arch(model)
    except KeyError as e:
        raise KeyError(f"{e.args[0]}; paper models: "
                       f"{sorted(_PAPER_MODELS)}") from None
    return arch_model_profile(cfg, platform,
                              seq=512 if seq is None else seq,
                              micro_batch=1 if micro_batch is None else micro_batch)
