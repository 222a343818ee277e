"""Serving cost model: per-request prefill + decode latency and $/1k
requests for a partitioned pipeline on a serverless platform.

Training plans amortize boundary transfers over ``mu`` micro-batches per
step; a serving request is one prefill pass (seq = prompt length) followed
by ``new_tokens - 1`` single-token pipeline rounds, each of which must round-
trip the stage's KV cache through the object store (serverless functions are
stateless between invocations — the cache *is* store traffic, which is what
makes the decode cost model different from simply scaling the training one).

All per-stage terms reuse :func:`repro.serverless.simulator.stage_aggregates`
built from a profile at ``seq = prefill_tokens`` / ``micro_batch = batch``,
so compute times, bandwidths and memory options come from exactly the tables
the training planner charges.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Tuple

import numpy as np

from repro.core.perfmodel import Config
from repro.serverless.platform import GB, Platform
from repro.serverless.simulator import stage_aggregates

#: greedy-token feedback object: int32 [B, 1]
TOKEN_BYTES = 4


def arch_config_for_model(model: str):
    """ArchConfig for a serving model id.

    Reads the spellings of ``repro.configs.resolve_arch`` but *rejects* the
    paper's Table 1 models: they are analytic layer tables with no runnable
    layers, and serving needs executable prefill/decode math.  Raises
    ``NotImplementedError`` for an architecture that trains only
    (``ArchConfig.decodes``).
    """
    from repro.configs import resolve_arch

    try:
        cfg = resolve_arch(model)
    except KeyError as e:
        raise KeyError(
            f"serving needs an executable architecture (paper Table 1 "
            f"models are analytic-only): {e.args[0]}") from None
    if not cfg.decodes:
        raise NotImplementedError(
            f"serving needs prefill and decode, which {cfg.name}'s blocks "
            f"(the paper's xLSTM: headwise q/k/v, group norms, sLSTM "
            f"convolution) do not have; it trains only")
    return cfg


@dataclass(frozen=True)
class ServingSpec:
    """One serving workload: SLO + request shape."""

    slo_s: float            # per-request latency objective
    batch: int              # requests decoded together
    prefill_tokens: int     # prompt length
    new_tokens: int         # tokens generated per request (incl. the
                            # prefill's first token)

    def __post_init__(self):
        if self.slo_s <= 0:
            raise ValueError(f"slo_s must be positive, got {self.slo_s}")
        if self.batch < 1 or self.prefill_tokens < 1 or self.new_tokens < 1:
            raise ValueError(
                "batch, prefill_tokens and new_tokens must all be >= 1 "
                f"(got {self.batch}, {self.prefill_tokens}, "
                f"{self.new_tokens})")

    @property
    def s_ctx(self) -> int:
        """KV-cache capacity: prompt + every generated token."""
        return self.prefill_tokens + self.new_tokens

    def as_dict(self) -> dict:
        return {"slo_s": self.slo_s, "batch": self.batch,
                "prefill_tokens": self.prefill_tokens,
                "new_tokens": self.new_tokens, "context": self.s_ctx}


def kv_bytes_per_instance(cfg, batch: int, s_ctx: int) -> float:
    """Decode-cache bytes of ONE period instance (shapes only, no allocs)."""
    import jax

    from repro.models import registry

    caches = jax.eval_shape(
        lambda: registry.init_decode_caches(cfg, batch, s_ctx))
    total = 0.0
    for leaf in jax.tree.leaves(caches):
        # leaves are stacked [n_periods, ...]; charge one instance
        total += float(np.prod(leaf.shape[1:]) * np.dtype(leaf.dtype).itemsize)
    return total


@dataclass(frozen=True)
class ServingEstimate:
    """Closed-form per-request latency/cost of one partition + memory
    assignment (the serving planner's objective terms)."""

    t_prefill: float                 # prompt pass through the pipeline
    t_token: float                   # one decode pipeline round
    t_request: float                 # t_prefill + (new_tokens-1) * t_token
    cost_per_request: float          # $ (all stages occupied for t_request)
    cost_per_1k: float
    kv_bytes: Tuple[float, ...]      # [S] per-stage decode-cache bytes
    mem: Tuple[float, ...]           # [S] allocated function memory (bytes)
    t_prefill_stage: Tuple[float, ...]   # [S] per-stage prefill compute
    t_decode_stage: Tuple[float, ...]    # [S] per-stage decode compute


def estimate_serving(profile, platform: Platform, config: Config, cfg,
                     spec: ServingSpec) -> ServingEstimate:
    """Per-request latency and cost of serving ``spec`` on ``config``.

    ``profile`` must have been built at ``seq = spec.prefill_tokens`` and
    ``micro_batch = spec.batch`` so the aggregates' compute/boundary terms
    describe the prompt pass; decode terms are derived per token from them.
    """
    from repro.serverless.runtime.worker import stage_instance_ranges

    agg = stage_aggregates(profile, platform, config, 1)
    S = agg.S
    S_pre = spec.prefill_tokens
    t_lat = agg.t_lat
    w = agg.w

    # ---- prefill: one prompt flows through the pipeline depth-first
    t_prefill = float(np.sum(agg.t_fc))
    for s in range(S - 1):
        t_prefill += agg.out_b[s] / w[s] + t_lat          # producer uplink
        t_prefill += agg.out_b[s] / w[s + 1] + t_lat      # consumer downlink

    # ---- decode: compute and boundary scale to a single token
    t_dec = agg.t_fc / S_pre
    tok_b = agg.out_b / S_pre                             # [B, 1, d] hidden
    per_inst = kv_bytes_per_instance(cfg, spec.batch, spec.s_ctx)
    spans = stage_instance_ranges(cfg, config.x)
    kv_b = tuple(float((sp.inst_hi - sp.inst_lo) * per_inst) for sp in spans)

    t_token = 0.0
    for s in range(S):
        t_token += float(t_dec[s])
        if kv_b[s]:
            # stateless functions: the KV cache round-trips the store
            t_token += 2.0 * (kv_b[s] / w[s] + t_lat)
        if s < S - 1:
            t_token += tok_b[s] / w[s] + t_lat
            t_token += tok_b[s] / w[s + 1] + t_lat
    # greedy-token feedback: last stage -> store -> stage 0
    fb = float(spec.batch * TOKEN_BYTES)
    t_token += fb / w[S - 1] + t_lat + fb / w[0] + t_lat

    t_request = t_prefill + (spec.new_tokens - 1) * t_token
    cost = float(platform.price_per_gb_s
                 * (np.sum(agg.mem) / GB) * t_request)
    return ServingEstimate(
        t_prefill=float(t_prefill), t_token=float(t_token),
        t_request=float(t_request), cost_per_request=cost,
        cost_per_1k=1000.0 * cost, kv_bytes=kv_b,
        mem=tuple(float(m) for m in agg.mem),
        t_prefill_stage=tuple(float(t) for t in agg.t_fc),
        t_decode_stage=tuple(float(t) for t in t_dec),
    )
