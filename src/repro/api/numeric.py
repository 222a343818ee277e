"""Numeric-mode deployments: a period-aligned manual plan plus the real JAX
model it executes (``repro emulate --numerics``).

    plan, profile, execution = numeric_plan("phi3-mini-3.8b@depth2",
                                            stages=2, dp=1, batch=8, seq=512)
    res = plan.emulate(ExecutionConfig(steps=3), execution=execution,
                       profile=profile)

The model spelling is any of ``repro.configs.resolve_arch``'s; the plan
records it, so a saved numeric plan replays on the timing axis through
``DeploymentPlan.resolve``.
"""
from __future__ import annotations

from typing import NamedTuple

from repro.serverless.platform import get_platform


class NumericDeployment(NamedTuple):
    plan: object          # DeploymentPlan
    profile: object       # ModelProfile the plan indexes into
    execution: object     # runtime.Execution: config, optimizer, params, data


def numeric_partition(cfg, n_stages: int) -> tuple:
    """Boundary vector over the arch profile ([embed]+layers+[head]) cutting
    at period boundaries so every stage owns whole instances."""
    L = cfg.n_layers + 2
    n_inst = cfg.n_periods
    if not 1 <= n_stages <= n_inst:
        raise ValueError(f"{n_stages} stages need 1..{n_inst} period "
                         f"instances of {cfg.name} at {cfg.n_layers} layers")
    x = [0] * (L - 1)
    for s in range(1, n_stages):
        inst = round(s * n_inst / n_stages)
        x[inst * cfg.period_len] = 1      # cut before stage s's first layer
    return tuple(x)


def numeric_plan(model: str, *, stages: int, dp: int, batch: int = 64,
                 seq: int = 16, platform: str = "aws",
                 pipelined_sync: bool = True) -> NumericDeployment:
    """Plan ``model`` as ``stages`` x ``dp`` workers over ``mu = batch //
    (2 dp)`` micro-batches per replica, with random weights from seed 0 and
    synthetic batches drawn per step.  Raises ``KeyError`` for an unknown
    model spelling, ``ValueError`` for a shape the plan cannot split and
    ``InfeasiblePlanError`` when no memory option fits a stage."""
    import jax

    from repro.api.plan import DeploymentPlan
    from repro.api.session import InfeasiblePlanError
    from repro.configs import resolve_arch
    from repro.configs.base import InputShape
    from repro.core import planner
    from repro.core.perfmodel import Config
    from repro.core.profiler import arch_model_profile
    from repro.data.synthetic import make_batch
    from repro.models import registry
    from repro.optim import AdamW
    from repro.serverless.runtime import Execution

    plat = get_platform(platform)
    cfg = resolve_arch(model)
    mu = max(1, batch // (dp * 2))
    if batch % (dp * mu):
        raise ValueError(f"batch {batch} must be divisible by dp*mu "
                         f"= {dp}*{mu}")
    mb = batch // (dp * mu)
    prof = arch_model_profile(cfg, plat, seq=seq, micro_batch=mb)
    x = numeric_partition(cfg, stages)
    stage_mem = planner._min_feasible_stage_mem(prof, plat, x, dp, mu)
    if stage_mem is None:
        raise InfeasiblePlanError(
            f"no {plat.name} memory option fits a stage of {model} at "
            f"{stages} stages, micro-batch {mb} x {seq} tokens")
    config = Config(x=x, d=dp, z=planner._expand_z(stage_mem, x, prof.L))
    plan = DeploymentPlan.from_config(
        prof, plat, config, dp * mu, model=model,
        pipelined_sync=pipelined_sync, seq=seq, micro_batch=mb,
        solver="manual")
    shape = InputShape("emulate", seq, batch, "train")
    ex = Execution(cfg=cfg, optimizer=AdamW(lr=1e-2),
                   init_params=registry.init_params(cfg, jax.random.PRNGKey(0)),
                   batch_fn=lambda k: make_batch(cfg, shape, step=k))
    return NumericDeployment(plan, prof, ex)
