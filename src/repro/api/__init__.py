"""Unified deployment API: one typed front door over the repro's
profile -> co-optimize -> simulate/emulate pipeline (paper workflow ①-⑤).

    from repro.api import session, DeploymentPlan

    s = session("bert-large", platform="aws").profile().plan(merge_to=14)
    s.save_plan("plan.json").simulate().emulate(steps=2)

    plan = DeploymentPlan.load("plan.json")   # later / elsewhere
    plan.simulate(); plan.emulate(steps=2)    # bit-identical replay

The CLI counterpart is ``python -m repro`` (see ``repro.cli``).
"""
from repro.api.plan import (
    DeploymentPlan,
    PlanCompatibilityError,
    ResolvedPlan,
    profile_fingerprint,
)
from repro.api.numeric import NumericDeployment, numeric_plan
from repro.api.plan_cache import PlanCache, resolve_plan_cache
from repro.api.session import (
    DEFAULT_ALPHA,
    InfeasiblePlanError,
    Session,
    session,
)
from repro.serverless.execution import ExecutionConfig

__all__ = [
    "DeploymentPlan",
    "ExecutionConfig",
    "InfeasiblePlanError",
    "NumericDeployment",
    "numeric_plan",
    "PlanCache",
    "PlanCompatibilityError",
    "ResolvedPlan",
    "profile_fingerprint",
    "resolve_plan_cache",
    "Session",
    "session",
    "DEFAULT_ALPHA",
]
