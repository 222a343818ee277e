"""Serving subsystem: pipelined decode parity, SLO planner, autoscaling.

The load-bearing claim is bit-identity: partitioned prefill + token-by-token
decode through the execution backends (KV caches round-tripping through the
object store every token) must emit exactly the tokens of the monolithic
single-process decode loop (:func:`repro.serving.reference_decode`).  The
SLO planner prefers a single stage for models this small — every extra
stage adds KV round-trips and boundary hops to each decoded token — so the
multi-stage path is exercised by forcing a 2-stage split of the planned
deployment.
"""
import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api.plan import DeploymentPlan, PlanCompatibilityError
from repro.api.session import InfeasiblePlanError, session
from repro.models import registry
from repro.serving import (
    InfeasibleSLOError,
    ServingSpec,
    arch_config_for_model,
    autoscale_plan,
    bursty_arrivals,
    estimate_serving,
    greedy_token,
    kv_bytes_per_instance,
    make_prompt,
    plan_serving,
    poisson_arrivals,
    reference_decode,
    run_serve_plan,
    simulate_replicas,
    trace_arrivals,
)

ARCHS = ["phi3-mini-3.8b@reduced", "qwen2.5-14b@reduced"]
BATCH, PREFILL, NEW = 2, 8, 3


@pytest.fixture(scope="module", params=ARCHS)
def served(request):
    """One planned serve deployment per arch + the monolithic oracle."""
    model = request.param
    plan = plan_serving(model, "aws", slo=60.0, batch=BATCH,
                        prefill_tokens=PREFILL, new_tokens=NEW)
    cfg = arch_config_for_model(model)
    params = registry.init_params(cfg, jax.random.PRNGKey(0))
    toks = make_prompt(cfg, BATCH, PREFILL, seed=0)
    ref = reference_decode(cfg, params, toks, NEW)
    return model, plan, cfg, ref


def _force_two_stages(plan):
    # cut after the embed instance (period_len=1 on the reduced archs, so
    # every profile-layer boundary is a legal stage cut)
    cuts = [0] * len(plan.x)
    cuts[1] = 1
    return dataclasses.replace(plan, x=tuple(cuts),
                               z=(0,) * (len(plan.x) + 1))


# ------------------------------------------------------------ decode parity
def test_planned_decode_parity_emulated(served):
    model, plan, cfg, ref = served
    res = run_serve_plan(plan, backend="emulated", seed=0)
    assert np.array_equal(res.tokens, ref), (res.tokens, ref)
    assert res.t_request > 0 and res.cost_per_request > 0
    assert res.store_stats.class_bytes_in.get("kv", 0) > 0
    assert res.kv_bytes and all(b > 0 for b in res.kv_bytes)


@pytest.mark.parametrize("backend", ["emulated", "process"])
def test_two_stage_decode_parity(served, backend, tmp_path):
    model, plan, cfg, ref = served
    plan2 = _force_two_stages(plan)
    kw = {"root": str(tmp_path)} if backend == "process" else {}
    res = run_serve_plan(plan2, backend=backend, seed=0, **kw)
    assert np.array_equal(res.tokens, ref), (model, backend)
    # both stages persisted KV through the store (verify_drained already ran
    # inside run_serve_plan: every boundary/token/kv key was consumed)
    assert res.store_stats.class_bytes_in.get("kv", 0) > 0
    assert len(res.kv_bytes) == 2 and all(b > 0 for b in res.kv_bytes)


def test_serve_phases_in_trace(served):
    model, plan, cfg, ref = served
    res = run_serve_plan(_force_two_stages(plan), backend="emulated",
                         seed=0, trace=True)
    phases = {s.phase for s in res.trace.spans}
    assert phases == {"prefill", "decode"}
    assert res.trace.meta["workload"] == "serve"


def test_unknown_backend_rejected(served):
    _, plan, _, _ = served
    with pytest.raises(ValueError, match="serving backend"):
        run_serve_plan(plan, backend="warp-drive")


# ------------------------------------------------------------------ planner
def test_planner_round_trip(tmp_path, served):
    model, plan, cfg, _ = served
    assert plan.workload == "serve"
    assert plan.serving["slo_s"] == 60.0
    assert plan.serving["t_request"] <= 60.0
    path = tmp_path / "serve_plan.json"
    plan.save(path)
    back = DeploymentPlan.load(path)
    assert back == plan
    assert back.content_hash == plan.content_hash
    rp = back.resolve()
    assert rp.config.x == plan.x
    # and the round-tripped plan still executes
    res = run_serve_plan(back, backend="emulated", seed=0)
    assert res.tokens.shape == (BATCH, NEW)


def test_train_plan_json_defaults_workload():
    # plans saved before the serving subsystem load as workload="train"
    plan = plan_serving(ARCHS[0], "aws", slo=60.0, batch=1,
                        prefill_tokens=4, new_tokens=2)
    doc = json.loads(plan.to_json())
    del doc["workload"], doc["serving"]
    old = DeploymentPlan.from_json(json.dumps(doc))
    assert old.workload == "train" and old.serving is None


def test_infeasible_slo_named_error():
    with pytest.raises(InfeasibleSLOError, match="SLO"):
        plan_serving(ARCHS[0], "aws", slo=1e-6, prefill_tokens=4,
                     new_tokens=2)
    # callers catching the planner's generic infeasibility still catch it
    assert issubclass(InfeasibleSLOError, InfeasiblePlanError)


def test_session_serve_front_door():
    s = session(ARCHS[0]).plan(workload="serve", slo=60.0, serve_batch=1,
                               prefill_tokens=4, new_tokens=2)
    plan = s.deployment_plan
    assert plan.workload == "serve" and plan.serving["batch"] == 1
    assert s.plan_result is None
    with pytest.raises(ValueError, match="slo"):
        session(ARCHS[0]).plan(workload="serve")
    with pytest.raises(ValueError, match="workload"):
        session(ARCHS[0]).plan(workload="batch-train")


def test_paper_models_rejected():
    with pytest.raises(KeyError, match="executable architecture"):
        plan_serving("bert-large", "aws", slo=60.0)


def test_serving_spec_validation():
    with pytest.raises(ValueError):
        ServingSpec(slo_s=0.0, batch=1, prefill_tokens=4, new_tokens=2)
    with pytest.raises(ValueError):
        ServingSpec(slo_s=1.0, batch=1, prefill_tokens=4, new_tokens=0)
    spec = ServingSpec(slo_s=1.0, batch=2, prefill_tokens=4, new_tokens=2)
    assert spec.s_ctx == 6


def test_estimate_counts_kv_in_memory(served):
    model, plan, cfg, _ = served
    spec = ServingSpec(slo_s=60.0, batch=BATCH, prefill_tokens=PREFILL,
                       new_tokens=NEW)
    kv = kv_bytes_per_instance(cfg, spec.batch, spec.s_ctx)
    assert kv > 0
    rp = plan.resolve()
    est = estimate_serving(rp.profile, rp.platform, rp.config, cfg, spec)
    assert est.kv_bytes and sum(est.kv_bytes) > 0
    assert est.t_request == pytest.approx(
        est.t_prefill + (NEW - 1) * est.t_token)


# ------------------------------------------------- workload guard rails
def test_training_entry_points_reject_serve_plans(served):
    _, plan, _, _ = served
    from repro.serverless.runtime import run_plan

    for call in (plan.evaluate, plan.simulate, plan.emulate,
                 lambda: run_plan(plan)):
        with pytest.raises(PlanCompatibilityError, match="serve"):
            call()


def test_serving_entry_points_reject_train_plans(served):
    _, plan, _, _ = served
    train_plan = dataclasses.replace(plan, workload="train", serving=None)
    with pytest.raises(PlanCompatibilityError, match="workload"):
        run_serve_plan(train_plan)
    with pytest.raises(PlanCompatibilityError, match="workload"):
        autoscale_plan(train_plan)


# -------------------------------------------------------------- autoscaling
def test_arrival_processes_deterministic():
    a = poisson_arrivals(2.0, 30.0, seed=7)
    b = poisson_arrivals(2.0, 30.0, seed=7)
    assert np.array_equal(a, b)
    assert len(a) and a[-1] < 30.0 and np.all(np.diff(a) >= 0)
    assert not np.array_equal(a, poisson_arrivals(2.0, 30.0, seed=8))
    c = bursty_arrivals(2.0, 30.0, seed=7)
    assert np.array_equal(c, bursty_arrivals(2.0, 30.0, seed=7))
    assert len(c) and c[-1] < 30.0


def test_trace_arrivals(tmp_path):
    p = tmp_path / "gaps.txt"
    p.write_text("# prod trace\n0.5\n0.25\n\n1.0\n")
    assert np.allclose(trace_arrivals(str(p)), [0.5, 0.75, 1.75])
    (tmp_path / "empty.txt").write_text("# nothing\n")
    with pytest.raises(ValueError, match="no inter-arrival"):
        trace_arrivals(str(tmp_path / "empty.txt"))


def test_simulate_replicas_queueing():
    arrivals = np.arange(10, dtype=np.float64)  # 1 req/s, back to back
    row = simulate_replicas(arrivals, replicas=2, t_request=1.5, slo_s=2.0,
                            mem_gb_total=1.0, price_per_gb_s=1e-4,
                            cold_start_s=0.0)
    assert row.requests == 10 and row.cold_starts == 2
    assert row.p50 >= 1.5 and 0.0 <= row.slo_violation_frac <= 1.0
    assert row.cost == pytest.approx(1e-4 * 1.0 * 10 * 1.5)
    # more replicas never increase tail latency on the same trace
    worse = simulate_replicas(arrivals, replicas=1, t_request=1.5, slo_s=2.0,
                              mem_gb_total=1.0, price_per_gb_s=1e-4,
                              cold_start_s=0.0)
    assert worse.p95 >= row.p95


def test_autoscale_plan_rows_deterministic(served):
    _, plan, _, _ = served
    kw = dict(rate=2.0, horizon=60.0, replicas=(1, 3), arrival="bursty",
              seed=3)
    rows = autoscale_plan(plan, **kw)
    again = autoscale_plan(plan, **kw)
    assert [r.as_dict() for r in rows] == [r.as_dict() for r in again]
    assert [r.replicas for r in rows] == [1, 3]
    assert all(r.requests == rows[0].requests for r in rows)


# -------------------------------------------------- pallas decode satellite
def test_pallas_decode_attention_parity():
    from repro.kernels import ref
    from repro.kernels.decode_attention import decode_attention

    key = jax.random.PRNGKey(3)
    B, Hq, Hkv, C, hd = 2, 4, 2, 32, 16
    q = jax.random.normal(key, (B, Hq, hd), jnp.float32)
    k = jax.random.normal(jax.random.fold_in(key, 1), (B, Hkv, C, hd))
    v = jax.random.normal(jax.random.fold_in(key, 2), (B, Hkv, C, hd))
    out = decode_attention(q, k, v, jnp.int32(20), interpret=True)
    expect = ref.decode_attention_ref(q, k, v, jnp.int32(20))
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=2e-5, atol=2e-5)


def test_pallas_capability_probe():
    from repro.kernels import ops as kops

    ok = dict(n_q_heads=8, n_kv_heads=2, capacity=512)
    assert kops.decode_attention_capable(**ok)
    assert kops.decode_attention_capable(**{**ok, "capacity": 64})
    assert kops.decode_attention_capable(**{**ok, "capacity": 1024})
    assert not kops.decode_attention_capable(**{**ok, "capacity": 520})
    assert not kops.decode_attention_capable(**{**ok, "window": 128})
    assert not kops.decode_attention_capable(**{**ok, "seq_shards": 2})
    assert not kops.decode_attention_capable(
        n_q_heads=6, n_kv_heads=4, capacity=512)


def test_serve_with_pallas_decode(served, monkeypatch):
    # the wired decode path: capability-probed Pallas attention per layer
    # (interpret mode on CPU), same greedy tokens as the jnp path
    model, plan, cfg, ref = served
    monkeypatch.setenv("REPRO_KERNEL_MODE", "interpret")
    res = run_serve_plan(plan, backend="emulated", seed=0, use_pallas=True)
    assert np.array_equal(res.tokens, ref)


# ------------------------------------------- mesh-pipelined serve_equiv
def test_serve_equiv_module(multidev):
    # a (4, 2) mesh needs 8 devices: run in the fake-device subprocess
    out = multidev("serve_equiv", "phi3-mini-3.8b", 2, 1, 1)
    assert "decode_err" in out


# ------------------------------------------------------------ worker pieces
def test_greedy_token_rule():
    logits = np.zeros((2, 3, 5), np.float32)
    logits[0, -1, 4] = 1.0
    logits[1, -1, 2] = 1.0
    tok = greedy_token(logits)
    assert tok.shape == (2, 1) and tok.dtype == np.int32
    assert tok[0, 0] == 4 and tok[1, 0] == 2
