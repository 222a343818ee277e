"""Unified deployment API: DeploymentPlan JSON round-trip + content hash,
fingerprint compatibility guard, Session fluency, parity of the plan-replay
paths against the raw (profile, platform, config, M) call paths, and smoke
tests for every ``python -m repro`` CLI subcommand."""
import dataclasses
import json

import pytest

from _hypo import given, settings, st

from repro.api import DeploymentPlan, PlanCompatibilityError, session
from repro.api.plan import profile_fingerprint
from repro.cli import main as cli_main
from repro.core import planner
from repro.core.partition import merge_layers
from repro.core.perfmodel import evaluate
from repro.core.profiler import paper_model_profile, resolve_profile
from repro.serverless.platform import ALIBABA_FC, AWS_LAMBDA
from repro.serverless.runtime import run_plan
from repro.serverless.simulator import simulate_funcpipe

ALPHA = (1.0, 2**16 * 1e-9)
FAST = dict(merge_to=6, d_options=(1, 2, 4))


@pytest.fixture(scope="module")
def bert_session():
    return session("bert-large", platform="aws", global_batch=64).plan(
        alpha=ALPHA, **FAST)


# ----------------------------------------------------------- serialization
def test_json_round_trip_and_stable_hash(bert_session):
    plan = bert_session.deployment_plan
    blob = plan.to_json()
    again = DeploymentPlan.from_json(blob)
    assert again == plan
    assert again.content_hash == plan.content_hash
    # hash is over content: provenance timing must not affect it
    assert dataclasses.replace(plan, solve_seconds=99.0).content_hash \
        == plan.content_hash
    # ... but decisions must
    assert dataclasses.replace(plan, d=plan.d * 2).content_hash \
        != plan.content_hash


def test_from_json_rejects_bad_schema(bert_session):
    d = json.loads(bert_session.deployment_plan.to_json())
    with pytest.raises(PlanCompatibilityError):
        DeploymentPlan.from_json(json.dumps({**d, "version": 99}))
    with pytest.raises(PlanCompatibilityError):
        DeploymentPlan.from_json(json.dumps({**d, "surprise": 1}))
    d.pop("x")
    with pytest.raises(PlanCompatibilityError):
        DeploymentPlan.from_json(json.dumps(d))


@settings(max_examples=30, deadline=None)
@given(st.data())
def test_round_trip_property(data):
    """Any plan-shaped value survives to_json/from_json exactly, and equal
    plans hash equal (solver provenance aside)."""
    L = data.draw(st.integers(min_value=2, max_value=8))
    x = tuple(data.draw(st.integers(0, 1)) for _ in range(L - 1))
    z = tuple(data.draw(st.integers(0, 7)) for _ in range(L))
    plan = DeploymentPlan(
        model=data.draw(st.sampled_from(["bert-large", "resnet101", "m"])),
        platform=data.draw(st.sampled_from(["aws_lambda", "alibaba_fc"])),
        x=x, z=z, d=data.draw(st.sampled_from([1, 2, 4, 8])),
        total_micro_batches=data.draw(st.integers(1, 64)),
        alpha=(1.0, data.draw(st.floats(0, 1e-2, allow_nan=False))),
        pipelined_sync=data.draw(st.booleans()),
        merge_to=data.draw(st.one_of(st.none(), st.integers(2, 16))),
        seq=data.draw(st.one_of(st.none(), st.integers(8, 512))),
        micro_batch=data.draw(st.one_of(st.none(), st.integers(1, 8))),
        profile_fingerprint="ab" * 8,
        t_iter=data.draw(st.floats(0, 1e4, allow_nan=False)),
        c_iter=data.draw(st.floats(0, 1e2, allow_nan=False)),
        objective=data.draw(st.floats(0, 1e4, allow_nan=False)),
        solver="cd", engine="batch",
        solve_seconds=data.draw(st.floats(0, 1e3, allow_nan=False)),
    )
    again = DeploymentPlan.from_json(plan.to_json())
    assert again == plan
    assert again.content_hash == plan.content_hash


# ----------------------------------------------------- dp-engine plan artifact
@pytest.fixture(scope="module")
def dp_session():
    return session("bert-large", platform="aws", global_batch=64).plan(
        alpha=ALPHA, engine="dp", **FAST)


def test_dp_plan_round_trip_and_replay(dp_session):
    """A DeploymentPlan produced by engine='dp' survives JSON exactly and
    replays through the simulator and the storage-backed engine."""
    plan = dp_session.deployment_plan
    assert plan.engine == "dp"
    again = DeploymentPlan.from_json(plan.to_json())
    assert again == plan
    assert again.content_hash == plan.content_hash
    sim = plan.simulate()
    eng = plan.emulate(steps=1)
    assert sim.t_iter > 0 and eng.t_iter > 0
    # solver-predicted numbers replay: simulate tracks the closed form
    assert sim.t_iter == pytest.approx(plan.t_iter, rel=0.1)


def test_content_hash_stable_across_engines(dp_session, bert_session):
    """Identical decisions hash identically whatever engine found them:
    solver/engine/solve_seconds are provenance, excluded from the hash."""
    plan = dp_session.deployment_plan
    for prov in (dict(engine="batch"), dict(solver="exhaustive"),
                 dict(solve_seconds=1234.5)):
        assert dataclasses.replace(plan, **prov).content_hash \
            == plan.content_hash
    assert dataclasses.replace(plan, z=tuple(plan.z[::-1])).content_hash \
        != plan.content_hash
    # at this depth the CD heuristic finds the DP optimum, so the two
    # engines' plans are the same deployment — and hash the same
    batch_plan = bert_session.deployment_plan
    assert (batch_plan.x, batch_plan.z, batch_plan.d) \
        == (plan.x, plan.z, plan.d)
    assert batch_plan.content_hash == plan.content_hash


def test_dp_full_depth_plan_records_unmerged(tmp_path):
    """merge_to=None round-trips and resolves against the unmerged profile."""
    s = session("bert-large", platform="aws", global_batch=32).plan(
        alpha=ALPHA, engine="dp", merge_to=None, d_options=(1, 2))
    plan = s.deployment_plan
    assert plan.merge_to is None
    assert len(plan.z) == resolve_profile("bert-large", AWS_LAMBDA).L
    path = tmp_path / "plan_dp.json"
    plan.save(path)
    loaded = DeploymentPlan.load(path)
    assert loaded == plan
    loaded.resolve()                      # fingerprint-checked rebuild
    assert loaded.simulate().t_iter > 0


# ------------------------------------------------------------- fingerprint
def test_resolve_profile_reduced_arch_spelling():
    """The numeric emulation mode records `<arch>@reduced<L>`; it must
    resolve to the same profile the mode built, so saved plans replay."""
    import dataclasses as dc

    from repro.configs import get_config
    from repro.core.profiler import arch_model_profile

    cfg = dc.replace(get_config("phi3-mini-3.8b").reduced(), n_layers=4)
    direct = arch_model_profile(cfg, AWS_LAMBDA, seq=16, micro_batch=2)
    via_id = resolve_profile("phi3-mini-3.8b@reduced4", AWS_LAMBDA,
                             seq=16, micro_batch=2)
    assert profile_fingerprint(via_id) == profile_fingerprint(direct)
    with pytest.raises(KeyError):
        resolve_profile("phi3-mini-3.8b@huge", AWS_LAMBDA)


def test_depth_spelling_keeps_published_widths():
    """`@depth<L>` cuts depth only: every published width of phi3 stays and
    n_layers is the one field that changes."""
    from repro.configs import get_config, resolve_arch

    full = get_config("phi3-mini-3.8b")
    cut = resolve_arch("phi3-mini-3.8b@depth2")
    assert cut == dataclasses.replace(full, n_layers=2)
    changed = {f.name for f in dataclasses.fields(full)
               if getattr(full, f.name) != getattr(cut, f.name)}
    assert changed == {"n_layers"}
    assert resolve_arch("phi3-mini-3.8b") == full


def test_reduced_spelling_unchanged():
    from repro.configs import get_config, resolve_arch

    red = get_config("qwen2.5-14b").reduced()
    assert resolve_arch("qwen2.5-14b@reduced") == red
    assert resolve_arch("qwen2.5-14b@reduced4") == dataclasses.replace(red, n_layers=4)


@pytest.mark.parametrize("bad", [
    "phi3-mini-3.8b@depth0", "phi3-mini-3.8b@depth33",
    "phi3-mini-3.8b@depthx", "phi3-mini-3.8b@depth", "phi3-mini-3.8b@wide2",
    "jamba-v0.1-52b@depth4", "bert-large@depth2", "no-such-arch"])
def test_bad_spellings_raise_key_error(bad):
    from repro.configs import resolve_arch

    with pytest.raises(KeyError):
        resolve_arch(bad)


def test_depth_plan_round_trips_resolve(tmp_path):
    """A plan recorded at `@depth2` replays through resolve() to the profile
    it was built on (fingerprint-checked), also after a JSON round trip."""
    from repro.api.numeric import numeric_partition
    from repro.configs import resolve_arch
    from repro.core.perfmodel import Config
    from repro.core.profiler import arch_model_profile

    cfg = resolve_arch("phi3-mini-3.8b@depth2")
    prof = arch_model_profile(cfg, AWS_LAMBDA, seq=512, micro_batch=2)
    x = numeric_partition(cfg, 2)
    plan = DeploymentPlan.from_config(
        prof, AWS_LAMBDA, Config(x=x, d=1, z=(3,) * prof.L), 4,
        model="phi3-mini-3.8b@depth2", seq=512, micro_batch=2)
    assert x == (0, 1, 0)
    path = tmp_path / "depth2.json"
    plan.save(path)
    rp = DeploymentPlan.load(path).resolve()
    assert profile_fingerprint(rp.profile) == profile_fingerprint(prof)
    assert rp.profile.L == 4


def test_numeric_plan_records_a_replayable_spelling():
    from repro.api import numeric_plan

    plan, prof, ex = numeric_plan("phi3-mini-3.8b@reduced4", stages=2, dp=2,
                                  batch=8, seq=16)
    assert plan.model == "phi3-mini-3.8b@reduced4"
    assert (plan.n_stages, plan.d, plan.total_micro_batches) == (2, 2, 4)
    assert ex.cfg.n_layers == 4 and ex.cfg.d_model <= 256
    rp = plan.resolve()               # rebuilt from the recorded spelling
    assert profile_fingerprint(rp.profile) == profile_fingerprint(prof)
    with pytest.raises(ValueError, match="stages"):
        numeric_plan("phi3-mini-3.8b@reduced4", stages=5, dp=1, batch=8)
    with pytest.raises(ValueError, match="divisible"):
        numeric_plan("phi3-mini-3.8b@reduced4", stages=2, dp=3, batch=8)


def test_fingerprint_tracks_profile_content():
    a = paper_model_profile("bert-large", AWS_LAMBDA)
    b = paper_model_profile("bert-large", AWS_LAMBDA)
    assert profile_fingerprint(a) == profile_fingerprint(b)
    assert profile_fingerprint(a) != profile_fingerprint(
        paper_model_profile("bert-large", ALIBABA_FC))
    assert profile_fingerprint(a) != profile_fingerprint(merge_layers(a, 8))


def test_fingerprint_catches_platform_drift(bert_session):
    """Pricing/bandwidth/latency drift doesn't change the layer tables, but
    a replayed plan must still refuse: the platform is folded into the
    recorded fingerprint."""
    plan = bert_session.deployment_plan
    drifted = dataclasses.replace(AWS_LAMBDA, price_per_gb_s=1e-3)
    prof = merge_layers(
        resolve_profile("bert-large", AWS_LAMBDA), plan.merge_to)
    plan.resolve(profile=prof, platform=AWS_LAMBDA)        # unchanged: fine
    with pytest.raises(PlanCompatibilityError, match="fingerprint"):
        plan.resolve(profile=prof, platform=drifted)


def test_mismatched_fingerprint_raises(bert_session):
    plan = bert_session.deployment_plan
    bad = dataclasses.replace(plan, profile_fingerprint="0" * 16)
    with pytest.raises(PlanCompatibilityError, match="fingerprint mismatch"):
        bad.resolve()
    with pytest.raises(PlanCompatibilityError):
        bad.simulate()
    # a plan replayed against the wrong platform must refuse too
    wrong = dataclasses.replace(plan, platform="alibaba_fc")
    with pytest.raises(PlanCompatibilityError):
        wrong.resolve()
    # unknown model / platform names give the clear error, not KeyError
    with pytest.raises(PlanCompatibilityError):
        dataclasses.replace(plan, model="no-such-model").resolve()
    with pytest.raises(PlanCompatibilityError):
        dataclasses.replace(plan, platform="no-such-cloud").resolve()


# ------------------------------------------------------------------ parity
def test_replay_matches_in_memory_paths_exactly(bert_session):
    """simulate/emulate through the DeploymentPlan front door must be
    bit-identical to the old hand-threaded (profile, platform, config, M)
    call paths — including after a JSON round trip."""
    plan = DeploymentPlan.from_json(bert_session.deployment_plan.to_json())
    prof = merge_layers(
        resolve_profile("bert-large", AWS_LAMBDA), plan.merge_to)
    M = plan.total_micro_batches
    r = planner.solve(prof, AWS_LAMBDA, alpha=ALPHA, total_micro_batches=M,
                      merge_to=plan.merge_to, d_options=FAST["d_options"])
    assert r.config == plan.config

    old_sim = simulate_funcpipe(r.profile, AWS_LAMBDA, r.config, M)
    old_eng = run_plan(r.profile, AWS_LAMBDA, r.config, M, steps=2)
    old_ev = evaluate(r.profile, AWS_LAMBDA, r.config, M)

    assert plan.simulate().t_iter == old_sim.t_iter
    assert plan.simulate().cost == old_sim.cost
    assert simulate_funcpipe(plan).t_iter == old_sim.t_iter  # direct accept
    assert plan.emulate(steps=2).t_iter == old_eng.t_iter
    assert run_plan(plan, steps=2).t_iter == old_eng.t_iter  # direct accept
    assert plan.evaluate().t_iter == old_ev.t_iter
    assert plan.t_iter == old_ev.t_iter


def test_funcpipe_baseline_accepts_deployment_plans(bert_session):
    from repro.serverless import frameworks

    plan = bert_session.deployment_plan
    res = frameworks.funcpipe_replay([plan, plan])
    assert len(res.sims) == 1                       # deduped identical configs
    assert res.deployment_plans == [plan]
    assert res.recommended_sim.t_iter == plan.simulate().t_iter


# ----------------------------------------------------------------- session
def test_session_fluent_chain(bert_session):
    s = bert_session.simulate().emulate(steps=1)
    assert s.sim_result is not None and s.engine_result is not None
    assert s.sim_result.t_iter == pytest.approx(s.deployment_plan.t_iter)
    assert s.plan_result.config == s.deployment_plan.config


def test_session_save_load_and_drift_guard(tmp_path):
    s = session("bert-large", platform="aws", global_batch=32).plan(
        alpha=ALPHA, **FAST)
    path = tmp_path / "plan.json"
    s.save_plan(path)
    s2 = session("bert-large", platform="aws", global_batch=32).load_plan(path)
    assert s2.deployment_plan == s.deployment_plan

    # a session whose freshly-built profile differs must refuse the plan
    blob = json.loads(path.read_text())
    blob["profile_fingerprint"] = "f" * 16
    path.write_text(json.dumps(blob))
    with pytest.raises(PlanCompatibilityError):
        session("bert-large", platform="aws", global_batch=32).load_plan(path)


def test_session_sweep_recommends():
    s = session("bert-large", platform="aws", global_batch=32).sweep(**FAST)
    assert len(s.plans) >= 1
    assert s.deployment_plan is s.plans[s.recommended]
    # every solver path produces a plan artifact
    for solver in ("tpdmp", "bayes"):
        s.plan(alpha=ALPHA, solver=solver, merge_to=6)
        assert s.deployment_plan.solver == solver


# --------------------------------------------------------------- plan cache
def test_plan_cache_hits_and_returns_identical_plan(tmp_path):
    cache_dir = tmp_path / "plans"
    s1 = session("bert-large", platform="aws", global_batch=64,
                 plan_cache=cache_dir).plan(alpha=ALPHA, **FAST)
    assert s1.plan_cache.misses == 1 and s1.plan_cache.hits == 0
    assert list(cache_dir.glob("plan-*.json"))

    s2 = session("bert-large", platform="aws", global_batch=64,
                 plan_cache=cache_dir).plan(alpha=ALPHA, **FAST)
    assert s2.plan_cache.hits == 1 and s2.plan_cache.misses == 0
    assert s2.deployment_plan == s1.deployment_plan
    assert s2.deployment_plan.content_hash == s1.deployment_plan.content_hash
    # the in-memory twin is rebuilt on hits, so sweep/recommend still work
    assert s2.plan_result.config == s1.plan_result.config
    assert s2.plan_result.objective == pytest.approx(s1.plan_result.objective)


def test_plan_cache_keys_on_solver_inputs(tmp_path):
    cache_dir = tmp_path / "plans"
    kw = dict(platform="aws", global_batch=64, plan_cache=cache_dir)
    session("bert-large", **kw).plan(alpha=ALPHA, **FAST)
    # a different objective weight must miss, not alias
    s = session("bert-large", **kw).plan(alpha=(1.0, 0.0), **FAST)
    assert s.plan_cache.hits == 0 and s.plan_cache.misses == 1
    # a different batch budget too
    s = session("bert-large", platform="aws", global_batch=32,
                plan_cache=cache_dir).plan(alpha=ALPHA, **FAST)
    assert s.plan_cache.hits == 0


def test_plan_cache_corrupt_entry_degrades_to_solve(tmp_path):
    cache_dir = tmp_path / "plans"
    s1 = session("bert-large", platform="aws", global_batch=64,
                 plan_cache=cache_dir).plan(alpha=ALPHA, **FAST)
    entry = next(cache_dir.glob("plan-*.json"))
    entry.write_text("{not json")
    s2 = session("bert-large", platform="aws", global_batch=64,
                 plan_cache=cache_dir).plan(alpha=ALPHA, **FAST)
    assert s2.plan_cache.hits == 0 and s2.plan_cache.misses == 1
    # re-solved (solve_seconds is fresh provenance) to the identical decision
    assert s2.deployment_plan.content_hash == s1.deployment_plan.content_hash
    assert not entry.exists() or json.loads(entry.read_text())


def test_plan_cache_drifted_entry_counts_as_miss(tmp_path):
    """An entry that parses but fails the resolve check (fingerprint drift)
    must be evicted and counted as a miss, not a hit — the hit counter is
    what the CLI (and the CI cache gate) reports."""
    cache_dir = tmp_path / "plans"
    session("bert-large", platform="aws", global_batch=64,
            plan_cache=cache_dir).plan(alpha=ALPHA, **FAST)
    entry = next(cache_dir.glob("plan-*.json"))
    blob = json.loads(entry.read_text())
    blob["profile_fingerprint"] = "f" * 16
    entry.write_text(json.dumps(blob))
    s2 = session("bert-large", platform="aws", global_batch=64,
                 plan_cache=cache_dir).plan(alpha=ALPHA, **FAST)
    assert s2.plan_cache.hits == 0 and s2.plan_cache.misses == 1
    assert s2.deployment_plan is not None    # re-solved
    # the drifted entry was evicted and replaced by the fresh solve
    fresh = json.loads(next(cache_dir.glob("plan-*.json")).read_text())
    assert fresh["profile_fingerprint"] != "f" * 16


def test_plan_cache_sweep_near_instant_on_rerun(tmp_path):
    cache_dir = tmp_path / "plans"
    s1 = session("bert-large", platform="aws", global_batch=32,
                 plan_cache=cache_dir).sweep(**FAST)
    n_solved = s1.plan_cache.misses
    assert n_solved >= 1
    s2 = session("bert-large", platform="aws", global_batch=32,
                 plan_cache=cache_dir).sweep(**FAST)
    assert s2.plan_cache.misses == 0 and s2.plan_cache.hits >= n_solved
    assert [p.content_hash for p in s2.plans] == \
        [p.content_hash for p in s1.plans]
    assert s2.recommended == s1.recommended


def test_cli_no_plan_cache_flag(tmp_path, capsys, monkeypatch):
    monkeypatch.setenv("REPRO_PLAN_CACHE", str(tmp_path / "cli-cache"))
    out1 = _run_cli(capsys, "plan", "--model", "bert-large", "--batch", "64",
                    "--fast")
    assert "[plan cache hit]" not in out1
    out2 = _run_cli(capsys, "plan", "--model", "bert-large", "--batch", "64",
                    "--fast")
    assert "[plan cache hit]" in out2
    out3 = _run_cli(capsys, "plan", "--model", "bert-large", "--batch", "64",
                    "--fast", "--no-plan-cache")
    assert "[plan cache hit]" not in out3


def test_session_rejects_unknown(tmp_path):
    with pytest.raises(KeyError):
        session("bert-large", platform="nope")
    with pytest.raises(KeyError):
        session("no-such-model").profile()
    with pytest.raises(ValueError):
        session("bert-large").plan(solver="gurobi", **FAST)
    with pytest.raises(ValueError, match="bayes"):
        session("bert-large").plan(solver="bayes", engine="dp", **FAST)


# --------------------------------------------------------------- CLI smoke
def _run_cli(capsys, *argv):
    rc = cli_main(list(argv))
    out = capsys.readouterr().out
    assert rc == 0, out
    return out


def test_cli_plan_simulate_emulate_replay(tmp_path, capsys):
    """Acceptance path: `repro plan -o f` then `repro simulate f` and
    `repro emulate f` replay the saved JSON bit-identically."""
    path = tmp_path / "plan.json"
    out = _run_cli(capsys, "plan", "--model", "bert-large", "--batch", "64",
                   "--fast", "-o", str(path))
    assert "wrote" in out
    plan = DeploymentPlan.load(path)

    sim_out = _run_cli(capsys, "simulate", str(path))
    eng_out = _run_cli(capsys, "emulate", str(path), "--steps", "2")
    sim = plan.simulate()
    eng = plan.emulate(steps=2)
    assert f"t_iter={sim.t_iter:.3f}s" in sim_out
    assert f"cost=${sim.cost:.6f}/iter" in sim_out
    assert f"t_iter={eng.t_iter:.3f}s" in eng_out
    assert plan.content_hash in sim_out


def test_cli_plan_engine_dp(tmp_path, capsys):
    """`repro plan --engine dp` plans at full depth by default, records the
    engine in the artifact, and the saved plan replays."""
    path = tmp_path / "plan_dp.json"
    out = _run_cli(capsys, "plan", "--model", "amoebanet-d18", "--batch", "32",
                   "--engine", "dp", "-o", str(path))
    assert "dp" in out
    plan = DeploymentPlan.load(path)
    assert plan.engine == "dp" and plan.merge_to is None
    _run_cli(capsys, "simulate", str(path))


def test_cli_sweep_engine_dp(capsys):
    out = _run_cli(capsys, "sweep", "--model", "amoebanet-d18", "--batch",
                   "16", "--engine", "dp", "--merge-to", "8")
    assert "engine=dp" in out
    assert "RECOMMENDED" in out


def test_cli_sweep(capsys, tmp_path):
    out = _run_cli(capsys, "sweep", "--model", "bert-large", "--batch", "32",
                   "--fast", "--save-dir", str(tmp_path / "plans"))
    assert "RECOMMENDED" in out
    assert "alpha2=" in out
    saved = list((tmp_path / "plans").glob("*.json"))
    assert saved, "sweep --save-dir wrote no plans"
    for p in saved:
        DeploymentPlan.load(p).resolve()    # all replayable


def test_cli_bench_list(capsys):
    out = _run_cli(capsys, "bench", "--list")
    assert "runtime_accuracy" in out and "planner" in out


def test_cli_train_dryrun_help(capsys):
    # the front door lists every subcommand (train/dryrun are pass-through;
    # importing repro.launch.dryrun sets XLA_FLAGS, so only `train --help`
    # is exercised in-process)
    with pytest.raises(SystemExit) as e:
        cli_main(["--help"])
    assert e.value.code == 0
    out = capsys.readouterr().out
    for sub in ("plan", "simulate", "emulate", "sweep", "bench", "train",
                "dryrun"):
        assert sub in out
    with pytest.raises(SystemExit) as e:
        cli_main(["train", "--help"])
    assert e.value.code == 0
    assert "usage" in capsys.readouterr().out.lower()


def test_launch_emulate_shim(capsys):
    from repro.launch import emulate

    rc = emulate.main(["--model", "bert-large", "--batch", "16", "--fast",
                       "--steps", "1"])
    assert rc == 0
    assert "engine[emulated]:" in capsys.readouterr().out


# --------------------------------------------------------- execution config
def test_execution_config_validation():
    from repro.serverless.execution import ExecutionConfig

    with pytest.raises(ValueError, match="steps"):
        ExecutionConfig(steps=0)
    with pytest.raises(ValueError, match="bandwidth"):
        ExecutionConfig(bandwidth=-1.0)
    with pytest.raises(ValueError, match="retries"):
        ExecutionConfig(retries=0)
    with pytest.raises(ValueError, match="checkpoint_every"):
        ExecutionConfig(checkpoint_every=0)
    # an explicit bandwidth is only meaningful as a throttle rate
    assert ExecutionConfig(bandwidth=1e6).throttle
    # the process-backend rule lives in ONE place: resolve_backend
    for bad in (ExecutionConfig(payload_true=True),
                ExecutionConfig(throttle=True),
                ExecutionConfig(bandwidth=1e6)):
        with pytest.raises(ValueError, match="process"):
            bad.resolve_backend()
    # ...and a process backend resolves configured
    be = ExecutionConfig(backend="process", payload_true=True,
                         bandwidth=2e6).resolve_backend()
    assert be.payload_true and be.throttle and be.bandwidth == 2e6


def test_execution_config_json_round_trip():
    from repro.serverless import faults as F
    from repro.serverless.backends import get_backend
    from repro.serverless.execution import ExecutionConfig

    ec = ExecutionConfig(
        backend="process", steps=3, trace=True, payload_true=True,
        bandwidth=1e6,
        faults=F.FaultPlan(events=(
            F.FaultEvent(kind="transient", stage=0, replica=0, step=0,
                         op="put", index=0),)),
        tolerance=F.FaultTolerance(retry=F.RetryPolicy(max_attempts=2)),
        checkpoint_every=2)
    again = ExecutionConfig.from_json(ec.to_json())
    assert again == ec
    with pytest.raises(ValueError, match="version"):
        ExecutionConfig.from_json(json.dumps({"version": 99}))
    with pytest.raises(ValueError, match="unknown"):
        ExecutionConfig.from_json(json.dumps({"version": 1, "surprise": 1}))
    # instance backends execute but do not serialize
    inst = ExecutionConfig(backend=get_backend("emulated"))
    with pytest.raises(TypeError, match="instance"):
        inst.to_json()


def test_emulate_legacy_kwargs_shim_bit_identical(bert_session):
    from repro.serverless.execution import ExecutionConfig

    plan = bert_session.deployment_plan
    with pytest.warns(DeprecationWarning, match="deprecated"):
        legacy = plan.emulate(steps=2)
    new = plan.emulate(ExecutionConfig(steps=2))
    assert legacy.t_iter == new.t_iter
    assert legacy.t_total == new.t_total
    assert legacy.store_stats.bytes_in == new.store_stats.bytes_in


def test_emulate_rejects_mixed_spellings(bert_session):
    from repro.serverless.execution import ExecutionConfig

    with pytest.raises(ValueError, match="not both"):
        bert_session.deployment_plan.emulate(ExecutionConfig(steps=1),
                                             steps=2)


def test_run_plan_legacy_shim_matches_config(bert_session):
    from repro.serverless.execution import ExecutionConfig

    rp = bert_session.deployment_plan.resolve()
    with pytest.warns(DeprecationWarning):
        legacy = run_plan(rp.profile, rp.platform, rp.config,
                          rp.total_micro_batches, steps=1,
                          pipelined_sync=rp.pipelined_sync)
    new = run_plan(rp.profile, rp.platform, rp.config,
                   rp.total_micro_batches, ExecutionConfig(steps=1),
                   pipelined_sync=rp.pipelined_sync)
    assert legacy.t_iter == new.t_iter
    assert legacy.cost == new.cost


def test_traced_emulate_embeds_plan_document(bert_session):
    from repro.serverless.execution import ExecutionConfig

    plan = bert_session.deployment_plan
    res = plan.emulate(ExecutionConfig(steps=1, trace=True))
    doc = res.trace.meta.get("plan")
    assert doc is not None
    assert DeploymentPlan.from_json(json.dumps(doc)) == plan
    # calibration-relevant metadata rides along
    assert res.trace.meta["t_lat"] == AWS_LAMBDA.storage_latency
    assert res.trace.meta["payload_true"] is False
