"""Chaos harness + fault-tolerant engine: FaultPlan determinism and JSON
round-trips, bit-identical recovery through transients/crashes/lifetime caps
on all backends (including real SIGKILL'd worker processes on ``process``)
and both sync schedules, retry exhaustion, checkpoint wire hardening,
LocalStore leases/heartbeats, and recovery observability."""
import json
import os
import threading
import time

import numpy as np
import pytest

from test_backends import _assert_bit_identical, _numeric_setup, _timing_plan

from repro.serverless import faults as F
from repro.serverless.backends.local import LocalStore
from repro.serverless.platform import AWS_LAMBDA
from repro.serverless.runtime import run_plan
from repro.serverless.runtime.store import (
    ProducerDeadError,
    StoreAbortedError,
)

jax = pytest.importorskip("jax")


# --------------------------------------------------------------- fault plans
def test_fault_plan_generation_is_deterministic():
    kw = dict(steps=4, S=3, d=2, n_transient=3, n_crashes=2, n_stragglers=1,
              lifetime_steps=3)
    a = F.FaultPlan.generate(11, **kw)
    b = F.FaultPlan.generate(11, **kw)
    assert a == b
    assert a.counts() == {"transient": 3, "crash": 2, "straggle": 1,
                          "lifetime_steps": 3}
    # a different seed reshuffles the schedule (same shape)
    c = F.FaultPlan.generate(12, **kw)
    assert c != a and c.counts() == a.counts()


def test_fault_plan_json_round_trip(tmp_path):
    plan = F.FaultPlan.generate(5, steps=3, S=2, d=2, n_stragglers=1,
                                lifetime_steps=2)
    assert F.FaultPlan.from_json(plan.to_json()) == plan
    path = tmp_path / "plan.json"
    plan.save(path)
    assert F.FaultPlan.load(path) == plan
    # the file is plain JSON a human can edit
    doc = json.loads(path.read_text())
    assert doc["version"] == 1 and doc["seed"] == 5


def test_fault_plan_rejects_unknown_fields_and_versions():
    with pytest.raises(ValueError, match="unknown FaultEvent fields"):
        F.FaultEvent.from_dict({"kind": "crash", "stage": 0, "replica": 0,
                                "step": 0, "flavor": "spicy"})
    with pytest.raises(ValueError, match="version 1"):
        F.FaultPlan.from_json('{"version": 2, "events": []}')
    with pytest.raises(ValueError, match="version 1"):
        F.FaultPlan.from_json('[1, 2]')


def test_retry_policy_backoff_is_deterministic_and_capped():
    pol = F.RetryPolicy(max_attempts=4, base_delay_s=0.05, multiplier=2.0,
                        max_delay_s=0.12, jitter=0.25)
    d1 = [pol.delay(a, "k0/r0/m0/act0") for a in (1, 2, 3)]
    d2 = [pol.delay(a, "k0/r0/m0/act0") for a in (1, 2, 3)]
    assert d1 == d2                                   # pure function
    assert all(d <= 0.12 * 1.25 + 1e-12 for d in d1)  # cap (+jitter)
    assert pol.delay(1, "other-key") != d1[0]          # token-jittered
    assert F.RetryPolicy(jitter=0.0).delay(3) == pytest.approx(0.2)


# -------------------------------------------------- chaos parity (numerics)
def _chaos_plan():
    """Hand-built schedule covering every recovery path: a transient put, a
    transient get, a mid-bwd crash, and a 2-step function-lifetime cap."""
    return F.FaultPlan(events=(
        F.FaultEvent(kind="transient", stage=0, replica=0, step=0,
                     op="put", index=0),
        F.FaultEvent(kind="transient", stage=1, replica=1, step=1,
                     op="get", index=1),
        F.FaultEvent(kind="crash", stage=1, replica=0, step=1, phase="bwd"),
    ), lifetime_steps=2, seed=None)


_REFERENCE = {}


def _fault_free_run(pipelined):
    """Fault-free reference (params, losses), cached; emulated — backend
    parity of the clean run is test_backends' business."""
    if pipelined not in _REFERENCE:
        _, prof, config, _, _, _, mk_exec = _numeric_setup(steps=3)
        res = run_plan(prof, AWS_LAMBDA, config, 4, steps=3,
                       pipelined_sync=pipelined, execution=mk_exec(),
                       backend="emulated")
        _REFERENCE[pipelined] = (res.params, res.losses)
    return _REFERENCE[pipelined]


@pytest.mark.parametrize("backend", ["emulated", "local", "process"])
@pytest.mark.parametrize("pipelined", [True, False],
                         ids=["eq2-pipelined", "eq1-three-phase"])
def test_chaos_run_recovers_bit_identical(backend, pipelined):
    """Training through transients + a crash + a lifetime cap must land on
    exactly the fault-free params — recovery replays from store checkpoints
    and replayed programs are idempotent over store keys.  On the process
    backend the injected crash SIGKILLs a real OS worker process."""
    _, prof, config, _, _, _, mk_exec = _numeric_setup(steps=3)
    res = run_plan(prof, AWS_LAMBDA, config, 4, steps=3,
                   pipelined_sync=pipelined, execution=mk_exec(),
                   backend=backend, faults=_chaos_plan(),
                   tolerance=F.FaultTolerance(
                       retry=F.RetryPolicy(base_delay_s=0.01),
                       # force the injector's lifetime kill (not only the
                       # Function Manager's planned restarts) to exercise
                       # the crash-recovery path for the cap too
                       lifetime_safety=0.9))
    rep = res.fault_report
    assert rep is not None
    assert rep.injected.get("transient", 0) >= 1
    assert rep.injected.get("crash", 0) >= 1
    assert rep.retries >= 1
    assert rep.restarts + rep.planned_restarts >= 2   # crash + lifetime cap
    assert rep.checkpoints >= 1
    params, losses = _fault_free_run(pipelined)
    _assert_bit_identical(res.params, params)
    # losses replayed identically too (run_plan verified drained internally)
    assert res.losses == losses


def test_chaos_report_identical_across_backends():
    """The injection schedule is deterministic per worker per step, so both
    backends see the *same* faults — not just the same final params."""
    _, prof, config, _, _, _, mk_exec = _numeric_setup(steps=3)
    reports = {}
    for name in ("emulated", "local"):
        res = run_plan(prof, AWS_LAMBDA, config, 4, steps=3,
                       pipelined_sync=True, execution=mk_exec(),
                       backend=name, faults=_chaos_plan(),
                       tolerance=F.FaultTolerance(
                           retry=F.RetryPolicy(base_delay_s=0.01)))
        reports[name] = res.fault_report
    em, lo = reports["emulated"], reports["local"]
    assert em.injected == lo.injected
    assert em.retries == lo.retries
    assert em.checkpoints == lo.checkpoints
    assert em.resumed_steps == lo.resumed_steps


def test_execution_tolerance_field_enables_recovery():
    """``Execution.tolerance`` is an alternative to the run_plan kwarg."""
    import dataclasses

    _, prof, config, _, _, _, mk_exec = _numeric_setup(steps=2)
    ex = dataclasses.replace(mk_exec(), tolerance=F.FaultTolerance(
        retry=F.RetryPolicy(base_delay_s=0.01)))
    plan = F.FaultPlan(events=(
        F.FaultEvent(kind="transient", stage=0, replica=1, step=0,
                     op="get", index=0),))
    res = run_plan(prof, AWS_LAMBDA, config, 4, steps=2,
                   pipelined_sync=True, execution=ex, backend="emulated",
                   faults=plan)
    assert res.fault_report.retries == 1
    ref = run_plan(prof, AWS_LAMBDA, config, 4, steps=2,
                   pipelined_sync=True, execution=mk_exec(),
                   backend="emulated")
    _assert_bit_identical(res.params, ref.params)


# ----------------------------------------------------- budgets + exhaustion
def test_retry_exhaustion_raises_typed_error():
    _, prof, config, _, _, _, mk_exec = _numeric_setup(steps=2)
    plan = F.FaultPlan(events=(
        F.FaultEvent(kind="transient", stage=0, replica=0, step=0,
                     op="put", index=0, times=10),))
    with pytest.raises(F.FaultToleranceExceeded, match="still failing"):
        run_plan(prof, AWS_LAMBDA, config, 4, steps=2, pipelined_sync=True,
                 execution=mk_exec(), backend="emulated", faults=plan,
                 tolerance=F.FaultTolerance(
                     retry=F.RetryPolicy(max_attempts=3,
                                         base_delay_s=0.001)))


def test_restart_budget_exhaustion_raises_typed_error():
    _, prof, config, _, _, _, mk_exec = _numeric_setup(steps=2)
    # one crash per step/phase, far more than the restart budget
    events = tuple(
        F.FaultEvent(kind="crash", stage=0, replica=0, step=k, phase=ph)
        for k in range(2) for ph in ("fwd", "bwd"))
    with pytest.raises(F.FaultToleranceExceeded, match="max_restarts"):
        run_plan(prof, AWS_LAMBDA, config, 4, steps=2, pipelined_sync=True,
                 execution=mk_exec(), backend="emulated",
                 faults=F.FaultPlan(events=events),
                 tolerance=F.FaultTolerance(max_restarts=2))


def test_faults_without_tolerance_use_default_recovery():
    """Injecting faults implies a default FaultTolerance — chaos runs should
    not need recovery boilerplate to terminate."""
    prof, cfg = _timing_plan(d=2)
    res = run_plan(prof, AWS_LAMBDA, cfg, 8, steps=2, pipelined_sync=True,
                   backend="emulated",
                   faults=F.FaultPlan(events=(
                       F.FaultEvent(kind="crash", stage=1, replica=0,
                                    step=1, phase="fwd"),)))
    assert res.fault_report.restarts == 1
    assert res.fault_report.resumed_steps == [1]


def test_checkpoint_restart_resumes_from_correct_step():
    """checkpoint_every=2 over 4 steps: a crash in step 3 must resume from
    step 2 (state-after-step-1 checkpoint), replaying steps 2 and 3."""
    _, prof, config, _, _, _, mk_exec = _numeric_setup(steps=4)
    plan = F.FaultPlan(events=(
        F.FaultEvent(kind="crash", stage=0, replica=1, step=3, phase="fwd"),))
    res = run_plan(prof, AWS_LAMBDA, config, 4, steps=4,
                   pipelined_sync=True, execution=mk_exec(),
                   backend="emulated", faults=plan,
                   tolerance=F.FaultTolerance(checkpoint_every=2))
    rep = res.fault_report
    assert rep.restarts == 1 and rep.resumed_steps == [2]
    assert rep.checkpoints >= 1
    ref = run_plan(prof, AWS_LAMBDA, config, 4, steps=4,
                   pipelined_sync=True, execution=mk_exec(),
                   backend="emulated")
    _assert_bit_identical(res.params, ref.params)


def test_straggler_slows_but_does_not_change_numbers():
    _, prof, config, _, _, _, mk_exec = _numeric_setup(steps=3)
    plan = F.FaultPlan(events=(
        F.FaultEvent(kind="straggle", stage=0, replica=0, step=0,
                     slow_s=0.5),))
    res = run_plan(prof, AWS_LAMBDA, config, 4, steps=3,
                   pipelined_sync=True, execution=mk_exec(),
                   backend="emulated", faults=plan)
    assert res.fault_report.injected == {"straggle": 1}
    assert res.fault_report.restarts == 0
    _assert_bit_identical(res.params, _fault_free_run(True)[0])


# -------------------------------------------------- recovery observability
def test_traced_chaos_run_validates_and_reports_recovery():
    from repro.obs import pipeline_health, validate_trace

    _, prof, config, _, _, _, mk_exec = _numeric_setup(steps=3)
    res = run_plan(prof, AWS_LAMBDA, config, 4, steps=3,
                   pipelined_sync=True, execution=mk_exec(),
                   backend="emulated", trace=True, faults=_chaos_plan(),
                   tolerance=F.FaultTolerance(
                       retry=F.RetryPolicy(base_delay_s=0.01)))
    validate_trace(res.trace)                  # replays stay schema-valid
    assert res.trace.meta["fault_report"] == res.fault_report.as_dict()
    h = pipeline_health(res.trace)
    rcv = h["recovery"]
    assert rcv["retry_count"] >= 1 and rcv["retry_s"] > 0.0
    assert rcv["restart_count"] >= 1 and rcv["restart_bytes"] > 0.0
    rec = h["reconciliation"]
    assert rec["ok"], rec                      # bytes still conserved


def test_chaos_timing_run_charges_recovery_on_virtual_clock():
    prof, cfg = _timing_plan(d=2)
    base = run_plan(prof, AWS_LAMBDA, cfg, 8, steps=2, pipelined_sync=True,
                    backend="emulated")
    chaos = run_plan(prof, AWS_LAMBDA, cfg, 8, steps=2, pipelined_sync=True,
                     backend="emulated", faults=_chaos_plan())
    assert chaos.fault_report.count_injected is not None
    assert chaos.t_iter > base.t_iter          # recovery is not free
    assert chaos.fault_report.recovery_s > 0.0


# -------------------------------------------------------- checkpoint wire
def test_ckpt_pack_unpack_round_trip():
    from repro.checkpoint import pack_state, unpack_state

    tree = {"w": np.arange(6, dtype=np.float32).reshape(2, 3),
            "b": np.float64(2.5)}
    blob = pack_state(tree, step=7)
    out, step = unpack_state(blob, tree)
    assert step == 7
    np.testing.assert_array_equal(np.asarray(out["w"]), tree["w"])


@pytest.mark.parametrize("mutate, match", [
    (lambda t: {"other": t["w"]}, "treedef"),
    (lambda t: {"w": t["w"].astype(np.float64), "b": t["b"]}, "dtype"),
    (lambda t: {"w": t["w"][:1], "b": t["b"]}, "shape"),
], ids=["treedef", "dtype", "shape"])
def test_ckpt_restore_validates_structure(mutate, match):
    from repro.checkpoint import CheckpointError, pack_state, unpack_state

    tree = {"w": np.arange(6, dtype=np.float32).reshape(2, 3),
            "b": np.zeros((), np.float32)}
    blob = pack_state(mutate(tree))
    with pytest.raises(CheckpointError, match=match):
        unpack_state(blob, tree)


def test_ckpt_rejects_garbage_payloads():
    from repro.checkpoint import CheckpointError, unpack_state

    with pytest.raises(CheckpointError, match="msgpack"):
        unpack_state(b"\xc1 definitely not msgpack", {"w": np.zeros(2)})
    import msgpack

    with pytest.raises(CheckpointError, match="leaves"):
        unpack_state(msgpack.packb({"step": 1}), {"w": np.zeros(2)})


def test_ckpt_atomic_write_survives_crash(tmp_path, monkeypatch):
    """A crash mid-save (simulated by failing the final rename) leaves the
    previous checkpoint intact — a truncated .tmp never shadows it."""
    from repro.checkpoint import restore_checkpoint, save_checkpoint

    path = str(tmp_path / "state.ckpt")
    v1 = {"w": np.full((3,), 1.0, np.float32)}
    save_checkpoint(path, v1, step=1)

    real_replace = os.replace

    def crash_replace(src, dst):
        raise OSError("simulated crash before publish")

    monkeypatch.setattr(os, "replace", crash_replace)
    with pytest.raises(OSError, match="simulated crash"):
        save_checkpoint(path, {"w": np.full((3,), 2.0, np.float32)}, step=2)
    monkeypatch.setattr(os, "replace", real_replace)

    tree, step = restore_checkpoint(path, v1)
    assert step == 1
    np.testing.assert_array_equal(np.asarray(tree["w"]), v1["w"])


# --------------------------------------------------- LocalStore leases
def test_local_store_dead_producer_fails_fast():
    store = LocalStore(timeout=30.0, lease_timeout=1.0)
    store.heartbeat((0, 0))
    store.mark_dead((0, 0))
    t0 = time.monotonic()
    with pytest.raises(ProducerDeadError, match="died"):
        store.get("k0/r0/m0/act0")             # produced by (0, 0)
    assert time.monotonic() - t0 < 5.0         # far under the get timeout


def test_local_store_stale_heartbeat_fails_fast():
    store = LocalStore(timeout=30.0, lease_timeout=0.2)
    store.heartbeat((1, 0))
    time.sleep(0.4)
    t0 = time.monotonic()
    # stage s+1 produces grad{s}: "k0/r0/m0/grad0" comes from worker (1, 0)
    with pytest.raises(ProducerDeadError, match="stopped heartbeating"):
        store.get("k0/r0/m0/grad0")
    assert time.monotonic() - t0 < 5.0


def test_lease_outlives_a_long_optimizer_update(monkeypatch):
    """A worker's lease is renewed when its update returns: an update that
    outlasts the lease (a loaded host, the eager optimizer's first-step
    compiles), then a thread slow to start the next step, must not read
    as a dead producer to the next stage's first download."""
    from repro.api import ExecutionConfig, numeric_plan
    from repro.serverless.backends.local import (
        DEFAULT_LEASE_TIMEOUT,
        LocalBackend,
    )
    from repro.serverless.runtime import engine
    from repro.serverless.runtime.worker import StageWorker

    apply_update, split = StageWorker.apply_update, engine._split_batch

    def slow_update(self, reduced, step):
        apply_update(self, reduced, step)
        if self.span.index == 0 and step == 0:
            time.sleep(DEFAULT_LEASE_TIMEOUT + 0.5)

    def late_start(batch, r, d, m, mu):
        if m == 0:
            time.sleep(0.5)              # before the worker's first op
        return split(batch, r, d, m, mu)

    monkeypatch.setattr(StageWorker, "apply_update", slow_update)
    monkeypatch.setattr(engine, "_split_batch", late_start)
    plan, prof, ex = numeric_plan("phi3-mini-3.8b@reduced", stages=2, dp=1,
                                  batch=8, seq=16)
    res = plan.emulate(ExecutionConfig(backend=LocalBackend(), steps=2),
                       execution=ex, profile=prof)
    assert len(res.losses) == 2


def test_local_store_abort_wakes_blocked_consumers():
    store = LocalStore(timeout=30.0)
    errs = []

    def consumer():
        try:
            store.get("k0/sync0/part/0/1")
        except BaseException as e:             # noqa: BLE001 - test capture
            errs.append(e)

    t = threading.Thread(target=consumer)
    t.start()
    time.sleep(0.05)
    store.abort(RuntimeError("worker exploded"))
    t.join(timeout=10.0)
    assert len(errs) == 1 and isinstance(errs[0], StoreAbortedError)
    assert "worker exploded" in str(errs[0])
    # revive() clears the poison for the next launch
    store.revive()
    store.put("x", 1.0, value=1)
    assert store.get("x") == 1


def test_local_store_timeout_diagnostic_names_the_suspect():
    store = LocalStore(timeout=0.1, lease_timeout=10.0)
    store.put("k0/r0/m0/act0", 8.0, value=b"x")
    store.heartbeat((1, 1))
    with pytest.raises(TimeoutError) as ei:
        store.get("k0/r1/m0/act1")             # producer (1, 1), never put
    msg = str(ei.value)
    assert "never became visible" in msg
    assert "stage 1, replica 1" in msg         # lease holder named
    assert "last heartbeat" in msg
    assert "k0/r0/m0/act0" in msg              # existing keys sampled
