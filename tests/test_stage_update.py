"""The stage worker's host round trip at the sync: one compiled pack per
stage that flattens the gradient on the device, and one compiled optimizer
step per stage that takes the replicas' mean and donates the optimizer
state.

The pack's reference is the per-leaf host ``np.concatenate``, which it must
match bit for bit.  The update's is the per-leaf eager AdamW formula,
written out here, from the host mean: the compiled update must reproduce it
on every master, moment and param, keep ``export_state()["opt_state"]``'s
per-leaf ``{"master", "m", "v"}`` layout, compile once per stage whatever the
step count, and never donate a buffer the caller still holds (the plan's
init params).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import ExecutionConfig, numeric_plan
from repro.serverless.runtime.worker import StageWorker, stage_instance_ranges

MODEL = "phi3-mini-3.8b@reduced"    # float32 params: astype(f32) aliases
STEPS = 3


def _eager_adamw(opt, g, master, m, v, step):
    """One AdamW step on one leaf, op by op."""
    t = jnp.asarray(step, jnp.int32).astype(jnp.float32) + 1.0
    m = opt.b1 * m + (1 - opt.b1) * g
    v = opt.b2 * v + (1 - opt.b2) * jnp.square(g)
    mhat = m / (1 - opt.b1**t)
    vhat = v / (1 - opt.b2**t)
    upd = mhat / (jnp.sqrt(vhat) + opt.eps) + opt.weight_decay * master
    return master - opt.lr * upd, m, v


def _close(have, want):
    """Equal to 1e-6 of each element, or of the leaf's largest where an
    element is the near-cancellation of two terms.  XLA's CPU backend fuses
    ``a * b + c`` into one multiply-add inside a compiled loop, which the
    eager ops round twice: the two differ by an ulp of the terms (measured:
    at most 1.7 float32 eps of the leaf's largest element), unbounded
    relative to a result near zero."""
    want = np.asarray(want)
    np.testing.assert_allclose(np.asarray(have), want, rtol=1e-6,
                               atol=1e-6 * np.max(np.abs(want)))


def _is_state(v):
    return isinstance(v, dict) and "master" in v


def _state_leaves(opt_state):
    return jax.tree.leaves(opt_state, is_leaf=_is_state)


def _host(tree):
    return jax.tree.map(lambda a: np.array(a, copy=True), tree)


@pytest.mark.parametrize("d", [1, 2])
def test_compiled_update_matches_eager_adamw(monkeypatch, d):
    """S=2 stages x d replicas, three steps through ``plan.emulate``: each
    worker's compiled update, handed the replicas' sum, gives the masters,
    moments and params that the eager formula gives from the same start and
    the sum's mean taken on the host."""
    plan, profile, ex = numeric_plan(MODEL, stages=2, dp=d, batch=8, seq=16)
    seen = {}                         # id -> (worker, start state, updates)
    apply_update = StageWorker.apply_update

    def recording(self, reduced, step):
        if id(self) not in seen:
            seen[id(self)] = (self, _host(self.export_state()), [])
        seen[id(self)][2].append((step, np.array(reduced, copy=True) / d))
        apply_update(self, reduced, step)

    monkeypatch.setattr(StageWorker, "apply_update", recording)
    plan.emulate(ExecutionConfig(steps=STEPS), execution=ex, profile=profile)

    assert len(seen) == 2 * d
    opt = ex.optimizer
    for worker, start, updates in seen.values():
        assert [k for k, _ in updates] == list(range(STEPS))
        got = worker.export_state()
        # the layout the benchmark's probe reads: per-leaf dicts
        assert (jax.tree.structure(got["opt_state"], is_leaf=_is_state)
                == jax.tree.structure(got["params"]))
        for st in _state_leaves(got["opt_state"]):
            assert set(st) == {"master", "m", "v"}

        params = jax.tree.leaves(start["params"])
        states = [dict(st) for st in _state_leaves(start["opt_state"])]
        for step, flat in updates:
            off = 0
            for p, st in zip(params, states):
                g = jnp.asarray(flat[off:off + p.size].reshape(p.shape))
                off += p.size
                st["master"], st["m"], st["v"] = _eager_adamw(
                    opt, g, jnp.asarray(st["master"]), jnp.asarray(st["m"]),
                    jnp.asarray(st["v"]), step)
            assert off == flat.size
        for want, have, p, p_have in zip(
                states, _state_leaves(got["opt_state"]), params,
                jax.tree.leaves(got["params"])):
            for key in ("master", "m", "v"):
                _close(have[key], want[key])
            _close(p_have, want["master"].astype(p.dtype))
            assert p_have.dtype == p.dtype


def _stage_worker(model, replicas=1, *, stages=2):
    """Stage 0 of ``model`` planned as ``stages`` x 1, on the plan's init
    params."""
    plan, _, ex = numeric_plan(model, stages=stages, dp=1, batch=8, seq=16)
    span = stage_instance_ranges(ex.cfg, plan.x)[0]
    return StageWorker(ex.cfg, span, ex.init_params, mu=2,
                       optimizer=ex.optimizer, replicas=replicas)


def test_update_takes_the_replicas_mean_on_the_device():
    """At d=2 the compiled update of the replicas' sum steps every master,
    moment and param to the same bits as the update of the mean taken on
    the host (the contract before the divide moved into the program)."""
    on_device, on_host = _stage_worker(MODEL, 2), _stage_worker(MODEL, 1)
    n = sum(on_device._sizes)
    rng = np.random.default_rng(7)
    for step in range(2):
        parts = rng.standard_normal((2, n), dtype=np.float32)
        total = parts[0] + parts[1]
        on_device.apply_update(total, step)
        on_host.apply_update(total / 2, step)
    for have, want in zip(jax.tree.leaves(on_device.export_state()),
                          jax.tree.leaves(on_host.export_state())):
        np.testing.assert_array_equal(np.asarray(have), np.asarray(want))


def _host_concatenate(grad_acc):
    """The pack's reference: every leaf to the host, then concatenated."""
    return np.concatenate([np.asarray(g, np.float32).ravel()
                           for g in jax.tree.leaves(grad_acc)])


@pytest.mark.parametrize("d", [1, 2])
def test_pack_matches_host_concatenate(monkeypatch, d):
    """S=2 stages x d replicas, three steps through ``plan.emulate``: every
    worker's compiled pack returns, bit for bit, the per-leaf host
    concatenation of its accumulated gradient, as one host array."""
    plan, profile, ex = numeric_plan(MODEL, stages=2, dp=d, batch=8, seq=16)
    grad_vector = StageWorker.grad_vector
    packs = []

    def checked(self):
        want = _host_concatenate(self._grad_acc)
        have = grad_vector(self)
        assert isinstance(have, np.ndarray)
        np.testing.assert_array_equal(have, want)
        assert have.dtype == np.float32
        packs.append(have.size)
        return have

    monkeypatch.setattr(StageWorker, "grad_vector", checked)
    plan.emulate(ExecutionConfig(steps=STEPS), execution=ex, profile=profile)
    assert len(packs) == STEPS * 2 * d


def test_pack_of_narrow_leaves_matches_host_concatenate():
    """xlstm-7-1-125m's stage leaves end in 4 (the q/k/v blocks and gate
    biases) beside leaves of 128 lanes: the pack, which flattens those
    behind an optimization barrier, still gives the host concatenation's
    bits, and hands the next step a fresh accumulator."""
    w = _stage_worker("xlstm-7-1-125m@reduced16", stages=1)
    leaves = jax.tree.leaves(w.params)
    assert any(a.shape[-1] == 4 for a in leaves)
    assert any(a.shape[-1] % 128 == 0 for a in leaves)
    keys = jax.random.split(jax.random.PRNGKey(3), len(leaves))
    w._grad_acc = jax.tree.unflatten(w._treedef, [
        jax.random.normal(k, a.shape, jnp.float32)
        for k, a in zip(keys, leaves)])
    want = _host_concatenate(w._grad_acc)
    np.testing.assert_array_equal(w.grad_vector(), want)
    assert w._grad_acc is None


def _compiles_per_call(backend, program):
    """Backend compiles of the jitted ``program`` in each of two
    consecutive calls on one ``Execution``, as the chip benchmark makes
    them; also checks that the init params the calls share stay
    readable."""
    plan, profile, ex = numeric_plan(MODEL, stages=2, dp=1, batch=8, seq=16)
    init = _host(ex.init_params)
    compiles, counts = [], []

    def listen(event, duration, fun_name=None, **_):
        if (event == "/jax/core/compile/backend_compile_duration"
                and fun_name == f"jit({program})"):
            compiles.append(fun_name)

    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        for _ in range(2):
            compiles.clear()
            res = plan.emulate(ExecutionConfig(backend=backend, steps=STEPS),
                               execution=ex, profile=profile)
            assert len(res.losses) == STEPS
            assert all(np.isfinite(res.losses))
            counts.append(len(compiles))
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)
    for a, b in zip(jax.tree.leaves(ex.init_params), jax.tree.leaves(init)):
        assert not a.is_deleted()
        np.testing.assert_array_equal(np.asarray(a), b)
    return plan.n_stages, counts


@pytest.mark.parametrize("backend", ["emulated", "local"])
def test_update_compiles_once_per_stage(backend):
    """Two consecutive calls on one ``Execution``: each compiles every
    stage's update program once over its steps, and the init params the
    calls share stay readable."""
    n_stages, counts = _compiles_per_call(backend, "_optimizer_update")
    assert counts == [n_stages, n_stages]


@pytest.mark.parametrize("backend", ["emulated", "local"])
def test_pack_compiles_once_per_stage(backend):
    """Two consecutive calls on one ``Execution``: each compiles every
    stage's pack program once over its steps."""
    n_stages, counts = _compiles_per_call(backend, "_pack")
    assert counts == [n_stages, n_stages]
