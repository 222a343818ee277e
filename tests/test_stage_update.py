"""The stage worker's optimizer step: one compiled program per stage that
donates the optimizer state.

The reference is the per-leaf eager AdamW formula, written out here: the
compiled update must reproduce it on every master, moment and param, keep
``export_state()["opt_state"]``'s per-leaf ``{"master", "m", "v"}`` layout,
compile once per stage whatever the step count, and never donate a buffer
the caller still holds (the plan's init params).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import ExecutionConfig, numeric_plan
from repro.serverless.runtime.worker import StageWorker

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
    worker's compiled update gives the masters, moments and params that the
    eager formula gives from the same start and the same averaged
    gradients."""
    plan, profile, ex = numeric_plan(MODEL, stages=2, dp=d, batch=8, seq=16)
    seen = {}                         # id -> (worker, start state, updates)
    apply_update = StageWorker.apply_update

    def recording(self, reduced, step):
        if id(self) not in seen:
            seen[id(self)] = (self, _host(self.export_state()), [])
        seen[id(self)][2].append((step, np.array(reduced, copy=True)))
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


@pytest.mark.parametrize("backend", ["emulated", "local"])
def test_update_compiles_once_per_stage(backend):
    """Two consecutive calls on one ``Execution``, as the chip benchmark
    makes them: each compiles every stage's update program once over its
    steps, and the init params the calls share stay readable."""
    plan, profile, ex = numeric_plan(MODEL, stages=2, dp=1, batch=8, seq=16)
    init = _host(ex.init_params)
    compiles = []

    def listen(event, duration, fun_name=None, **_):
        if (event == "/jax/core/compile/backend_compile_duration"
                and fun_name == "jit(_optimizer_update)"):
            compiles.append(fun_name)

    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        for _ in range(2):
            compiles.clear()
            res = plan.emulate(ExecutionConfig(backend=backend, steps=STEPS),
                               execution=ex, profile=profile)
            assert len(res.losses) == STEPS
            assert all(np.isfinite(res.losses))
            assert len(compiles) == plan.n_stages
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)
    for a, b in zip(jax.tree.leaves(ex.init_params), jax.tree.leaves(init)):
        assert not a.is_deleted()
        np.testing.assert_array_equal(np.asarray(a), b)
