"""Multi-device equivalence check: pipelined train step == single-device step.

Run in a subprocess with fake devices:
    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python -m repro.testing.pipeline_equiv [model] [stages] [tensor]

``model`` is any spelling of ``repro.configs.resolve_arch`` (the tests pass
``<arch>@reduced[<L>]``); the mesh is every visible device, ``data`` =
devices / (stages * tensor).  Exits nonzero on mismatch.  Used by
tests/test_multidev.py.
"""
import os
import sys

if __name__ == "__main__" and "--xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
    )

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import resolve_arch
from repro.configs.base import InputShape
from repro.core import sharding
from repro.core.plan import make_plan
from repro.data.synthetic import make_batch
from repro.models import registry
from repro.optim import AdamW, SGD
from repro.train.train_step import (
    grad_sync_tree,
    init_opt_state,
    make_train_state,
    make_train_step,
)


def reference_step(cfg, base_params, batch, optimizer, step_idx=0):
    """Plain single-device step with fp32 masters (same math as ZeRO path)."""
    def loss_of(p):
        loss, metrics = registry.loss_fn(cfg, p, batch)
        return loss, metrics

    (loss, metrics), grads = jax.value_and_grad(loss_of, has_aux=True)(base_params)

    def upd(g, p):
        master = p.astype(jnp.float32)
        st = optimizer.init_state(master)
        new_m, _ = optimizer.update(g.astype(jnp.float32), master, st,
                                    jnp.asarray(step_idx, jnp.int32))
        return new_m.astype(p.dtype)

    return jax.tree.map(upd, grads, base_params), loss, metrics


def run(model="phi3-mini-3.8b@reduced", stages=4, tensor=1,
        bidirectional=True, seed=0, tol=2e-4):
    data_ax = len(jax.devices()) // (stages * tensor)
    mesh = jax.make_mesh((data_ax, stages * tensor), ("data", "model"))
    cfg = resolve_arch(model)
    if cfg.moe is not None:
        # capacity: avoid drop mismatches between micro-batch groupings;
        # aux: the load-balance loss is an expectation over the routing group,
        # which legitimately differs between per-micro-batch and full-batch
        # routing — zero it for exact equivalence checking.
        cfg = dataclasses.replace(
            cfg,
            moe=dataclasses.replace(
                cfg.moe,
                capacity_factor=float(cfg.moe.n_experts),
                router_aux_weight=0.0,
            ),
        )
    cfg = dataclasses.replace(cfg, stages=stages, tensor=tensor)
    shape = InputShape("equiv", 64, 8, "train")
    plan = make_plan(cfg, shape, data=data_ax, model=stages * tensor,
                     microbatches=2, remat="tick")

    key = jax.random.PRNGKey(seed)
    base = registry.init_params(cfg, key)
    batch = make_batch(cfg, shape, seed=seed)
    optimizer = AdamW(lr=1e-2)

    with jax.set_mesh(mesh):
        params = sharding.to_pipeline_layout(cfg, plan, base)
        opt_state = init_opt_state(cfg, plan, optimizer, params)
        step = make_train_step(cfg, plan, mesh, optimizer, shape,
                               bidirectional=bidirectional, donate=False)
        new_params, new_opt, metrics = step(params, opt_state, batch, 0)

    ref_new_base, ref_loss, ref_metrics = reference_step(cfg, base, batch, optimizer)
    ref_new_layout = sharding.to_pipeline_layout(cfg, plan, ref_new_base)

    errs = {}
    loss_err = abs(float(metrics["loss"]) - float(ref_loss))
    errs["loss"] = loss_err
    flat_new = jax.tree.leaves_with_path(new_params)
    flat_ref = jax.tree.leaves(ref_new_layout)
    worst = ("", 0.0)
    for (path, a), b in zip(flat_new, flat_ref):
        e = float(jnp.max(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32))))
        if e > worst[1]:
            worst = (jax.tree_util.keystr(path), e)
    errs["param"] = worst
    print(f"[pipeline_equiv] {model} stages={stages} tp={tensor} "
          f"loss={float(metrics['loss']):.5f} ref={float(ref_loss):.5f} "
          f"loss_err={loss_err:.2e} worst_param={worst[0]} err={worst[1]:.2e}")
    ok = loss_err < tol and worst[1] < tol * 50
    return ok


if __name__ == "__main__":
    import argparse

    ap = argparse.ArgumentParser(description="pipeline-vs-monolithic check")
    ap.add_argument("model", nargs="?", default="phi3-mini-3.8b@reduced")
    ap.add_argument("stages", nargs="?", type=int, default=4)
    ap.add_argument("tensor", nargs="?", type=int, default=1)
    a = ap.parse_args()
    sys.exit(0 if run(a.model, a.stages, a.tensor) else 1)
