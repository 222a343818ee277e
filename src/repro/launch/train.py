"""Training launcher: mesh + plan + pipelined train loop.

On real hardware this runs the production 16x16 (or 2x16x16) mesh; on CPU it
runs any mesh of fake host devices for bring-up, e.g.:

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
    PYTHONPATH=src python -m repro.launch.train \\
        --arch phi3-mini-3.8b@reduced --data 2 --model 4 --stages 4 \\
        --steps 20

``--arch`` takes any spelling of ``repro.configs.resolve_arch``: the
published config, ``@reduced[<L>]`` (CPU-sized) or ``@depth<L>`` (published
widths, ``L`` layers).

``--plan auto`` asks core.tpu_planner for the best (stages x tp x mu x remat)
factorization instead of the config default.  Checkpoints via the
Function-Manager policy every --ckpt-every steps.
"""
from __future__ import annotations

import argparse
import dataclasses
import time

import jax
from jax.sharding import NamedSharding

from repro.checkpoint import FunctionManager
from repro.configs import INPUT_SHAPES, resolve_arch
from repro.configs.base import InputShape
from repro.core import sharding, tpu_planner
from repro.core.plan import make_plan
from repro.data.synthetic import make_batch
from repro.launch.mesh import make_production_mesh, make_test_mesh
from repro.models import registry
from repro.optim import AdamW
from repro.train.train_step import (
    init_opt_state,
    make_train_step,
    opt_state_specs,
)


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(prog="repro train")
    ap.add_argument("--arch", required=True,
                    help="arch spelling: <arch>, <arch>@reduced[<L>] or "
                         "<arch>@depth<L>")
    ap.add_argument("--shape", default=None, help="named input shape or none")
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--steps", type=int, default=10)
    ap.add_argument("--data", type=int, default=16)
    ap.add_argument("--model", type=int, default=16)
    ap.add_argument("--pods", type=int, default=1)
    ap.add_argument("--plan", default="config", choices=["config", "auto"])
    ap.add_argument("--stages", type=int, default=None)
    ap.add_argument("--tensor", type=int, default=None)
    ap.add_argument("--microbatches", type=int, default=None)
    ap.add_argument("--uni-ring", action="store_true",
                    help="LambdaML-analog unidirectional ring sync")
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt", default="/tmp/repro_train.msgpack")
    ap.add_argument("--ckpt-every", type=int, default=100)
    return ap


def run(args) -> list:
    """Train for ``args.steps`` steps; returns per-step host floats
    ``{"loss", "ce", "seconds"}`` (``seconds`` includes step 0's compile)."""
    cfg = resolve_arch(args.arch)
    if args.shape:
        shape = INPUT_SHAPES[args.shape]
    else:
        shape = InputShape("cli", args.seq, args.batch, "train")

    if args.pods > 1 and args.data == 16 and args.model == 16:
        mesh = make_production_mesh(multi_pod=True)
    elif args.data == 16 and args.model == 16 and args.pods == 1:
        mesh = make_production_mesh()
    else:
        mesh = make_test_mesh(args.data, args.model, pods=args.pods)

    overrides = {}
    if args.plan == "auto":
        best = tpu_planner.solve(cfg, shape, data=args.data, model=args.model,
                                 pods=args.pods)
        assert best, "no feasible plan"
        p = best[0].plan
        overrides = dict(stages=p.stages, tensor=p.tensor,
                         microbatches=p.microbatches, remat=p.remat)
        print(f"[plan auto] S={p.stages} tp={p.tensor} mu={p.microbatches} "
              f"remat={p.remat} (est {best[0].t_step_est*1e3:.1f} ms/step)")
    for k in ("stages", "tensor", "microbatches"):
        v = getattr(args, k)
        if v is not None:
            overrides[k] = v
    if overrides.get("stages") or overrides.get("tensor"):
        cfg = dataclasses.replace(
            cfg,
            stages=overrides.get("stages", cfg.stages),
            tensor=overrides.get("tensor", cfg.tensor),
        )
    plan = make_plan(cfg, shape, data=args.data, model=args.model,
                     pods=args.pods, **overrides)
    print(f"plan: stages={plan.stages} tensor={plan.tensor} "
          f"mu={plan.microbatches} ep={plan.ep} remat={plan.remat}")

    optimizer = AdamW(lr=args.lr)
    fm = FunctionManager(args.ckpt)
    with jax.set_mesh(mesh):
        base = registry.init_params(cfg, jax.random.PRNGKey(0))
        params = sharding.to_pipeline_layout(cfg, plan, base)
        opt_state = init_opt_state(cfg, plan, optimizer, params)
        # lay the state out as the step returns it, so step 1 reuses step
        # 0's program instead of compiling again for new input shardings
        on_mesh = lambda spec: NamedSharding(mesh, spec)  # noqa: E731
        params = jax.device_put(params, jax.tree.map(
            on_mesh, sharding.pipeline_param_specs(cfg, plan)))
        opt_state = jax.device_put(opt_state, jax.tree.map(
            on_mesh, opt_state_specs(cfg, plan, optimizer)[1]))
        step_fn = make_train_step(cfg, plan, mesh, optimizer, shape,
                                  bidirectional=not args.uni_ring)
        history = []
        for i in range(args.steps):
            batch = make_batch(cfg, shape, step=i)
            t0 = time.perf_counter()
            params, opt_state, metrics = step_fn(params, opt_state, batch, i)
            row = {"loss": float(metrics["loss"]), "ce": float(metrics["ce"])}
            row["seconds"] = time.perf_counter() - t0
            history.append(row)
            print(f"step {i:4d} loss={row['loss']:.4f} ce={row['ce']:.4f} "
                  f"({row['seconds']:.2f}s)", flush=True)
            if (i + 1) % args.ckpt_every == 0 or fm.should_checkpoint():
                fm.checkpoint_and_restart((params, opt_state), i + 1)
                print(f"  checkpointed -> {fm.path}")
    print("done.")
    return history


def main(argv=None) -> None:
    run(build_parser().parse_args(argv))


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
