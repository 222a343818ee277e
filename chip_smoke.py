#!/usr/bin/env python3
"""Bring-up check on a TPU: the repo's train and serve paths at phi3-mini
widths (2 layers, random weights from seed 0), plus its Pallas kernels.

    python chip_smoke.py            # one chip: train, serve, kernels
    python chip_smoke.py --chips 4  # four chips: the mesh training path only

One process drives the chip.  Each phase compares its output with a plain
reference and raises on a mismatch; the last line of standard output is
``{"ok": true, "device": {...}}``, printed only when every phase passed.
Without a TPU the script exits non-zero before any phase runs.
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import gc
import json
import math
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
MODEL = "phi3-mini-3.8b@depth2"   # every published width, 2 of 32 layers

# --- tolerances, each set from the dtypes before any chip run ---------------
#: train step-0 CE, bf16 engine vs float32 "highest" reference.  The engine
#: rounds activations and logits to bf16 (8 significant bits, relative error
#: 2^-9 per rounding); CE averages 8 x 511 tokens at ~ln(32064) = 10.4, so
#: rounding moves it by ~1e-3.  0.02 (0.2%) admits that and little else.
TRAIN_CE_TOL = 0.02
#: mesh step-0 loss vs pipeline_equiv.reference_step: both bf16, summed in a
#: different order (micro-batches, pipeline stages, data replicas).
MESH_LOSS_TOL = 0.02
#: serve: engine (jitted stage programs) and reference (op-by-op loop) round
#: the same bf16 logits in different orders.  A different greedy token is
#: accepted only where the reference's logit for it is within this many bf16
#: ulps of the reference's best logit (a rounding tie, not a wrong model).
SERVE_MARGIN_ULPS = 4
#: kernels: bf16 inputs and outputs (output rounding up to 2^-9 relative),
#: in-kernel f32 accumulation vs a float32 "highest" reference.
KERNEL_TOL = dict(rtol=2e-2, atol=2e-2)


class CheckFailed(AssertionError):
    """A phase's output disagreed with its reference."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise CheckFailed(what)


class CompileClock:
    """Seconds JAX spent compiling (or loading from the persistent cache)."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.seconds = 0.0

    def __call__(self, event: str, duration: float, **_) -> None:
        if event == self.EVENT:
            self.seconds += duration


def peak_gib() -> float:
    import jax

    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use", 0) / 2**30


# ------------------------------------------------------------------ phases
def device_check(chips: int) -> dict:
    import jax

    devs = jax.devices()
    d0 = devs[0]
    if d0.platform != "tpu":
        sys.exit(f"chip_smoke: no TPU found (JAX platform {d0.platform!r}); "
                 "this check runs only on a TPU")
    if len(devs) < chips:
        sys.exit(f"chip_smoke: --chips {chips} needs {chips} TPU devices, "
                 f"found {len(devs)}")
    print(f"device: {d0.platform} {d0.device_kind} x{len(devs)} "
          f"jax {jax.__version__}", flush=True)
    return {"platform": d0.platform, "kind": d0.device_kind,
            "count": len(devs)}


def train_phase(clock: CompileClock, model: str = MODEL, seq: int = 512,
                batch: int = 8) -> None:
    """``repro emulate --numerics`` through its library front door: S=2,
    d=1, 4 micro-batches; 3 steps on ``emulated``, then 1 on ``local``."""
    import jax
    import jax.numpy as jnp

    from repro.api import ExecutionConfig, numeric_plan
    from repro.models import registry

    plan, profile, ex = numeric_plan(model, stages=2, dp=1, batch=batch,
                                     seq=seq)
    check(plan.n_stages == 2 and plan.d == 1 and plan.total_micro_batches == 4,
          f"unexpected plan {plan.describe()}")
    print(f"train: {plan.describe()}", flush=True)
    starts = []

    def timed_batch(k, draw=ex.batch_fn):
        starts.append(time.perf_counter())     # each step begins by drawing
        return draw(k)

    c0 = clock.seconds
    res = plan.emulate(ExecutionConfig(backend="emulated", steps=3),
                       execution=dataclasses.replace(ex, batch_fn=timed_batch),
                       profile=profile)
    ends = starts[1:] + [time.perf_counter()]
    for k, (m, t0, t1) in enumerate(zip(res.metrics, starts, ends)):
        print(f"train step {k}: loss={m['loss']:.6f} ce={m['ce']:.6f} "
              f"wall={t1 - t0:.3f}s", flush=True)
    print(f"train: compile {clock.seconds - c0:.2f}s (step 0 includes it); "
          f"peak {peak_gib():.2f} GiB", flush=True)
    losses = [m["loss"] for m in res.metrics]
    check(all(map(math.isfinite, losses)), f"non-finite losses {losses}")
    del res
    gc.collect()

    # step-0 CE vs the float32 monolithic loss on the same params and batch
    cfg = ex.cfg
    params32 = jax.tree.map(lambda a: a.astype(jnp.float32), ex.init_params)
    with jax.default_matmul_precision("highest"):
        _, ref = jax.jit(functools.partial(registry.loss_fn, cfg))(
            params32, ex.batch_fn(0))
    ref_ce = float(ref["ce"])
    del params32
    err = abs(losses[0] - ref_ce)
    print(f"train: step-0 ce {losses[0]:.6f} vs float32 reference "
          f"{ref_ce:.6f}: |diff| {err:.2e} (tol {TRAIN_CE_TOL})", flush=True)
    check(err <= TRAIN_CE_TOL, f"step-0 CE off by {err:.3e}")

    local = plan.emulate(ExecutionConfig(backend="local", steps=1),
                         execution=ex, profile=profile)
    print(f"train[local] step 0: loss={local.losses[0]:.6f}", flush=True)
    check(local.losses[0] == losses[0],
          f"local step-0 loss {local.losses[0]!r} != emulated {losses[0]!r}")
    del local
    gc.collect()


def serve_phase(clock: CompileClock, model: str = MODEL,
                prefill: int = 512, new: int = 8) -> None:
    """``repro serve --execute emulated`` vs ``reference_decode``."""
    import jax
    import numpy as np

    from repro.models import registry
    from repro.serving import (
        arch_config_for_model,
        make_prompt,
        plan_serving,
        reference_decode,
        run_serve_plan,
    )

    c0 = clock.seconds
    plan = plan_serving(model, "aws", slo=3600.0, batch=1,
                        prefill_tokens=prefill, new_tokens=new)
    print(f"serve: {plan.describe()}", flush=True)
    t0 = time.perf_counter()
    res = run_serve_plan(plan, backend="emulated", seed=0)
    wall = time.perf_counter() - t0
    got = res.tokens
    print(f"serve: tokens {got.tolist()} wall {wall:.2f}s compile "
          f"{clock.seconds - c0:.2f}s peak {peak_gib():.2f} GiB", flush=True)

    cfg = arch_config_for_model(model)
    params = registry.init_params(cfg, jax.random.PRNGKey(0))
    toks = make_prompt(cfg, 1, prefill, seed=0)
    want, logits = reference_decode(cfg, params, toks, new,
                                    return_logits=True)
    check(got.shape == want.shape, f"token shape {got.shape} != {want.shape}")
    diff = np.flatnonzero(got[0] != want[0])
    if diff.size == 0:
        print(f"serve: all {new} tokens match reference_decode", flush=True)
        return
    # later tokens follow a different prefix: judge the first divergence
    t = int(diff[0])
    row = logits[0, t]
    best = float(row[want[0, t]])
    margin = best - float(row[got[0, t]])
    ulp = 2.0 ** (np.floor(np.log2(abs(best))) - 7)      # bf16 ulp at best
    print(f"serve: first difference at token {t}: engine {got[0, t]} vs "
          f"reference {want[0, t]}, reference logit margin {margin:.4g} "
          f"= {margin / ulp:.2f} bf16 ulps (tol {SERVE_MARGIN_ULPS})",
          flush=True)
    check(margin <= SERVE_MARGIN_ULPS * ulp,
          f"serve token {t} differs by a margin of {margin:.4g}")


def kernel_phase(clock: CompileClock) -> None:
    """Each Pallas kernel once through ``repro.kernels.ops`` at phi3 shapes,
    compiled (not interpreted) and compared with ``kernels/ref.py``."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import ops, ref

    mode = ops.kernel_mode()
    check(mode == "pallas", f"kernel mode resolved to {mode!r}, not pallas")
    key = jax.random.PRNGKey(0)
    rnd = lambda i, shape, scale=1.0: (  # noqa: E731
        scale * jax.random.normal(jax.random.fold_in(key, i), shape)
    ).astype(jnp.bfloat16)
    qkv = [rnd(i, (1, 512, 32, 96)) for i in range(3)]
    ffn = [rnd(3, (1024, 3072)), rnd(4, (3072, 8192), 3072 ** -0.5),
           rnd(5, (3072, 8192), 3072 ** -0.5)]
    dec = [rnd(6, (1, 32, 96)), rnd(7, (1, 32, 1024, 96)),
           rnd(8, (1, 32, 1024, 96)), jnp.int32(700)]
    cases = [
        ("flash B1 S512 H32 hd96",
         functools.partial(ops.flash_attention, causal=True),
         functools.partial(ref.flash_attention_ref, causal=True), qkv),
        ("swiglu T1024 d3072 f8192", ops.swiglu, ref.swiglu_ref, ffn),
        ("decode B1 H32 C1024 hd96", ops.decode_attention,
         ref.decode_attention_ref, dec),
    ]
    for name, kernel, oracle, args in cases:
        c0 = clock.seconds
        fn = jax.jit(kernel).lower(*args).compile()
        compile_s = clock.seconds - c0
        check("tpu_custom_call" in fn.as_text(),
              f"{name}: compiled HLO holds no tpu_custom_call")
        out = np.asarray(fn(*args), np.float32)
        up = [a.astype(jnp.float32) if a.dtype == jnp.bfloat16 else a
              for a in args]
        with jax.default_matmul_precision("highest"):
            want = np.asarray(jax.jit(oracle)(*up), np.float32)
        err = float(np.max(np.abs(out - want)))
        print(f"kernel {name}: max |err| {err:.3e} vs float32 reference; "
              f"compile {compile_s:.2f}s", flush=True)
        check(out.shape == want.shape and np.all(np.isfinite(out)),
              f"{name}: bad output {out.shape}")
        check(np.allclose(out, want, **KERNEL_TOL),
              f"{name}: max |err| {err:.3e} beyond {KERNEL_TOL}")


def mesh_phase(clock: CompileClock, model: str = MODEL, seq: int = 512,
               batch: int = 8) -> None:
    """``repro train`` on a data=2 x model=2 mesh (2 stages, tensor 1),
    2 steps; step-0 loss vs ``pipeline_equiv.reference_step`` on one
    device."""
    import jax

    from repro.configs import resolve_arch
    from repro.configs.base import InputShape
    from repro.data.synthetic import make_batch
    from repro.launch import train
    from repro.models import registry
    from repro.optim import AdamW
    from repro.testing.pipeline_equiv import reference_step

    args = train.build_parser().parse_args([
        "--arch", model, "--data", "2", "--model", "2", "--stages", "2",
        "--tensor", "1", "--steps", "2", "--seq", str(seq),
        "--batch", str(batch)])
    c0 = clock.seconds
    history = train.run(args)
    print(f"mesh: compile {clock.seconds - c0:.2f}s; step walls "
          f"{[round(h['seconds'], 3) for h in history]}; peak (device 0) "
          f"{peak_gib():.2f} GiB", flush=True)
    cfg = resolve_arch(model)
    base = registry.init_params(cfg, jax.random.PRNGKey(0))
    batch0 = make_batch(cfg, InputShape("cli", seq, batch, "train"), step=0)
    _, ref_loss, _ = reference_step(cfg, base, batch0, AdamW(lr=args.lr))
    err = abs(history[0]["loss"] - float(ref_loss))
    print(f"mesh: step-0 loss {history[0]['loss']:.6f} vs one-device "
          f"reference {float(ref_loss):.6f}: |diff| {err:.2e} "
          f"(tol {MESH_LOSS_TOL})", flush=True)
    check(err <= MESH_LOSS_TOL, f"mesh step-0 loss off by {err:.3e}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the four-chip mesh training path")
    args = ap.parse_args(argv)

    sys.path.insert(0, os.path.join(ROOT, "src"))
    device = device_check(args.chips)
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    import jax

    clock = CompileClock()
    jax.monitoring.register_event_duration_secs_listener(clock)
    t0 = time.perf_counter()
    phases = ([mesh_phase] if args.chips == 4
              else [train_phase, serve_phase, kernel_phase])
    for phase in phases:
        t = time.perf_counter()
        phase(clock)
        print(f"{phase.__name__}: passed in {time.perf_counter() - t:.1f}s",
              flush=True)
    print(f"all phases passed in {time.perf_counter() - t0:.1f}s; compile "
          f"{clock.seconds:.2f}s; peak {peak_gib():.2f} GiB", flush=True)
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
