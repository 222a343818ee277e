"""Pipelined serving on the execution backends: partitioned prefill +
token-by-token decode as worker programs over the object store.

Each stage runs one :func:`serve_worker_program` generator over its
:class:`~repro.serverless.backends.base.WorkerContext`:

* **prefill** — download the upstream hidden state (``serve/p/act{s-1}``),
  run the stage's prefill, publish the boundary (``serve/p/act{s}``) and the
  stage's decode caches (``kv/s{s}``); the head stage emits token 0 and
  feeds it back (``serve/tok/t0``).
* **decode round t** — download the stage KV (``kv/s{s}``) and the input
  (the fed-back token on stage 0, ``serve/dec/t{t}/act{s-1}`` upstream
  hidden elsewhere), run one decode step, re-publish the KV, forward the
  boundary; the head stage emits token t.

Serverless functions are stateless between invocations, so the KV cache
*is* store traffic — every decode round round-trips it, which is exactly
what the serving planner's cost model charges.  Token ids are bit-identical
to the monolithic ``registry.prefill`` + ``registry.decode_step`` loop on
every backend (``tests/test_serving.py``).
"""
from __future__ import annotations

import time
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from repro.serving.cost import ServingSpec, arch_config_for_model, estimate_serving
from repro.serving.worker import ServeStageWorker, greedy_token

SERVE_BACKENDS = ("emulated", "process")


def _after(*deps):
    """Combine dependency tokens: the latest virtual-clock time on the
    emulated backend (floats), None on wall-clock backends (blocking order
    already happened inside ``download``)."""
    real = [d for d in deps if d is not None]
    return max(real) if real else None


@dataclass(frozen=True)
class ServeResult:
    """One pipelined serving request, executed."""

    tokens: np.ndarray              # [B, new_tokens] int32 greedy tokens
    t_request: float                # backend-clock request latency (s)
    cost_per_request: float         # $ (stage memory occupied for t_request)
    cost_per_1k: float
    backend: str
    store_stats: Any                # runtime.store.StoreStats
    kv_bytes: Tuple[float, ...]     # [S] modeled per-stage KV-cache bytes
    trace: Optional[Any] = None     # repro.obs.Trace when tracing


def serve_worker_program(ctx, *, s: int, S: int, worker: ServeStageWorker,
                         toks: np.ndarray, n_new: int,
                         t_prefill=None, t_decode=None,
                         sink: Optional[List[np.ndarray]] = None,
                         on_decode=None):
    """Stage ``s``'s serving program; yields once per pipeline round.

    ``t_prefill``/``t_decode`` are per-stage compute costs for virtual-clock
    backends (ignored by wall-clock ones).  The head stage appends each
    greedy token ([B, 1] int32) to ``sink``.  ``on_decode`` fires once when
    the program leaves prefill (wall-clock tracers flip their phase there;
    the emulated driver uses the recorder instead).
    """
    tp = 0.0 if t_prefill is None else float(t_prefill[s])
    td = 0.0 if t_decode is None else float(t_decode[s])

    # ------------------------------------------------------------- prefill
    if s == 0:
        x_in, dep = toks, None
    else:
        x_in, dep = ctx.download(f"serve/p/act{s - 1}")
    out, caches = ctx.compute(tp, lambda: worker.prefill(x_in), after=dep)
    kv_nbytes = 0.0
    if worker.has_layers:
        import jax

        kv_nbytes = float(sum(leaf.nbytes
                              for leaf in jax.tree.leaves(caches)))
    if s < S - 1:
        ctx.upload(f"serve/p/act{s}", float(out.nbytes), out)
    else:
        tok = greedy_token(out)
        if sink is not None:
            sink.append(tok)
        if n_new > 1:
            ctx.upload("serve/tok/t0", float(tok.nbytes), tok)
    if worker.has_layers:
        ctx.upload(f"kv/s{s}", kv_nbytes, caches)
    yield

    # -------------------------------------------------------- decode rounds
    if on_decode is not None and n_new > 1:
        on_decode()
    for t in range(1, n_new):
        if worker.has_layers:
            caches, dep_kv = ctx.download(f"kv/s{s}")
        else:
            caches, dep_kv = None, None
        if s == 0:
            x_in, dep_in = ctx.download(f"serve/tok/t{t - 1}")
        else:
            x_in, dep_in = ctx.download(f"serve/dec/t{t}/act{s - 1}")
        out, caches = ctx.compute(
            td, lambda c=caches, x=x_in: worker.decode(c, x),
            after=_after(dep_kv, dep_in))
        if worker.has_layers:
            ctx.upload(f"kv/s{s}", kv_nbytes, caches)
        if s < S - 1:
            ctx.upload(f"serve/dec/t{t}/act{s}", float(out.nbytes), out)
        else:
            tok = greedy_token(out)
            if sink is not None:
                sink.append(tok)
            if t < n_new - 1:
                ctx.upload(f"serve/tok/t{t}", float(tok.nbytes), tok)
        yield


def _spec_from_plan(plan) -> ServingSpec:
    sv = plan.serving or {}
    return ServingSpec(slo_s=sv["slo_s"], batch=sv["batch"],
                       prefill_tokens=sv["prefill_tokens"],
                       new_tokens=sv["new_tokens"])


def make_prompt(cfg, batch: int, prefill_tokens: int, *,
                seed: int = 0) -> np.ndarray:
    """Deterministic prompt token ids [batch, prefill_tokens] int32."""
    import jax

    key = jax.random.fold_in(jax.random.PRNGKey(seed), 1)
    toks = jax.random.randint(key, (batch, prefill_tokens), 0,
                              cfg.vocab_size, dtype=np.int32)
    return np.asarray(toks)


def run_serve_plan(plan, *, backend: str = "emulated", seed: int = 0,
                   prompt: Optional[np.ndarray] = None, trace: bool = False,
                   use_pallas: bool = False, root: Optional[str] = None,
                   payload_true: bool = True,
                   throttle: bool = False) -> ServeResult:
    """Execute a ``workload="serve"`` plan end to end on a backend.

    ``"emulated"`` charges the serving cost model on per-stage virtual
    clocks (deterministic latency/cost); ``"process"`` runs each stage as a
    real OS process over the file store and reports wall-clock latency
    (cold jit compiles included — it is a parity/chaos vehicle, not a
    latency oracle).  Token ids are bit-identical across backends and to
    the monolithic decode loop.
    """
    from repro.api.plan import PlanCompatibilityError
    from repro.models import registry
    from repro.serverless.platform import GB
    from repro.serverless.runtime.worker import stage_instance_ranges
    from repro.serverless.simulator import stage_aggregates

    if getattr(plan, "workload", "train") != "serve":
        raise PlanCompatibilityError(
            "run_serve_plan executes serving plans; this plan for "
            f"{plan.model!r} has workload={plan.workload!r}. Train it "
            "through DeploymentPlan.emulate()/repro emulate instead.")
    if backend not in SERVE_BACKENDS:
        raise ValueError(
            f"unknown serving backend {backend!r}; supported: "
            f"{SERVE_BACKENDS}")

    import jax

    rp = plan.resolve()
    cfg = arch_config_for_model(plan.model)
    spec = _spec_from_plan(plan)
    est = estimate_serving(rp.profile, rp.platform, rp.config, cfg, spec)
    agg = stage_aggregates(rp.profile, rp.platform, rp.config, 1)
    ranges = stage_instance_ranges(cfg, plan.x)
    S = len(ranges)
    params = registry.init_params(cfg, jax.random.PRNGKey(seed))
    toks = (np.asarray(prompt, dtype=np.int32) if prompt is not None
            else make_prompt(cfg, spec.batch, spec.prefill_tokens, seed=seed))
    if toks.shape != (spec.batch, spec.prefill_tokens):
        raise ValueError(
            f"prompt shape {toks.shape} != plan's request shape "
            f"({spec.batch}, {spec.prefill_tokens})")

    rec = None
    if trace:
        from repro.obs import SpanRecorder

        rec = SpanRecorder()

    if backend == "emulated":
        from repro.serverless.backends.emulated import EmulatedBackend

        be = EmulatedBackend()
        if rec is not None:
            be.attach_recorder(rec)
        be.open(agg)
        try:
            workers = [ServeStageWorker(cfg, ranges[s], params,
                                        s_ctx=spec.s_ctx,
                                        use_pallas=use_pallas)
                       for s in range(S)]
            sink: List[np.ndarray] = []
            programs = [serve_worker_program(
                be.context(s, 0), s=s, S=S, worker=workers[s], toks=toks,
                n_new=spec.new_tokens, t_prefill=est.t_prefill_stage,
                t_decode=est.t_decode_stage,
                sink=sink if s == S - 1 else None) for s in range(S)]
            if rec is not None:
                rec.set_step(0)
                rec.set_phase("prefill")
            for s in range(S):          # producers before consumers
                next(programs[s])
            for t in range(1, spec.new_tokens):
                if rec is not None:
                    rec.set_phase("decode")
                for s in range(S):
                    next(programs[s])
            for p in programs:
                p.close()
            tokens = np.hstack(sink)
            t_request = max(float(be.channels[s][0].now) for s in range(S))
            for s in range(S):
                if workers[s].has_layers:
                    be.delete(f"kv/s{s}")
            be.verify_drained()
            stats = be.store_stats
        finally:
            be.close()
    else:
        from repro.serverless.backends.process import ProcessBackend

        be = ProcessBackend(root=root, payload_true=payload_true,
                            throttle=throttle)
        if rec is not None:
            be.attach_recorder(rec)
        be.open(agg)
        try:
            spec_doc = {
                "cfg": cfg, "x": tuple(plan.x),
                "params": jax.tree.map(np.asarray, params),
                "toks": toks, "n_new": spec.new_tokens,
                "s_ctx": spec.s_ctx, "use_pallas": bool(use_pallas),
            }
            wall0 = time.monotonic()
            sink = be.serve(spec_doc)
            t_request = time.monotonic() - wall0
            tokens = np.hstack([np.asarray(t) for t in sink])
            for s in range(S):
                if ranges[s].inst_hi > ranges[s].inst_lo:
                    be.delete(f"kv/s{s}")
            be.verify_drained()
            stats = be.store_stats
        finally:
            be.close()

    price = rp.platform.price_per_gb_s
    cost = float(price * (np.sum(agg.mem) / GB) * t_request)
    tr = None
    if rec is not None:
        from repro.obs import Trace

        tr = Trace(spans=rec.spans,
                   meta={"plan": plan._as_dict(), "backend": backend,
                         "workload": "serve", "model": plan.model,
                         "clock": ("wall" if backend == "process"
                                   else "virtual"),
                         "t_request": t_request, "t_total": t_request,
                         "steps": 1, "d": 1, "S": S,
                         "store": stats.as_dict()})
    return ServeResult(
        tokens=tokens, t_request=float(t_request),
        cost_per_request=cost, cost_per_1k=1000.0 * cost,
        backend=backend, store_stats=stats,
        kv_bytes=est.kv_bytes, trace=tr)


def reference_decode(cfg, params, toks: np.ndarray, n_new: int, *,
                     s_ctx: Optional[int] = None, return_logits: bool = False):
    """Monolithic greedy loop (the parity oracle): ``registry.prefill`` +
    ``registry.decode_step`` on one worker, same sampling rule.  Returns the
    tokens [B, n_new], and with ``return_logits`` also the float32 logits
    each token was chosen from [B, n_new, V]."""
    import jax.numpy as jnp

    from repro.models import registry

    if s_ctx is None:
        s_ctx = toks.shape[1] + n_new
    logits, caches = registry.prefill(cfg, params, {"tokens": jnp.asarray(toks)},
                                      capacity=s_ctx)
    out, seen = [greedy_token(logits)], [logits[:, -1]]
    for _ in range(1, n_new):
        logits, caches = registry.decode_step(
            cfg, params, caches, jnp.asarray(out[-1]))
        out.append(greedy_token(logits))
        seen.append(logits[:, -1])
    if return_logits:
        return np.hstack(out), np.stack(
            [np.asarray(lg, np.float32) for lg in seen], axis=1)
    return np.hstack(out)
