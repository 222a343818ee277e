"""Orchestrator: run a FuncPipe plan end-to-end through an execution backend.

Takes a profiled model + platform + planner configuration and executes the
GPipe schedule of Fig 3 for K steps on an ``S x d`` grid of serverless
workers: per replica, all micro-batch forwards flow downstream through
activation keys, the reversed backwards flow gradient keys upstream, then
each stage's ``d`` replicas synchronize with a storage scatter-reduce
(pipelined eq (2) or the 3-phase eq (1) baseline).

The orchestrator talks *only* to the :class:`ExecutionBackend` protocol
(``repro.serverless.backends``): each worker's step is expressed once, as a
generator program over its :class:`WorkerContext` (download, compute,
upload, phase fence, sync request), and the backend decides what a clock and
a store are —

  * ``backend="emulated"`` (default): virtual clocks charging the same
    per-stage costs as the analytic simulator (``simulator.stage_aggregates``),
    so the engine's simulated iteration time independently validates
    ``simulate_funcpipe``;
  * ``backend="local"``: the programs run on real concurrent threads over a
    blocking wall-clock store — actual visibility/ordering races, host
    timings, bit-identical trained params.

Two axes of use on any backend:

  * timing-only (``execution=None``): objects carry sizes, not values; used
    by ``benchmarks/runtime_accuracy.py`` for the three-level accuracy table.
  * numeric (``execution=Execution(...)``): K full training steps with real
    JAX stage workers; final params match a monolithic fp32 loop within
    summation-order noise — and match *bit-for-bit* across backends.

Not charged (matching the simulator): input-batch fetches (the shared-
nothing synthetic loader regenerates shards in-function, ``data.synthetic``),
the optimizer update FLOPs, and function cold-starts.

After the last step the engine verifies the store drained — every put
deleted, bytes conserved — on whichever backend ran (the paper's storage
bill depends on exactly this invariant holding across steps).
"""
from __future__ import annotations

import time as _time
from dataclasses import dataclass, field
from typing import TYPE_CHECKING, Any, Callable, Dict, List, Optional, Union

import numpy as np

from repro.core.perfmodel import Config
from repro.serverless.execution import ExecutionConfig
from repro.serverless.platform import GB, Platform
from repro.serverless.runtime.store import StoreStats
from repro.serverless.simulator import stage_aggregates, unpack_plan_args

if TYPE_CHECKING:
    # typing only: backends imports runtime.store, so the runtime package
    # must not import backends at module scope (get_backend is pulled in
    # lazily inside run_plan)
    from repro.serverless.backends import ExecutionBackend, WorkerContext


@dataclass(frozen=True)
class Execution:
    """Numeric-execution attachment: which arch to actually run."""

    cfg: Any                                  # ArchConfig
    optimizer: Any                            # repro.optim.Optimizer
    init_params: dict                         # registry.init_params layout
    batch_fn: Callable[[int], dict]           # step -> global batch (leaves [B, ...])
    jit: bool = True                          # jit-cache stage fwd/bwd per shape
    remat: bool = False                       # recompute fwd in bwd (A/B only)
    tolerance: Optional[Any] = None           # faults.FaultTolerance (retry /
    #                                           checkpoint / restart policy)


@dataclass(frozen=True)
class EngineResult:
    t_iter: float                 # seconds per training iteration (backend clock)
    t_total: float                # seconds for all steps (backend clock)
    steps: int
    cost: float                   # $ per iteration (GB-s pricing, all workers)
    n_workers: int
    total_mem_gb: float
    backend: str = "emulated"     # which ExecutionBackend executed the plan
    wall_clock: bool = False      # True: t_* are host seconds, not modeled
    breakdown: Dict[str, float] = field(default_factory=dict)
    metrics: List[Dict[str, float]] = field(default_factory=list)  # per step
    params: Optional[dict] = None          # final assembled params (numeric mode)
    store_stats: Optional[StoreStats] = None
    trace: Optional[Any] = None            # repro.obs.Trace (trace=True runs)
    fault_report: Optional[Any] = None     # faults.FaultReport (chaos /
    #                                        fault-tolerant runs), else None

    @property
    def losses(self) -> List[float]:
        return [m["loss"] for m in self.metrics]


def _split_batch(batch: dict, r: int, d: int, m: int, mu: int):
    """Micro-batch m of replica r from the global batch (row-contiguous)."""
    import jax

    def sl(a):
        B = a.shape[0]
        assert B % (d * mu) == 0, (B, d, mu)
        per_r = B // d
        mb = per_r // mu
        lo = r * per_r + m * mb
        return a[lo:lo + mb]

    return jax.tree.map(sl, batch)


def _worker_step_program(ctx: WorkerContext, *, k: int, s: int, r: int, agg,
                         worker, batch, losses: Dict) -> Any:
    """One stage worker's step-``k`` program over its backend context.

    The single expression of the GPipe schedule from a worker's point of
    view, shared by every backend: ``mu`` forward micro-batches (yield after
    each op group so virtual-clock drivers can interleave workers), the
    fwd/bwd phase fence, ``mu`` backwards in reverse order, then a
    ``("sync", grad_vector)`` yield answered by the backend with the
    gradient summed over the stage's replicas, from which the worker's
    optimizer update takes the mean and steps.  Packing
    the gradient and applying the update are the worker's host work
    (``ctx.host``: ``"pack"`` and ``"update"`` spans on traced wall-clock
    backends); timing-only plans (no ``worker``) have neither.
    """
    S, mu, d = agg.S, agg.mu, agg.d
    ce_acc = 0.0
    aux_acc = 0.0

    # ---------------------------------------------------------------- forward
    for m in range(mu):
        x_val, dep = (None, None)
        if s > 0:
            x_val, dep = ctx.download(f"k{k}/r{r}/m{m}/act{s - 1}")
        fn = None
        if worker is not None:
            batch_mb = _split_batch(batch, r, d, m, mu)
            fn = (lambda x_val=x_val, batch_mb=batch_mb, m=m:
                  worker.forward(m, x_val, batch_mb))
        res = ctx.compute(agg.t_fc[s], fn, after=dep)
        out = None
        if worker is not None:
            out, aux = res
            aux_acc += aux / (mu * d)
            if s == S - 1:
                ce_acc += float(out) / (mu * d)
        if s < S - 1:
            ctx.upload(f"k{k}/r{r}/m{m}/act{s}", agg.out_b[s], value=out)
        yield

    # program order: backward downloads wait for forward uploads
    ctx.phase_barrier()

    # --------------------------------------------------------------- backward
    for m in range(mu - 1, -1, -1):
        g_in, dep = (None, None)
        if s < S - 1:
            g_in, dep = ctx.download(f"k{k}/r{r}/m{m}/grad{s}")
        fn = None
        if worker is not None:
            fn = lambda g_in=g_in, m=m: worker.backward(m, g_in)  # noqa: E731
        g_out = ctx.compute(agg.t_bc[s], fn, after=dep)
        if s > 0:
            ctx.upload(f"k{k}/r{r}/m{m}/grad{s - 1}", agg.grad_b[s],
                       value=g_out)
        yield

    # ------------------------------------------------------------------- sync
    if worker is None:
        yield ("sync", None)
        return
    vec = ctx.host("pack", worker.grad_vector)
    reduced = yield ("sync", vec)
    ctx.host("update", lambda: worker.apply_update(reduced, step=k),
             nbytes=reduced.nbytes)
    losses[(s, r)] = (ce_acc, aux_acc)


def run_plan(
    profile,
    platform: Optional[Platform] = None,
    config: Optional[Config] = None,
    total_micro_batches: Optional[int] = None,
    exec_config: Optional[ExecutionConfig] = None,
    *,
    steps: Optional[int] = None,
    pipelined_sync: Optional[bool] = None,
    contention: bool = False,
    execution: Optional[Execution] = None,
    backend: Union[None, str, ExecutionBackend] = None,
    trace: Optional[bool] = None,
    faults: Optional[Any] = None,
    tolerance: Optional[Any] = None,
) -> EngineResult:
    """Execute training iterations of the plan through a backend.

    Accepts either the explicit ``(profile, platform, config, M)`` tuple or a
    single :class:`repro.api.DeploymentPlan` as the first argument (see
    ``simulator.unpack_plan_args``).  How to execute — backend, step count,
    tracing, the process backend's calibration axes, fault injection and
    recovery policy — is an :class:`repro.serverless.execution.
    ExecutionConfig` (``exec_config``); the individual ``steps`` / ``backend``
    / ``trace`` / ``faults`` / ``tolerance`` keywords are the deprecated
    legacy spelling of the same settings and may not be mixed with it.
    ``trace=True`` records one span per worker resource task
    (download/compute/upload/barrier, plus per-chunk scatter-reduce
    transfers) on the backend's clock and returns it as
    ``EngineResult.trace`` (a :class:`repro.obs.Trace`).

    Fault tolerance: ``faults`` (a :class:`repro.serverless.faults.FaultPlan`
    or a path to its JSON) wraps the backend in a chaos
    :class:`~repro.serverless.faults.FaultInjector`; ``tolerance`` (a
    :class:`~repro.serverless.faults.FaultTolerance`, also settable via
    ``Execution.tolerance``) enables the recovery machinery — retry with
    backoff on transient store errors, per-stage param/opt checkpoints into
    the object store every N steps, and checkpoint/restart of the whole
    worker grid on a crash or function-lifetime expiry.  A chaos run must
    train to params bit-identical to the fault-free run."""
    ec = ExecutionConfig.merge(
        exec_config,
        dict(backend=backend, steps=steps, trace=trace, faults=faults,
             tolerance=tolerance),
        where="run_plan")
    steps, trace = ec.steps, ec.trace

    # plan-accepting front door: remember the plan so a traced run is
    # self-describing (repro calibrate reads it back out of the file)
    plan_doc = None
    if hasattr(profile, "_as_dict") and hasattr(profile, "resolve"):
        plan_doc = profile._as_dict()
        if plan_doc.get("workload", "train") != "train":
            from repro.api.plan import PlanCompatibilityError

            raise PlanCompatibilityError(
                "run_plan executes *training* plans; this plan for "
                f"{plan_doc.get('model')!r} has "
                f"workload={plan_doc.get('workload')!r}. Serve it through "
                "`repro serve` / repro.serving.run_serve_plan(plan) "
                "instead.")
    profile, platform, config, total_micro_batches, pipelined_sync = \
        unpack_plan_args("run_plan", profile, platform, config,
                         total_micro_batches, pipelined_sync)
    agg = stage_aggregates(profile, platform, config, total_micro_batches,
                           contention=contention)
    S, mu, d = agg.S, agg.mu, agg.d
    be = ec.resolve_backend()

    # ------------------------------------------------- fault-tolerance setup
    # lazy import: runtime/__init__ imports this module at package-import
    # time, and faults.py imports backends (which imports runtime.store)
    report = None
    faults_obj = ec.resolved_faults()
    tol = ec.resolved_tolerance()
    if tol is None and execution is not None:
        tol = execution.tolerance
    if faults_obj is not None or tol is not None:
        from repro.serverless import faults as F

        if faults_obj is not None:
            if tol is None:
                tol = F.FaultTolerance()    # chaos implies recovery
        report = F.FaultReport()
        if faults_obj is not None:
            be = F.FaultInjector(be, faults_obj, report)
        # the Function Manager's lifetime policy: an explicit tolerance cap
        # wins; otherwise the engine knows the platform's cap the same way
        # it knows Lambda's 15 minutes — from the environment (fault plan)
        fm = None
        if tol is not None:
            cap = tol.lifetime_steps
            if cap is None and faults_obj is not None:
                cap = faults_obj.lifetime_steps
            if cap is not None:
                from repro.checkpoint import FunctionManager

                fm = FunctionManager(lifetime_steps=cap,
                                     safety=tol.lifetime_safety)
    else:
        fm = None

    def mk_ctx(s: int, r: int):
        ctx = be.context(s, r)
        if tol is not None:
            ctx = F.ResilientContext(ctx, tol.retry, report)
        return ctx

    recorder = None
    if trace:
        from repro.obs import SpanRecorder

        recorder = SpanRecorder()
        be.attach_recorder(recorder)

    # program-hosting backends (process, real platforms) run the worker
    # programs *inside* their own workers: generators cannot cross a process
    # boundary, so the engine ships the run's execution spec up front and
    # receives RPC worker proxies instead of building StageWorkers in-process
    hosts = bool(getattr(be, "hosts_programs", False))
    if hosts:
        be.bind_run(execution=execution, config=config, tolerance=tol,
                    report=report)

    def make_workers():
        if hosts:
            return be.worker_handles()
        from repro.serverless.runtime.worker import (
            StageWorker,
            stage_instance_ranges,
        )

        spans = stage_instance_ranges(execution.cfg, config.x)
        assert len(spans) == S
        return [[StageWorker(execution.cfg, spans[s], execution.init_params,
                             mu=mu, optimizer=execution.optimizer,
                             replicas=d, jit=execution.jit,
                             remat=execution.remat)
                 for r in range(d)] for s in range(S)]

    be.open(agg)
    workers = make_workers() if execution is not None else None
    metrics_by_step: Dict[int, Dict[str, float]] = {}
    iter_ends: Dict[int, float] = {}
    sync_durations: Dict[int, float] = {}

    # ------------------------------------------------ checkpoint / restart
    last_ckpt_step = -1          # state-after-step index of the newest ckpt
    ckpt_stages: set = set()     # stages with a live ckpt/s{s} object

    def write_checkpoint(k_done: int) -> None:
        """Checkpoint every stage's param/opt state into the object store
        (state after step ``k_done``), charged like any upload.  Replicas
        hold identical state, so one object per stage suffices."""
        nonlocal last_ckpt_step
        from repro.checkpoint import pack_state

        for s in range(S):
            blob = None
            if workers is not None:
                blob = pack_state(workers[s][0].export_state(),
                                  step=k_done + 1)
                nbytes = float(len(blob))
            else:
                # timing-only: fp32 masters + two moments alongside the
                # stage's params — the modeled checkpoint payload
                nbytes = 3.0 * float(agg.s_stage[s])
            mk_ctx(s, 0).upload(f"ckpt/s{s}", nbytes, value=blob)
            ckpt_stages.add(s)
        last_ckpt_step = k_done
        report.checkpoints += 1

    def restore_from_checkpoint() -> None:
        """Relaunch the worker grid from the newest store checkpoint (or
        from scratch when none exists yet): every worker re-fetches its
        stage's state — ``op="restart"`` spans — and resets its transient
        step state.  Bit-identical to having never crashed."""
        nonlocal workers
        from repro.checkpoint import unpack_state

        if last_ckpt_step < 0:
            # nothing persisted yet: rebuild from initial state
            if execution is not None:
                workers = make_workers()
            return
        for s in range(S):
            state = None
            for r in range(d):
                value, _ = mk_ctx(s, r).fetch(f"ckpt/s{s}", op="restart")
                if workers is not None:
                    if state is None:
                        state, _step = unpack_state(
                            value, workers[s][r].export_state())
                    workers[s][r].load_state(state)

    restarts = 0
    steps_since_launch = 0
    pending_restore = False
    k = 0
    try:
        while k < steps:
            try:
                if pending_restore:
                    t0r = _time.perf_counter()
                    restore_from_checkpoint()
                    report.recovery_s += _time.perf_counter() - t0r
                    pending_restore = False
                if fm is not None and fm.should_restart(steps_since_launch):
                    # planned relaunch under the platform's lifetime cap —
                    # checkpoint current progress, recycle the functions,
                    # restore (the paper's Function Manager, §3.1 ⑧)
                    if last_ckpt_step < k - 1:
                        write_checkpoint(k - 1)
                    be.recover()
                    fm.restarted()
                    report.planned_restarts += 1
                    t0r = _time.perf_counter()
                    restore_from_checkpoint()
                    report.recovery_s += _time.perf_counter() - t0r
                    steps_since_launch = 0
                batch = (execution.batch_fn(k)
                         if execution is not None else None)
                losses: Dict = {}
                if hosts:
                    be.stage_step(k, batch=batch, losses=losses)
                programs = {
                    (s, r): _worker_step_program(
                        mk_ctx(s, r), k=k, s=s, r=r, agg=agg,
                        worker=None if workers is None else workers[s][r],
                        batch=batch, losses=losses)
                    for s in range(S) for r in range(d)
                }
                timing = be.run_step(k, programs,
                                     pipelined_sync=pipelined_sync)
            except Exception as e:
                from repro.serverless import faults as F

                if tol is None or not F.is_recoverable(e):
                    raise
                if restarts >= tol.max_restarts:
                    raise F.FaultToleranceExceeded(
                        f"step {k} still failing after {restarts} restarts "
                        f"(max_restarts={tol.max_restarts}): {e}") from e
                restarts += 1
                report.restarts += 1
                be.recover()        # purge residual keys, revive the store
                k = last_ckpt_step + 1
                report.resumed_steps.append(k)
                steps_since_launch = 0
                pending_restore = True
                continue
            # ---------------------------------------------- step succeeded
            # keyed by step index: a replayed step overwrites its earlier,
            # aborted attempt's bookkeeping
            iter_ends[k] = timing.end
            sync_durations[k] = timing.sync
            if workers is not None:
                ce_sum = sum(losses[(S - 1, r)][0] for r in range(d))
                aux_sum = sum(losses[(s, r)][1]
                              for s in range(S) for r in range(d))
                metrics_by_step[k] = {"ce": ce_sum, "aux": aux_sum,
                                      "loss": ce_sum + aux_sum}
            if (tol is not None and tol.checkpoint_every
                    and (k + 1) % tol.checkpoint_every == 0
                    and k + 1 < steps):
                write_checkpoint(k)
            k += 1
            steps_since_launch += 1
        # checkpoint objects are engine-owned state, not leaked traffic:
        # delete them (counted) before asserting the drain invariant
        for s in sorted(ckpt_stages):
            be.delete(f"ckpt/s{s}")
        be.verify_drained()
        stats = be.store_stats
        # assemble before close(): program-hosting backends read final
        # params out of their worker processes, which close() tears down
        params = None
        if workers is not None:
            from repro.serverless.runtime.worker import assemble_params

            params = assemble_params(execution.cfg,
                                     [workers[s][0] for s in range(S)])
    finally:
        be.close()
    metrics = [metrics_by_step[i] for i in sorted(metrics_by_step)]

    t_total = iter_ends[steps - 1]
    t_iter = t_total / steps
    mem_total = d * float(agg.mem.sum())
    cost = platform.price_per_gb_s * (mem_total / GB) * t_iter
    comp = float(agg.t_fc.sum() + agg.t_bc.sum())
    sync_t = float(np.mean([sync_durations[i] for i in sorted(sync_durations)]))
    trace_obj = None
    if recorder is not None:
        from repro.obs import Trace

        trace_obj = Trace(
            spans=recorder.spans,
            meta={
                "model": profile.name,
                "backend": be.name,
                "clock": "wall" if be.wall_clock else "virtual",
                "S": S, "d": d, "mu": mu, "steps": steps,
                "n_workers": agg.n_workers,
                "t_total": float(t_total),
                "t_iter": float(t_iter),
                "step_ends": [float(iter_ends[i]) for i in sorted(iter_ends)],
                "step_syncs": [float(sync_durations[i])
                               for i in sorted(sync_durations)],
                "bandwidth": [float(w) for w in agg.w],
                "t_lat": float(agg.t_lat),
                "pipelined_sync": bool(pipelined_sync),
                "contention": bool(contention),
                "payload_true": bool(ec.payload_true),
                "throttle": bool(ec.throttle),
                "store": stats.as_dict(),
            },
        )
        if report is not None:
            trace_obj.meta["fault_report"] = report.as_dict()
        if plan_doc is not None:
            trace_obj.meta["plan"] = plan_doc
    return EngineResult(
        t_iter=float(t_iter),
        t_total=float(t_total),
        steps=steps,
        cost=float(cost),
        n_workers=agg.n_workers,
        total_mem_gb=mem_total / GB,
        backend=be.name,
        wall_clock=be.wall_clock,
        breakdown={
            "compute": comp,
            "pipeline_comm": float(max(0.0, t_iter - comp - sync_t)) if S > 1 else 0.0,
            "sync": sync_t,
        },
        metrics=metrics,
        params=params,
        store_stats=stats,
        trace=trace_obj,
        fault_report=report,
    )
