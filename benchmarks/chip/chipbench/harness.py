"""One run of one cell: set-up, the measured window, the check.

The window drives the program's training front door exactly as a user
would: ``repro.api.numeric_plan`` -> ``DeploymentPlan.emulate`` on the
cell's backend (``local``: the S x d stage workers as real threads over a
blocking store), with the benchmark's weights, batches and optimizer put
into the ``Execution`` in place of the plan's seed-0 defaults.

Set-up, all counted in ``setup_s``:
  1. weights on the device from ``--seed`` (one jitted call);
  2. the plan; the program's own seed-0 weights are released at once;
  3. a warm-up call of ``warmup_steps`` steps that compiles every program
     and times one steady step, from which the window's step count is set;
  4. the timed call's first ``steps_before_window`` steps, the steps the
     reference follows.  After step 0 and after step 3 the benchmark reads
     the workers' state (gradient and change norms).  Then it waits until
     the device holds no more work and opens the window.

The window ends when the timed call has returned and every worker's state
is on the device; ``train_tokens_per_s`` is all tokens of the window's
steps over its length.  After it, the peak memory is read, the program's
state is freed, and the reference runs the first three steps again.
"""
from __future__ import annotations

import dataclasses
import gc
import math
import os
import sys
import time
from types import SimpleNamespace
from typing import Optional

import jax
import jax.numpy as jnp

from chipbench import check, devtrace
from chipbench import reference as reference_run
from chipbench.data import batch_maker, weights_key
from chipbench.spec import Cell, peaks_for


class NoChip(RuntimeError):
    """JAX finds no TPU, or fewer chips than the cell asks for."""


class CompileClock:
    """Backend compiles (and loads from the persistent cache) as JAX reports
    them: a count and seconds."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.count = 0
        self.seconds = 0.0

    def __call__(self, event: str, duration: float, **_) -> None:
        if event == self.EVENT:
            self.count += 1
            self.seconds += duration


def chips(n: int):
    """The first ``n`` TPU devices; raises :class:`NoChip` otherwise."""
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX's devices are {devs[0].platform!r}")
    if len(devs) < n:
        raise NoChip(f"the cell needs {n} chips, JAX finds {len(devs)}")
    return devs[:n]


def use_compile_cache(directory) -> None:
    """Keep every compiled program, however small, in ``directory``, or in
    ``JAX_COMPILATION_CACHE_DIR`` where that is set."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(directory))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def backend(traffic: dict):
    """A new instance of the mix's execution backend with its options
    (``backend_options``: constructor keywords, e.g. the local store's
    ``lease_timeout``)."""
    from repro.serverless.backends import get_backend

    return type(get_backend(traffic["backend"]))(
        **traffic.get("backend_options", {}))


def _same_layout(a, b) -> bool:
    if jax.tree.structure(a) != jax.tree.structure(b):
        return False
    return all(x.shape == y.shape and x.dtype == y.dtype
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


class Probe:
    """Reads the stage workers the timed call drives, between its steps."""

    def __init__(self, expect: int, b1: float, weights):
        self.expect = expect
        self.b1 = b1
        self.weights = weights
        self.workers = []
        self.before = set()
        self.grads = []
        self.changes = []

    @staticmethod
    def _alive():
        from repro.serverless.runtime.worker import StageWorker

        return [o for o in gc.get_objects() if isinstance(o, StageWorker)]

    def exclude_alive(self) -> None:
        """Workers alive before a call (held elsewhere in the process) are
        not the call's."""
        gc.collect()
        self.before = {id(o) for o in self._alive()}

    def find(self) -> None:
        self.workers = [o for o in self._alive() if id(o) not in self.before]
        if len(self.workers) != self.expect:
            raise RuntimeError(f"found {len(self.workers)} stage workers, "
                               f"expected {self.expect}")

    def note_layouts(self) -> None:
        """Between the warm-up call's steps: the workers' parameter shapes
        and layer ranges, for :meth:`warm`."""
        self.find()
        self.layouts = [
            (jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                          w.params), w.span.inst_lo, w.span.inst_hi)
            for w in self.workers]
        self.workers = []

    def warm(self) -> None:
        """Compile the reads on zeros of the workers' shapes, outside any
        call: the program's store declares a worker dead after 5 s without a
        heartbeat, so the timed call's pauses between steps must stay
        short."""
        for params, lo, hi in self.layouts:
            zeros = jax.tree.map(
                lambda a: jnp.zeros(a.shape, jnp.float32), params)
            check.leaf_norms(zeros, lo)
            check.change_norms(zeros, check.take(self.weights, params, lo, hi),
                               lo)
            del zeros

    def _state(self, w):
        st = w.export_state()
        opt = st["opt_state"]
        is_leaf = lambda v: isinstance(v, dict) and "master" in v  # noqa: E731
        pick = lambda k: jax.tree.map(lambda s: s[k], opt, is_leaf=is_leaf)  # noqa: E731
        return st["params"], pick("master"), pick("m")

    def read_grads(self) -> None:
        """State after step 0: the gradient AdamW got is m / (1 - b1)."""
        self.find()
        for w in self.workers:
            _, _, m = self._state(w)
            norms = check.leaf_norms(m, w.span.inst_lo)
            self.grads.append({k: v / (1 - self.b1) for k, v in norms.items()})

    def read_changes(self) -> None:
        for w in self.workers:
            params, master, _ = self._state(w)
            init = check.take(self.weights, params, w.span.inst_lo,
                              w.span.inst_hi)
            self.changes.append(check.change_norms(master, init,
                                                   w.span.inst_lo))

    def drain(self) -> None:
        jax.block_until_ready([w.export_state() for w in self.workers])


def _window_spans(trace, steps, draws):
    """Program spans of the window's steps on the harness's perf_counter
    clock.  The backend's clock starts at an instant the program keeps to
    itself; each step's first span starts just after the benchmark handed
    out its batch, so the latest (draw - first span) over the steps is the
    offset, to within the shortest such delay."""
    by_step = {}
    for s in trace.spans:
        by_step.setdefault(s.step, []).append(s)
    t0 = max(draws[k] - min(s.start for s in by_step[k]) for k in steps
             if k in by_step)
    return [(s.start + t0, s.end + t0, s) for k in steps
            for s in by_step.get(k, [])]


def run(cell: Cell, *, seed: int, seconds: float, trace: bool,
        t_start: float, trace_dir=None, require_chip: bool = True, warmup: bool = True,
        readings: Optional[dict] = None, log=sys.stderr) -> dict:
    """One run of ``cell``; returns the result line's object.

    ``warmup=False`` skips the warm-up call and measures a one-step window
    (for readings of the check alone); ``readings``, where given, receives
    the program's and the reference's readings."""
    from repro.api import ExecutionConfig, numeric_plan
    from repro.optim import AdamW

    def say(msg):
        print(f"[{time.perf_counter() - t_start:8.2f}s] {msg}", file=log,
              flush=True)

    clock = CompileClock()
    jax.monitoring.register_event_duration_secs_listener(clock)
    if require_chip:
        devices = chips(cell.chips)
        peaks = peaks_for(devices[0].device_kind)
    else:
        devices, peaks = jax.devices()[:cell.chips], None
    cfg, tr, ref = cell.config, cell.traffic, cell.reference
    S, d, B, T = tr["stages"], tr["dp"], tr["global_batch"], tr["seq_len"]
    mb = tr["micro_batch"]
    mu = B // (d * mb)
    opt = cfg["optimizer"]

    weights = jax.jit(lambda k: ref.init_params(cfg, k))(weights_key(seed))
    jax.block_until_ready(weights)
    plan, profile, ex = numeric_plan(cfg["spelling"], stages=S, dp=d,
                                     batch=B, seq=T)
    if (plan.n_stages, plan.d, plan.total_micro_batches) != (S, d, d * mu):
        raise RuntimeError(f"plan {plan.describe()} is not S={S} x d={d} "
                           f"with {mu} micro-batches per replica")
    if not _same_layout(ex.init_params, weights):
        raise RuntimeError("the reference's parameter layout is not the "
                           "program's")
    make_batch = batch_maker(tr, cfg["vocab_size"], seed)
    ex = dataclasses.replace(
        ex, init_params=weights, batch_fn=make_batch,
        optimizer=AdamW(lr=opt["lr"], b1=opt["b1"], b2=opt["b2"],
                        eps=opt["eps"], weight_decay=opt["weight_decay"]))
    gc.collect()                      # the plan's seed-0 weights go here
    say(f"plan {plan.describe()}")

    # ---- warm-up call: compiles, and times one steady step
    draws = []
    probe = Probe(S * d, opt["b1"], weights)
    n_pre = tr["steps_before_window"]
    n_win = 1

    def warm_batch(k):
        if k == 1:
            probe.note_layouts()
        draws.append(time.perf_counter())
        return make_batch(k)

    probe.exclude_alive()
    if warmup:
        res = plan.emulate(
            ExecutionConfig(backend=backend(tr), steps=tr["warmup_steps"]),
            execution=dataclasses.replace(ex, batch_fn=warm_batch),
            profile=profile)
        jax.block_until_ready(res.params)
        t_step = time.perf_counter() - draws[-1]
        del res
        gc.collect()
        probe.warm()
        n_win = max(1, math.ceil(seconds / t_step))
        say(f"warm-up: steady step {t_step:.3f}s -> {n_win} window steps; "
            f"compiles so far {clock.count} ({clock.seconds:.2f}s)")

    # ---- the timed call
    draws = {}
    win = SimpleNamespace(start=None, compiles=None, annotation=None)

    def open_window():
        probe.read_changes()
        probe.drain()
        if trace:
            jax.profiler.start_trace(str(trace_dir))
            win.annotation = jax.profiler.TraceAnnotation(devtrace.WINDOW_SPAN)
            win.annotation.__enter__()
        win.compiles = clock.count
        win.start = time.perf_counter()

    def timed_batch(k):
        if k == 1:
            probe.read_grads()
        if k == n_pre:
            open_window()
        batch = make_batch(k)
        draws[k] = time.perf_counter()
        return batch

    probe.exclude_alive()
    res = plan.emulate(
        ExecutionConfig(backend=backend(tr), steps=n_pre + n_win,
                        trace=trace),
        execution=dataclasses.replace(ex, batch_fn=timed_batch),
        profile=profile)
    jax.block_until_ready(res.params)
    probe.drain()
    t_end = time.perf_counter()
    window_s = t_end - win.start
    compiles = clock.count - win.compiles
    if trace:
        win.annotation.__exit__(None, None, None)
        jax.profiler.stop_trace()
    setup_s = win.start - t_start
    tokens = n_win * B * T
    say(f"window: {n_win} steps in {window_s:.3f}s, {compiles} compiles; "
        f"set-up {setup_s:.2f}s")

    stats = [dv.memory_stats() or {} for dv in devices]
    memory_peak = max(s.get("peak_bytes_in_use", 0) for s in stats)
    losses = res.losses
    prog = {"losses": losses[:n_pre], "grads": probe.grads,
            "changes": probe.changes}
    window_losses = losses[n_pre:]
    spans = None
    if trace:
        spans = _window_spans(res.trace, range(n_pre, n_pre + n_win), draws)
        step_syncs = res.trace.meta["step_syncs"][n_pre:]
    n_params = sum(int(a.size) for a in jax.tree.leaves(weights))
    n_embed = int(weights["embed"].size)
    del res, probe, ex, weights
    gc.collect()

    # ---- the check: the reference follows the first three steps
    t_ref = time.perf_counter()
    ref_out = reference_run.run(cell, seed, steps=n_pre)
    numbers = check.compare(prog, ref_out)
    correct, shown = check.verdict(numbers, cell.limits)
    if readings is not None:
        readings.update(program=prog, reference=ref_out, numbers=numbers)
    say(f"reference {time.perf_counter() - t_ref:.2f}s; losses program "
        f"{prog['losses']} reference {ref_out['losses']}")

    result = {
        "correct": bool(correct and all(map(math.isfinite, window_losses))),
        "attempted": n_win,
        "failed": sum(not math.isfinite(x) for x in window_losses),
        "metrics": {},
        "device": {"platform": devices[0].platform,
                   "kind": devices[0].device_kind, "count": len(devices),
                   "memory_peak_bytes": int(memory_peak)},
    }
    if not trace:
        values = {"train_tokens_per_s": tokens / window_s, "setup_s": setup_s}
        for m in cell.end_to_end:
            result["metrics"][m.name] = {"value": values[m.name],
                                         "unit": m.unit}
    else:
        dev_trace, breakdown = _reduce_trace(str(trace_dir), spans, win.start,
                                              say)
        if dev_trace is not None:
            result["device"]["busy_s"] = dev_trace.busy_s
            result["device"]["window_s"] = dev_trace.window_s
        fwd = ref.forward_flops_per_token(cfg, T)
        bf16 = 2
        act = mb * T * ref.dims(cfg)[0] * bf16
        per_mb = (2 * bf16 * (n_params - n_embed) + 4 * n_params
                  + act * (1 + 2 * (S - 1)))
        run_rec = SimpleNamespace(
            cell=cell, peaks=peaks, window_s=window_s, window_steps=n_win,
            tokens_per_step=B * T, tokens_per_s=tokens / window_s,
            train_flops_per_token=3 * fwd,
            stage_flops_per_step=3 * fwd * B * T,
            stage_bytes_per_step=d * mu * per_mb,
            device=dev_trace, spans=spans, n_workers=S * d,
            step_syncs=step_syncs, window_compiles=compiles, log=say)
        for m in cell.per_layer:
            v = m.read(run_rec)
            if v is not None:
                result["metrics"][m.name] = {"value": float(v), "unit": m.unit}
        result["breakdown"] = breakdown
    for name, row in shown.items():
        print(f"check {name}: {row['value']!r} limit {row['limit']!r}",
              file=log, flush=True)
    result["compared"] = shown
    return result


def _reduce_trace(trace_dir: str, spans, window_start: float, say):
    """(DeviceTrace or None, breakdown) of the traced window.  The window
    span opened at ``window_start`` on the perf_counter clock, which puts the
    program's spans on the trace's clock."""
    events = devtrace.events_from_xplane(devtrace.newest_xplane(trace_dir))
    lines = {}
    for e in events:
        if e.plane.startswith("/device:"):
            lines.setdefault(e.plane, set()).add(e.line)
    say(f"trace: {len(events)} events; device planes and lines "
        f"{ {p: sorted(v) for p, v in sorted(lines.items())} }")
    lo, hi = devtrace.window_of(events)
    dev = devtrace.reduce_events(events, (lo, hi))
    if dev is None:
        return None, {"device_ops": [], "idle_gaps": []}
    spans_ns = [((s - window_start) * 1e9 + lo,
                 (e - window_start) * 1e9 + lo,
                 f"s{sp.stage}r{sp.replica} {sp.phase}.{sp.op}")
                for s, e, sp in spans]
    longest = sorted(dev.idle_gaps(), key=lambda g: g[0] - g[1])[:10]
    return dev, {"device_ops": devtrace.top(dev.op_s),
                 "idle_gaps": [list(r) for r in
                               devtrace.label_gaps(longest, spans_ns)]}
