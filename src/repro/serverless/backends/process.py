"""Process-parallel execution backend: S x d real OS processes.

Where the ``local`` backend runs the plan's workers as threads in one
Python process (GIL-serialized JAX compute, thread-state liveness), this
backend launches each stage worker as a *real OS process* over the
file-backed :class:`~repro.serverless.backends.process_worker.FileStore` —
true parallel JAX compute, real cross-process visibility/ordering races,
and fault semantics with teeth: an injected crash SIGKILLs an actual
process, a lifetime cap makes it exit planned, and consumers notice either
through frozen heartbeat mtimes, not shared memory.

The engine cooperates through the ``hosts_programs`` hooks on the backend
protocol: generator programs cannot cross a process boundary, so each child
runs the engine's own ``_worker_step_program`` locally over the shared
store (``bind_run`` ships the execution spec before ``open``,
``stage_step`` ships each step's evaluated batch, ``worker_handles`` hands
the engine RPC proxies that quack like ``StageWorker`` for checkpointing
and final param assembly).  Numerics are the acceptance bar, same as every
backend: K-step trained params bit-identical to ``emulated``/``local`` on
both sync schedules, through injected crashes, with the store drained
(``tests/test_backends.py`` / ``tests/test_faults.py``).

``payload_true=True`` charges real payload ``nbytes`` per transfer and
``throttle=True`` sleeps each worker's uplink/downlink to the platform's
configured per-worker bandwidth (``agg.w[s]``), giving the wall-clock time
axis a calibration the trace-feedback loop can act on.
"""
from __future__ import annotations

import os
import shutil
import tempfile
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

from repro.serverless.backends.base import (
    ExecutionBackend,
    StepTiming,
    WorkerProgram,
)
from repro.serverless.backends.cloud import BackendUnavailableError
from repro.serverless.backends.local import (
    DEFAULT_GET_TIMEOUT,
    LocalWorkerContext,
    _primary_error,
)
from repro.serverless.backends.process_worker import (
    EXIT_LIFETIME,
    FileStore,
    worker_main,
)
from repro.serverless.runtime.store import (
    ProducerDeadError,
    StoreAbortedError,
    StoreStats,
)

# a producer process whose heartbeat file mtime is older than this is dead;
# generous vs the thread backend's 5s — child heartbeats ride a daemon
# thread, but process scheduling and cold jit compiles add real jitter
DEFAULT_PROCESS_LEASE = 20.0

# S x d real OS processes, each importing jax: beyond this the host is
# benchmarking its scheduler and RAM, not the plan
MAX_PROCESSES = 64

#: extra slack the parent's collect loop grants past the store get timeout
#: before declaring the step wedged
_COLLECT_SLACK = 60.0


def accelerator_conflict() -> Optional[str]:
    """None when worker processes may run JAX beside this one; otherwise
    why not.  An accelerator belongs to one process at a time: a parent
    whose JAX backend is not the CPU holds the device, so every child that
    needs it would fail or hang."""
    import jax

    platform = jax.default_backend()
    if platform == "cpu":
        return None
    return (f"this process holds the {platform} device and each worker "
            "process would need it too, but an accelerator belongs to one "
            "process at a time; run on 'emulated' or 'local' instead")


def _errors_by_name() -> Dict[str, Any]:
    from repro.serverless import faults as F

    return {
        "WorkerCrashed": F.WorkerCrashed,
        "TransientStoreError": F.TransientStoreError,
        "FaultToleranceExceeded": F.FaultToleranceExceeded,
        "StoreAbortedError": StoreAbortedError,
        "ProducerDeadError": ProducerDeadError,
        "TimeoutError": TimeoutError,
        "BrokenBarrierError": threading.BrokenBarrierError,
    }


class ProcessWorkerHandle:
    """RPC proxy for one child's :class:`StageWorker`: exposes the
    ``params``/``span``/``export_state``/``load_state`` surface the engine's
    checkpoint and param-assembly paths touch, forwarding over the pipe.
    State reads are memoized per backend generation (a run_step or recover
    invalidates them)."""

    def __init__(self, backend: "ProcessBackend", s: int, r: int, span):
        self._backend = backend
        self._s = s
        self._r = r
        self.span = span
        self._cache: Optional[Tuple[int, dict]] = None

    def export_state(self) -> dict:
        gen = self._backend._generation
        if self._cache is not None and self._cache[0] == gen:
            return self._cache[1]
        state = self._backend._rpc((self._s, self._r),
                                   {"op": "export_state"})["state"]
        self._cache = (gen, state)
        return state

    def load_state(self, state: dict) -> None:
        self._backend._rpc((self._s, self._r),
                           {"op": "load_state", "state": state})
        self._cache = None

    def reset(self) -> None:
        self._backend._rpc((self._s, self._r), {"op": "reset"})
        self._cache = None

    @property
    def params(self) -> dict:
        return self.export_state()["params"]


class ProcessBackend(ExecutionBackend):
    """S x d worker OS processes over a payload-true-capable file store."""

    name = "process"
    wall_clock = True
    hosts_programs = True

    def __init__(self, *, root: Optional[str] = None,
                 get_timeout: float = DEFAULT_GET_TIMEOUT,
                 lease_timeout: float = DEFAULT_PROCESS_LEASE,
                 payload_true: bool = False, throttle: bool = False,
                 bandwidth: Optional[float] = None):
        self.root = root
        self.get_timeout = get_timeout
        self.lease_timeout = lease_timeout
        self.payload_true = payload_true
        self.throttle = throttle
        self.bandwidth = bandwidth      # override; default = agg.w[s]
        self.agg = None
        self.store: Optional[FileStore] = None
        self._t0 = 0.0
        self._steps_done = 0
        self._generation = 0            # bumps invalidate handle caches
        self._procs: Dict[Tuple[int, int], Any] = {}
        self._conns: Dict[Tuple[int, int], Any] = {}
        self._dead: Dict[Tuple[int, int], str] = {}   # worker -> crash kind
        self._handles: Optional[List[List[ProcessWorkerHandle]]] = None
        self._owns_root = False
        # bound run state (hosts_programs cooperation)
        self._execution = None
        self._config = None
        self._tolerance = None
        self._injector = None
        self._batch = None
        self._losses: Optional[Dict] = None

    # ------------------------------------------------------- run cooperation
    def bind_run(self, *, execution=None, config=None, tolerance=None,
                 report=None, injector=None) -> None:
        self._execution = execution
        self._config = config
        self._tolerance = tolerance
        self._injector = injector
        del report      # child retries merge through the injector's report

    def stage_step(self, k: int, *, batch=None, losses=None) -> None:
        if batch is not None:
            import jax
            import numpy as np

            batch = jax.tree.map(np.asarray, batch)
        self._batch = batch
        self._losses = losses

    def worker_handles(self) -> List[List[ProcessWorkerHandle]]:
        if self._handles is None:
            from repro.serverless.runtime.worker import stage_instance_ranges

            spans = stage_instance_ranges(self._execution.cfg,
                                          self._config.x)
            self._handles = [
                [ProcessWorkerHandle(self, s, r, spans[s])
                 for r in range(self.agg.d)]
                for s in range(self.agg.S)]
        else:
            # the engine rebuilding "from scratch" (crash before the first
            # checkpoint): every surviving child reloads its initial state
            for row in self._handles:
                for h in row:
                    h.reset()
        return self._handles

    # -------------------------------------------------------------- lifecycle
    def open(self, agg) -> None:
        if os.name != "posix":
            raise RuntimeError(
                "the process backend needs POSIX file locks and signals; "
                "replay this plan on 'local' or 'emulated' instead")
        why = accelerator_conflict()
        if why is not None:
            raise BackendUnavailableError(f"process backend: {why}")
        if agg.S * agg.d > MAX_PROCESSES:
            raise ValueError(
                f"plan spawns {agg.S}x{agg.d}={agg.S * agg.d} worker "
                f"processes; the process backend caps at {MAX_PROCESSES} "
                "— replay this plan on the emulated backend instead")
        self.agg = agg
        self._owns_root = self.root is None
        root = self.root or tempfile.mkdtemp(prefix="funcpipe-procstore-")
        self._root = root
        # the parent's store client is unthrottled: it only moves engine-
        # owned checkpoint objects, which a platform's control plane writes
        self.store = FileStore(root, timeout=self.get_timeout,
                               lease_timeout=self.lease_timeout,
                               payload_true=self.payload_true)
        self._t0 = time.monotonic()
        self._steps_done = 0
        self._generation += 1
        self._procs.clear()
        self._conns.clear()
        self._dead.clear()
        self._handles = None
        for s in range(agg.S):
            for r in range(agg.d):
                self._spawn(s, r)
        self._await_ready(list(self._procs))

    def _exec_spec(self) -> Optional[dict]:
        if self._execution is None:
            return None
        import jax
        import numpy as np

        ex = self._execution
        return {"cfg": ex.cfg, "x": tuple(self._config.x),
                "init_params": jax.tree.map(np.asarray, ex.init_params),
                "mu": int(self.agg.mu), "optimizer": ex.optimizer,
                "jit": ex.jit, "remat": ex.remat}

    def _spawn(self, s: int, r: int) -> None:
        import multiprocessing as mp

        ctx = mp.get_context("spawn")   # no forked jax/thread state
        parent_conn, child_conn = ctx.Pipe()
        bw = None
        if self.throttle:
            bw = self.bandwidth or float(self.agg.w[s])
        init = {"root": self._root, "s": s, "r": r,
                "agg": self.agg, "exec_spec": self._exec_spec(),
                "get_timeout": self.get_timeout,
                "lease_timeout": self.lease_timeout,
                "payload_true": self.payload_true,
                "bandwidth": bw, "t_lat": float(self.agg.t_lat),
                "t0": self._t0}
        p = ctx.Process(target=worker_main, args=(child_conn, init),
                        name=f"funcpipe-s{s}r{r}", daemon=True)
        p.start()
        child_conn.close()
        self._procs[(s, r)] = p
        self._conns[(s, r)] = parent_conn

    def _await_ready(self, workers) -> None:
        # generous: each child imports jax from scratch under spawn
        deadline = time.monotonic() + 120.0
        for w in workers:
            while not self._conns[w].poll(0.2):
                if not self._procs[w].is_alive():
                    raise RuntimeError(
                        f"worker process s{w[0]}r{w[1]} died during spawn "
                        f"(exit code {self._procs[w].exitcode})")
                if time.monotonic() > deadline:
                    raise TimeoutError(
                        f"worker process s{w[0]}r{w[1]} never reported "
                        "ready (jax import wedged?)")
            try:
                msg = self._conns[w].recv()
            except EOFError:
                self._procs[w].join(timeout=5.0)
                raise RuntimeError(
                    f"worker process s{w[0]}r{w[1]} died during spawn "
                    f"(exit code {self._procs[w].exitcode})") from None
            assert "ready" in msg, msg

    def _rpc(self, w: Tuple[int, int], cmd: dict) -> dict:
        conn = self._conns[w]
        conn.send(cmd)
        if not conn.poll(self.get_timeout + _COLLECT_SLACK):
            raise TimeoutError(
                f"worker s{w[0]}r{w[1]} did not answer {cmd['op']!r}")
        return conn.recv()

    # ------------------------------------------------------------ observation
    def _clock(self) -> float:
        return time.monotonic() - self._t0

    def context(self, s: int, r: int) -> LocalWorkerContext:
        # parent-side contexts carry only engine traffic (checkpoint
        # write/restore); worker=None — the parent must not heartbeat a
        # child's lease
        if self.recorder is None:
            return LocalWorkerContext(self.store)
        tr = self.recorder.tracer(s, r)
        tr.step = self._steps_done
        tr.phase = "fwd"
        return LocalWorkerContext(self.store, tracer=tr, clock=self._clock)

    @property
    def store_stats(self) -> StoreStats:
        return self.store.stats

    def _store_for_verification(self):
        return self.store

    # --------------------------------------------------------------- stepping
    def _fault_payload(self) -> Optional[dict]:
        inj = self._injector
        if inj is None:
            return None
        return {"events": [e.to_dict() for e in inj.plan.events],
                "lifetime_steps": inj.plan.lifetime_steps,
                "remaining": dict(inj.state.remaining),
                "fired": sorted(inj.state.fired),
                "age": inj.age}

    def _merge_fault(self, delta: Optional[dict]) -> None:
        """Fold a child's fault-consumption state back into the parent's
        injector (the authoritative once-only schedule) and count what
        actually fired for the report."""
        inj = self._injector
        if delta is None:
            return
        if inj is not None and "remaining" in delta:
            state = inj.state
            for i, rem in delta["remaining"].items():
                i = int(i)
                spent = state.remaining.get(i, 0) - rem
                if spent > 0:
                    state.remaining[i] = rem
                    for _ in range(spent):
                        state._note("transient")
            for i in delta.get("fired", ()):
                if i not in state.fired:
                    state.fired.add(i)
                    state._note(inj.plan.events[i].kind)
        report = self._report()
        if report is not None:
            report.retries += delta.get("retries", 0)
            report.recovery_s += delta.get("recovery_s", 0.0)

    def _report(self):
        inj = self._injector
        return None if inj is None else inj.state.report

    def _note_lifetime(self) -> None:
        inj = self._injector
        if inj is None or inj._lifetime_noted:
            return
        inj._lifetime_noted = True
        if inj.state.report is not None:
            inj.state.report.count_injected("lifetime")

    def _on_death(self, w: Tuple[int, int], k: int, errors: list,
                  had_dying_msg: bool) -> None:
        """A worker process died: join it, classify the death from its exit
        code, poison the substrate for its peers, and synthesize the
        :class:`WorkerCrashed` the engine's recovery path expects."""
        from repro.serverless import faults as F

        p = self._procs[w]
        p.join(timeout=5.0)
        kind = "lifetime" if p.exitcode == EXIT_LIFETIME else "crash"
        self._dead[w] = kind
        self.store.mark_dead(w)
        s, r = w
        if kind == "lifetime":
            self._note_lifetime()
            msg = (f"worker (stage {s}, replica {r}) exceeded the function "
                   "lifetime cap — the platform recycled its process "
                   f"(exit {EXIT_LIFETIME})")
        else:
            msg = (f"worker process (stage {s}, replica {r}) died in step "
                   f"{k} (exit code {p.exitcode})")
            if not had_dying_msg and self._injector is not None:
                # dying report lost with the process: consume the matching
                # crash event so the replay does not re-fire it
                state = self._injector.state
                for i, e in enumerate(self._injector.plan.events):
                    if (e.kind == "crash" and i not in state.fired
                            and e.stage == s and e.replica == r
                            and e.step == k):
                        state.fired.add(i)
                        state._note("crash")
                        break
        err = F.WorkerCrashed(msg, stage=s, replica=r, step=k, kind=kind)
        self.store.abort(err)
        if not had_dying_msg:
            errors.append(err)

    def _absorb(self, w: Tuple[int, int], k: int, msg: dict, errors: list,
                syncs: list) -> bool:
        """Process one child reply; True when the worker is accounted for
        this step."""
        s, r = w
        if "ready" in msg:      # stale handshake (respawn race); ignore
            return False
        body = msg.get("ok") and msg or msg.get("error") or msg.get("dying")
        if isinstance(body, dict) and self.recorder is not None:
            for span in body.get("spans") or ():
                self.recorder.spans.append(span)
        if msg.get("ok"):
            self._merge_fault(msg.get("fault"))
            syncs.append(float(msg.get("sync_s") or 0.0))
            loss = msg.get("loss")
            if loss is not None and self._losses is not None:
                self._losses[(s, r)] = tuple(loss)
            return True
        if "dying" in msg:
            from repro.serverless import faults as F

            d = msg["dying"]
            self._merge_fault(d.get("fault"))
            if d["kind"] == "lifetime":
                self._note_lifetime()
            errors.append(F.WorkerCrashed(d["msg"], stage=s, replica=r,
                                          step=k, kind=d["kind"]))
            # the process is now killing itself; reap it when it lands
            self._dead[w] = d["kind"]
            self._procs[w].join(timeout=5.0)
            self.store.mark_dead(w)
            return True
        if "error" in msg:
            d = msg["error"]
            self._merge_fault(d.get("fault"))
            cls = _errors_by_name().get(d["type"], RuntimeError)
            errors.append(_reconstruct_error(cls, d["msg"]))
            return True
        return False

    def run_step(self, k: int, programs: Dict[Tuple[int, int], WorkerProgram],
                 *, pipelined_sync: bool = True) -> StepTiming:
        # the engine's generator programs cannot cross the process boundary;
        # each child runs the identical program locally — close these
        # unstarted (no op ever fires on the parent's copies)
        for gen in programs.values():
            gen.close()
        cmd = {"op": "step", "k": k, "pipelined": bool(pipelined_sync),
               "batch": self._batch, "fault": self._fault_payload(),
               "retry": (self._tolerance.retry
                         if self._tolerance is not None else None),
               "trace": self.recorder is not None,
               "trace_step": self._steps_done}
        errors: list = []
        syncs: List[float] = []
        pending = set(self._conns)
        for w in list(pending):
            try:
                self._conns[w].send(cmd)
            except (BrokenPipeError, OSError):
                self._on_death(w, k, errors, had_dying_msg=False)
                pending.discard(w)
        deadline = time.monotonic() + self.get_timeout + _COLLECT_SLACK
        while pending:
            progressed = False
            for w in list(pending):
                conn = self._conns[w]
                try:
                    has_msg = conn.poll(0.0)
                except (BrokenPipeError, OSError):
                    has_msg = False
                if has_msg:
                    try:
                        msg = conn.recv()
                    except EOFError:
                        self._on_death(w, k, errors, had_dying_msg=False)
                        pending.discard(w)
                        progressed = True
                        continue
                    if self._absorb(w, k, msg, errors, syncs):
                        pending.discard(w)
                    progressed = True
                elif not self._procs[w].is_alive():
                    # drain any message the kernel buffered before death
                    if conn.poll(0.0):
                        continue
                    had = self._dead.get(w) is not None
                    self._on_death(w, k, errors, had_dying_msg=had)
                    pending.discard(w)
                    progressed = True
            if pending and not progressed:
                if time.monotonic() > deadline:
                    who = ", ".join(f"s{s}r{r}" for s, r in sorted(pending))
                    budget = self.get_timeout + _COLLECT_SLACK
                    raise TimeoutError(
                        f"step {k} wedged: no reply from worker processes "
                        f"[{who}] within {budget:.0f}s")
                time.sleep(0.01)
        self._generation += 1
        if errors:
            raise _primary_error(errors)
        self._steps_done += 1
        return StepTiming(end=time.monotonic() - self._t0,
                          sync=max(syncs) if syncs else 0.0)

    # ---------------------------------------------------------------- serving
    def serve(self, spec: dict, *, trace_step: int = 0) -> list:
        """Broadcast one pipelined serving request (``repro.serving``) to
        every stage worker and collect replies.  Each child builds its
        ``ServeStageWorker`` from ``spec`` and drives its serving program to
        completion over the shared file store (the blocking ``take``\\ s
        self-synchronize the pipeline); the head stage replies with the
        greedy tokens.  Returns the head stage's token list ([B, 1] int32
        arrays in decode order)."""
        cmd = {"op": "serve", "spec": spec,
               "trace": self.recorder is not None, "trace_step": trace_step}
        errors: list = []
        tokens: Optional[list] = None
        pending = set(self._conns)
        for w in list(pending):
            try:
                self._conns[w].send(cmd)
            except (BrokenPipeError, OSError):
                self._on_death(w, 0, errors, had_dying_msg=False)
                pending.discard(w)
        deadline = time.monotonic() + self.get_timeout + _COLLECT_SLACK
        while pending:
            progressed = False
            for w in list(pending):
                conn = self._conns[w]
                try:
                    has_msg = conn.poll(0.0)
                except (BrokenPipeError, OSError):
                    has_msg = False
                if has_msg:
                    try:
                        msg = conn.recv()
                    except EOFError:
                        self._on_death(w, 0, errors, had_dying_msg=False)
                        pending.discard(w)
                        progressed = True
                        continue
                    if "ready" in msg:      # stale handshake; ignore
                        progressed = True
                        continue
                    body = msg.get("ok") and msg or msg.get("error")
                    if isinstance(body, dict) and self.recorder is not None:
                        for span in body.get("spans") or ():
                            self.recorder.spans.append(span)
                    if msg.get("ok"):
                        if msg.get("tokens") is not None:
                            tokens = msg["tokens"]
                    elif "error" in msg:
                        d = msg["error"]
                        cls = _errors_by_name().get(d["type"], RuntimeError)
                        errors.append(_reconstruct_error(cls, d["msg"]))
                    pending.discard(w)
                    progressed = True
                elif not self._procs[w].is_alive():
                    if conn.poll(0.0):
                        continue
                    had = self._dead.get(w) is not None
                    self._on_death(w, 0, errors, had_dying_msg=had)
                    pending.discard(w)
                    progressed = True
            if pending and not progressed:
                if time.monotonic() > deadline:
                    who = ", ".join(f"s{s}r{r}" for s, r in sorted(pending))
                    raise TimeoutError(
                        "serve request wedged: no reply from worker "
                        f"processes [{who}] within "
                        f"{self.get_timeout + _COLLECT_SLACK:.0f}s")
                time.sleep(0.01)
        self._generation += 1
        if errors:
            raise _primary_error(errors)
        if tokens is None:
            raise RuntimeError(
                "serve request produced no tokens (head stage never "
                "replied with its sink)")
        return tokens

    # --------------------------------------------------------------- recovery
    def recover(self) -> int:
        """Engine-driven relaunch: revive the poisoned store, purge residual
        non-checkpoint objects (counted), clear the barrier rendezvous
        files, and respawn only the *dead* worker processes — survivors
        keep their warm jit caches and are re-stated through
        ``load_state``/``reset`` RPCs, exactly what a Function Manager
        relaunching failed functions does."""
        self.store.revive()
        shutil.rmtree(self.store.barriers_root, ignore_errors=True)
        os.makedirs(self.store.barriers_root, exist_ok=True)
        purged = 0
        for key in list(self.store.keys()):
            if not key.startswith("ckpt/"):
                self.store.delete(key)
                purged += 1
        dead = sorted(self._dead)
        self._dead.clear()
        for w in dead:
            try:
                self._conns[w].close()
            except OSError:
                pass
            self._procs[w].join(timeout=5.0)
            self._spawn(*w)
        if dead:
            self._await_ready(dead)
        self._generation += 1
        return purged

    def delete(self, key: str) -> None:
        self.store.delete(key)

    def close(self) -> None:
        for w, conn in list(self._conns.items()):
            try:
                conn.send({"op": "exit"})
            except (BrokenPipeError, OSError):
                pass
        for w, p in list(self._procs.items()):
            p.join(timeout=5.0)
            if p.is_alive():
                p.terminate()
                p.join(timeout=2.0)
            if p.is_alive():    # pragma: no cover - terminate() sufficed
                p.kill()
                p.join(timeout=2.0)
        for conn in self._conns.values():
            try:
                conn.close()
            except OSError:
                pass
        self._procs.clear()
        self._conns.clear()
        self._dead.clear()
        self._handles = None
        if self.store is not None and self._owns_root:
            shutil.rmtree(self._root, ignore_errors=True)
        self.store = None


def _reconstruct_error(cls, msg):
    """Rebuild a child-reported exception as its real type so the engine's
    ``is_recoverable`` classification works across the process boundary."""
    try:
        return cls(msg)
    except TypeError:   # pragma: no cover - exotic signature
        return RuntimeError(msg)
