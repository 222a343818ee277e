"""Spawn-safe worker entrypoint + process-grade file store for the
``process`` backend.

This module is what runs *inside* each of the ``S x d`` worker OS processes
(``multiprocessing`` spawn target), plus the storage substrate they share:

* :class:`FileStore` — the process-grade :class:`~repro.serverless.backends.
  local.LocalStore`: a directory of object files with fcntl-file-lock atomic
  put/get/take/delete, a shared ``stats.json`` accounting file maintained
  through the same :class:`~repro.serverless.runtime.store.StoreStats`
  methods every other store uses, and *mtime-based* producer heartbeats and
  leases — a SIGKILL'd producer's heartbeat file freezes, so its consumers
  raise :class:`ProducerDeadError` instead of burning the get timeout, and
  ``abort()`` poisons the store through a file every process sees.
* :class:`FileBarrier` — a ``threading.Barrier`` lookalike over marker files
  (``wait()`` only), generation-counted so the eq (1) collective's three
  phase fences line up across processes; poisoned stores break it.
* :func:`worker_main` — the child process: builds its
  :class:`~repro.serverless.runtime.worker.StageWorker`, heartbeats from a
  daemon thread, and serves step/export/load/reset commands over a pipe,
  driving the engine's own ``_worker_step_program`` generator locally
  (generators cannot cross a process boundary, so the program runs where
  the state lives).  Injected crashes are *real*: the worker marks itself
  dead, poisons the store, flushes a dying message and SIGKILLs its own
  process; lifetime-cap kills exit with :data:`EXIT_LIFETIME` so the parent
  can tell a planned platform recycle from a crash.

Payload-true mode charges each transfer the *real* payload size
(``np.ndarray.nbytes`` / ``len(blob)``) instead of the modeled one, and the
optional per-worker bandwidth throttle sleeps ``nbytes / bandwidth + t_lat``
per transfer — together they give wall-clock traces a calibrated time axis.

Crash-consistency note: fault-injected kills fire at op *boundaries* (the
injector raises before delegating to the store), so the lock-protected
object+accounting updates are never torn by an injected SIGKILL.
"""
from __future__ import annotations

import contextlib
import json
import os
import pickle
import struct
import threading
import time
from typing import Any, Dict, Optional, Tuple

try:
    import fcntl
except ImportError:                      # non-POSIX host
    fcntl = None

from repro.serverless.runtime.store import (
    ProducerDeadError,
    StoreAbortedError,
    StoreStats,
    producer_of_key,
    producer_worker_of_key,
)

#: planned process exit code for a function-lifetime-cap kill (vs SIGKILL
#: for a crash): the parent's Function Manager relaunch telling them apart
EXIT_LIFETIME = 43

#: object-file header: little-endian float64 charged nbytes + payload flag
_HEADER = struct.Struct("<d")


def _true_payload_nbytes(value: Any, blob: bytes) -> float:
    """Real transfer size of ``value``: array ``nbytes`` when it has one,
    raw length for bytes-likes, else the pickled wire size."""
    nb = getattr(value, "nbytes", None)
    if nb is not None:
        return float(nb)
    if isinstance(value, (bytes, bytearray, memoryview)):
        return float(len(value))
    return float(len(blob))


class FileStore:
    """Cross-process key -> object namespace with blocking visibility.

    API-compatible with :class:`~repro.serverless.backends.local.LocalStore`
    (so ``LocalWorkerContext`` and ``local_scatter_reduce`` run over it
    unchanged): ``put`` publishes atomically (tmp file + ``os.replace``
    under a global file lock), ``get``/``take`` poll for the object file,
    failing over on a dead/poisoned producer; accounting lives in one shared
    ``stats.json`` updated through :class:`StoreStats` under the same lock.

    Liveness is filesystem truth, not thread state: ``heartbeat`` touches a
    per-worker file's mtime, so a SIGKILL'd worker's lease goes stale by
    itself; ``mark_dead`` drops a marker file; ``abort`` writes a poison
    file every blocked consumer in every process notices on its next poll.
    """

    def __init__(self, root: str, timeout: float = 120.0,
                 lease_timeout: float = 20.0, payload_true: bool = False,
                 bandwidth: Optional[float] = None, t_lat: float = 0.0):
        if fcntl is None:
            raise RuntimeError(
                "FileStore needs POSIX file locks (fcntl); the process "
                "backend is unavailable on this host")
        self.root = root
        self.timeout = timeout
        self.lease_timeout = lease_timeout
        self.payload_true = payload_true
        self.bandwidth = bandwidth      # bytes/s uplink+downlink throttle
        self.t_lat = t_lat              # per-request round-trip, throttled
        self._objects = os.path.join(root, "objects")
        self._tmp = os.path.join(root, "tmp")
        self._hb = os.path.join(root, "hb")
        self._dead = os.path.join(root, "dead")
        self.barriers_root = os.path.join(root, "barriers")
        self._lock_path = os.path.join(root, "lock")
        self._stats_path = os.path.join(root, "stats.json")
        self._poison_path = os.path.join(root, "poison")
        self._seq = 0
        for d in (self._objects, self._tmp, self._hb, self._dead,
                  self.barriers_root):
            os.makedirs(d, exist_ok=True)
        with self._locked():
            if not os.path.exists(self._stats_path):
                self._dump_acct(StoreStats(), 0.0)

    # ---------------------------------------------------------------- locking
    @contextlib.contextmanager
    def _locked(self):
        fd = os.open(self._lock_path, os.O_RDWR | os.O_CREAT, 0o644)
        try:
            fcntl.flock(fd, fcntl.LOCK_EX)
            yield
        finally:
            os.close(fd)        # close releases the flock, even on SIGKILL

    # ------------------------------------------------------------- accounting
    def _load_acct(self) -> Tuple[StoreStats, float]:
        with open(self._stats_path) as f:
            d = json.load(f)
        live = d.pop("live_bytes", 0.0)
        return StoreStats(**d), live

    def _dump_acct(self, stats: StoreStats, live: float) -> None:
        d = stats.as_dict()
        d["live_bytes"] = live
        tmp = self._tmp_path()
        with open(tmp, "w") as f:
            json.dump(d, f)
        os.replace(tmp, self._stats_path)

    @property
    def stats(self) -> StoreStats:
        with self._locked():
            return self._load_acct()[0]

    @property
    def live_bytes(self) -> float:
        with self._locked():
            return self._load_acct()[1]

    # ------------------------------------------------------------------ paths
    def _obj_path(self, key: str) -> str:
        return os.path.join(self._objects, *key.split("/"))

    def _tmp_path(self) -> str:
        self._seq += 1
        return os.path.join(
            self._tmp, f"t{os.getpid()}-{threading.get_ident()}-{self._seq}")

    def _hb_path(self, worker: Tuple[int, int]) -> str:
        return os.path.join(self._hb, f"s{worker[0]}r{worker[1]}")

    def _dead_path(self, worker: Tuple[int, int]) -> str:
        return os.path.join(self._dead, f"s{worker[0]}r{worker[1]}")

    @staticmethod
    def _read_header(path: str) -> Optional[float]:
        try:
            with open(path, "rb") as f:
                return _HEADER.unpack(f.read(_HEADER.size))[0]
        except (OSError, struct.error):
            return None

    # ------------------------------------------------------ liveness / leases
    def heartbeat(self, worker: Tuple[int, int]) -> None:
        path = self._hb_path(worker)
        try:
            os.utime(path)
        except OSError:
            with open(path, "a"):
                pass

    def mark_dead(self, worker: Tuple[int, int]) -> None:
        with open(self._dead_path(worker), "a"):
            pass

    def heartbeat_age(self, worker: Tuple[int, int]) -> Optional[float]:
        try:
            return time.time() - os.stat(self._hb_path(worker)).st_mtime
        except OSError:
            return None

    def _poison_text(self) -> Optional[str]:
        try:
            with open(self._poison_path) as f:
                return f.read()
        except OSError:
            return None

    def abort(self, reason: BaseException) -> None:
        # first poison wins (matches LocalStore): collateral errors from
        # peers failing over must not overwrite the originating crash
        try:
            fd = os.open(self._poison_path,
                         os.O_WRONLY | os.O_CREAT | os.O_EXCL, 0o644)
        except FileExistsError:
            return
        with os.fdopen(fd, "w") as f:
            f.write(f"{type(reason).__name__}: {reason}")

    def revive(self) -> None:
        try:
            os.remove(self._poison_path)
        except OSError:
            pass
        for d in (self._dead, self._hb):
            for fn in os.listdir(d):
                try:
                    os.remove(os.path.join(d, fn))
                except OSError:
                    pass

    # --------------------------------------------------------------- throttle
    def _throttle(self, nbytes: float) -> None:
        if self.bandwidth:
            time.sleep(nbytes / self.bandwidth + self.t_lat)

    # -------------------------------------------------------------- store API
    def put(self, key: str, nbytes: float, value: Any = None) -> None:
        blob = None
        if value is not None:
            blob = pickle.dumps(value, protocol=pickle.HIGHEST_PROTOCOL)
            if self.payload_true:
                nbytes = _true_payload_nbytes(value, blob)
        nbytes = float(nbytes)
        self._throttle(nbytes)          # uplink: transfer precedes visibility
        tmp = self._tmp_path()
        with open(tmp, "wb") as f:
            f.write(_HEADER.pack(nbytes))
            if blob is None:
                f.write(b"\x00")
            else:
                f.write(b"\x01")
                f.write(blob)
        path = self._obj_path(key)
        os.makedirs(os.path.dirname(path), exist_ok=True)
        with self._locked():
            stats, live = self._load_acct()
            prev = self._read_header(path)
            if prev is not None:
                # overwrite frees the old object: count the implicit delete
                live -= prev
                stats.count_delete(key, prev)
            os.replace(tmp, path)
            live += nbytes
            stats.count_put(key, nbytes, live)
            self._dump_acct(stats, live)

    def _wait_for(self, key: str) -> str:
        deadline = time.monotonic() + self.timeout
        producer = producer_worker_of_key(key)
        path = self._obj_path(key)
        poll = min(0.01, self.lease_timeout / 4.0)
        while True:
            poison = self._poison_text()
            if poison is not None:
                raise StoreAbortedError(
                    f"store aborted while waiting for {key!r}: {poison}")
            if os.path.exists(path):
                return path
            if producer is not None:
                if os.path.exists(self._dead_path(producer)):
                    raise ProducerDeadError(
                        f"object {key!r} will never arrive: its producer "
                        f"worker (stage {producer[0]}, replica "
                        f"{producer[1]}) died")
                age = self.heartbeat_age(producer)
                if age is not None and age > self.lease_timeout:
                    raise ProducerDeadError(
                        f"object {key!r} will never arrive: its producer "
                        f"worker (stage {producer[0]}, replica "
                        f"{producer[1]}) stopped heartbeating "
                        f"{age:.1f}s ago (lease timeout "
                        f"{self.lease_timeout:.0f}s)")
            if time.monotonic() > deadline:
                raise TimeoutError(self._diagnose_timeout(key))
            time.sleep(poll)

    def _diagnose_timeout(self, key: str) -> str:
        producer = producer_worker_of_key(key)
        existing = sorted(self.keys())
        sample = ", ".join(existing[:8]) if existing else "none"
        if producer is None:
            lease = f"no producer lease on record ({producer_of_key(key)})"
        else:
            age = self.heartbeat_age(producer)
            state = ("marked dead"
                     if os.path.exists(self._dead_path(producer))
                     else f"last heartbeat {age:.1f}s ago" if age is not None
                     else "never heartbeat")
            lease = (f"producer lease held by worker (stage {producer[0]}, "
                     f"replica {producer[1]}) — {state}")
        return (f"object {key!r} never became visible within "
                f"{self.timeout:.0f}s; {lease}; "
                f"{len(existing)} keys present (e.g. [{sample}])")

    def _read_obj(self, path: str) -> Tuple[float, Optional[bytes]]:
        with open(path, "rb") as f:
            nbytes = _HEADER.unpack(f.read(_HEADER.size))[0]
            flag = f.read(1)
            blob = f.read() if flag == b"\x01" else None
        return nbytes, blob

    def get(self, key: str, return_nbytes: bool = False) -> Any:
        path = self._obj_path(key)
        while True:
            self._wait_for(key)
            with self._locked():
                if not os.path.exists(path):
                    continue            # consumed between poll and lock
                nbytes, blob = self._read_obj(path)
                stats, live = self._load_acct()
                stats.count_get(key, nbytes)
                self._dump_acct(stats, live)
            break
        self._throttle(nbytes)          # downlink
        value = None if blob is None else pickle.loads(blob)
        return (value, nbytes) if return_nbytes else value

    def take(self, key: str, return_nbytes: bool = False) -> Any:
        path = self._obj_path(key)
        while True:
            self._wait_for(key)
            with self._locked():
                if not os.path.exists(path):
                    continue
                nbytes, blob = self._read_obj(path)
                os.remove(path)
                stats, live = self._load_acct()
                stats.count_get(key, nbytes)
                live -= nbytes
                stats.count_delete(key, nbytes)
                self._dump_acct(stats, live)
            break
        self._throttle(nbytes)
        value = None if blob is None else pickle.loads(blob)
        return (value, nbytes) if return_nbytes else value

    def delete(self, key: str) -> None:
        path = self._obj_path(key)
        with self._locked():
            nbytes = self._read_header(path)
            if nbytes is None:
                return
            os.remove(path)
            stats, live = self._load_acct()
            live -= nbytes
            stats.count_delete(key, nbytes)
            self._dump_acct(stats, live)

    def keys(self):
        out = []
        for dirpath, _dirs, files in os.walk(self._objects):
            rel = os.path.relpath(dirpath, self._objects)
            for fn in files:
                out.append(fn if rel == "."
                           else f"{rel}/{fn}".replace(os.sep, "/"))
        return out

    def __contains__(self, key: str) -> bool:
        return os.path.exists(self._obj_path(key))

    def __len__(self) -> int:
        return len(self.keys())


class FileBarrier:
    """``threading.Barrier``-shaped rendezvous over marker files: party
    ``index`` of ``parties`` drops ``g{generation}/r{index}`` and polls until
    all parties arrived.  The generation counter advances per ``wait()``
    call, which is what keeps the eq (1) collective's successive fences
    distinct across processes.  A poisoned store (peer died) breaks the
    barrier with :class:`threading.BrokenBarrierError` — the same
    recoverable type the thread backend's aborted barriers raise."""

    def __init__(self, store: FileStore, name: str, parties: int, index: int,
                 timeout: float):
        self.store = store
        self.dir = os.path.join(store.barriers_root, name)
        self.parties = parties
        self.index = index
        self.timeout = timeout
        self._generation = 0

    def wait(self) -> None:
        gen_dir = os.path.join(self.dir, f"g{self._generation}")
        self._generation += 1
        os.makedirs(gen_dir, exist_ok=True)
        with open(os.path.join(gen_dir, f"r{self.index}"), "a"):
            pass
        deadline = time.monotonic() + self.timeout
        while True:
            if self.store._poison_text() is not None:
                raise threading.BrokenBarrierError
            try:
                if len(os.listdir(gen_dir)) >= self.parties:
                    return
            except OSError:             # purged under us by recover()
                raise threading.BrokenBarrierError from None
            if time.monotonic() > deadline:
                raise threading.BrokenBarrierError
            time.sleep(0.005)


# =========================================================== child entrypoint
def _np_tree(tree):
    import jax
    import numpy as np

    return jax.tree.map(np.asarray, tree)


def _fault_delta(state, report) -> Optional[dict]:
    """The step's fault-consumption state, shipped back so the parent keeps
    the authoritative once-only schedule across workers and replays."""
    out: Dict[str, Any] = {}
    if state is not None:
        out["remaining"] = dict(state.remaining)
        out["fired"] = sorted(state.fired)
    if report is not None:
        out["retries"] = report.retries
        out["recovery_s"] = report.recovery_s
    return out or None


def _run_step(conn, store: FileStore, s: int, r: int, agg, worker, cmd,
              t0: float) -> None:
    """Drive one training step's program locally; reply ok / error / dying."""
    import os as _os
    import signal

    from repro.serverless import faults as F
    from repro.serverless.backends.local import LocalWorkerContext
    from repro.serverless.runtime.engine import _worker_step_program
    from repro.serverless.runtime.scatter_reduce import local_scatter_reduce

    k = cmd["k"]
    d = agg.d
    spans: list = []
    tracer = None
    clock = None
    if cmd["trace"]:
        from repro.obs.schema import WorkerTracer

        tracer = WorkerTracer(spans, s, r)
        tracer.step = cmd["trace_step"]
        tracer.phase = "fwd"
        clock = lambda: time.monotonic() - t0          # noqa: E731

    ctx = LocalWorkerContext(store, tracer=tracer, clock=clock, worker=(s, r))
    fault_state = None
    if cmd.get("fault") is not None:
        fp = cmd["fault"]
        plan = F.FaultPlan(
            events=tuple(F.FaultEvent.from_dict(e) for e in fp["events"]),
            lifetime_steps=fp["lifetime_steps"])
        fault_state = F._PlanState(plan, None)   # parent owns the report
        fault_state.remaining = {int(i): n
                                 for i, n in fp["remaining"].items()}
        fault_state.fired = set(fp["fired"])

        class _InjectorShim:
            """What FaultyWorkerContext reads off its injector, mirrored
            from the parent's FaultInjector for this one step."""

        shim = _InjectorShim()
        shim.plan = plan
        shim.current_step = k
        shim.age = fp["age"]
        shim._lifetime_noted = True      # the parent counts "lifetime"
        ctx = F.FaultyWorkerContext(ctx, fault_state, s, r, shim)
    report = None
    if cmd.get("retry") is not None:
        report = F.FaultReport()
        ctx = F.ResilientContext(ctx, cmd["retry"], report)

    barrier = (FileBarrier(store, f"k{k}-s{s}", d, r, store.timeout)
               if d > 1 else None)
    losses: Dict = {}
    sync_s = 0.0
    gen = _worker_step_program(ctx, k=k, s=s, r=r, agg=agg, worker=worker,
                               batch=cmd["batch"], losses=losses)
    try:
        y = next(gen)
        while True:
            if isinstance(y, tuple) and y[0] == "sync":
                if tracer is not None:
                    tracer.phase = "sync"
                ts = time.monotonic()
                reduced = local_scatter_reduce(
                    store, r, d, agg.s_stage[s], y[1],
                    key_prefix=f"k{k}/sync{s}",
                    pipelined=cmd["pipelined"], barrier=barrier,
                    tracer=tracer, clock=clock)
                sync_s = time.monotonic() - ts
                y = gen.send(reduced)
            else:
                y = next(gen)
    except StopIteration:
        conn.send({"ok": True, "sync_s": sync_s, "loss": losses.get((s, r)),
                   "spans": spans, "fault": _fault_delta(fault_state, report)})
    except F.WorkerCrashed as e:
        # a real function death: poison the substrate so peers fail over,
        # flush the dying report (the kernel buffers it past our death),
        # then actually die — SIGKILL for a crash, a planned exit code for
        # the lifetime cap so the parent relaunches instead of blaming us
        store.mark_dead((s, r))
        store.abort(e)
        conn.send({"dying": {"kind": e.kind, "msg": str(e), "step": k,
                             "spans": spans,
                             "fault": _fault_delta(fault_state, report)}})
        if e.kind == "lifetime":
            _os._exit(EXIT_LIFETIME)
        _os.kill(_os.getpid(), signal.SIGKILL)
    except BaseException as e:  # noqa: BLE001 - shipped to the parent
        store.mark_dead((s, r))
        store.abort(e)
        conn.send({"error": {"type": type(e).__name__, "msg": str(e),
                             "step": k, "spans": spans, "sync_s": sync_s,
                             "fault": _fault_delta(fault_state, report)}})
        # stay alive: the parent's recover() revives the store and this
        # worker serves the replay (its jit caches survive the recovery)


def _run_serve(conn, store: FileStore, s: int, r: int, cmd,
               t0: float) -> None:
    """Drive one serving request's stage program locally (``repro.serving``):
    build the stage's ServeStageWorker from the shipped spec and run its
    prefill + decode program to completion over the shared store — the
    blocking ``take``\\ s synchronize the pipeline, no barriers needed.  The
    head stage replies with the greedy-token sink."""
    from repro.serverless.backends.local import LocalWorkerContext

    tr_spans: list = []
    tracer = None
    clock = None
    if cmd["trace"]:
        from repro.obs.schema import WorkerTracer

        tracer = WorkerTracer(tr_spans, s, r)
        tracer.step = cmd["trace_step"]
        tracer.phase = "prefill"
        clock = lambda: time.monotonic() - t0          # noqa: E731

    ctx = LocalWorkerContext(store, tracer=tracer, clock=clock, worker=(s, r))
    try:
        from repro.serverless.runtime.worker import stage_instance_ranges
        from repro.serving.engine import serve_worker_program
        from repro.serving.worker import ServeStageWorker

        spec = cmd["spec"]
        ranges = stage_instance_ranges(spec["cfg"], spec["x"])
        S = len(ranges)
        sworker = ServeStageWorker(spec["cfg"], ranges[s], spec["params"],
                                   s_ctx=spec["s_ctx"],
                                   use_pallas=spec["use_pallas"])
        sink: list = []

        def on_decode() -> None:
            if tracer is not None:
                tracer.phase = "decode"

        gen = serve_worker_program(
            ctx, s=s, S=S, worker=sworker, toks=spec["toks"],
            n_new=spec["n_new"], sink=sink, on_decode=on_decode)
        for _ in gen:
            pass
        conn.send({"ok": True, "spans": tr_spans,
                   "tokens": sink if s == S - 1 else None})
    except BaseException as e:  # noqa: BLE001 - shipped to the parent
        store.mark_dead((s, r))
        store.abort(e)
        conn.send({"error": {"type": type(e).__name__, "msg": str(e),
                             "spans": tr_spans}})


def worker_main(conn, init: dict) -> None:
    """Child-process entrypoint (``multiprocessing`` spawn target): build
    the stage worker, start heartbeating, then serve commands until told to
    exit (or until an injected fault kills the process for real)."""
    s, r = init["s"], init["r"]
    store = FileStore(
        init["root"], timeout=init["get_timeout"],
        lease_timeout=init["lease_timeout"],
        payload_true=init["payload_true"],
        bandwidth=init["bandwidth"], t_lat=init["t_lat"])

    worker = None
    initial_state = None
    if init["exec_spec"] is not None:
        from repro.serverless.runtime.worker import (
            StageWorker,
            stage_instance_ranges,
        )

        es = init["exec_spec"]
        spans = stage_instance_ranges(es["cfg"], es["x"])
        worker = StageWorker(es["cfg"], spans[s], es["init_params"],
                             mu=es["mu"], optimizer=es["optimizer"],
                             replicas=int(init["agg"].d), jit=es["jit"],
                             remat=es["remat"])
        # cheap in-process reset snapshot: load_state keeps jit caches warm
        initial_state = _np_tree(worker.export_state())

    # liveness from a daemon thread, not op progress: a long jit compile
    # must not look like death; a SIGKILL stops the thread with the process,
    # freezing the mtime — which is exactly the lease going stale
    stop = threading.Event()

    def beat() -> None:
        while not stop.is_set():
            store.heartbeat((s, r))
            stop.wait(init["lease_timeout"] / 4.0)

    threading.Thread(target=beat, daemon=True,
                     name=f"heartbeat-s{s}r{r}").start()
    store.heartbeat((s, r))
    conn.send({"ready": [s, r]})

    while True:
        try:
            cmd = conn.recv()
        except EOFError:        # parent went away; nothing left to serve
            return
        op = cmd["op"]
        if op == "exit":
            return
        if op == "step":
            _run_step(conn, store, s, r, init["agg"], worker, cmd,
                      init["t0"])
        elif op == "serve":
            _run_serve(conn, store, s, r, cmd, init["t0"])
        elif op == "export_state":
            conn.send({"state": _np_tree(worker.export_state())})
        elif op == "load_state":
            worker.load_state(cmd["state"])
            conn.send({"ok": True})
        elif op == "reset":
            worker.load_state(initial_state)
            conn.send({"ok": True})
        else:  # pragma: no cover - protocol error
            conn.send({"error": {"type": "ValueError",
                                 "msg": f"unknown worker op {op!r}",
                                 "spans": [], "fault": None}})
