"""Wall-clock backend: real concurrent stage workers on one host.

Where the emulated backend *models* serverless execution on a virtual clock,
this backend *performs* it: the plan's ``S x d`` stage workers run as real
threads, exchanging every boundary activation, gradient and scatter-reduce
chunk through a thread-safe :class:`LocalStore` whose ``get`` genuinely
blocks until the producer's ``put`` lands — the storage-visibility and
ordering races of a real platform, which the deterministic virtual-clock
interleave can never hit.  Numerics are the point: a plan replayed here must
train to params bit-identical to the emulated backend (same JAX stage math,
same ring-ordered fp32 reduction — see ``tests/test_backends.py``).

Time is host wall-clock (``wall_clock=True``): ``t_iter`` measures this
machine, not Lambda, so cost/time outputs are only self-relative; modeled
compute costs are ignored (no sleeping) and the *modeled* byte sizes are
still recorded in ``StoreStats`` so byte accounting matches the emulated
backend object-for-object.

The store is dict-backed by default; pass ``fs_root`` to spill every payload
through files (pickle round-trip per object) — closer to an object-store
client, useful for exercising serialization of the values that would cross
S3/OSS.
"""
from __future__ import annotations

import os
import pickle
import threading
import time
from dataclasses import dataclass
from typing import Any, Callable, Dict, List, Optional, Tuple

from repro.obs.schema import open_span
from repro.serverless.backends.base import (
    ExecutionBackend,
    StepTiming,
    WorkerContext,
    WorkerProgram,
)
from repro.serverless.runtime.scatter_reduce import local_scatter_reduce
from repro.serverless.runtime.store import (
    ProducerDeadError,
    StoreAbortedError,
    StoreStats,
    producer_of_key,
    producer_worker_of_key,
)

# deadlock backstop: a blocking get that outwaits this is a lost producer
# (a peer worker thread died), not a slow one
DEFAULT_GET_TIMEOUT = 120.0

# a producer whose last heartbeat is older than this is *dead*, not slow:
# its consumers fail over immediately instead of burning the get timeout
DEFAULT_LEASE_TIMEOUT = 5.0

# S x d real threads; past this the run would be measuring the host's
# scheduler, not the plan — replay large plans on the emulated backend
MAX_WORKERS = 256


@dataclass
class _Stored:
    nbytes: float
    value: Any = None
    path: Optional[str] = None


class LocalStore:
    """Thread-safe key -> object namespace with *blocking* visibility.

    ``put`` makes the object immediately visible and wakes waiters; ``get``
    blocks until the key exists (raising ``TimeoutError`` after ``timeout``
    seconds so a dead producer fails the run instead of hanging it);
    ``take`` is the fetch-and-consume used for single-consumer pipeline
    boundary objects.  ``nbytes`` is the *modeled* object size (the same
    numbers the emulated store charges), kept for byte accounting; payloads
    ride in memory, or through ``fs_root`` files when given.

    Liveness: workers ``heartbeat()`` as they make progress and are
    ``mark_dead()``-ed when their thread dies.  A blocked ``get`` checks the
    awaited key's *producer lease* (the engine key schema names exactly one
    producer worker per key): a dead or heartbeat-stale producer raises
    :class:`ProducerDeadError` immediately — "dead", not "slow" — instead of
    burning the full get timeout.  ``abort()`` poisons the store, waking
    every waiter with :class:`StoreAbortedError`; ``revive()`` un-poisons it
    for the engine's recovery replay.
    """

    def __init__(self, timeout: float = DEFAULT_GET_TIMEOUT,
                 fs_root: Optional[str] = None,
                 lease_timeout: float = DEFAULT_LEASE_TIMEOUT):
        self.timeout = timeout
        self.lease_timeout = lease_timeout
        self.fs_root = fs_root
        self._cv = threading.Condition()
        self._objects: Dict[str, _Stored] = {}
        self._live_bytes = 0.0
        self._seq = 0
        self._poison: Optional[BaseException] = None
        self._heartbeats: Dict[Tuple[int, int], float] = {}
        self._dead: set = set()
        self.stats = StoreStats()
        if fs_root is not None:
            os.makedirs(fs_root, exist_ok=True)

    # ------------------------------------------------------ liveness / leases
    def heartbeat(self, worker: Tuple[int, int]) -> None:
        """Record that worker (stage, replica) is alive and making progress
        (called by its context on every store/compute op)."""
        with self._cv:
            self._heartbeats[worker] = time.monotonic()

    def mark_dead(self, worker: Tuple[int, int]) -> None:
        """Declare a worker dead (its thread raised); wakes every waiter so
        consumers of its keys fail over immediately."""
        with self._cv:
            self._dead.add(worker)
            self._cv.notify_all()

    def heartbeat_age(self, worker: Tuple[int, int]) -> Optional[float]:
        """Seconds since the worker's last heartbeat (None: never beat)."""
        with self._cv:
            beat = self._heartbeats.get(worker)
        return None if beat is None else time.monotonic() - beat

    def abort(self, reason: BaseException) -> None:
        """Poison the store: every current and future blocking op raises
        :class:`StoreAbortedError` naming ``reason`` (the first worker death
        of the step) instead of hanging until its timeout."""
        with self._cv:
            if self._poison is None:
                self._poison = reason
            self._cv.notify_all()

    def revive(self) -> None:
        """Clear poison and liveness state for a recovery replay (the
        engine respawns every worker, so old leases are meaningless)."""
        with self._cv:
            self._poison = None
            self._dead.clear()
            self._heartbeats.clear()

    # ----------------------------------------------------------- fs payloads
    def _spill(self, value: Any) -> Optional[str]:
        if self.fs_root is None or value is None:
            return None
        with self._cv:
            self._seq += 1
            path = os.path.join(self.fs_root, f"obj-{self._seq}.pkl")
        with open(path, "wb") as f:
            pickle.dump(value, f)
        return path

    @staticmethod
    def _load(obj: _Stored) -> Any:
        if obj.path is None:
            return obj.value
        with open(obj.path, "rb") as f:
            return pickle.load(f)

    # ------------------------------------------------------------ store API
    def put(self, key: str, nbytes: float, value: Any = None) -> None:
        path = self._spill(value)
        with self._cv:
            prev = self._objects.get(key)
            if prev is not None:
                # overwrite frees the old object: count the implicit delete
                # (and its spill file) so drain accounting stays conserved
                self._live_bytes -= prev.nbytes
                self.stats.count_delete(key, prev.nbytes)
                if prev.path is not None:
                    try:
                        os.remove(prev.path)
                    except OSError:
                        pass
            obj = _Stored(nbytes=float(nbytes),
                          value=None if path is not None else value, path=path)
            self._objects[key] = obj
            self._live_bytes += obj.nbytes
            self.stats.count_put(key, obj.nbytes, self._live_bytes)
            self._cv.notify_all()

    def _wait_for(self, key: str) -> _Stored:
        deadline = time.monotonic() + self.timeout
        producer = producer_worker_of_key(key)
        while True:
            if self._poison is not None:
                raise StoreAbortedError(
                    f"store aborted while waiting for {key!r}: "
                    f"{self._poison}") from self._poison
            if key in self._objects:
                return self._objects[key]
            if producer is not None:
                if producer in self._dead:
                    raise ProducerDeadError(
                        f"object {key!r} will never arrive: its producer "
                        f"worker (stage {producer[0]}, replica "
                        f"{producer[1]}) died")
                beat = self._heartbeats.get(producer)
                if (beat is not None
                        and time.monotonic() - beat > self.lease_timeout):
                    raise ProducerDeadError(
                        f"object {key!r} will never arrive: its producer "
                        f"worker (stage {producer[0]}, replica "
                        f"{producer[1]}) stopped heartbeating "
                        f"{time.monotonic() - beat:.1f}s ago (lease "
                        f"timeout {self.lease_timeout:.0f}s)")
            remaining = deadline - time.monotonic()
            if remaining <= 0:
                raise TimeoutError(self._diagnose_timeout_locked(key))
            # woken early by put/abort/mark_dead; the poll interval only
            # bounds how late a *silently* stale heartbeat is noticed
            self._cv.wait(min(remaining, self.lease_timeout / 4.0, 0.25))

    def _diagnose_timeout_locked(self, key: str) -> str:
        """Rich get-timeout message (caller holds the lock): the missing
        key, which keys *do* exist, who held the producer lease, and how
        stale its heartbeat is — a statement, not a guess."""
        producer = producer_worker_of_key(key)
        existing = sorted(self._objects)
        sample = ", ".join(existing[:8]) if existing else "none"
        if producer is None:
            who = producer_of_key(key)
            lease = f"no producer lease on record ({who})"
        else:
            age = None
            beat = self._heartbeats.get(producer)
            if beat is not None:
                age = time.monotonic() - beat
            state = ("marked dead" if producer in self._dead
                     else f"last heartbeat {age:.1f}s ago" if age is not None
                     else "never heartbeat")
            lease = (f"producer lease held by worker (stage {producer[0]}, "
                     f"replica {producer[1]}) — {state}")
        return (f"object {key!r} never became visible within "
                f"{self.timeout:.0f}s; {lease}; "
                f"{len(existing)} keys present (e.g. [{sample}])")

    def get(self, key: str, return_nbytes: bool = False) -> Any:
        """Block until ``key`` is visible, then return its payload (or a
        ``(payload, modeled_nbytes)`` pair with ``return_nbytes=True`` —
        tracing needs the object size alongside the value)."""
        with self._cv:
            obj = self._wait_for(key)
            self.stats.count_get(key, obj.nbytes)
        value = self._load(obj)
        return (value, obj.nbytes) if return_nbytes else value

    def take(self, key: str, return_nbytes: bool = False) -> Any:
        """Blocking fetch-and-consume (get + delete, atomically)."""
        with self._cv:
            obj = self._wait_for(key)
            self.stats.count_get(key, obj.nbytes)
            value = self._load(obj)   # before delete unlinks any spill file
            self._delete_locked(key)
        return (value, obj.nbytes) if return_nbytes else value

    def delete(self, key: str) -> None:
        with self._cv:
            self._delete_locked(key)

    def _delete_locked(self, key: str) -> None:
        obj = self._objects.pop(key, None)
        if obj is not None:
            self._live_bytes -= obj.nbytes
            self.stats.count_delete(key, obj.nbytes)
            if obj.path is not None:
                try:
                    os.remove(obj.path)
                except OSError:
                    pass

    def keys(self):
        with self._cv:
            return list(self._objects)

    def __contains__(self, key: str) -> bool:
        with self._cv:
            return key in self._objects

    def __len__(self) -> int:
        with self._cv:
            return len(self._objects)

    @property
    def live_bytes(self) -> float:
        return self._live_bytes


class LocalWorkerContext(WorkerContext):
    """A stage worker on a real thread: blocking store, no modeled clock.

    With ``tracer``/``clock`` set (``repro.obs.WorkerTracer`` + seconds since
    run start), every store op, compute and host step emits one *wall-clock*
    span (``repro.obs.open_span``, which also marks it in a running
    ``jax.profiler`` trace); a blocking download's visibility wait is part
    of its span, which is exactly the stall the timeline should show.
    """

    def __init__(self, store: LocalStore, tracer=None, clock=None,
                 worker: Optional[Tuple[int, int]] = None):
        self.store = store
        self.tracer = tracer
        self.clock = clock
        self.worker = worker

    def _beat(self) -> None:
        if self.worker is not None:
            self.store.heartbeat(self.worker)

    def _span(self, op: str, *, nbytes: float = 0.0,
              key: Optional[str] = None):
        return open_span(self.tracer, self.clock, op, nbytes=nbytes, key=key)

    def download(self, key: str):
        self._beat()
        with self._span("download", key=key) as sp:
            value, sp.nbytes = self.store.take(key, return_nbytes=True)
        return value, None

    def compute(self, cost_s: float, fn: Optional[Callable[[], Any]] = None,
                after: Any = None) -> Any:
        # modeled cost is the virtual clock's business; here compute is real
        self._beat()
        with self._span("compute"):
            return fn() if fn is not None else None

    def upload(self, key: str, nbytes: float, value: Any = None) -> Any:
        self._beat()
        with self._span("upload", nbytes=nbytes, key=key):
            self.store.put(key, nbytes, value=value)
        return None

    def host(self, op: str, fn: Callable[[], Any], *,
             nbytes: Optional[float] = None) -> Any:
        # host work belongs to the sync phase: for tracing, this is the
        # worker's bwd -> sync flip (its sync yield comes later)
        self._beat()
        if self.tracer is not None:
            self.tracer.phase = "sync"
        with self._span(op) as sp:
            out = fn()
            sp.nbytes = getattr(out, "nbytes", 0.0) if nbytes is None else nbytes
        # beat again: the next step's consumers may check this lease before
        # the worker's first op of that step, and the update can outlast it
        self._beat()
        return out

    def phase_barrier(self) -> None:
        # a serial worker's forward uploads complete before it proceeds;
        # for tracing this is also the worker's fwd -> bwd phase flip
        self._beat()
        if self.tracer is not None:
            self.tracer.phase = "bwd"
        return None

    def wait(self, seconds: float, op: str = "retry") -> None:
        # real backoff on the wall-clock backend (the time is honest, and
        # the op span makes recovery overhead visible in the trace)
        self._beat()
        with self._span(op):
            time.sleep(seconds)

    def fetch(self, key: str, op: str = "download"):
        # non-consuming blocking get (checkpoint restore)
        self._beat()
        with self._span(op, key=key) as sp:
            value, sp.nbytes = self.store.get(key, return_nbytes=True)
        return value, None


def _primary_error(errors: List[BaseException]) -> BaseException:
    """The error that *caused* a failed step, not its collateral: an
    exceeded tolerance budget must surface over the crash it wraps, a crash
    over the StoreAborted/BrokenBarrier/Timeout wreckage it strands its
    peers in."""
    def rank(e: BaseException) -> int:
        name = type(e).__name__
        if name == "FaultToleranceExceeded":
            return 0
        if name == "WorkerCrashed":
            return 1
        if name == "TransientStoreError":
            return 2
        if isinstance(e, (StoreAbortedError, ProducerDeadError,
                          threading.BrokenBarrierError, TimeoutError)):
            return 4
        return 3
    return min(errors, key=rank)


class LocalBackend(ExecutionBackend):
    """Real-concurrency substitute platform on the host."""

    name = "local"
    wall_clock = True

    def __init__(self, *, fs_root: Optional[str] = None,
                 get_timeout: float = DEFAULT_GET_TIMEOUT,
                 lease_timeout: float = DEFAULT_LEASE_TIMEOUT):
        self.fs_root = fs_root
        self.get_timeout = get_timeout
        self.lease_timeout = lease_timeout
        self.agg = None
        self.store: Optional[LocalStore] = None
        self._t0 = 0.0
        # per-(stage, replica) WorkerTracers when a recorder is attached;
        # contexts for step k are handed out after run_step(k-1) returned,
        # so _steps_done stamps each tracer's step at context creation
        self._tracers: Dict[Tuple[int, int], Any] = {}
        self._steps_done = 0

    # --------------------------------------------------------------- lifecycle
    def open(self, agg) -> None:
        if agg.S * agg.d > MAX_WORKERS:
            raise ValueError(
                f"plan spawns {agg.S}x{agg.d}={agg.S * agg.d} concurrent "
                f"workers; the local backend caps at {MAX_WORKERS} threads "
                "— replay this plan on the emulated backend instead")
        self.agg = agg
        self.store = self._make_store()
        self._tracers = {}
        self._steps_done = 0
        self._t0 = time.perf_counter()

    def _make_store(self) -> LocalStore:
        """Store-provisioning hook: cloud adapters subclass this backend and
        swap in a client-backed store with the same blocking surface."""
        return LocalStore(timeout=self.get_timeout, fs_root=self.fs_root,
                          lease_timeout=self.lease_timeout)

    def recover(self) -> int:
        """Revive the poisoned store and purge residual non-checkpoint keys
        so the engine can replay from the last checkpoint."""
        self.store.revive()
        return super().recover()

    def _clock(self) -> float:
        """Seconds since run start — the trace's wall-clock time base."""
        return time.perf_counter() - self._t0

    def context(self, s: int, r: int) -> LocalWorkerContext:
        if self.recorder is None:
            return LocalWorkerContext(self.store, worker=(s, r))
        tr = self.recorder.tracer(s, r)
        tr.step = self._steps_done
        tr.phase = "fwd"
        self._tracers[(s, r)] = tr
        return LocalWorkerContext(self.store, tracer=tr, clock=self._clock,
                                  worker=(s, r))

    @property
    def store_stats(self) -> StoreStats:
        return self.store.stats

    def _store_for_verification(self):
        return self.store

    # --------------------------------------------------------------- stepping
    def run_step(self, k: int, programs: Dict[Tuple[int, int], WorkerProgram],
                 *, pipelined_sync: bool = True) -> StepTiming:
        agg = self.agg
        S, d = agg.S, agg.d
        # the barrier timeout mirrors the store's: a peer that never arrives
        # (died worker) breaks the barrier instead of hanging the run
        barriers = ({s: threading.Barrier(d, timeout=self.get_timeout)
                     for s in range(S)} if d > 1 else {})
        sync_secs: Dict[Tuple[int, int], float] = {}
        errors: List[BaseException] = []
        err_lock = threading.Lock()

        def drive(s: int, r: int, gen: WorkerProgram) -> None:
            try:
                y = next(gen)
                while True:
                    if isinstance(y, tuple) and y[0] == "sync":
                        tr = self._tracers.get((s, r))
                        if tr is not None:
                            tr.phase = "sync"   # this worker's own tracer
                        t0 = time.perf_counter()
                        reduced = local_scatter_reduce(
                            self.store, r, d, agg.s_stage[s], y[1],
                            key_prefix=f"k{k}/sync{s}",
                            pipelined=pipelined_sync, barrier=barriers.get(s),
                            tracer=tr, clock=self._clock)
                        sync_secs[(s, r)] = time.perf_counter() - t0
                        y = gen.send(reduced)
                    else:
                        y = next(gen)
            except StopIteration:
                return
            except BaseException as e:  # propagate to the main thread
                with err_lock:
                    errors.append(e)
                # a died worker starves its peers' blocking gets *and* their
                # sync barrier: mark it dead, poison the store and break the
                # barriers so every peer fails over now, not at timeout
                self.store.mark_dead((s, r))
                self.store.abort(e)
                for b in barriers.values():
                    b.abort()

        threads = [
            threading.Thread(target=drive, args=(s, r, gen),
                             name=f"funcpipe-s{s}r{r}", daemon=True)
            for (s, r), gen in programs.items()
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        if errors:
            raise _primary_error(errors)

        sync = 0.0
        for s in range(S):
            stage = [sync_secs.get((s, r), 0.0) for r in range(d)]
            sync = max(sync, max(stage))
        self._steps_done += 1
        return StepTiming(end=time.perf_counter() - self._t0, sync=sync)
