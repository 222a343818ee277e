"""The execution-backend contract behind ``DeploymentPlan.emulate()``.

FuncPipe's deployment story is a *plan* executing on a storage+invocation
substrate: AWS Lambda + S3, Alibaba FC + OSS, or — here — substitutes that
run on one host.  An :class:`ExecutionBackend` is exactly that substrate,
split into the two interfaces the paper's workers need:

* an **object store** (``put``/``get``/``delete``/``keys``, byte accounting
  via :class:`~repro.serverless.runtime.store.StoreStats`, and a visibility
  rule — virtual ``visible_at`` timestamps or real blocking gets);
* a **worker-invocation surface**: spawn the plan's ``S x d`` stage workers
  and drive each one's per-step program (:class:`WorkerContext`), either on
  a per-worker virtual clock or on real concurrent threads.

The GPipe orchestrator (``runtime.engine``) expresses each worker's training
step as a *generator program* over its :class:`WorkerContext` — download,
compute, upload, a fwd/bwd phase fence, then a ``("sync", grad_vector)``
yield that the backend answers with the reduced gradient.  The engine never
touches a store or a clock directly; a real boto3/OSS backend slots in by
implementing this module's two classes and registering a name.

Time semantics are the one axis backends may legitimately differ on
(``wall_clock``): the emulated backend charges the paper's cost model on a
virtual clock, the local backend measures the host.  *Numerics may not
differ*: a plan replayed on any backend must train to bit-identical params.
"""
from __future__ import annotations

from abc import ABC, abstractmethod
from dataclasses import dataclass
from typing import Any, Callable, Dict, Generator, Optional, Tuple

from repro.serverless.runtime.store import StoreStats, assert_store_drained

# a worker's per-step program: yields None after each fwd/bwd micro-batch op
# group, then yields ("sync", grad_vector_or_None) and receives the reduced
# vector via .send(); see engine._worker_step_program
WorkerProgram = Generator[Optional[Tuple[str, Any]], Any, None]


@dataclass(frozen=True)
class StepTiming:
    """What one executed training step cost on the backend's clock.

    ``end`` is the step's completion time measured from the start of the run
    (virtual seconds on the emulated clock, host seconds on wall-clock
    backends) — monotone across steps, so the engine derives per-iteration
    time as ``end_of_last_step / steps``.  ``sync`` is the slowest stage's
    scatter-reduce duration within the step.
    """

    end: float
    sync: float


class WorkerContext(ABC):
    """One stage worker's handle onto the backend: its serial resources
    (CPU, uplink, downlink) and its view of the shared object store.

    ``download``/``compute`` return opaque *tokens* that express data
    dependencies to virtual-clock backends (the engine passes a download's
    token as ``compute(after=...)``); wall-clock backends return ``None``
    and rely on real blocking order.
    """

    @abstractmethod
    def download(self, key: str) -> Tuple[Any, Any]:
        """Fetch-and-consume ``key``: waits for visibility, charges the
        downlink, frees the object (every pipeline boundary object has
        exactly one consumer).  Returns ``(value, token)``."""

    @abstractmethod
    def compute(self, cost_s: float, fn: Optional[Callable[[], Any]] = None,
                after: Any = None) -> Any:
        """Charge ``cost_s`` of serial CPU (starting no earlier than the
        ``after`` token) and run the real math ``fn`` if given.  Returns
        ``fn()``'s result (or None)."""

    @abstractmethod
    def upload(self, key: str, nbytes: float, value: Any = None) -> Any:
        """Publish ``value`` under ``key``, charging ``nbytes`` on the
        uplink; the object becomes visible to downloads when the upload
        completes.  Returns a token."""

    @abstractmethod
    def phase_barrier(self) -> None:
        """Program-order fence between the forward and backward phases: the
        worker issues no backward download before its forward uploads are
        done (virtual clocks must model this; real serial workers get it
        for free)."""

    def host(self, op: str, fn: Callable[[], Any], *,
             nbytes: Optional[float] = None) -> Any:
        """Run the worker's host work ``fn`` at the sync boundary (``op``:
        ``"pack"``, the gradient to the host; ``"update"``, the reduced
        gradient applied) and return its result.  Wall-clock backends keep
        the worker's lease alive around it and, when traced, record it as one
        ``op`` span of phase ``sync`` whose ``nbytes`` is ``nbytes``, or the
        ``.nbytes`` of what ``fn`` returns.  The default runs ``fn`` and
        charges nothing: the cost model has no term for this work."""
        return fn()

    def wait(self, seconds: float, op: str = "retry") -> None:
        """Charge ``seconds`` of idle occupancy on this worker (retry
        backoff, injected straggle).  Virtual clocks stall the worker's
        resources and emit an ``op`` span; wall-clock backends sleep.  The
        default is a no-op so minimal backends stay valid."""

    def fetch(self, key: str, op: str = "download") -> Tuple[Any, Any]:
        """Non-consuming ``download``: waits for visibility and charges the
        downlink but leaves the object in the store (checkpoint restores
        read the same object once per stage worker).  Emits an ``op`` span
        (``"restart"`` for recovery reads).  Returns ``(value, token)``.

        Default raises — backends that support fault-tolerant recovery
        must implement it."""
        raise NotImplementedError(
            f"{type(self).__name__} does not implement fetch(); this "
            "backend cannot restore from store-backed checkpoints")


class ExecutionBackend(ABC):
    """One storage+invocation substrate a DeploymentPlan can execute on.

    Lifecycle: ``open(agg)`` provisions the store and the ``S x d`` worker
    slots for one run; ``context(s, r)`` hands out worker handles;
    ``run_step(k, programs, ...)`` drives one training step's programs to
    completion (answering their sync yields) and reports its timing;
    ``close()`` tears down.  ``verify_drained()`` asserts the byte-
    conservation invariant — puts == deletes, nothing residual — after the
    final step.
    """

    #: registry name (see ``repro.serverless.backends.get_backend``)
    name: str = "?"
    #: True when timings are host wall-clock (local/real platforms); False
    #: when the backend charges the paper's cost model on a virtual clock
    wall_clock: bool = False
    #: optional ``repro.obs.SpanRecorder`` installed before ``open()``;
    #: tracing-capable backends emit one Span per resource task into it
    recorder = None
    #: True when the backend *hosts* the worker programs itself (each worker
    #: runs ``engine._worker_step_program`` in its own OS process/container
    #: rather than receiving a generator from the engine).  The engine then
    #: calls ``bind_run``/``stage_step``/``worker_handles`` instead of
    #: building workers and generators in-process — generators cannot cross
    #: a process boundary.
    hosts_programs: bool = False

    def bind_run(self, **kw) -> None:
        """Program-hosting hook: receive the run's execution spec before
        ``open()`` (``execution=``, ``config=``, ``tolerance=``, ``report=``
        and, when fault injection is active, ``injector=``).  Backends with
        ``hosts_programs=False`` ignore it."""

    def stage_step(self, k: int, *, batch=None, losses=None) -> None:
        """Program-hosting hook: called right before ``run_step(k, ...)``
        with the step's evaluated batch (``Execution.batch_fn`` closures are
        not picklable, so the engine evaluates and the backend ships) and
        the mutable ``losses`` dict the hosted programs must fill.  No-op
        for backends that run engine-built generators."""

    def worker_handles(self):
        """Program-hosting hook: the ``S x d`` grid of stage-worker proxies
        (each exposing ``.params``/``.span``/``export_state``/``load_state``
        like ``runtime.worker.StageWorker``) in place of the engine's own
        ``make_workers()``.  Only meaningful when ``hosts_programs``."""
        raise NotImplementedError(
            f"{type(self).__name__} does not host worker programs")

    def attach_recorder(self, recorder) -> None:
        """Install a span recorder (``repro.obs.SpanRecorder``) for the next
        ``open()``/run: the emulated backend emits virtual-clock spans, the
        local backend wall-clock spans.  Backends that do not trace simply
        leave the recorder empty — attaching is never an error."""
        self.recorder = recorder

    @abstractmethod
    def open(self, agg) -> None:
        """Provision the store + worker slots for one run of the plan whose
        per-stage cost terms are ``agg`` (``simulator.StageAggregates``)."""

    @abstractmethod
    def context(self, s: int, r: int) -> WorkerContext:
        """The handle for stage ``s``, replica ``r`` (valid after open)."""

    @abstractmethod
    def run_step(self, k: int, programs: Dict[Tuple[int, int], WorkerProgram],
                 *, pipelined_sync: bool = True) -> StepTiming:
        """Drive every worker's step-``k`` program to completion, including
        the scatter-reduce each program requests via its ``("sync", vec)``
        yield, and return the step's timing."""

    @property
    @abstractmethod
    def store_stats(self) -> StoreStats:
        """Byte-accounting counters of the run's object store."""

    def delete(self, key: str) -> None:
        """Remove ``key`` from the run's store with counted accounting
        (engine-side cleanup of checkpoint objects before the final drain
        check).  Missing keys are ignored."""
        self._store_for_verification().delete(key)

    def recover(self) -> int:
        """Reset the substrate after a failed step so the engine can replay
        from a checkpoint: purge every residual non-checkpoint object (with
        counted deletes, preserving byte conservation) and revive any
        aborted machinery.  Returns the number of purged objects.  The
        default store-purge suffices for backends whose workers hold no
        cross-step state."""
        store = self._store_for_verification()
        purged = 0
        for key in list(store.keys()):
            if not key.startswith("ckpt/"):
                store.delete(key)
                purged += 1
        return purged

    def verify_drained(self) -> None:
        """Raise if the store holds residual objects or the put/delete byte
        accounting does not conserve (see ``store.assert_store_drained``)."""
        assert_store_drained(self._store_for_verification())

    @abstractmethod
    def _store_for_verification(self):
        """The underlying store object (must expose keys/live_bytes/stats)."""

    def close(self) -> None:
        """Release resources (thread pools, temp dirs).  Idempotent."""
