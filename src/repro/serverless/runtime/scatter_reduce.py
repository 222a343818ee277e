"""Storage-based gradient scatter-reduce, executed over store keys (§3.3).

Two algorithms, both operating on the emulated :class:`ObjectStore`:

``three_phase_scatter_reduce``
    LambdaML's barriered collective (paper eq (1)).  Phase 1: every worker
    uploads the n-1 gradient chunks owned by the others; phase 2 (after a
    barrier): each worker downloads the n-1 partials of its own chunk,
    reduces, re-uploads the result; phase 3 (after a barrier): everyone
    downloads the n-1 reduced chunks.  Within a phase the chunk puts/gets
    pipeline on one request stream, so the emulated completion time equals
    eq (1) exactly: ``3 s/w - 2 s/(n w) + 4 t_lat``.

``pipelined_scatter_reduce``
    FuncPipe's barrier-free full-duplex schedule (paper eq (2)).  Worker i
    uploads its partial chunks in staggered round order (chunk for worker
    (i+r) mod n in round r) so that each destination can start pulling
    immediately; the downlink pulls each partial as soon as it becomes
    visible (a fresh GET round-trip each, since availability events are
    distinct), reduces incrementally, re-uploads its reduced chunk and pulls
    the other reduced chunks.  Uplink and downlink overlap, giving
    ``~2 s/w + O(n) t_lat`` — the eq (2) schedule.

Numerics: when per-worker gradient vectors are supplied they are moved
through the same keys and the returned reduction is the exact chunk-wise sum
(identical, bit for bit, on every worker — all workers download the same
reduced chunk objects).
"""
from __future__ import annotations

from typing import List, Optional, Sequence, Tuple

import numpy as np

from repro.obs.schema import open_span
from repro.serverless.runtime.store import ObjectStore, StageChannel


def _chunk_values(values, n: int):
    if values is None:
        return None
    return [np.array_split(np.asarray(v), n) for v in values]


def _cleanup(store: ObjectStore, key_prefix: str, n: int) -> None:
    """Every consumer has pulled its chunks by return time; free the keys so
    live storage stays bounded across training steps."""
    for j in range(n):
        for i in range(n):
            if i != j:
                store.delete(f"{key_prefix}/part/{j}/{i}")
        store.delete(f"{key_prefix}/red/{j}")


def ring_reduce(own, parts):
    """The collective's deterministic fp32 reduction: start from the owned
    chunk, add partials in the order given.  Both the emulated collectives
    and the wall-clock :func:`local_scatter_reduce` reduce through this one
    function (with partials in the same ring order), so trained params are
    bit-identical across backends."""
    acc = np.asarray(own, dtype=np.float32).copy()
    for p in parts:
        acc += np.asarray(p, dtype=np.float32)
    return acc


def _reduce_chunks(chunks, owner: int, n: int):
    """Owner's deterministic reduction order: own chunk, then ring order."""
    return ring_reduce(chunks[owner][owner],
                       [chunks[(owner - r) % n][owner] for r in range(1, n)])


def three_phase_scatter_reduce(
    store: ObjectStore,
    channels: Sequence[StageChannel],
    nbytes: float,
    ready: Sequence[float],
    *,
    values: Optional[Sequence[np.ndarray]] = None,
    key_prefix: str = "sr3",
) -> Tuple[Optional[np.ndarray], List[float]]:
    """LambdaML 3-phase collective.  Returns (reduced vector | None, end times)."""
    n = len(channels)
    assert len(ready) == n
    assert all(ch.store is store for ch in channels)
    if n == 1:
        v = None if values is None else np.asarray(values[0], dtype=np.float32)
        return v, [ready[0]]
    chunk_b = nbytes / n
    chunks = _chunk_values(values, n)

    # phase 1: worker i uploads its partials of everyone else's chunk
    for i, ch in enumerate(channels):
        first = True
        for r in range(1, n):
            j = (i + r) % n
            val = None if chunks is None else chunks[i][j]
            ch.upload(f"{key_prefix}/part/{j}/{i}", chunk_b, ready=ready[i],
                      value=val, new_request=first)
            first = False
    barrier1 = max(ch.up_free for ch in channels)

    # phase 2: download the n-1 partials of the owned chunk, reduce, re-upload
    reduced_chunks: List[Optional[np.ndarray]] = [None] * n
    for i, ch in enumerate(channels):
        first = True
        for r in range(1, n):
            src = (i - r) % n
            _, t = ch.download(f"{key_prefix}/part/{i}/{src}", ready=barrier1,
                               new_request=first)
            first = False
        if chunks is not None:
            reduced_chunks[i] = _reduce_chunks(chunks, i, n)
        ch.upload(f"{key_prefix}/red/{i}", chunk_b, ready=t,
                  value=reduced_chunks[i], new_request=True)
    barrier2 = max(ch.up_free for ch in channels)

    # phase 3: everyone downloads the other n-1 reduced chunks
    ends = []
    for i, ch in enumerate(channels):
        t = barrier2
        first = True
        for r in range(1, n):
            src = (i + r) % n
            _, t = ch.download(f"{key_prefix}/red/{src}", ready=barrier2,
                               new_request=first)
            first = False
        ends.append(t)

    _cleanup(store, key_prefix, n)
    reduced = None if chunks is None else np.concatenate(reduced_chunks)
    return reduced, ends


def local_scatter_reduce(
    store,
    index: int,
    n: int,
    nbytes: float,
    value: Optional[np.ndarray],
    *,
    key_prefix: str,
    pipelined: bool = True,
    barrier=None,
    tracer=None,
    clock=None,
) -> Optional[np.ndarray]:
    """One worker's share of the storage scatter-reduce on a *wall-clock*
    store (``backends.local.LocalStore``): call from ``n`` concurrent worker
    threads, each with its own ``index``.

    Moves the same objects under the same keys as the emulated collectives
    and reduces through :func:`ring_reduce` in the identical ring order, so
    the returned vector is bit-identical to the virtual-clock backends' —
    but here ``store.get`` genuinely *blocks* until the producer's put lands,
    exercising the visibility/ordering races the virtual clock never hits.

    ``pipelined=False`` inserts the two phase barriers of the LambdaML eq (1)
    collective (``barrier`` must then be a ``threading.Barrier(n)``); the
    pipelined eq (2) schedule needs no phase barriers — downlinks ride on
    blocking visibility alone.  Either way one final barrier fences the
    cleanup: a worker frees its reduced chunk only after every peer has
    pulled it, which is what keeps the store drained across steps.

    With ``tracer``/``clock`` set (``repro.obs.WorkerTracer`` + a seconds
    clock), every per-chunk put/take/get and barrier wait emits one
    wall-clock span through ``repro.obs.open_span`` — the local mirror of
    the emulated collectives' per-chunk channel spans.
    """
    i = index
    if n == 1:
        return None if value is None else np.asarray(value, dtype=np.float32)

    def put(key, val):
        with open_span(tracer, clock, "upload", nbytes=chunk_b, key=key):
            store.put(key, chunk_b, value=val)

    def fetch(fetch_fn, key):
        # the blocking visibility wait is inside fetch_fn(); the span covers
        # it, matching the emulated download span which starts at data-ready
        with open_span(tracer, clock, "download", key=key) as sp:
            val, sp.nbytes = fetch_fn(key, True)
        return val

    def wait(b):
        with open_span(tracer, clock, "barrier"):
            b.wait()

    chunk_b = nbytes / n
    chunks = None if value is None else np.array_split(np.asarray(value), n)

    # scatter: upload my partials of everyone else's chunk, staggered order
    for r in range(1, n):
        j = (i + r) % n
        put(f"{key_prefix}/part/{j}/{i}", None if chunks is None else chunks[j])
    if not pipelined and barrier is not None:
        wait(barrier)                     # eq (1) phase-1 barrier

    # reduce: pull the n-1 partials of the owned chunk (blocking as they
    # surface), reduce in ring order, publish the reduced chunk
    parts = [fetch(store.take, f"{key_prefix}/part/{i}/{(i - r) % n}")
             for r in range(1, n)]
    reduced_i = None if chunks is None else ring_reduce(chunks[i], parts)
    put(f"{key_prefix}/red/{i}", reduced_i)
    if not pipelined and barrier is not None:
        wait(barrier)                     # eq (1) phase-2 barrier

    # all-gather: pull the other reduced chunks
    out: List[Optional[np.ndarray]] = [None] * n
    out[i] = reduced_i
    for r in range(1, n):
        src = (i + r) % n
        out[src] = fetch(store.get, f"{key_prefix}/red/{src}")
    if barrier is not None:
        wait(barrier)                     # cleanup fence: all peers have read
    store.delete(f"{key_prefix}/red/{i}")
    return None if chunks is None else np.concatenate(out)


def pipelined_scatter_reduce(
    store: ObjectStore,
    channels: Sequence[StageChannel],
    nbytes: float,
    ready: Sequence[float],
    *,
    values: Optional[Sequence[np.ndarray]] = None,
    key_prefix: str = "srp",
) -> Tuple[Optional[np.ndarray], List[float]]:
    """FuncPipe pipelined collective.  Returns (reduced vector | None, end times)."""
    n = len(channels)
    assert len(ready) == n
    assert all(ch.store is store for ch in channels)
    if n == 1:
        v = None if values is None else np.asarray(values[0], dtype=np.float32)
        return v, [ready[0]]
    chunk_b = nbytes / n
    chunks = _chunk_values(values, n)

    # scatter: staggered partial-chunk uploads, one pipelined stream each
    for i, ch in enumerate(channels):
        first = True
        for r in range(1, n):
            j = (i + r) % n
            val = None if chunks is None else chunks[i][j]
            ch.upload(f"{key_prefix}/part/{j}/{i}", chunk_b, ready=ready[i],
                      value=val, new_request=first)
            first = False

    # reduce: each worker pulls its partials as they surface (overlapping its
    # own uplink), reduces, and re-uploads the reduced chunk — no barrier
    reduced_chunks: List[Optional[np.ndarray]] = [None] * n
    red_up_end = [0.0] * n
    for i, ch in enumerate(channels):
        # downloads need no explicit ready[i] gate: the reduced-chunk upload
        # below serializes behind the scatter uploads via up_free, which
        # already start at ready[i]
        for r in range(1, n):
            src = (i - r) % n
            _, t = ch.download(f"{key_prefix}/part/{i}/{src}", new_request=True)
        if chunks is not None:
            reduced_chunks[i] = _reduce_chunks(chunks, i, n)
        red_up_end[i] = ch.upload(f"{key_prefix}/red/{i}", chunk_b, ready=t,
                                  value=reduced_chunks[i], new_request=True)

    # all-gather: pull the other reduced chunks as they surface
    ends = []
    for i, ch in enumerate(channels):
        t = red_up_end[i]
        for r in range(1, n):
            src = (i + r) % n
            _, t = ch.download(f"{key_prefix}/red/{src}", new_request=True)
        ends.append(max(t, red_up_end[i]))

    _cleanup(store, key_prefix, n)
    reduced = None if chunks is None else np.concatenate(reduced_chunks)
    return reduced, ends
