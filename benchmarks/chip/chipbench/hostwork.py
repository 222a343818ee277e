"""Reductions of the program's host work at the sync boundary: the ``pack``
spans (each worker's gradient copied to the host as one flat vector) and
the ``update`` spans (the reduced vector back on the device, the optimizer
step).  A program that records no such span reads nothing; the op names
are spelled here, not imported from ``repro.obs``, so that the readers also
run on a program older than these spans."""
from __future__ import annotations

HOST_OPS = ("pack", "update")


def seconds_per_step(run, op: str):
    """Seconds in ``op`` spans: each worker's per window step, the most of
    any worker per step, the mean over the window's steps; ``None`` when
    the window holds no ``op`` span."""
    if run.spans is None:
        return None
    per_worker = {}
    for start, end, span in run.spans:
        if span.op == op:
            key = (span.step, span.stage, span.replica)
            per_worker[key] = per_worker.get(key, 0.0) + (end - start)
    if not per_worker:
        return None
    per_step = {}
    for (step, _, _), s in per_worker.items():
        per_step[step] = max(per_step.get(step, 0.0), s)
    return sum(per_step.values()) / run.window_steps


def bytes_per_step(run):
    """Bytes the ``pack`` and ``update`` spans moved, summed over workers,
    over the window's steps; ``None`` when the window holds neither."""
    if run.spans is None:
        return None
    moved = [span.nbytes for _, _, span in run.spans if span.op in HOST_OPS]
    if not moved:
        return None
    return sum(moved) / run.window_steps
