"""Reduction of a ``jax.profiler`` trace to device busy time, idle gaps and
per-program device time.

The profiler's ``.xplane.pb`` is first flattened to :class:`Event` rows
(plane, line, name, start and duration in ns, one clock for host and
device).  Everything after that works on rows, so a small recorded trace
can be kept as JSON and reduced in a test without a chip.

A device plane is one whose name starts with ``/device:`` (``/device:TPU:0``
and so on).  Its operations are the events on its ``XLA Ops`` line; the
``XLA Modules`` line holds one event per program run, named after the
program (``jit_fwd_fn(12)``).  Busy time is the union of operation
intervals inside the window; idle gaps are the window minus that union.
"""
from __future__ import annotations

import glob
import gzip
import json
import os
import re
from dataclasses import dataclass
from typing import Dict, Iterable, List, NamedTuple, Optional, Sequence, Tuple

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW_SPAN = "chipbench.window"


class Event(NamedTuple):
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float


def events_from_xplane(path: str) -> List[Event]:
    """Every event of a profiler trace file, device and host."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    out = []
    for plane in pd.planes:
        for line in plane.lines:
            for e in line.events:
                out.append(Event(plane.name, line.name, e.name,
                                 float(e.start_ns), float(e.duration_ns)))
    return out


def newest_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")),
                   key=os.path.getmtime)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def _open(path: str, mode: str):
    return (gzip.open(path, mode + "t") if path.endswith(".gz")
            else open(path, mode))


def save_events(events: Sequence[Event], path: str) -> None:
    """Events as JSON rows (gzip where ``path`` ends in ``.gz``)."""
    with _open(path, "w") as f:
        json.dump([list(e) for e in events], f)


def load_events(path: str) -> List[Event]:
    with _open(path, "r") as f:
        return [Event(*row) for row in json.load(f)]


def window_of(events: Iterable[Event]) -> Tuple[float, float]:
    """(start, end) ns of the host span the harness opens over the window."""
    spans = [e for e in events if e.name == WINDOW_SPAN]
    if len(spans) != 1:
        raise ValueError(f"expected one {WINDOW_SPAN!r} span, found "
                         f"{len(spans)}")
    return spans[0].start_ns, spans[0].start_ns + spans[0].dur_ns


def union(intervals: Iterable[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Sorted, merged intervals."""
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def clip(intervals, lo: float, hi: float):
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def gaps(busy: Sequence[Tuple[float, float]], lo: float, hi: float):
    """The parts of [lo, hi) that no busy interval covers."""
    out, t = [], lo
    for a, b in busy:
        if a > t:
            out.append((t, a))
        t = max(t, b)
    if hi > t:
        out.append((t, hi))
    return out


def _program(name: str) -> str:
    """``jit_fwd_fn(12)`` -> ``jit_fwd_fn``."""
    return re.sub(r"\(\d+\)$", "", name)


@dataclass
class DeviceTrace:
    window_s: float
    busy_s: float                      # mean over device planes
    busy: List[Tuple[float, float]]    # ns, union over device planes
    window_ns: Tuple[float, float]
    op_s: Dict[str, float]             # operation name -> device seconds
    program_s: Dict[str, float]        # program name -> device seconds

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def idle_gaps(self):
        return gaps(self.busy, *self.window_ns)


def reduce_events(events: Sequence[Event],
                  window_ns: Optional[Tuple[float, float]] = None
                  ) -> Optional[DeviceTrace]:
    """Busy time and device time per operation and program inside the
    window (by default the harness's window span).  ``None`` when the trace
    holds no device operation in the window."""
    lo, hi = window_ns if window_ns is not None else window_of(events)
    planes: Dict[str, List[Tuple[float, float]]] = {}
    op_s: Dict[str, float] = {}
    program_s: Dict[str, float] = {}
    for e in events:
        if not e.plane.startswith("/device:"):
            continue
        iv = clip([(e.start_ns, e.start_ns + e.dur_ns)], lo, hi)
        if not iv:
            continue
        sec = (iv[0][1] - iv[0][0]) / 1e9
        if e.line == OPS_LINE:
            planes.setdefault(e.plane, []).extend(iv)
            op_s[e.name] = op_s.get(e.name, 0.0) + sec
        elif e.line == MODULES_LINE:
            key = _program(e.name)
            program_s[key] = program_s.get(key, 0.0) + sec
    if not planes:
        return None
    per_plane = {p: union(iv) for p, iv in planes.items()}
    busy_s = sum(sum(b - a for a, b in u) for u in per_plane.values())
    return DeviceTrace(
        window_s=(hi - lo) / 1e9, busy_s=busy_s / 1e9 / len(per_plane),
        busy=union(iv for u in per_plane.values() for iv in u),
        window_ns=(lo, hi), op_s=op_s, program_s=program_s)


NO_SPAN = "no span open (grad_vector, apply_update, step bookkeeping)"


def label_gaps(gap_list, spans_ns: Sequence[Tuple[float, float, str]]):
    """[(label, seconds)] for each gap: the host spans open at its middle,
    as sorted ``worker phase.op`` names, or :data:`NO_SPAN`."""
    out = []
    for a, b in gap_list:
        mid = (a + b) / 2
        open_ = sorted({name for s, e, name in spans_ns if s <= mid < e})
        out.append((" | ".join(open_) if open_ else NO_SPAN, (b - a) / 1e9))
    return out


def top(items: Dict[str, float], n: int = 10):
    return [[k, v] for k, v in sorted(items.items(), key=lambda kv: -kv[1])[:n]]
