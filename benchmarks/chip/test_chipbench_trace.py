"""The reduction from a profiler trace to busy time, idle gaps and their
labels, on hand-made rows and on a trace recorded here."""
from __future__ import annotations

import pytest

from chipbench import devtrace
from chipbench.devtrace import Event


def _rows():
    dev = "/device:TPU:0"
    return [
        Event("/host:CPU", "python", devtrace.WINDOW_SPAN, 1000, 9000),
        Event(dev, devtrace.MODULES_LINE, "jit_fwd_fn(3)", 900, 2100),
        Event(dev, devtrace.OPS_LINE, "fusion.1", 900, 600),    # half inside
        Event(dev, devtrace.OPS_LINE, "fusion.2", 1400, 1000),  # overlaps
        Event(dev, devtrace.OPS_LINE, "dot.3", 5000, 1000),
        Event(dev, devtrace.MODULES_LINE, "jit_bwd_fn(4)", 5000, 1000),
        Event(dev, devtrace.OPS_LINE, "dot.3", 9500, 1000),     # past the end
        Event("/host:CPU", "python", "other", 2000, 100),
    ]


def test_busy_is_the_union_of_operations_inside_the_window():
    dev = devtrace.reduce_events(_rows())
    assert dev.window_s == pytest.approx(9e-6)
    # [1000, 2400) + [5000, 6000) + [9500, 10000)
    assert dev.busy_s == pytest.approx((1400 + 1000 + 500) * 1e-9)
    assert dev.idle_share == pytest.approx(1 - 2900 / 9000)
    assert dev.op_s["dot.3"] == pytest.approx(1500e-9)
    assert dev.program_s == pytest.approx({"jit_fwd_fn": 2000e-9,
                                           "jit_bwd_fn": 1000e-9})
    assert dev.idle_gaps() == [(2400, 5000), (6000, 9500)]


def test_gaps_are_labelled_by_the_spans_open_at_their_middle():
    spans = [(2000, 4000, "s0r0 fwd.compute"), (3000, 3800, "s1r0 fwd.download"),
             (6000, 7000, "s1r0 bwd.compute")]
    labels = devtrace.label_gaps([(2400, 5000), (6000, 9500)], spans)
    assert labels[0] == ("s0r0 fwd.compute | s1r0 fwd.download", 2600e-9)
    assert labels[1] == (devtrace.NO_SPAN, 3500e-9)


def test_no_device_operation_reads_nothing():
    rows = [e for e in _rows() if not e.plane.startswith("/device:")]
    assert devtrace.reduce_events(rows) is None


def test_events_round_trip_through_json(tmp_path):
    path = str(tmp_path / "ev.json.gz")
    devtrace.save_events(_rows(), path)
    assert devtrace.load_events(path) == _rows()


def test_cpu_profile_reads_back(tmp_path):
    """A trace recorded here, through the profiler's own file: the window
    span is found, and with no device plane nothing is read."""
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation(devtrace.WINDOW_SPAN):
        f(x).block_until_ready()
    jax.profiler.stop_trace()
    events = devtrace.events_from_xplane(devtrace.newest_xplane(str(tmp_path)))
    lo, hi = devtrace.window_of(events)
    assert hi > lo
    assert devtrace.reduce_events(events) is None


def _covered_ns(intervals, lo, hi):
    """Length of [lo, hi) covered by any interval, by a sweep over the
    sorted endpoints with a count of the intervals open."""
    points = sorted([(max(a, lo), 1) for a, b in intervals if b > lo and a < hi]
                    + [(min(b, hi), -1) for a, b in intervals
                       if b > lo and a < hi])
    total, open_, last = 0.0, 0, lo
    for t, step in points:
        if open_ > 0:
            total += t - last
        open_ += step
        last = t
    return total


def test_recorded_tpu_window():
    """11 ms of a window recorded on a TPU v5e (the phi3 cell): the stage
    programs are found on the modules line, busy time is the covered part of
    the operations line, and busy time and idle gaps fill the window."""
    from pathlib import Path

    events = devtrace.load_events(str(
        Path(__file__).parent / "testdata" / "tpu_v5e_phi3_11ms.json.gz"))
    lo, hi = devtrace.window_of(events)
    dev = devtrace.reduce_events(events)
    ops = [(e.start_ns, e.start_ns + e.dur_ns) for e in events
           if e.line == devtrace.OPS_LINE]
    assert dev.busy_s == pytest.approx(_covered_ns(ops, lo, hi) / 1e9)
    assert 0.0 < dev.idle_share < 1.0
    idle = sum(b - a for a, b in dev.idle_gaps()) / 1e9
    assert idle + dev.busy_s == pytest.approx(dev.window_s)
    assert dev.program_s["jit_fwd_fn"] > 0.0
    gap = max(dev.idle_gaps(), key=lambda g: g[1] - g[0])
    label, seconds = devtrace.label_gaps(
        [gap], [(gap[0] - 1, gap[1] + 1, "s0r0 fwd.compute")])[0]
    assert label == "s0r0 fwd.compute"
    assert seconds == pytest.approx((gap[1] - gap[0]) / 1e9)
