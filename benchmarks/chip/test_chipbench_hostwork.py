"""The readers of the program's host work at the sync boundary (``pack``
and ``update`` spans): on hand-built run records, and on a traced run of
the harness on the CPU at the program's reduced widths."""
from __future__ import annotations

import io
import time
from types import SimpleNamespace

import jax
import pytest

from chipbench import devtrace, harness, spec, testkit

READERS = ("grad_pack_s_per_step", "update_s_per_step",
           "host_grad_bytes_per_step")


def _reader(name):
    return spec._module(spec.BENCH_DIR / "metrics" / f"{name}.py",
                        f"test_metric_{name}").read


def _span(step, stage, op, nbytes=0.0, phase="sync"):
    return SimpleNamespace(step=step, stage=stage, replica=0, op=op,
                           phase=phase, nbytes=nbytes)


def _run(spans, steps=2):
    return SimpleNamespace(spans=spans, window_steps=steps)


def test_readers_on_a_hand_built_run():
    spans = [
        (0.0, 0.1, _span(3, 0, "compute", phase="bwd")),
        (0.1, 0.4, _span(3, 0, "pack", 40.0)),
        (0.1, 0.3, _span(3, 1, "pack", 60.0)),
        (0.4, 1.4, _span(3, 0, "update", 40.0)),
        (0.5, 2.5, _span(3, 1, "update", 60.0)),
        (3.0, 3.5, _span(4, 0, "pack", 40.0)),
        (3.0, 3.1, _span(4, 1, "pack", 60.0)),
        (3.5, 4.0, _span(4, 0, "update", 40.0)),
        (3.5, 4.5, _span(4, 1, "update", 60.0)),
    ]
    read = {name: _reader(name) for name in READERS}
    run = _run(spans)
    # per step the slowest worker, then the mean over the window's steps
    assert read["grad_pack_s_per_step"](run) == pytest.approx((0.3 + 0.5) / 2)
    assert read["update_s_per_step"](run) == pytest.approx((2.0 + 1.0) / 2)
    assert read["host_grad_bytes_per_step"](run) == pytest.approx(200.0)


@pytest.mark.parametrize("name", READERS)
def test_readers_read_nothing_without_host_spans(name):
    """A program that records no pack or update span (one older than
    them, or an untraced run) reads nothing."""
    read = _reader(name)
    assert read(_run(None)) is None
    assert read(_run([(0.0, 1.0, _span(3, 0, "download", 8.0, "fwd"))])) \
        is None


def test_traced_cpu_run_reports_the_host_work(tmp_path):
    name = "phi3r.s2d1"
    root = testkit.make_root(tmp_path, cells=(name,))
    cell = spec.load_cell(root, name, bench_dir=root / "benchmarks" / "chip")
    trace_dir = tmp_path / "trace"
    out = harness.run(cell, seed=2**33 + 11, seconds=0.2, trace=True,
                      t_start=time.perf_counter(), trace_dir=trace_dir,
                      require_chip=False, log=io.StringIO())
    assert out["correct"] is True
    metrics = {k: v["value"] for k, v in out["metrics"].items()}
    assert set(READERS) <= set(metrics)
    shapes = jax.eval_shape(lambda: cell.reference.init_params(
        cell.config, jax.random.PRNGKey(0)))
    n_params = sum(a.size for a in jax.tree.leaves(shapes))
    # fp32 gradients: 4 bytes down and 4 back for every parameter
    assert metrics["host_grad_bytes_per_step"] == 8 * n_params
    assert metrics["grad_pack_s_per_step"] > 0
    assert metrics["update_s_per_step"] > 0
    # the same work, as host events of the profiler's trace
    names = {e.name for e in devtrace.events_from_xplane(
        devtrace.newest_xplane(str(trace_dir)))}
    assert {"s0r0 sync.pack", "s1r0 sync.update", "s0r0 fwd.compute"} <= names
