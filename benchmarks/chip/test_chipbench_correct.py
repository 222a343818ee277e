"""The check that decides ``correct``, driven through whole runs of the
harness on the CPU (the look for a chip skipped) at the program's reduced
widths: a sound run is correct; with the timed path broken underneath, in
each way a training cell can be broken, it is not; and the control (the
reference in fp8, the precision below the configurations' bfloat16) fails
the limits."""
from __future__ import annotations

import io
import math
import time

import numpy as np
import pytest

from chipbench import check, harness, reference, spec, testkit


def _run(tmp_path, name, seed=2**33 + 5):
    root = testkit.make_root(tmp_path, cells=(name,))
    cell = spec.load_cell(root, name, bench_dir=root / "benchmarks" / "chip")
    return harness.run(cell, seed=seed, seconds=0.2, trace=False,
                       t_start=time.perf_counter(), require_chip=False,
                       log=io.StringIO())


def test_sound_run_is_correct(tmp_path):
    out = _run(tmp_path, "phi3r.s2d1")
    assert out["correct"] is True
    assert out["attempted"] >= 1 and out["failed"] == 0
    assert set(out["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert list(out)[-1] == "compared"
    for row in out["compared"].values():
        assert row["value"] <= row["limit"]


def _unchanged(mp):
    from repro.serverless.runtime.worker import StageWorker

    mp.setattr(StageWorker, "apply_update", lambda self, reduced, step: None)


def _half_batch(mp):
    """Half of every batch left out: its rows replaced by the other half's,
    so the mean is taken over the rest."""
    make = harness.batch_maker

    def half(traffic, vocab, seed):
        fn = make(traffic, vocab, seed)
        n = traffic["global_batch"] // 2

        def batch(k):
            b = fn(k)
            return {key: v.at[n:].set(v[:n]) for key, v in b.items()}
        return batch

    mp.setattr(harness, "batch_maker", half)


def _answer_altered(mp):
    """The loss the last stage produces, altered for one micro-batch."""
    from repro.serverless.runtime.worker import StageWorker

    forward = StageWorker.forward

    def altered(self, m, x_in, batch_mb):
        out, aux = forward(self, m, x_in, batch_mb)
        if self.span.owns_head and m == 0:
            out = out * 1.01
        return out, aux

    mp.setattr(StageWorker, "forward", altered)


def _no_exchange(mp):
    from repro.serverless.backends import local

    mp.setattr(local, "local_scatter_reduce",
               lambda store, index, n, nbytes, value, **kw:
               np.asarray(value, np.float32))


FAULTS = [("state_unchanged", _unchanged, "phi3r.s2d1"),
          ("half_batch", _half_batch, "phi3r.s2d1"),
          ("answer_altered", _answer_altered, "phi3r.s2d1"),
          ("no_exchange", _no_exchange, "xlstmr.s2d2")]


@pytest.mark.parametrize("fault,plant,cell", FAULTS, ids=[f[0] for f in FAULTS])
def test_broken_path_is_not_correct(tmp_path, monkeypatch, fault, plant, cell):
    plant(monkeypatch)
    out = _run(tmp_path, cell)
    assert out["correct"] is False
    assert any(not (r["value"] <= r["limit"]) for r in out["compared"].values())


@pytest.mark.parametrize("reduced", ["phi3r.s2d1", "xlstmr.s2d2"])
def test_control_fails_the_limits(tmp_path, reduced):
    """The reference computed in fp8 in the program's place, against the
    float32 reference, over two seeds, fails the limits of the cell at the
    test's size (at the chip cells' sizes the control is read on the chip by
    ``calibrate.py``)."""
    root = testkit.make_root(tmp_path, cells=(reduced,))
    cell = spec.load_cell(root, reduced, bench_dir=root / "benchmarks" / "chip")
    for seed in (3, 2**40 + 1):
        ref = reference.run(cell, seed)
        ctl = reference.run(cell, seed, numerics="fp8")
        numbers = check.compare(
            {"losses": ctl["losses"], "grads": [ctl["grads"]],
             "changes": [ctl["changes"]]}, ref)
        assert all(math.isfinite(v) for v in numbers.values())
        ok, _ = check.verdict(numbers, cell.limits)
        assert not ok, numbers
