"""The paper's xLSTM[7:1] model (``xlstm-7-1-125m``) through the harness on
the CPU, at the program's ``@reduced`` widths (float32, d_model 256,
vocabulary 1024) and two periods of eight blocks, against its plain
reference ``configs/xlstm_7_1.py``: a sound two-replica run is correct, one
whose scatter-reduce exchanges nothing is not, and the reference's FLOP
counter agrees with XLA's count of the program's model."""
from __future__ import annotations

import io
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import harness, spec, testkit
from chipbench.spec import BENCH_DIR, _module

XLSTM71_REDUCED = {
    "spelling": "xlstm-7-1-125m@reduced16", "reference": "xlstm_7_1",
    "embedding_dim": 256, "num_heads": 4, "num_blocks": 16,
    "slstm_at": [7, 15], "mlstm_proj_factor": 2.0, "qkv_proj_blocksize": 4,
    "conv1d_kernel_size": 4, "slstm_conv1d_kernel_size": 4, "ffn_dim": 1024,
    "initializer_range": 0.02, "norm_eps": 1e-05, "torch_dtype": "float32",
    "vocab_size": 1024, "mlstm_chunk": 256,
}
CELL = "xlstm71r.s2d2"


def _run(tmp_path, monkeypatch, seed=2**33 + 7):
    monkeypatch.setitem(testkit.CELLS, CELL, (
        "xlstm71r", XLSTM71_REDUCED, "tiny.d2", testkit.traffic(2)))
    root = testkit.make_root(tmp_path, cells=(CELL,))
    cell = spec.load_cell(root, CELL, bench_dir=root / "benchmarks" / "chip")
    # no warm-up call: the timed call's three checked steps and a one-step
    # window, as ``calibrate.py`` runs it
    return harness.run(cell, seed=seed, seconds=0.0, trace=False,
                       t_start=time.perf_counter(), require_chip=False,
                       warmup=False, log=io.StringIO())


def test_sound_two_replica_run_is_correct(tmp_path, monkeypatch):
    out = _run(tmp_path, monkeypatch)
    assert out["correct"] is True, out["compared"]
    assert out["attempted"] >= 1 and out["failed"] == 0
    for row in out["compared"].values():
        assert row["value"] <= row["limit"]


def test_no_exchange_is_not_correct(tmp_path, monkeypatch):
    """Each replica keeps its own gradient: the scatter-reduce exchanges
    nothing, and grad_norm_gap reads it."""
    from repro.serverless.backends import local

    monkeypatch.setattr(local, "local_scatter_reduce",
                        lambda store, index, n, nbytes, value, **kw:
                        np.asarray(value, np.float32))
    out = _run(tmp_path, monkeypatch)
    assert out["correct"] is False
    row = out["compared"]["grad_norm_gap"]
    assert not row["value"] <= row["limit"]


def _flops(compiled) -> float:
    ca = compiled.cost_analysis()
    return (ca[0] if isinstance(ca, list) else ca)["flops"]


@pytest.mark.parametrize("seq", [16, 128])
def test_counter_matches_xla(seq):
    """As ``test_chipbench_flops``: one period (XLA counts a loop's body
    once) and a sequence within one mLSTM chunk; 8 % for the sLSTM
    recurrence's product, which XLA sees once and not once per position,
    and for the gate, norm and convolution arithmetic the counter leaves
    out.  The model is taken without its mLSTM recompute (``remat_mlstm``),
    whose second forward pass is no model FLOP."""
    import dataclasses

    from repro.configs import resolve_arch
    from repro.models import registry

    cfg = dict(XLSTM71_REDUCED, num_blocks=8, slstm_at=[7])
    counter = _module(BENCH_DIR / "configs" / "xlstm_7_1.py", "flops_xlstm71")
    arch = resolve_arch("xlstm-7-1-125m@reduced")
    arch = dataclasses.replace(arch, xlstm=dataclasses.replace(
        arch.xlstm, remat_mlstm=False))
    B = 2
    params = jax.eval_shape(lambda k: registry.init_params(arch, k),
                            jax.random.PRNGKey(0))
    tok = jax.ShapeDtypeStruct((B, seq), jnp.int32)
    batch = {"tokens": tok, "labels": tok}
    loss = lambda p, b: registry.loss_fn(arch, p, b)[0]  # noqa: E731
    fwd = _flops(jax.jit(loss).lower(params, batch).compile())
    train = _flops(jax.jit(jax.value_and_grad(loss)).lower(
        params, batch).compile())
    want = counter.forward_flops_per_token(cfg, seq) * B * seq
    assert fwd == pytest.approx(want, rel=0.08)
    assert train == pytest.approx(3 * want, rel=0.08)
