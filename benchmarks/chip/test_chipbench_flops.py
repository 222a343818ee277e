"""Each configuration's FLOP counter against XLA's own count of the
program's model at a reduced size.

XLA's ``cost_analysis`` counts the body of a loop once, whatever its trip
count, so the model is taken at one layer instance (one period) and a
sequence that fits one mLSTM chunk: then the only loop that runs more than
once is the sLSTM's step-by-step recurrence.  Tolerances:

* dense decoder, 2 %: XLA also counts the elementwise work (softmax, norms,
  rotary embedding, SiLU) that the counter leaves out, about 0.5-0.8 % here;
* xLSTM, 8 %: XLA sees the sLSTM recurrence's matrix product once instead of
  once per position (about 3 % of the counter at these widths), and counts
  the gate arithmetic the counter leaves out.  The backward pass of the
  program also recomputes the mLSTM chunks it checkpoints, which the
  counter, counting model FLOPs, leaves out.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import pytest

from chipbench import testkit
from chipbench.spec import _module, BENCH_DIR

CASES = [
    ("phi3-mini-3.8b@reduced1",
     dict(testkit.PHI3_REDUCED, num_hidden_layers=1), "dense_decoder", 0.02),
    ("xlstm-125m@reduced", dict(testkit.XLSTM_REDUCED, num_blocks=2),
     "xlstm", 0.08),
]


def _flops(compiled) -> float:
    ca = compiled.cost_analysis()
    return (ca[0] if isinstance(ca, list) else ca)["flops"]


@pytest.mark.parametrize("spelling,cfg,ref,tol", CASES,
                         ids=[c[2] for c in CASES])
@pytest.mark.parametrize("seq", [16, 128])
def test_counter_matches_xla(spelling, cfg, ref, tol, seq):
    from repro.configs import resolve_arch
    from repro.models import registry

    counter = _module(BENCH_DIR / "configs" / f"{ref}.py", f"flops_{ref}")
    arch = resolve_arch(spelling)
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
    assert fwd == pytest.approx(want, rel=tol)
    assert train == pytest.approx(3 * want, rel=tol)
