"""Pallas-TPU fused SwiGLU: silu(x @ w_gate) * (x @ w_up) in one pass.

Both matmuls share the streamed x tile, the d (contraction) dimension is the
sequential innermost grid axis with two fp32 VMEM accumulators, and the
silu*mul epilogue runs on the last d block — saving one full [T, f] round
trip to HBM versus two separate matmuls + elementwise (the dense/expert FFN
hot loop).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _swiglu_kernel(
    x_ref,                   # [TB, DB]
    wg_ref, wu_ref,          # [DB, FB]
    o_ref,                   # [TB, FB]
    accg_ref, accu_ref,      # scratch [TB, FB] fp32
    *,
    n_d: int,
):
    di = pl.program_id(2)

    @pl.when(di == 0)
    def _init():
        accg_ref[...] = jnp.zeros_like(accg_ref)
        accu_ref[...] = jnp.zeros_like(accu_ref)

    x = x_ref[...].astype(jnp.float32)
    accg_ref[...] += jax.lax.dot_general(
        x, wg_ref[...].astype(jnp.float32), (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    accu_ref[...] += jax.lax.dot_general(
        x, wu_ref[...].astype(jnp.float32), (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    @pl.when(di == n_d - 1)
    def _emit():
        g = accg_ref[...]
        o_ref[...] = (g * jax.nn.sigmoid(g) * accu_ref[...]).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("t_block", "f_block", "d_block", "interpret")
)
def swiglu(
    x: jax.Array,        # [T, d]
    w_gate: jax.Array,   # [d, f]
    w_up: jax.Array,
    *,
    t_block: int = 256,
    f_block: int = 512,
    d_block: int = 512,
    interpret: bool = False,
) -> jax.Array:
    T, d = x.shape
    f = w_gate.shape[1]
    t_block = min(t_block, T)
    f_block = min(f_block, f)
    d_block = min(d_block, d)
    assert T % t_block == 0 and f % f_block == 0 and d % d_block == 0
    n_t, n_f, n_d = T // t_block, f // f_block, d // d_block

    kernel = functools.partial(_swiglu_kernel, n_d=n_d)
    return pl.pallas_call(
        kernel,
        grid=(n_t, n_f, n_d),
        in_specs=[
            pl.BlockSpec((t_block, d_block), lambda ti, fi, di: (ti, di)),
            pl.BlockSpec((d_block, f_block), lambda ti, fi, di: (di, fi)),
            pl.BlockSpec((d_block, f_block), lambda ti, fi, di: (di, fi)),
        ],
        out_specs=pl.BlockSpec((t_block, f_block), lambda ti, fi, di: (ti, fi)),
        out_shape=jax.ShapeDtypeStruct((T, f), x.dtype),
        scratch_shapes=[
            pltpu.VMEM((t_block, f_block), jnp.float32),
            pltpu.VMEM((t_block, f_block), jnp.float32),
        ],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
    )(x, w_gate, w_up)
