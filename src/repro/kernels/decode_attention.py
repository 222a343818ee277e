"""Pallas-TPU flash-decode: one query token against a long KV cache.

The cache length is a runtime scalar (scalar-prefetch), the grid walks cache
blocks sequentially with the partial-softmax (m, l, acc) state in VMEM
scratch — the same combiner the data-axis-sharded 500k decode uses across
chips (models.attention sharded path), here applied within a chip.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _decode_kernel(
    length_ref,                  # scalar prefetch: [1] int32
    q_ref,                       # [1, G, hd]  (one kv-head group)
    k_ref, v_ref,                # [1, CB, hd]
    o_ref,                       # [1, G, hd]
    m_ref, l_ref, acc_ref,       # scratch [G], [G], [G, hd]
    *,
    c_block: int,
    n_c: int,
    scale: float,
):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32) * scale               # [G, hd]
    k = k_ref[0].astype(jnp.float32)                       # [CB, hd]
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # [G, CB]
    slot = ci * c_block + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    s = jnp.where(slot < length_ref[0], s, NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
    p = jnp.exp(s - m_new[:, None])
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=1)
    acc_ref[...] = acc_ref[...] * corr[:, None] + jax.lax.dot_general(
        p, v_ref[0].astype(jnp.float32), (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    m_ref[...] = m_new

    @pl.when(ci == n_c - 1)
    def _emit():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("c_block", "interpret"))
def decode_attention(
    q: jax.Array,        # [B, Hq, hd]
    k_cache: jax.Array,  # [B, Hkv, C, hd]
    v_cache: jax.Array,
    length: jax.Array,   # scalar int32: valid cache slots
    *,
    c_block: int = 512,
    interpret: bool = False,
) -> jax.Array:
    B, Hq, hd = q.shape
    Hkv, C = k_cache.shape[1], k_cache.shape[2]
    G = Hq // Hkv
    c_block = min(c_block, C)
    assert C % c_block == 0
    n_c = C // c_block

    qr = q.reshape(B * Hkv, G, hd)
    kr = k_cache.reshape(B * Hkv, C, hd)
    vr = v_cache.reshape(B * Hkv, C, hd)
    length = jnp.asarray(length, jnp.int32).reshape(1)

    kernel = functools.partial(
        _decode_kernel, c_block=c_block, n_c=n_c, scale=hd**-0.5
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B * Hkv, n_c),
        in_specs=[
            pl.BlockSpec((1, G, hd), lambda bh, ci, *_: (bh, 0, 0)),
            pl.BlockSpec((1, c_block, hd), lambda bh, ci, *_: (bh, ci, 0)),
            pl.BlockSpec((1, c_block, hd), lambda bh, ci, *_: (bh, ci, 0)),
        ],
        out_specs=pl.BlockSpec((1, G, hd), lambda bh, ci, *_: (bh, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((G,), jnp.float32),
            pltpu.VMEM((G,), jnp.float32),
            pltpu.VMEM((G, hd), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B * Hkv, G, hd), q.dtype),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
    )(length, qr, kr, vr)
    return out.reshape(B, Hq, hd)
