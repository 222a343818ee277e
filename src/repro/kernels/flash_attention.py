"""Pallas-TPU flash attention (causal / sliding-window / GQA).

TPU adaptation notes (DESIGN.md §7): tiles are MXU-aligned (q-block x k-block
= 128-multiples), the (m, l, acc) online-softmax state lives in VMEM scratch
persisted across the sequential innermost k-block grid dimension, and the
output block is emitted on the last k iteration — the standard TPU flash
schedule (no warps/shared-memory banking to port from the CUDA version).

Validated on CPU with interpret=True against kernels.ref.flash_attention_ref.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(
    q_ref, k_ref, v_ref,          # [1, QB, hd], [1, KB, hd]
    o_ref,                        # [1, QB, hd]
    m_ref, l_ref, acc_ref,        # VMEM scratch: [QB], [QB], [QB, hd]
    *,
    q_block: int,
    k_block: int,
    n_k: int,
    scale: float,
    causal: bool,
    window: int,
):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32) * scale        # [QB, hd]
    k = k_ref[0].astype(jnp.float32)                # [KB, hd]
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )                                               # [QB, KB]

    if causal:
        q_pos = qi * q_block + jax.lax.broadcasted_iota(jnp.int32, (q_block, k_block), 0)
        k_pos = ki * k_block + jax.lax.broadcasted_iota(jnp.int32, (q_block, k_block), 1)
        allow = k_pos <= q_pos
        if window:
            allow &= k_pos > (q_pos - window)
        s = jnp.where(allow, s, NEG_INF)

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

    @pl.when(ki == n_k - 1)
    def _emit():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "q_block", "k_block", "interpret"),
)
def flash_attention(
    q: jax.Array,   # [B, S, Hq, hd]
    k: jax.Array,   # [B, S, Hkv, hd]
    v: jax.Array,
    *,
    causal: bool = True,
    window: int = 0,
    q_block: int = 128,
    k_block: int = 128,
    interpret: bool = False,
) -> jax.Array:
    B, S, Hq, hd = q.shape
    Hkv = k.shape[2]
    G = Hq // Hkv
    q_block = min(q_block, S)
    k_block = min(k_block, S)
    assert S % q_block == 0 and S % k_block == 0
    n_q = S // q_block
    n_k = S // k_block

    qr = q.transpose(0, 2, 1, 3).reshape(B * Hq, S, hd)
    kr = k.transpose(0, 2, 1, 3).reshape(B * Hkv, S, hd)
    vr = v.transpose(0, 2, 1, 3).reshape(B * Hkv, S, hd)

    def q_index(bh, qi, ki):
        return (bh, qi, 0)

    def kv_index(bh, qi, ki):
        b, h = bh // Hq, bh % Hq
        return (b * Hkv + h // G, ki, 0)

    kernel = functools.partial(
        _flash_kernel,
        q_block=q_block, k_block=k_block, n_k=n_k,
        scale=hd**-0.5, causal=causal, window=window,
    )
    out = pl.pallas_call(
        kernel,
        grid=(B * Hq, n_q, n_k),
        in_specs=[
            pl.BlockSpec((1, q_block, hd), q_index),
            pl.BlockSpec((1, k_block, hd), kv_index),
            pl.BlockSpec((1, k_block, hd), kv_index),
        ],
        out_specs=pl.BlockSpec((1, q_block, hd), q_index),
        out_shape=jax.ShapeDtypeStruct((B * Hq, S, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((q_block,), jnp.float32),
            pltpu.VMEM((q_block,), jnp.float32),
            pltpu.VMEM((q_block, hd), jnp.float32),
        ],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
    )(qr, kr, vr)
    return out.reshape(B, Hq, S, hd).transpose(0, 2, 1, 3)
