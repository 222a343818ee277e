"""Shared model building blocks (pure JAX, mesh-agnostic).

All layer functions are *shape driven*: they accept possibly tensor-parallel
sliced parameters and a ``ParallelCtx`` providing the collectives; with the
default local context they are ordinary single-device modules.  The pipeline
runtime (repro.core.pipeline) supplies real collectives inside shard_map.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np


# --------------------------------------------------------------------- context
@dataclasses.dataclass
class ParallelCtx:
    """Collective hooks.  Defaults are single-device no-ops.

    tp_size / psum_tp: tensor parallelism within a pipeline stage
                       (sub-groups of the 'model' mesh axis).
    dp_size / ep_all_to_all: expert parallelism over the 'data' axis.
    seq_shards / psum_seq: KV/sequence sharding over the 'data' axis for
                       long-context decode (partial-softmax combination).
    """

    tp_size: int = 1
    dp_size: int = 1
    seq_shards: int = 1
    psum_tp: Callable[[Any], Any] = lambda x: x
    ep_all_to_all: Optional[Callable[[Any], Any]] = None  # split/concat experts
    ep_all_to_all_back: Optional[Callable[[Any], Any]] = None
    psum_seq: Callable[[Any], Any] = lambda x: x
    pmax_seq: Optional[Callable[[Any], Any]] = None
    seq_index: Any = 0  # index of this device's sequence shard


LOCAL_CTX = ParallelCtx()


# ---------------------------------------------------------------------- layers
def rms_norm(x: jax.Array, scale: jax.Array, eps: float = 1e-5) -> jax.Array:
    dtype = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    y = x * jax.lax.rsqrt(var + eps)
    return (y * (1.0 + scale.astype(jnp.float32))).astype(dtype)


def layer_norm(x: jax.Array, scale: jax.Array, eps: float = 1e-5,
               groups: int = 1) -> jax.Array:
    """LayerNorm without a bias of each of ``groups`` equal slices of the
    last axis (``groups`` > 1: a group norm, as the xLSTM cells use per
    head), scaled by (1 + scale) like :func:`rms_norm`."""
    dtype = x.dtype
    xs = x.astype(jnp.float32).reshape(*x.shape[:-1], groups, -1)
    xs = xs - jnp.mean(xs, axis=-1, keepdims=True)
    y = xs * jax.lax.rsqrt(jnp.mean(jnp.square(xs), axis=-1, keepdims=True) + eps)
    return (y.reshape(x.shape) * (1.0 + scale.astype(jnp.float32))).astype(dtype)


def block_norm(x: jax.Array, scale: jax.Array, cfg) -> jax.Array:
    """The norm ``cfg.norm`` names, before a block's sublayers and at the
    end of the model."""
    if cfg.norm == "layer":
        return layer_norm(x, scale, cfg.norm_eps)
    return rms_norm(x, scale, cfg.norm_eps)


def init_norm(d: int, dtype) -> jax.Array:
    return jnp.zeros((d,), dtype=dtype)


def dense_init(key, shape, dtype, scale: float = 0.02) -> jax.Array:
    return (scale * jax.random.normal(key, shape, dtype=jnp.float32)).astype(dtype)


# ------------------------------------------------------------------------ rope
def rope_frequencies(hd: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (np.arange(0, hd, 2, dtype=np.float32) / hd))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: [..., seq, heads, hd]; positions: broadcastable to [..., seq]."""
    hd = x.shape[-1]
    freqs = jnp.asarray(rope_frequencies(hd, theta))  # [hd/2]
    angles = positions[..., :, None].astype(jnp.float32) * freqs  # [..., seq, hd/2]
    cos = jnp.cos(angles)[..., :, None, :]  # [..., seq, 1, hd/2]
    sin = jnp.sin(angles)[..., :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# --------------------------------------------------------------- cross entropy
def softmax_cross_entropy(logits: jax.Array, labels: jax.Array) -> jax.Array:
    """logits [..., V] fp32 upcast; labels int [...] -> per-token loss [...]."""
    logits = logits.astype(jnp.float32)
    logz = jax.scipy.special.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return logz - gold


def vocab_parallel_cross_entropy(
    local_logits: jax.Array,
    labels: jax.Array,
    vocab_start: jax.Array,
    vocab_shard: int,
    psum: Callable[[Any], Any],
    pmax: Optional[Callable[[Any], Any]] = None,
) -> jax.Array:
    """Megatron-style CE with the vocabulary sharded across an axis.

    local_logits [..., V/s] — this device's vocab slice; combination via psum
    of (max, sumexp, gold-hit).  Matches softmax_cross_entropy on gathered
    logits.
    """
    local_logits = local_logits.astype(jnp.float32)
    local_max = jnp.max(local_logits, axis=-1)
    gmax = pmax(local_max) if pmax is not None else local_max
    sumexp = jnp.sum(jnp.exp(local_logits - gmax[..., None]), axis=-1)
    sumexp = psum(sumexp)
    logz = gmax + jnp.log(sumexp)
    local_label = labels - vocab_start
    in_shard = (local_label >= 0) & (local_label < vocab_shard)
    safe = jnp.clip(local_label, 0, vocab_shard - 1)
    gold_local = jnp.take_along_axis(local_logits, safe[..., None], axis=-1)[..., 0]
    gold = psum(jnp.where(in_shard, gold_local, 0.0))
    return logz - gold


def make_causal_mask(q_pos: jax.Array, k_pos: jax.Array, window: int) -> jax.Array:
    """[q, k] boolean mask.  window==0 -> plain causal; else sliding window."""
    causal = k_pos[None, :] <= q_pos[:, None]
    if window:
        causal &= k_pos[None, :] > (q_pos[:, None] - window)
    return causal
