"""Seeds and training batches, made on the device from ``--seed``.

One general generator serves every traffic mix: a mix file gives its
parameters (``generator: zipf``, ``zipf_exponent``, batch and sequence
length), and :func:`batch_maker` returns the step -> batch function the
program is fed.  The same seed and step give the same
batch in the program, the reference and the control.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def root_key(seed: int):
    """A PRNG key that depends on all 64 bits of ``seed`` (a 32-bit key
    alone would drop the high half of a large seed)."""
    if not 0 <= seed < 2 ** 64:
        raise ValueError(f"seed {seed} is not in [0, 2**64)")
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, seed >> 32)


def weights_key(seed: int):
    return jax.random.fold_in(root_key(seed), 0)


def data_key(seed: int):
    return jax.random.fold_in(root_key(seed), 1)


def _zipf(key, step, *, batch, seq, vocab, exponent):
    """Token ids with P(rank r) proportional to r^-exponent, by inverse CDF;
    the labels are the tokens (next-token objective)."""
    ranks = jnp.arange(1, vocab + 1, dtype=jnp.float32)
    cdf = jnp.cumsum(ranks ** -exponent)
    u = jax.random.uniform(jax.random.fold_in(key, step), (batch, seq),
                           maxval=cdf[-1])
    tokens = jnp.minimum(jnp.searchsorted(cdf, u), vocab - 1).astype(jnp.int32)
    return {"tokens": tokens, "labels": tokens}


def batch_maker(traffic: dict, vocab: int, seed: int):
    """step -> global batch {"tokens", "labels"} [batch, seq] int32, one
    jitted call per step."""
    if traffic["generator"] != "zipf":
        raise ValueError(f"unknown generator {traffic['generator']!r}")
    fn = jax.jit(lambda key, step: _zipf(
        key, step, batch=traffic["global_batch"], seq=traffic["seq_len"],
        vocab=vocab, exponent=traffic["zipf_exponent"]))
    key = data_key(seed)
    return lambda step: fn(key, jnp.int32(step))
