"""Plain float32 building blocks of the references, and the lower-precision
control.  Nothing here imports the program under test.

A reference model is a module beside its configuration file (see
``configs/*.py``) written with these helpers.  Every matrix product of a
reference goes through a :class:`Numerics`, so the same code computes the
float32 reference (``F32``) and the control: the same model with every
product's operands rounded to fp8 (e4m3, per-tensor scaled) on the way in and
every cotangent rounded to fp8 (e5m2, per-tensor scaled) on the way back,
the usual recipe of fp8 training.  The configurations state bfloat16, and fp8
is the next precision below it.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp


def round_to(a, dtype):
    """``a`` rounded to the nearest value of the float type ``dtype``, kept
    in ``a``'s type.  ``reduce_precision`` and not a round trip through
    ``astype``: XLA may drop a float32 -> narrower -> float32 pair of
    conversions as excess precision, on a TPU among others."""
    fi = jnp.finfo(dtype)
    return jax.lax.reduce_precision(a, exponent_bits=fi.nexp,
                                    mantissa_bits=fi.nmant)


def _scaled_round(a, dtype, fmax):
    """Round ``a`` to ``dtype``'s exponent and mantissa after scaling its
    largest magnitude to ``fmax``; returned in float32."""
    a = a.astype(jnp.float32)
    amax = jnp.max(jnp.abs(a))
    scale = jnp.where(amax > 0, amax / fmax, 1.0)
    return round_to(a / scale, dtype) * scale


# 240 and not e4m3fn's 448: reduce_precision keeps IEEE rules, under which
# 4 exponent and 3 mantissa bits reach 240
@jax.custom_vjp
def fp8_round(a):
    return _scaled_round(a, jnp.float8_e4m3fn, 240.0)


def _fp8_fwd(a):
    return fp8_round(a), None


def _fp8_bwd(_, g):
    return (_scaled_round(g, jnp.float8_e5m2, 57344.0),)


fp8_round.defvjp(_fp8_fwd, _fp8_bwd)


@dataclass(frozen=True)
class Numerics:
    """How a reference multiplies: ``round`` (or None) applied to both
    operands of every product and to the embedding rows it gathers."""

    name: str
    round: Optional[object] = None

    def q(self, a):
        a = a.astype(jnp.float32)
        return a if self.round is None else self.round(a)

    def mm(self, a, b):
        return jnp.matmul(self.q(a), self.q(b))

    def einsum(self, spec, a, b):
        return jnp.einsum(spec, self.q(a), self.q(b))


F32 = Numerics("float32")
FP8 = Numerics("fp8", fp8_round)
NUMERICS = {"float32": F32, "fp8": FP8}


def rms_norm(x, w, eps):
    """x * rsqrt(mean(x^2) + eps) * (1 + w): the scale is stored as an
    offset from one, so a zero vector is the identity."""
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x / jnp.sqrt(var + eps) * (1.0 + w)


def silu(x):
    return x / (1.0 + jnp.exp(-x))


def sigmoid(x):
    return 1.0 / (1.0 + jnp.exp(-x))


def log_sigmoid(x):
    return -jnp.logaddexp(0.0, -x)


def gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(0.7978845608028654 * (x + 0.044715 * x ** 3)))


def next_token_ce(logits, labels):
    """Mean cross-entropy of position t predicting label t+1."""
    logits = logits[:, :-1]
    labels = labels[:, 1:]
    logz = jax.scipy.special.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(logz - gold)


def adamw_step(params, grads, m, v, step, opt):
    """One AdamW update (decoupled weight decay, bias-corrected moments) at
    0-based ``step``; ``opt`` holds lr, b1, b2, eps, weight_decay."""
    t = step + 1.0
    b1, b2 = opt["b1"], opt["b2"]
    m = jax.tree.map(lambda mi, g: b1 * mi + (1 - b1) * g, m, grads)
    v = jax.tree.map(lambda vi, g: b2 * vi + (1 - b2) * g * g, v, grads)

    def upd(p, mi, vi):
        mhat = mi / (1 - b1 ** t)
        vhat = vi / (1 - b2 ** t)
        return p - opt["lr"] * (mhat / (jnp.sqrt(vhat) + opt["eps"])
                                + opt["weight_decay"] * p)

    return jax.tree.map(upd, params, m, v), m, v
