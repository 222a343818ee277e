"""Gated feed-forward (dense): SwiGLU, or GeGLU (``ArchConfig.ff_act``).
Column-parallel gate/up, row-parallel down."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models.common import ParallelCtx, LOCAL_CTX, dense_init


def init_mlp_params(key, cfg: ArchConfig, dtype) -> dict:
    d, f = cfg.d_model, cfg.d_ff
    ks = jax.random.split(key, 3)
    return {
        "w_gate": dense_init(ks[0], (d, f), dtype),
        "w_up": dense_init(ks[1], (d, f), dtype),
        "w_down": dense_init(ks[2], (f, d), dtype, scale=0.02 / max(1, cfg.n_layers) ** 0.5),
    }


def mlp_forward(
    p: dict,
    x: jax.Array,
    *,
    ctx: ParallelCtx = LOCAL_CTX,
    use_pallas: bool = False,
    act: str = "silu",
) -> jax.Array:
    if use_pallas:
        if act != "silu":
            raise ValueError(f"the Pallas feed-forward is SwiGLU only, not "
                             f"{act!r}")
        from repro.kernels import ops as kops

        h = kops.swiglu(x, p["w_gate"], p["w_up"])
    elif act == "gelu":   # the exact (erf) GeLU, as PyTorch's default
        h = jax.nn.gelu(x @ p["w_gate"], approximate=False) * (x @ p["w_up"])
    else:
        h = jax.nn.silu(x @ p["w_gate"]) * (x @ p["w_up"])
    return ctx.psum_tp(h @ p["w_down"])
