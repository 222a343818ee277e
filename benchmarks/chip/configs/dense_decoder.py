"""Plain reference of a dense decoder-only transformer (Phi-3-mini and its
kin): pre-norm RMSNorm, rotary position embedding on the two halves of each
head, causal multi-head attention with grouped key/value heads, SwiGLU
feed-forward, untied output head.  Float32 throughout; imports nothing of the
program under test.

Parameters are held in the layout the program's training path takes: one
leaf per weight kind, stacked over layers on axis 0, in a one-element tuple
(the model's layer pattern has period one).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from chipbench.refmath import next_token_ce, rms_norm, silu


def dims(cfg):
    d = cfg["hidden_size"]
    H = cfg["num_attention_heads"]
    return d, H, cfg["num_key_value_heads"], d // H, cfg["intermediate_size"]


def init_params(cfg, key):
    """Random weights: normal with the config's ``initializer_range``, the
    two projections that write into the residual stream divided by
    sqrt(2 x layers), norm offsets zero.  In the config's ``torch_dtype``."""
    d, H, KV, hd, f = dims(cfg)
    L, V = cfg["num_hidden_layers"], cfg["vocab_size"]
    std = cfg["initializer_range"]
    out_std = std / (2 * L) ** 0.5
    dtype = jnp.dtype(cfg["torch_dtype"])
    shapes = {
        "wq": ((L, d, H * hd), std), "wk": ((L, d, KV * hd), std),
        "wv": ((L, d, KV * hd), std), "wo": ((L, H * hd, d), out_std),
        "w_gate": ((L, d, f), std), "w_up": ((L, d, f), std),
        "w_down": ((L, f, d), out_std),
        "embed": ((V, d), std), "head": ((V, d), std),
    }
    w = {name: (s * jax.random.normal(jax.random.fold_in(key, i), shape,
                                      jnp.float32)).astype(dtype)
         for i, (name, (shape, s)) in enumerate(sorted(shapes.items()))}
    zeros = lambda *shape: jnp.zeros(shape, dtype)  # noqa: E731
    layer = {
        "norm1": zeros(L, d),
        "mixer": {k: w[k] for k in ("wq", "wk", "wv", "wo")},
        "norm2": zeros(L, d),
        "ff": {k: w[k] for k in ("w_gate", "w_up", "w_down")},
    }
    return {"embed": w["embed"], "final_norm": zeros(d), "head": w["head"],
            "layers": (layer,)}


def _rope(x, theta):
    """x [B, S, H, hd]: rotate (first half, second half) pairs by angle
    position * theta^(-2i/hd)."""
    S, hd = x.shape[1], x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv      # [S, hd/2]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _layer(cfg, nx, p, x):
    d, H, KV, hd, _ = dims(cfg)
    B, S, _ = x.shape
    eps = cfg["rms_norm_eps"]
    a = p["mixer"]
    h = rms_norm(x, p["norm1"], eps)
    q = _rope(nx.mm(h, a["wq"]).reshape(B, S, H, hd), cfg["rope_theta"])
    k = _rope(nx.mm(h, a["wk"]).reshape(B, S, KV, hd), cfg["rope_theta"])
    v = nx.mm(h, a["wv"]).reshape(B, S, KV, hd)
    k = jnp.repeat(k, H // KV, axis=2)
    v = jnp.repeat(v, H // KV, axis=2)
    s = nx.einsum("bqhd,bkhd->bhqk", q, k) / hd ** 0.5
    causal = jnp.tril(jnp.ones((S, S), bool))
    s = jnp.where(causal, s, -jnp.inf)
    pr = jax.nn.softmax(s, axis=-1)
    o = nx.einsum("bhqk,bkhd->bqhd", pr, v).reshape(B, S, H * hd)
    x = x + nx.mm(o, a["wo"])
    ff = p["ff"]
    h = rms_norm(x, p["norm2"], eps)
    return x + nx.mm(silu(nx.mm(h, ff["w_gate"])) * nx.mm(h, ff["w_up"]),
                     ff["w_down"])


def loss(cfg, params, tokens, labels, nx):
    """Mean next-token cross-entropy of ``tokens`` [B, S] (float32 params)."""
    x = nx.q(params["embed"])[tokens]
    layers = params["layers"][0]
    for i in range(cfg["num_hidden_layers"]):
        x = _layer(cfg, nx, jax.tree.map(lambda a: a[i], layers), x)
    h = rms_norm(x, params["final_norm"], cfg["rms_norm_eps"])
    return next_token_ce(nx.mm(h, params["head"].T), labels)


def forward_flops_per_token(cfg, seq):
    """Multiply-adds x 2 of one token's forward pass at sequence length
    ``seq``: the projections, the full (unmasked) score and value products
    the attention computes, and the output head.  The embedding is a gather
    and counts nothing."""
    d, H, KV, hd, f = dims(cfg)
    per_layer = (d * H * hd + 2 * d * KV * hd + H * hd * d   # q, k, v, o
                 + 3 * d * f                                 # gate, up, down
                 + 2 * seq * H * hd)                         # scores, values
    return 2.0 * (cfg["num_hidden_layers"] * per_layer
                  + d * cfg["vocab_size"])
