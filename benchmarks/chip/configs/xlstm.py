"""Plain reference of the xLSTM language model as this system runs it
(arXiv:2405.04517): blocks alternate mLSTM and sLSTM, each pre-norm RMSNorm
plus a residual; float32 throughout; imports nothing of the program.

mLSTM block: up-projection u = h W_up and gate z = h W_z to
``proj_factor`` x d; a causal depthwise convolution and SiLU give the query
and key input; exponential input gate and sigmoid forget gate per head; the
matrix memory is read in the paper's stabilised parallel form (every
position against every earlier one, normalised by max(|n|, exp(-m)));
RMSNorm over the heads, times SiLU(z), down-projection.  sLSTM block: the
scalar-memory recurrence with exponential gating and per-head recurrent
matrices, run step by step, then RMSNorm and a GELU feed-forward of
``ff_proj_factor`` x d.

Departures from the paper, as the program has them: RMSNorm in place of
LayerNorm and group norm; no convolution in front of the sLSTM gates; one
sLSTM feed-forward and no separate mLSTM output gate beyond SiLU(z); GELU is
the tanh approximation.  The model is the registry's ``xlstm-125m``: 12
blocks alternating 1:1, dense q/k/v projections.  The paper's 125M-class
models have 24 blocks, block-diagonal q/k/v in blocks of 4 and, in
xLSTM[7:1], one sLSTM block in eight; until the program builds that block
no cell runs this reference, and the tests run it at reduced widths as the
harness's two-replica case.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from chipbench.refmath import (
    gelu_tanh,
    log_sigmoid,
    next_token_ce,
    rms_norm,
    sigmoid,
    silu,
)


def dims(cfg):
    d = cfg["embedding_dim"]
    H = cfg["num_heads"]
    di = int(d * cfg["mlstm_proj_factor"])
    f = int(d * cfg["slstm_ff_proj_factor"])
    return d, H, di, f


def init_params(cfg, key):
    """Random weights in the config's ``torch_dtype``: normal with
    ``initializer_range``; down-projections divided by sqrt(blocks); the
    convolution at 0.1 and the sLSTM recurrent matrices at head_dim^-1/2;
    forget-gate bias 3 (remember), other biases and norm offsets zero."""
    d, H, di, f = dims(cfg)
    P = cfg["num_blocks"] // 2
    k = cfg["conv1d_kernel_size"]
    dh = d // H
    std = cfg["initializer_range"]
    down = std / cfg["num_blocks"] ** 0.5
    V = cfg["vocab_size"]
    dtype = jnp.dtype(cfg["torch_dtype"])
    normal = {
        "m.w_up": ((P, d, di), std), "m.w_z": ((P, d, di), std),
        "m.conv_w": ((P, k, di), 0.1), "m.wq": ((P, di, di), std),
        "m.wk": ((P, di, di), std), "m.wv": ((P, di, di), std),
        "m.w_if": ((P, di, 2 * H), std), "m.w_down": ((P, di, d), down),
        "s.w_gates": ((P, d, 4 * d), std),
        "s.r_gates": ((P, H, dh, 4 * dh), dh ** -0.5),
        "s.w_up_ff": ((P, d, f), std), "s.w_down_ff": ((P, f, d), down),
        "embed": ((V, d), std), "head": ((V, d), std),
    }
    w = {name: (s * jax.random.normal(jax.random.fold_in(key, i), shape,
                                      jnp.float32)).astype(dtype)
         for i, (name, (shape, s)) in enumerate(sorted(normal.items()))}
    full = lambda shape, v: jnp.full(shape, v, dtype)  # noqa: E731
    m = {n[2:]: a for n, a in w.items() if n.startswith("m.")}
    m.update(conv_b=full((P, di), 0), b_i=full((P, H), 0),
             b_f=full((P, H), 3.0), out_norm=full((P, di), 0))
    s = {n[2:]: a for n, a in w.items() if n.startswith("s.")}
    s.update(b_gates=full((P, 4 * d), 0), out_norm=full((P, d), 0))
    return {"embed": w["embed"], "final_norm": full((d,), 0),
            "head": w["head"],
            "layers": ({"norm1": full((P, d), 0), "mixer": m},
                       {"norm1": full((P, d), 0), "mixer": s})}


def _mlstm(cfg, nx, p, h):
    d, H, di, _ = dims(cfg)
    B, S, _ = h.shape
    dh = di // H
    u = nx.mm(h, p["w_up"])
    z = nx.mm(h, p["w_z"])
    k = p["conv_w"].shape[0]
    up = jnp.pad(u, ((0, 0), (k - 1, 0), (0, 0)))
    conv = sum(up[:, i:i + S] * p["conv_w"][i] for i in range(k)) + p["conv_b"]
    uc = silu(conv)
    q = nx.mm(uc, p["wq"]).reshape(B, S, H, dh)
    kk = nx.mm(uc, p["wk"]).reshape(B, S, H, dh) / dh ** 0.5
    v = nx.mm(u, p["wv"]).reshape(B, S, H, dh)
    gates = nx.mm(u, p["w_if"])
    log_i = gates[..., :H] + p["b_i"]                       # [B, S, H]
    log_f = log_sigmoid(gates[..., H:] + p["b_f"])
    F = jnp.cumsum(log_f, axis=1)
    # D[t, s] = F_t - F_s + i_s for s <= t: the log weight of key s at t
    D = F[:, :, None, :] - F[:, None, :, :] + log_i[:, None, :, :]
    D = jnp.where(jnp.tril(jnp.ones((S, S), bool))[None, :, :, None], D,
                  -jnp.inf)
    m = jnp.max(D, axis=2)                                  # [B, t, H]
    C = jnp.exp(D - m[:, :, None, :]) * nx.einsum("bthd,bshd->btsh", q, kk)
    n = jnp.maximum(jnp.abs(jnp.sum(C, axis=2)), jnp.exp(-m))
    ht = nx.einsum("btsh,bshd->bthd", C, v) / n[..., None]
    ht = rms_norm(ht.reshape(B, S, di), p["out_norm"], cfg["norm_eps"])
    return nx.mm(ht * silu(z), p["w_down"])


def _slstm(cfg, nx, p, h):
    d, H, _, _ = dims(cfg)
    B, S, _ = h.shape
    dh = d // H
    xg = (nx.mm(h, p["w_gates"]) + p["b_gates"]).reshape(B, S, H, 4 * dh)
    r = p["r_gates"]

    def step(state, xg_t):
        c, n, m, hp = state
        g = xg_t + nx.einsum("bhd,hde->bhe", hp, r)
        zt, it, ft, ot = jnp.split(g, 4, axis=-1)
        lf = log_sigmoid(ft)
        m_new = jnp.maximum(lf + m, it)
        fd = jnp.exp(lf + m - m_new)
        ii = jnp.exp(it - m_new)
        c = fd * c + ii * jnp.tanh(zt)
        n = jnp.maximum(fd * n + ii, 1.0)
        hn = sigmoid(ot) * c / n
        return (c, n, m_new, hn), hn

    zero = jnp.zeros((B, H, dh), jnp.float32)
    _, hs = jax.lax.scan(step, (zero, zero, zero - 1e30, zero),
                         jnp.swapaxes(xg, 0, 1))
    hs = rms_norm(jnp.swapaxes(hs, 0, 1).reshape(B, S, d), p["out_norm"],
                  cfg["norm_eps"])
    return nx.mm(gelu_tanh(nx.mm(hs, p["w_up_ff"])), p["w_down_ff"])


def loss(cfg, params, tokens, labels, nx):
    """Mean next-token cross-entropy of ``tokens`` [B, S] (float32 params)."""
    x = nx.q(params["embed"])[tokens]
    eps = cfg["norm_eps"]
    mpos, spos = params["layers"]
    for i in range(cfg["num_blocks"] // 2):
        pm = jax.tree.map(lambda a: a[i], mpos)
        x = x + _mlstm(cfg, nx, pm["mixer"], rms_norm(x, pm["norm1"], eps))
        ps = jax.tree.map(lambda a: a[i], spos)
        x = x + _slstm(cfg, nx, ps["mixer"], rms_norm(x, ps["norm1"], eps))
    h = rms_norm(x, params["final_norm"], eps)
    return next_token_ce(nx.mm(h, params["head"].T), labels)


def forward_flops_per_token(cfg, seq):
    """Multiply-adds x 2 of one token's forward pass as the program computes
    it: the mLSTM memory in chunks of ``mlstm_chunk`` positions (scores and
    values against the whole chunk, plus the carried memory's read and
    update), the sLSTM recurrence, the projections and the output head.
    Elementwise gate arithmetic counts nothing."""
    d, H, di, f = dims(cfg)
    dh = di // H
    Q = min(cfg["mlstm_chunk"], seq)
    k = cfg["conv1d_kernel_size"]
    mlstm = (2 * d * di + 3 * di * di + di * 2 * H + di * d + k * di
             + H * (2 * Q * dh + 2 * dh * dh + 2 * dh))
    slstm = d * 4 * d + d * 4 * (d // H) + 2 * d * f
    return 2.0 * (cfg["num_blocks"] // 2 * (mlstm + slstm)
                  + d * cfg["vocab_size"])
