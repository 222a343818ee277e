"""xLSTM blocks: mLSTM (matrix memory, parallelizable) and sLSTM (scalar
memory, sequential recurrence) following arXiv:2405.04517.

mLSTM train/prefill uses the stabilized quadratic parallel form (attention-like
[S,S] weights built from cumulative log-forget-gates); decode is the O(1)
recurrence over the (C, n, m) state.  sLSTM is inherently sequential (its
gates see h_{t-1}) and runs as a lax.scan over time.

Two blocks share this code (``configs.base.XLSTMCfg``).  The registry's
``xlstm-125m`` block: dense q/k/v, gates read from the up-projection, RMSNorm
of the cell outputs, and an sLSTM that carries its own post-up-projection
FFN (ff=NO_FF in the arch config).  The paper's block (``xlstm-7-1-125m``):
block-diagonal ("headwise") q/k/v with the gates read from (q, k, v), a
learnable skip, per-head group norms, a convolution in front of the sLSTM's
input and forget gates with block-diagonal gate inputs and no input-gate
bias, and the sLSTM's gated feed-forward as the layer's own ``ff``
sublayer; it trains only.

The stage programs carry the named scopes ``mlstm``, ``mlstm.memory``,
``slstm`` and ``slstm.recurrence`` in their op metadata.

TP note: mLSTM tensors are sliced on d_inner/heads; the sLSTM recurrent matrix
R couples all of h, so sLSTM runs TP-replicated (documented in DESIGN.md).
"""
from __future__ import annotations

from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models.common import (ParallelCtx, LOCAL_CTX, dense_init,
                                 layer_norm, rms_norm)


def headwise(x: jax.Array, w: jax.Array) -> jax.Array:
    """Block-diagonal projection: x [..., n*b] through the n blocks
    w [n, b, b'] -> [..., n*b']."""
    n, b, _ = w.shape
    y = jnp.einsum("...ni,nio->...no", x.reshape(*x.shape[:-1], n, b), w)
    return y.reshape(*x.shape[:-1], -1)


def _paper(cfg: ArchConfig) -> bool:
    return cfg.xlstm.block == "paper"


def _out_norm(h, w, heads: int, cfg: ArchConfig, dtype):
    """Norm of a cell's output h [..., heads*dh] (float32), cast to
    ``dtype``: the paper's per-head group norm, or the registry block's
    RMSNorm over the whole width."""
    if _paper(cfg):
        return layer_norm(h, w, cfg.norm_eps, groups=heads).astype(dtype)
    return rms_norm(h.astype(dtype), w, cfg.norm_eps)


def _m_dims(cfg: ArchConfig, local_heads: int | None = None):
    xc = cfg.xlstm
    di = int(cfg.d_model * xc.m_proj_factor)
    H = local_heads if local_heads is not None else cfg.n_heads
    return di, H


# ======================================================================= mLSTM
def init_mlstm_params(key, cfg: ArchConfig, dtype) -> dict:
    d = cfg.d_model
    di, H = _m_dims(cfg)
    xc = cfg.xlstm
    ks = jax.random.split(key, 7)
    b = xc.qkv_block
    qkv, gate_in = ((di // b, b, b), 3 * di) if _paper(cfg) else ((di, di), di)
    p = {
        "w_up": dense_init(ks[0], (d, di), dtype),
        "w_z": dense_init(ks[1], (d, di), dtype),
        "conv_w": dense_init(ks[2], (xc.conv_kernel, di), dtype, scale=0.1),
        "conv_b": jnp.zeros((di,), dtype),
        "wq": dense_init(ks[3], qkv, dtype),
        "wk": dense_init(ks[4], qkv, dtype),
        "wv": dense_init(ks[5], qkv, dtype),
        "w_if": dense_init(ks[6], (gate_in, 2 * H), dtype),
        "b_i": jnp.zeros((H,), dtype),
        "b_f": jnp.full((H,), 3.0, dtype),  # forget-gate bias toward remembering
        "out_norm": jnp.zeros((di,), dtype),
        "w_down": dense_init(ks[0], (di, d), dtype, scale=0.02 / max(1, cfg.n_layers) ** 0.5),
    }
    if _paper(cfg):
        p["skip"] = jnp.ones((di,), dtype)
    return p


def _conv1d(xc: jax.Array, w: jax.Array, b: jax.Array) -> jax.Array:
    k = w.shape[0]
    pad = jnp.pad(xc, ((0, 0), (k - 1, 0), (0, 0)))
    return sum(pad[:, i : i + xc.shape[1], :] * w[i] for i in range(k)) + b


def _mlstm_qkvgates(p, x, cfg):
    u = x @ p["w_up"]
    z = x @ p["w_z"]
    uc = jax.nn.silu(_conv1d(u, p["conv_w"], p["conv_b"]))
    di = u.shape[-1]
    H = p["b_i"].shape[0]
    dh = di // H
    B, S = x.shape[0], x.shape[1]

    def heads(t):
        return t.reshape(B, S, H, dh)

    if _paper(cfg):   # the paper's cell: its gates read (q, k, v)
        q, k, v = (headwise(uc, p["wq"]), headwise(uc, p["wk"]),
                   headwise(u, p["wv"]))
        gates = jnp.concatenate([q, k, v], axis=-1) @ p["w_if"]
    else:
        q, k, v = uc @ p["wq"], uc @ p["wk"], u @ p["wv"]
        gates = u @ p["w_if"]
    q = heads(q)
    k = heads(k) / dh**0.5
    v = heads(v)
    gates = gates.astype(jnp.float32)  # [B,S,2H]
    log_i = gates[..., :H] + p["b_i"].astype(jnp.float32)
    log_f = jax.nn.log_sigmoid(gates[..., H:] + p["b_f"].astype(jnp.float32))
    return q, k, v, z, log_i, log_f, H, dh, uc


MLSTM_CHUNK = 256


def mlstm_forward(
    p: dict, x: jax.Array, *, cfg: ArchConfig, ctx: ParallelCtx = LOCAL_CTX,
    return_state: bool = False,
):
    """Chunkwise-parallel stabilized mLSTM (TFLA-style): quadratic form inside
    fixed-size chunks + a sequential (C, n, m) state across chunks, so memory
    is O(S * chunk) instead of O(S^2).  x [B,S,d] -> [B,S,d]."""
    def fwd(p, x):
        return _mlstm_forward(p, x, cfg, ctx, return_state)

    with jax.named_scope("mlstm"):
        return (jax.checkpoint(fwd) if cfg.xlstm.remat_mlstm else fwd)(p, x)


def _mlstm_forward(p, x, cfg, ctx, return_state):
    B, S, _ = x.shape
    q, k, v, z, log_i, log_f, H, dh, uc = _mlstm_qkvgates(p, x, cfg)
    Q = min(MLSTM_CHUNK, S)
    assert S % Q == 0, f"seq {S} not a multiple of mLSTM chunk {Q}"
    nchunks = S // Q

    def to_chunks(t):  # [B,S,...] -> [nchunks,B,Q,...]
        return t.reshape(B, nchunks, Q, *t.shape[2:]).swapaxes(0, 1)

    qf = to_chunks(q.astype(jnp.float32))
    kf = to_chunks(k.astype(jnp.float32))
    vf = to_chunks(v.astype(jnp.float32))
    lif = to_chunks(log_i)
    lff = to_chunks(log_f)
    tri = jnp.tril(jnp.ones((Q, Q), bool))

    def chunk_body(state, chunk):
        C_prev, n_prev, m_prev = state  # [B,H,dh,dh], [B,H,dh], [B,H]
        qc, kc, vc, ic, fc = chunk      # [B,Q,H,dh] / [B,Q,H]
        F = jnp.cumsum(fc, axis=1)      # [B,Q,H] cumulative log-forget in chunk
        # intra-chunk decay D[t,s] = F_t - F_s + i_s  (s <= t)
        D = F[:, :, None, :] - F[:, None, :, :] + ic[:, None, :, :]
        D = jnp.where(tri[None, :, :, None], D, -jnp.inf)
        m_intra = jnp.max(D, axis=2)                      # [B,Q,H]
        m_inter = F + m_prev[:, None, :]                  # carried-state scale
        m_t = jnp.maximum(m_intra, m_inter)               # [B,Q,H]
        a = jnp.exp(D - m_t[:, :, None, :])               # [B,t,s,H]
        qk = jnp.einsum("bthd,bshd->btsh", qc, kc)
        w = a * qk
        num = jnp.einsum("btsh,bshd->bthd", w, vc)
        den_intra = jnp.sum(w, axis=2)                    # [B,t,H]
        scale = jnp.exp(m_inter - m_t)                    # [B,Q,H]
        num = num + scale[..., None] * jnp.einsum("bthk,bhkv->bthv", qc, C_prev)
        den = den_intra + scale * jnp.einsum("bthk,bhk->bth", qc, n_prev)
        den = jnp.maximum(jnp.abs(den), jnp.exp(-m_t))
        h = num / den[..., None]                          # [B,Q,H,dh]
        # ----- state to next chunk
        F_tot = F[:, -1]                                  # [B,H]
        g = F_tot[:, None, :] - F + ic                    # decay of k_s to chunk end
        m_state = jnp.maximum(jnp.max(g, axis=1), F_tot + m_prev)
        gw = jnp.exp(g - m_state[:, None, :])             # [B,Q,H]
        C_new = jnp.exp(F_tot + m_prev - m_state)[..., None, None] * C_prev + jnp.einsum(
            "bsh,bshk,bshv->bhkv", gw, kc, vc
        )
        n_new = jnp.exp(F_tot + m_prev - m_state)[..., None] * n_prev + jnp.einsum(
            "bsh,bshk->bhk", gw, kc
        )
        return (C_new, n_new, m_state), h

    state0 = (
        jnp.zeros((B, H, dh, dh), jnp.float32),
        jnp.zeros((B, H, dh), jnp.float32),
        jnp.full((B, H), -1e30, jnp.float32),
    )
    with jax.named_scope("mlstm.memory"):
        (C_f, n_f, m_f), hs = jax.lax.scan(
            jax.checkpoint(chunk_body), state0, (qf, kf, vf, lif, lff)
        )
    h = _out_norm(hs.swapaxes(0, 1).reshape(B, S, -1), p["out_norm"], H, cfg,
                  x.dtype)
    if _paper(cfg):
        h = h + p["skip"] * uc
    h = h * jax.nn.silu(z)
    out = ctx.psum_tp(h @ p["w_down"])
    if return_state:
        kc = cfg.xlstm.conv_kernel - 1
        u_raw = x @ p["w_up"]
        cache = MLSTMCache(C=C_f, n=n_f, m=m_f, conv=u_raw[:, S - kc :, :])
        return out, cache
    return out


class MLSTMCache(NamedTuple):
    C: jax.Array      # [B,H,dk,dv] fp32
    n: jax.Array      # [B,H,dk] fp32
    m: jax.Array      # [B,H] fp32
    conv: jax.Array   # [B,k-1,di]


def init_mlstm_cache(batch: int, cfg: ArchConfig, di_local: int, H_local: int, dtype) -> MLSTMCache:
    dh = di_local // H_local
    return MLSTMCache(
        C=jnp.zeros((batch, H_local, dh, dh), jnp.float32),
        n=jnp.zeros((batch, H_local, dh), jnp.float32),
        m=jnp.full((batch, H_local), -1e30, jnp.float32),
        conv=jnp.zeros((batch, cfg.xlstm.conv_kernel - 1, di_local), dtype),
    )


def mlstm_decode(
    p: dict, x: jax.Array, cache: MLSTMCache, *, cfg: ArchConfig, ctx: ParallelCtx = LOCAL_CTX
) -> Tuple[jax.Array, MLSTMCache]:
    """x [B,1,d] -> ([B,1,d], cache)."""
    B = x.shape[0]
    u = x @ p["w_up"]  # [B,1,di]
    z = x @ p["w_z"]
    hist = jnp.concatenate([cache.conv, u], axis=1)
    conv_out = jnp.einsum("bkd,kd->bd", hist, p["conv_w"]) + p["conv_b"]
    uc = jax.nn.silu(conv_out)  # [B,di]
    di = u.shape[-1]
    H = p["b_i"].shape[0]
    dh = di // H
    q = (uc @ p["wq"]).reshape(B, H, dh).astype(jnp.float32)
    k = ((uc @ p["wk"]) / dh**0.5).reshape(B, H, dh).astype(jnp.float32)
    v = (u[:, 0] @ p["wv"]).reshape(B, H, dh).astype(jnp.float32)
    gates = (u[:, 0] @ p["w_if"]).astype(jnp.float32)
    log_i = gates[:, :H] + p["b_i"].astype(jnp.float32)
    log_f = jax.nn.log_sigmoid(gates[:, H:] + p["b_f"].astype(jnp.float32))

    m_new = jnp.maximum(log_f + cache.m, log_i)  # [B,H]
    fdec = jnp.exp(log_f + cache.m - m_new)
    iinc = jnp.exp(log_i - m_new)
    C = fdec[..., None, None] * cache.C + iinc[..., None, None] * (k[..., :, None] * v[..., None, :])
    n = fdec[..., None] * cache.n + iinc[..., None] * k
    num = jnp.einsum("bhkv,bhk->bhv", C, q)
    den = jnp.maximum(jnp.abs(jnp.einsum("bhk,bhk->bh", n, q)), jnp.exp(-m_new))
    h = (num / den[..., None]).reshape(B, 1, di).astype(x.dtype)
    h = rms_norm(h, p["out_norm"], cfg.norm_eps) * jax.nn.silu(z)
    out = ctx.psum_tp(h @ p["w_down"])
    return out, MLSTMCache(C=C, n=n, m=m_new, conv=hist[:, 1:])


# ======================================================================= sLSTM
def _s_dims(cfg: ArchConfig):
    d = cfg.d_model
    H = cfg.n_heads
    dh = d // H
    f_ff = int(d * cfg.xlstm.s_proj_factor)
    return d, H, dh, f_ff


def init_slstm_params(key, cfg: ArchConfig, dtype, inner_ff: bool = True) -> dict:
    """``inner_ff``: the mixer carries its own post-up-projection FFN (the
    registry's block); the paper's block has it as the layer's ``ff``."""
    d, H, dh, f_ff = _s_dims(cfg)
    xc = cfg.xlstm
    ks = jax.random.split(key, 4)
    p = {
        "r_gates": dense_init(ks[1], (H, dh, 4 * dh), dtype, scale=dh**-0.5),
        "out_norm": jnp.zeros((d,), dtype),
    }
    if _paper(cfg):   # gates (z, i, f, o), each block-diagonal per head
        p["w_gates"] = dense_init(ks[0], (4, H, dh, dh), dtype)
        p["b_gates"] = jnp.zeros((3 * d,), dtype)   # z, f, o (see below)
        p["conv_w"] = dense_init(jax.random.fold_in(key, 4),
                                 (xc.conv_kernel, d), dtype, scale=0.1)
        p["conv_b"] = jnp.zeros((d,), dtype)
    else:
        p["w_gates"] = dense_init(ks[0], (d, 4 * d), dtype)
        p["b_gates"] = jnp.zeros((4 * d,), dtype)
    if inner_ff:
        p["w_up_ff"] = dense_init(ks[2], (d, f_ff), dtype)
        p["w_down_ff"] = dense_init(ks[3], (f_ff, d), dtype, scale=0.02 / max(1, cfg.n_layers) ** 0.5)
    return p


class SLSTMCache(NamedTuple):
    c: jax.Array  # [B,H,dh] fp32
    n: jax.Array
    m: jax.Array  # [B,H,dh]
    h: jax.Array  # [B,H,dh] (in x dtype)


def init_slstm_cache(batch: int, cfg: ArchConfig, dtype) -> SLSTMCache:
    _, H, dh, _ = _s_dims(cfg)
    z = jnp.zeros((batch, H, dh), jnp.float32)
    return SLSTMCache(c=z, n=z, m=z - 1e30, h=jnp.zeros((batch, H, dh), dtype))


def _slstm_cell(p, cfg, xg, state: SLSTMCache) -> Tuple[SLSTMCache, jax.Array]:
    """xg: pre-computed input contribution [B, 4d] for one step."""
    d, H, dh, _ = _s_dims(cfg)
    B = xg.shape[0]
    rec = jnp.einsum("bhd,hde->bhe", state.h.astype(jnp.float32), p["r_gates"].astype(jnp.float32))
    g = xg.astype(jnp.float32).reshape(B, H, 4 * dh) + rec  # [B,H,4dh]
    zt, it, ft, ot = jnp.split(g, 4, axis=-1)
    log_i = it
    log_f = jax.nn.log_sigmoid(ft)
    m_new = jnp.maximum(log_f + state.m, log_i)
    fdec = jnp.exp(log_f + state.m - m_new)
    iinc = jnp.exp(log_i - m_new)
    c = fdec * state.c + iinc * jnp.tanh(zt)
    n = jnp.maximum(fdec * state.n + iinc, 1.0)
    h = jax.nn.sigmoid(ot) * c / n
    return SLSTMCache(c=c, n=n, m=m_new, h=h.astype(state.h.dtype)), h


def slstm_forward(
    p: dict, x: jax.Array, *, cfg: ArchConfig, ctx: ParallelCtx = LOCAL_CTX,
    return_state: bool = False,
):
    """Sequential sLSTM over the sequence (+ the post-up FFN where the mixer
    carries it).  x [B,S,d]."""
    with jax.named_scope("slstm"):
        B, S, d = x.shape
        xg = _slstm_gate_inputs(p, x, cfg)  # [B,S,4d]
        state = init_slstm_cache(B, cfg, x.dtype)
        if _paper(cfg):
            # R in float32 outside the scan, so that its gradient sums the
            # steps in float32: cast inside the step, each step's share is
            # added to a bfloat16 sum, which drops the small ones (7.6 % of
            # its norm at 512 steps and the paper's widths, on a CPU)
            p = dict(p, r_gates=p["r_gates"].astype(jnp.float32))

        def step(st, xg_t):
            st2, h = _slstm_cell(p, cfg, xg_t, st)
            return st2, h

        with jax.named_scope("slstm.recurrence"):
            st_f, hs = jax.lax.scan(step, state, jnp.swapaxes(xg, 0, 1))  # [S,B,H,dh]
        h = _out_norm(jnp.swapaxes(hs, 0, 1).reshape(B, S, d), p["out_norm"],
                      cfg.n_heads, cfg, x.dtype)
        if "w_up_ff" in p:
            h = jax.nn.gelu(h @ p["w_up_ff"]) @ p["w_down_ff"]
    if return_state:
        return h, st_f
    return h


def _slstm_gate_inputs(p, x, cfg: ArchConfig) -> jax.Array:
    """Input contribution to the gates, [B,S,4d] laid out (head, gate, dh)
    with gates (z, i, f, o).  In the paper's block the input and forget
    gates read SiLU(causal conv(x)), z and o read x, each through its own
    block-diagonal per-head weights; and the input gate has no bias: h =
    o * c / n, with c and n sums over the same input-gate weights, is the
    same for any constant added to the input gate at every step, so such a
    bias would get no gradient and move under Adam by round-off alone."""
    if not _paper(cfg):
        return x @ p["w_gates"] + p["b_gates"]
    B, S, d = x.shape
    _, H, dh, _ = _s_dims(cfg)
    xc = jax.nn.silu(_conv1d(x, p["conv_w"], p["conv_b"]))
    src = jnp.stack([x, xc, xc, x]).reshape(4, B, S, H, dh)
    g = jnp.einsum("gbshd,ghde->bshge", src, p["w_gates"])
    bz, bf, bo = jnp.moveaxis(p["b_gates"].reshape(H, 3, dh), 1, 0)
    g = g + jnp.stack([bz, jnp.zeros_like(bz), bf, bo], axis=1)
    return g.reshape(B, S, 4 * d)


def slstm_decode(
    p: dict, x: jax.Array, cache: SLSTMCache, *, cfg: ArchConfig, ctx: ParallelCtx = LOCAL_CTX
) -> Tuple[jax.Array, SLSTMCache]:
    B, _, d = x.shape
    xg = (x[:, 0] @ p["w_gates"]) + p["b_gates"]
    st, h = _slstm_cell(p, cfg, xg, cache)
    h = h.reshape(B, 1, d).astype(x.dtype)
    h = rms_norm(h, p["out_norm"], cfg.norm_eps)
    ff = jax.nn.gelu(h @ p["w_up_ff"]) @ p["w_down_ff"]
    return ff, st
