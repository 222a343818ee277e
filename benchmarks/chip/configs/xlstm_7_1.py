"""Plain reference of the paper's xLSTM[7:1] language model (Beck et al.,
arXiv:2405.04517; code github.com/NX-AI/xlstm): ``num_blocks`` residual
blocks, sLSTM blocks at ``slstm_at`` (one in each period of
``num_blocks / len(slstm_at)``), mLSTM blocks elsewhere; float32
throughout; imports nothing of the program.

Every norm before a block or sublayer, and the last, is a LayerNorm
without a bias.

mLSTM block: x + mLSTM(norm(x)).  Up-projection u = h W_up and output gate
input z = h W_z to ``mlstm_proj_factor`` x d; a causal depthwise convolution
of ``conv1d_kernel_size`` and SiLU give c; q = c Wq, k = c Wk, v = u Wv, each
block-diagonal in blocks of ``qkv_proj_blocksize``; the input gate
(exponential) and the forget gate (sigmoid) per head read (q, k, v); the
matrix memory is read in the paper's stabilised parallel form, every position
against every earlier one: D[t, s] = F_t - F_s + i_s (F the cumulative log
forget gate), m_t = max_s D[t, s], C = exp(D - m) * q k^T / sqrt(dh),
h = C v / max(|sum_s C|, exp(-m)); a per-head group norm, plus a learnable
skip times c, times SiLU(z), down-projection.

sLSTM block: x + sLSTM(norm(x)), then x + FF(norm(x)).  A causal
convolution of ``slstm_conv1d_kernel_size`` and SiLU feed the input and
forget gates, x itself the cell input z and the output gate; each gate's
input weights and the recurrent matrix are block-diagonal per head, and the
gates z, f and o have biases; the
scalar memory runs step by step with exponential gating and the stabiliser
m (c = f c + i tanh(z), n = f n + i, h = o c / n, with f and i scaled by
exp(-m)); then a per-head group norm.  FF is the gated GeLU (exact, erf) of
width ``ffn_dim``: (GeLU(h W_gate) * h W_up) W_down.

Departures from the paper's code that remain, as the program has them: no
bias on the sLSTM's input gate (h = o c / n is the same for any constant
added to the input gate at every step, since c and n sum the same input
weights, so that bias has no gradient and Adam moves it by round-off
alone); no 1e-6 added to the memory's normaliser; no dropout; the mLSTM memory's first stabiliser is the
largest log weight of the positions seen (the paper's code too), and the
sLSTM's first step sets m to the input gate's log (as the paper's code does
where n is zero).  The program reads the memory in chunks of
``mlstm_chunk`` positions with the state carried between them, which is
the same sum in another order.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from chipbench.refmath import log_sigmoid, next_token_ce, sigmoid, silu


def dims(cfg):
    d = cfg["embedding_dim"]
    H = cfg["num_heads"]
    di = int(d * cfg["mlstm_proj_factor"])
    return d, H, di, cfg["ffn_dim"]


def pattern(cfg):
    """(period, the sLSTM block's position in it, periods)."""
    at = cfg["slstm_at"]
    period = cfg["num_blocks"] // len(at)
    pos = at[0] % period
    if at != [pos + i * period for i in range(len(at))]:
        raise ValueError(f"slstm_at {at} is not one sLSTM block in each "
                         f"period of {period}")
    return period, pos, len(at)


def init_params(cfg, key):
    """Random weights in the config's ``torch_dtype``: normal with
    ``initializer_range``; down-projections divided by sqrt(blocks); the
    convolutions at 0.1 and the sLSTM recurrent matrices at head_dim^-1/2;
    mLSTM forget-gate biases 3..6 over the heads and input-gate biases
    normal(0, 0.1) (the paper's code); sLSTM forget-gate biases 3, other
    biases and norm offsets zero, skips one.  The sLSTM's gate biases are
    laid out (head, gate, head_dim) for the gates z, f and o."""
    d, H, di, f = dims(cfg)
    period, spos, P = pattern(cfg)
    k, ks = cfg["conv1d_kernel_size"], cfg["slstm_conv1d_kernel_size"]
    b = cfg["qkv_proj_blocksize"]
    dh = d // H
    std = cfg["initializer_range"]
    down = std / cfg["num_blocks"] ** 0.5
    V = cfg["vocab_size"]
    dtype = jnp.dtype(cfg["torch_dtype"])
    m_normal = {
        "w_up": ((P, d, di), std), "w_z": ((P, d, di), std),
        "conv_w": ((P, k, di), 0.1), "wq": ((P, di // b, b, b), std),
        "wk": ((P, di // b, b, b), std), "wv": ((P, di // b, b, b), std),
        "w_if": ((P, 3 * di, 2 * H), std), "w_down": ((P, di, d), down),
        "b_i": ((P, H), 0.1),
    }
    s_normal = {
        "conv_w": ((P, ks, d), 0.1), "w_gates": ((P, 4, H, dh, dh), std),
        "r_gates": ((P, H, dh, 4 * dh), dh ** -0.5),
    }
    ff_normal = {"w_gate": ((P, d, f), std), "w_up": ((P, d, f), std),
                 "w_down": ((P, f, d), down)}
    n = iter(range(1 << 20))

    def draw(table):
        return {name: (s * jax.random.normal(jax.random.fold_in(key, next(n)),
                                             shape, jnp.float32)).astype(dtype)
                for name, (shape, s) in sorted(table.items())}

    full = lambda shape, v: jnp.full(shape, v, dtype)  # noqa: E731
    layers = []
    for j in range(period):
        if j == spos:
            mixer = draw(s_normal)
            mixer.update(
                conv_b=full((P, d), 0), out_norm=full((P, d), 0),
                b_gates=jnp.zeros((P, H, 3, dh), dtype).at[:, :, 1].set(3.0)
                .reshape(P, 3 * d))
            layers.append({"norm1": full((P, d), 0), "mixer": mixer,
                           "norm2": full((P, d), 0), "ff": draw(ff_normal)})
        else:
            mixer = draw(m_normal)
            mixer.update(
                conv_b=full((P, di), 0), out_norm=full((P, di), 0),
                skip=full((P, di), 1.0),
                b_f=jnp.broadcast_to(jnp.linspace(3.0, 6.0, H), (P, H))
                .astype(dtype))
            layers.append({"norm1": full((P, d), 0), "mixer": mixer})
    emb = draw({"embed": ((V, d), std), "head": ((V, d), std)})
    return {"embed": emb["embed"], "final_norm": full((d,), 0),
            "head": emb["head"], "layers": tuple(layers)}


def gelu(x):
    return 0.5 * x * (1.0 + jax.scipy.special.erf(x / 2.0 ** 0.5))


def group_norm(x, w, heads, eps):
    """Layer norm of each head's slice of the last axis, times (1 + w)."""
    xs = x.reshape(*x.shape[:-1], heads, -1)
    xs = xs - jnp.mean(xs, axis=-1, keepdims=True)
    xs = xs / jnp.sqrt(jnp.mean(jnp.square(xs), axis=-1, keepdims=True) + eps)
    return xs.reshape(x.shape) * (1.0 + w)


def layer_norm(x, w, eps):
    """LayerNorm without a bias, times (1 + w)."""
    return group_norm(x, w, 1, eps)


def causal_conv(x, w, bias):
    """Depthwise causal convolution of x [B, S, C] with w [k, C]."""
    k, S = w.shape[0], x.shape[1]
    xp = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    return sum(xp[:, i:i + S] * w[i] for i in range(k)) + bias


def headwise(nx, x, w):
    """x [B, S, n*b] through the n blocks w [n, b, b]."""
    n, b, _ = w.shape
    B, S, _ = x.shape
    return nx.einsum("bsni,nio->bsno", x.reshape(B, S, n, b), w).reshape(
        B, S, -1)


def _mlstm(cfg, nx, p, h):
    d, H, di, _ = dims(cfg)
    B, S, _ = h.shape
    dh = di // H
    u = nx.mm(h, p["w_up"])
    z = nx.mm(h, p["w_z"])
    c = silu(causal_conv(u, p["conv_w"], p["conv_b"]))
    q, k, v = (headwise(nx, c, p["wq"]), headwise(nx, c, p["wk"]),
               headwise(nx, u, p["wv"]))
    gates = nx.mm(jnp.concatenate([q, k, v], axis=-1), p["w_if"])
    log_i = gates[..., :H] + p["b_i"]                       # [B, S, H]
    log_f = log_sigmoid(gates[..., H:] + p["b_f"])
    q, k, v = (a.reshape(B, S, H, dh) for a in (q, k, v))
    F = jnp.cumsum(log_f, axis=1)
    D = F[:, :, None, :] - F[:, None, :, :] + log_i[:, None, :, :]
    D = jnp.where(jnp.tril(jnp.ones((S, S), bool))[None, :, :, None], D,
                  -jnp.inf)
    m = jnp.max(D, axis=2)                                  # [B, t, H]
    C = (jnp.exp(D - m[:, :, None, :])
         * nx.einsum("bthd,bshd->btsh", q, k) / dh ** 0.5)
    n = jnp.maximum(jnp.abs(jnp.sum(C, axis=2)), jnp.exp(-m))
    ht = nx.einsum("btsh,bshd->bthd", C, v) / n[..., None]
    ht = group_norm(ht.reshape(B, S, di), p["out_norm"], H, cfg["norm_eps"])
    ht = ht + p["skip"] * c
    return nx.mm(ht * silu(z), p["w_down"])


def _slstm(cfg, nx, p, h):
    d, H, _, _ = dims(cfg)
    B, S, _ = h.shape
    dh = d // H
    c = silu(causal_conv(h, p["conv_w"], p["conv_b"]))
    xh, ch = h.reshape(B, S, H, dh), c.reshape(B, S, H, dh)
    w = p["w_gates"]                           # gates z, i, f, o
    xg = jnp.stack([nx.einsum("bshd,hde->bshe", src, w[g])
                    for g, src in enumerate((xh, ch, ch, xh))], axis=3)
    bz, bf, bo = (p["b_gates"].reshape(H, 3, dh)[:, i] for i in range(3))
    xg = xg + jnp.stack([bz, jnp.zeros_like(bz), bf, bo], axis=1)  # [B, S, H, 4, dh]
    r = p["r_gates"]

    def step(state, xg_t):
        cs, n, m, hp = state
        g = xg_t + nx.einsum("bhd,hde->bhe", hp, r).reshape(B, H, 4, dh)
        zt, it, ft, ot = (g[:, :, i] for i in range(4))
        lf = log_sigmoid(ft)
        m_new = jnp.maximum(lf + m, it)
        fd = jnp.exp(lf + m - m_new)
        ii = jnp.exp(it - m_new)
        cs = fd * cs + ii * jnp.tanh(zt)
        n = fd * n + ii
        hn = sigmoid(ot) * cs / n
        return (cs, n, m_new, hn), hn

    zero = jnp.zeros((B, H, dh), jnp.float32)
    _, hs = jax.lax.scan(step, (zero, zero, zero - 1e30, zero),
                         jnp.swapaxes(xg, 0, 1))
    return group_norm(jnp.swapaxes(hs, 0, 1).reshape(B, S, d), p["out_norm"],
                      H, cfg["norm_eps"])


def _ff(nx, p, h):
    return nx.mm(gelu(nx.mm(h, p["w_gate"])) * nx.mm(h, p["w_up"]),
                 p["w_down"])


def loss(cfg, params, tokens, labels, nx):
    """Mean next-token cross-entropy of ``tokens`` [B, S] (float32 params)."""
    x = nx.q(params["embed"])[tokens]
    eps = cfg["norm_eps"]
    period, spos, _ = pattern(cfg)
    for i in range(cfg["num_blocks"]):
        lp = jax.tree.map(lambda a: a[i // period],
                          params["layers"][i % period])
        if i % period == spos:
            x = x + _slstm(cfg, nx, lp["mixer"], layer_norm(x, lp["norm1"], eps))
            x = x + _ff(nx, lp["ff"], layer_norm(x, lp["norm2"], eps))
        else:
            x = x + _mlstm(cfg, nx, lp["mixer"], layer_norm(x, lp["norm1"], eps))
    h = layer_norm(x, params["final_norm"], eps)
    return next_token_ce(nx.mm(h, params["head"].T), labels)


def forward_flops_per_token(cfg, seq):
    """Multiply-adds x 2 of one token's forward pass as the program computes
    it: the mLSTM memory in chunks of ``mlstm_chunk`` positions (scores and
    values against the whole chunk, plus the carried memory's read and
    update), the block-diagonal projections at their blocks' sizes, the
    convolutions, the sLSTM recurrence, the feed-forward and the output
    head.  Elementwise gate and norm arithmetic counts nothing."""
    d, H, di, f = dims(cfg)
    _, _, n_s = pattern(cfg)
    dh, dhs = di // H, d // H
    Q = min(cfg["mlstm_chunk"], seq)
    mlstm = (2 * d * di + 3 * di * cfg["qkv_proj_blocksize"] + 3 * di * 2 * H
             + di * d + cfg["conv1d_kernel_size"] * di
             + H * (2 * Q * dh + 2 * dh * dh + 2 * dh))
    slstm = (cfg["slstm_conv1d_kernel_size"] * d + 4 * d * dhs + 4 * d * dhs
             + 3 * d * f)
    n_m = cfg["num_blocks"] - n_s
    return 2.0 * (n_m * mlstm + n_s * slstm + d * cfg["vocab_size"])
