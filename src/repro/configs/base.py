"""Architecture / shape configuration system.

Every assigned architecture is a module ``src/repro/configs/<id>.py`` (dashes and
leading digits sanitized to underscores) exporting ``CONFIG: ArchConfig``.  The
registry in ``repro.configs`` maps the public ``--arch`` id strings to them.

Design notes (see DESIGN.md §4):
  * A model is a sequence of *period instances*.  Each period is a statically
    known list of ``LayerSpec`` (mixer kind + ff kind + attention window).  The
    pipeline scans over period instances, so heterogeneous families (jamba's
    mamba:attn 7:1, xlstm's sLSTM/mLSTM alternation) stay SPMD-uniform as long
    as every stage holds an integer number of periods.
  * ``stages``/``tensor`` give the default factorization of the 16-wide
    ``model`` mesh axis into (pipeline stages x tensor parallel); the TPU
    planner may override them.
"""
from __future__ import annotations

import dataclasses
import math
from dataclasses import dataclass, field, replace
from typing import Optional, Sequence

# Mixer kinds.
ATTN = "attn"
MAMBA = "mamba"
SLSTM = "slstm"
MLSTM = "mlstm"

# FF kinds.
DENSE_FF = "dense"
MOE_FF = "moe"
NO_FF = "none"

GLOBAL_WINDOW = 0  # sentinel: full (global) attention


@dataclass(frozen=True)
class LayerSpec:
    """One layer inside the repeating period."""

    mixer: str = ATTN
    ff: str = DENSE_FF
    window: int = GLOBAL_WINDOW  # sliding-window size; 0 = full attention

    def __post_init__(self):
        assert self.mixer in (ATTN, MAMBA, SLSTM, MLSTM), self.mixer
        assert self.ff in (DENSE_FF, MOE_FF, NO_FF), self.ff


@dataclass(frozen=True)
class MoECfg:
    n_experts: int
    top_k: int
    d_ff_expert: int
    capacity_factor: float = 1.25
    router_aux_weight: float = 0.01


@dataclass(frozen=True)
class MambaCfg:
    d_state: int = 16
    d_conv: int = 4
    expand: int = 2

    def d_inner(self, d_model: int) -> int:
        return self.expand * d_model


@dataclass(frozen=True)
class XLSTMCfg:
    """xLSTM block settings."""

    # Projection factor of the mLSTM up-projection and sLSTM ffn.
    m_proj_factor: float = 2.0
    s_proj_factor: float = 4.0 / 3.0
    conv_kernel: int = 4
    # "registry": xlstm-125m's block (dense q/k/v, gates read from the
    # up-projection, RMSNorm of the cell outputs, an sLSTM that carries its
    # own FFN; prefill and decode exist).  "paper": arXiv:2405.04517's block,
    # which trains only: q/k/v block-diagonal ("headwise") in blocks of
    # ``qkv_block`` with the mLSTM gates read from (q, k, v), a learnable
    # skip, per-head group norms of both cells' outputs, a causal convolution
    # + SiLU in front of the sLSTM's input and forget gates, block-diagonal
    # sLSTM gate inputs, and no sLSTM input-gate bias
    block: str = "registry"
    qkv_block: int = 4
    # recompute each mLSTM mixer's internals in the backward pass instead of
    # holding them from the forward (about 130 MB per block and micro-batch
    # of 2 x 512 tokens at the paper's widths)
    remat_mlstm: bool = False


@dataclass(frozen=True)
class ArchConfig:
    name: str
    family: str  # dense | moe | ssm | hybrid | vlm | audio
    citation: str
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: Optional[int] = None  # default d_model // n_heads
    period: Sequence[LayerSpec] = (LayerSpec(),)
    moe: Optional[MoECfg] = None
    mamba: Optional[MambaCfg] = None
    xlstm: Optional[XLSTMCfg] = None
    qkv_bias: bool = False
    rope_theta: float = 10_000.0
    norm_eps: float = 1e-5
    causal: bool = True
    is_encoder: bool = False          # encoder-only (no decode shapes)
    frontend: str = "none"            # none | audio | vision
    n_frontend_tokens: int = 256      # vision: #patch embeddings prepended
    tie_embeddings: bool = False
    qk_norm: bool = False
    # Default mesh-axis factorization: stages * tensor == model axis size (16).
    stages: int = 16
    tensor: int = 1
    # dtype of params/activations on the target hardware
    param_dtype: str = "bfloat16"
    # activation of the gated dense feed-forward: silu (SwiGLU) | gelu (GeGLU)
    ff_act: str = "silu"
    # the norm before each block's sublayers and at the end: rms (RMSNorm) |
    # layer (LayerNorm without a bias)
    norm: str = "rms"

    # ------------------------------------------------------------------ helpers
    @property
    def hd(self) -> int:
        return self.head_dim if self.head_dim is not None else self.d_model // self.n_heads

    @property
    def period_len(self) -> int:
        return len(self.period)

    @property
    def n_periods(self) -> int:
        """Number of period instances, rounded up.  When the layer count is not
        a multiple of the period (gemma3: 34 = 5x6 + 4) the trailing layers of
        the last period are masked to identity by the runtime (layer index >=
        n_layers)."""
        return -(-self.n_layers // self.period_len)

    def layer_spec(self, i: int) -> LayerSpec:
        return self.period[i % self.period_len]

    @property
    def uses_attention(self) -> bool:
        return any(s.mixer == ATTN for s in self.period)

    @property
    def subquadratic(self) -> bool:
        """True if a 500k-token decode context is feasible (no full O(L^2) attn
        with an unbounded KV cache on every layer)."""
        if all(s.mixer != ATTN for s in self.period):
            return True
        # windowed attention on most layers + a few globals is acceptable
        # (globals use data-axis-sharded KV); pure-global attn everywhere is not.
        n_attn = sum(1 for s in self.period if s.mixer == ATTN)
        n_global = sum(1 for s in self.period if s.mixer == ATTN and s.window == GLOBAL_WINDOW)
        return n_global < n_attn or n_attn * 4 <= len(self.period)

    @property
    def decodes(self) -> bool:
        """False for blocks that only train: prefill and one-token decode
        exist for the registry's xLSTM block, not the paper's."""
        return self.xlstm is None or self.xlstm.block == "registry"

    def supports_shape(self, shape_name: str) -> bool:
        if not self.decodes and INPUT_SHAPES[shape_name].kind != "train":
            return False
        if self.is_encoder and shape_name in ("decode_32k", "long_500k"):
            return False
        if shape_name == "long_500k" and not self.subquadratic:
            return False
        return True

    # --------------------------------------------------------------- counting
    def param_count(self) -> int:
        """Approximate parameter count (embeddings + per-layer, excl. norms)."""
        d, hd = self.d_model, self.hd
        total = self.vocab_size * d * (1 if self.tie_embeddings else 2)
        for i in range(self.n_layers):
            spec = self.layer_spec(i)
            if spec.mixer == ATTN:
                q = d * self.n_heads * hd
                kv = 2 * d * self.n_kv_heads * hd
                o = self.n_heads * hd * d
                total += q + kv + o
            elif spec.mixer == MAMBA:
                mc = self.mamba or MambaCfg()
                di = mc.d_inner(d)
                total += d * 2 * di + di * mc.d_conv + di * (2 * mc.d_state + 2) + di * d
            elif spec.mixer in (SLSTM, MLSTM) and self.decodes:
                xc = self.xlstm or XLSTMCfg()
                f = xc.m_proj_factor if spec.mixer == MLSTM else xc.s_proj_factor
                di = int(d * f)
                total += 2 * d * di + di * d + 4 * d * di  # up/gate/down + gates
            elif spec.mixer == MLSTM:   # the paper's block, counted exactly
                xc = self.xlstm
                di = int(d * xc.m_proj_factor)
                H = self.n_heads
                total += (2 * d * di + di * d           # up, output gate, down
                          + 3 * di * xc.qkv_block       # headwise q, k, v
                          + 3 * di * 2 * H + 2 * H      # gates from (q, k, v)
                          + (xc.conv_kernel + 1) * di   # convolution, bias
                          + 2 * di)                     # group norm, skip
            elif spec.mixer == SLSTM:
                xc = self.xlstm
                dh = d // self.n_heads
                total += (4 * d * dh + 4 * d * dh       # gate inputs, recurrence
                          + 3 * d + d                   # gate biases, group norm
                          + (xc.conv_kernel + 1) * d)   # convolution, bias
            if spec.ff == DENSE_FF:
                total += 3 * d * self.d_ff
            elif spec.ff == MOE_FF:
                assert self.moe is not None
                total += self.moe.n_experts * 3 * d * self.moe.d_ff_expert + d * self.moe.n_experts
        return total

    def active_param_count(self) -> int:
        """Params touched per token (MoE: only top_k experts)."""
        if self.moe is None:
            return self.param_count()
        d = self.d_model
        full = self.param_count()
        n_moe_layers = sum(
            1 for i in range(self.n_layers) if self.layer_spec(i).ff == MOE_FF
        )
        inactive = (
            n_moe_layers
            * (self.moe.n_experts - self.moe.top_k)
            * 3
            * d
            * self.moe.d_ff_expert
        )
        return full - inactive

    # ----------------------------------------------------------------- reduce
    def reduced(self) -> "ArchConfig":
        """Smoke-test variant: <=2 periods, d_model<=512, <=4 experts."""
        d_model = min(self.d_model, 256)
        n_heads = min(self.n_heads, 4)
        n_kv = min(self.n_kv_heads, max(1, n_heads // 2))
        head_dim = d_model // n_heads
        moe = None
        if self.moe is not None:
            moe = replace(
                self.moe,
                n_experts=min(4, self.moe.n_experts),
                top_k=min(2, self.moe.top_k),
                d_ff_expert=min(self.moe.d_ff_expert, 2 * d_model),
            )
        # Dense families shrink to 2 layers; multi-kind families keep one full
        # period so every mixer/ff kind is exercised.
        n_layers = self.period_len * (2 if self.period_len == 1 else 1)
        period = tuple(
            replace(s, window=min(s.window, 64) if s.window else 0) for s in self.period
        )
        return replace(
            self,
            name=self.name + "-reduced",
            n_layers=n_layers,
            d_model=d_model,
            n_heads=n_heads,
            n_kv_heads=n_kv,
            head_dim=head_dim,
            d_ff=min(self.d_ff, 4 * d_model) if self.d_ff else 0,
            vocab_size=min(self.vocab_size, 1024),
            moe=moe,
            period=period,
            stages=1,
            tensor=1,
            n_frontend_tokens=min(self.n_frontend_tokens, 16),
            param_dtype="float32",
        )


# --------------------------------------------------------------------- shapes
@dataclass(frozen=True)
class InputShape:
    name: str
    seq_len: int
    global_batch: int
    kind: str  # train | prefill | decode


INPUT_SHAPES = {
    "train_4k": InputShape("train_4k", 4_096, 256, "train"),
    "prefill_32k": InputShape("prefill_32k", 32_768, 32, "prefill"),
    "decode_32k": InputShape("decode_32k", 32_768, 128, "decode"),
    "long_500k": InputShape("long_500k", 524_288, 1, "decode"),
}


def validate(cfg: ArchConfig) -> None:
    assert cfg.n_periods >= 1
    assert cfg.n_heads % cfg.n_kv_heads == 0 or cfg.n_kv_heads % cfg.n_heads == 0
    if any(s.ff == MOE_FF for s in cfg.period):
        assert cfg.moe is not None
    if any(s.mixer == MAMBA for s in cfg.period):
        assert cfg.mamba is not None
    if any(s.mixer in (SLSTM, MLSTM) for s in cfg.period):
        assert cfg.xlstm is not None
    assert cfg.ff_act in ("silu", "gelu"), cfg.ff_act
    assert cfg.norm in ("rms", "layer"), cfg.norm
    assert cfg.xlstm is None or cfg.xlstm.block in ("registry", "paper")
    assert 16 % cfg.stages == 0 and cfg.stages * cfg.tensor in (cfg.stages * cfg.tensor,)
