"""xlstm-7-1-125m  [ssm]  — the paper's xLSTM[7:1] 125M-class model
[arXiv:2405.04517, the SlimPajama comparison; code github.com/NX-AI/xlstm]

24 residual blocks at embedding 768: in each period of eight, seven mLSTM
blocks and one sLSTM block (the paper gives the 7:1 ratio, not where the
sLSTM blocks sit: here last in each eight, blocks 7, 15 and 23).

mLSTM: pre up-projection x2 (inner width 1536), 4 heads, a causal
convolution of kernel 4 + SiLU feeding q and k, block-diagonal q/k/v in
blocks of 4, exponential input and sigmoid forget gates per head read from
(q, k, v), a per-head group norm, a learnable skip from the convolution, the
output gate SiLU(z) and a down-projection.  sLSTM: 4 heads, a causal
convolution of kernel 4 + SiLU feeding the input and forget gates, gate
input weights and recurrent matrix block-diagonal per head, a per-head group
norm; then its own pre-normed residual feed-forward, gated GeLU of width
1024 (4/3 x 768).  LayerNorm (no bias) before each block and sublayer and at
the end.  Vocabulary 50304, untied; 163.6M parameters.

Departure from the paper's code: the sLSTM input gate has no bias.  The cell
reads h = o * c / n, where c and n sum the same input-gate weights, so a
constant added to the input gate's pre-activation at every step leaves h as
it was: such a bias has no gradient, and Adam would move it by round-off
alone.  Training only: serving has no prefill or decode for this block.
The mLSTM mixers are recomputed in the backward pass (``remat_mlstm``): two
micro-batches of 2 x 512 tokens held for all 21 of them would take 10.6 GB
more than one TPU v5e chip has beside two replicas' state.
"""
from repro.configs.base import (ArchConfig, LayerSpec, XLSTMCfg, DENSE_FF,
                                MLSTM, NO_FF, SLSTM)

CONFIG = ArchConfig(
    name="xlstm-7-1-125m",
    family="ssm",
    citation="arXiv:2405.04517",
    n_layers=24,
    d_model=768,
    n_heads=4,
    n_kv_heads=4,
    head_dim=192,
    d_ff=1024,          # the sLSTM blocks' gated feed-forward
    vocab_size=50304,
    period=(LayerSpec(mixer=MLSTM, ff=NO_FF),) * 7
    + (LayerSpec(mixer=SLSTM, ff=DENSE_FF),),
    xlstm=XLSTMCfg(block="paper", qkv_block=4, remat_mlstm=True),
    ff_act="gelu",
    norm="layer",
    stages=2,
    tensor=8,
)
