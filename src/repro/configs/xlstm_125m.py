"""xlstm-125m  [ssm]  — the registry's 1:1 xLSTM variant  [after arXiv:2405.04517]

Not the paper's model: 12 blocks alternating mLSTM and sLSTM, dense q/k/v
projections, RMSNorm in place of the group norms, no sLSTM convolution and
an ungated GeLU feed-forward inside the sLSTM mixer (168.3M parameters).
The paper's xLSTM[7:1] 125M-class model is ``xlstm-7-1-125m``.

d_ff=0: xLSTM blocks carry their own up-projections (mLSTM pre-up-projection
x2, sLSTM post-up-projection 4/3), so there is no separate FFN.
"""
from repro.configs.base import ArchConfig, LayerSpec, XLSTMCfg, MLSTM, SLSTM, NO_FF

CONFIG = ArchConfig(
    name="xlstm-125m",
    family="ssm",
    citation="arXiv:2405.04517",
    n_layers=12,
    d_model=768,
    n_heads=4,
    n_kv_heads=4,
    head_dim=192,
    d_ff=0,
    vocab_size=50304,
    period=(LayerSpec(mixer=MLSTM, ff=NO_FF), LayerSpec(mixer=SLSTM, ff=NO_FF)),
    xlstm=XLSTMCfg(),
    stages=2,  # 12 layers = 6 periods -> 3 periods per stage; tensor=8
    tensor=8,
)
