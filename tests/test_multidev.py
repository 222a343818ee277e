"""Multi-device integration tests.  Each runs a repro.testing.* module in a
subprocess with 8 fake CPU devices so this pytest process keeps seeing 1
device (dry-run isolation rule)."""
import pytest


def test_ring_collectives(multidev):
    multidev("collectives_check")


@pytest.mark.parametrize("arch,stages,tensor,layers", [
    ("phi3-mini-3.8b", 4, 1, 4),      # pure pipeline + padding-free
    ("qwen2.5-14b", 2, 4, 4),         # deep TP, qkv bias
    ("gemma3-4b", 2, 4, "none"),      # sliding window + kv-share sync
    ("dbrx-132b", 4, 1, 4),           # MoE + expert parallelism
    ("jamba-v0.1-52b", 2, 1, "none"), # hybrid mamba+attn+moe period
    ("xlstm-125m", 2, 2, "none"),     # sLSTM/mLSTM, tp-replicated mixers
    ("hubert-xlarge", 4, 2, 4),       # encoder, no shift
])
def test_pipeline_train_equivalence(multidev, arch, stages, tensor, layers):
    """Pipelined train step == single-device step (loss + updated params)."""
    depth = "" if layers == "none" else layers
    out = multidev("pipeline_equiv", f"{arch}@reduced{depth}", stages, tensor)
    assert "loss_err" in out


@pytest.mark.parametrize("arch,stages,tensor,seq_shards", [
    ("phi3-mini-3.8b", 4, 1, 1),
    ("gemma3-4b", 2, 2, 2),           # data-axis-sharded KV (long-ctx path)
    ("jamba-v0.1-52b", 2, 1, 1),
    ("dbrx-132b", 2, 2, 1),
    ("xlstm-125m", 2, 2, 1),
])
def test_pipeline_serve_equivalence(multidev, arch, stages, tensor, seq_shards):
    """Pipelined prefill+decode == single-device prefill+decode logits."""
    out = multidev("serve_equiv", arch, stages, tensor, seq_shards)
    assert "decode_err" in out
