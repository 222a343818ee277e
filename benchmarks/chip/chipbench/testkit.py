"""CPU-sized cells for the benchmark's own tests.

:func:`make_root` copies the benchmark's directory into a scratch checkout
and adds configurations at the program's ``@reduced`` widths (float32,
d_model 256, vocabulary 1024), a short traffic mix and limits, so a test can
drive a whole run of the harness on the CPU.
"""
from __future__ import annotations

import json
import shutil
from pathlib import Path

from chipbench.spec import BENCH_DIR

PHI3_REDUCED = {
    "spelling": "phi3-mini-3.8b@reduced", "reference": "dense_decoder",
    "hidden_size": 256, "initializer_range": 0.02, "intermediate_size": 1024,
    "num_attention_heads": 4, "num_hidden_layers": 2,
    "num_key_value_heads": 2, "rms_norm_eps": 1e-05, "rope_theta": 10000.0,
    "torch_dtype": "float32", "vocab_size": 1024,
}
XLSTM_REDUCED = {
    "spelling": "xlstm-125m@reduced4", "reference": "xlstm",
    "conv1d_kernel_size": 4, "embedding_dim": 256, "initializer_range": 0.02,
    "mlstm_proj_factor": 2.0, "norm_eps": 1e-05, "num_blocks": 4,
    "num_heads": 4, "slstm_ff_proj_factor": 1.3333333333333333,
    "torch_dtype": "float32", "vocab_size": 1024, "mlstm_chunk": 256,
}
OPTIMIZER = {"name": "adamw", "lr": 1e-3, "b1": 0.9, "b2": 0.95,
             "eps": 1e-8, "weight_decay": 0.0}


def traffic(dp: int, seq: int = 16) -> dict:
    return {"stages": 2, "dp": dp, "global_batch": 8, "seq_len": seq,
            "micro_batch": 2, "backend": "local",
            "backend_options": {"lease_timeout": 60.0}, "loop": "closed",
            "generator": "zipf", "zipf_exponent": 1.2, "warmup_steps": 2,
            "steps_before_window": 3}


#: limits for the CPU cells: float32 program against the float32 reference
CPU_LIMITS = {"loss_gap": 1e-3, "grad_norm_gap": 1e-2,
              "update_norm_gap": 1e-2}

CELLS = {
    "phi3r.s2d1": ("phi3r", PHI3_REDUCED, "tiny.d1", traffic(1)),
    "xlstmr.s2d2": ("xlstmr", XLSTM_REDUCED, "tiny.d2", traffic(2)),
}


def make_root(tmp: Path, cells=tuple(CELLS)) -> Path:
    """A checkout under ``tmp`` holding a copy of the benchmark and the
    named CPU cells; returns its root."""
    root = Path(tmp) / "checkout"
    bench = root / "benchmarks" / "chip"
    shutil.copytree(BENCH_DIR, bench, ignore=shutil.ignore_patterns(
        "__pycache__", ".trace", "test_*.py"))
    spec = json.loads((BENCH_DIR.parents[1] / "BENCHMARK.json").read_text())
    spec["configs"], spec["workloads"] = [], []
    for name in cells:
        cfg_name, cfg, traffic_name, mix = CELLS[name]
        cfg_file = bench / "configs" / f"{cfg_name}.json"
        cfg_file.write_text(json.dumps(dict(cfg, optimizer=OPTIMIZER)))
        (bench / "traffic" / f"{traffic_name}.json").write_text(
            json.dumps(mix))
        (bench / "limits" / f"{name}.json").write_text(json.dumps(
            {k: {"limit": v} for k, v in CPU_LIMITS.items()}))
        spec["configs"].append({"name": cfg_name, "source": "test",
                                "file": str(cfg_file.relative_to(root)),
                                "reduced": [], "why": "test"})
        spec["workloads"].append({"name": name, "config": cfg_name,
                                  "traffic": traffic_name, "chips": 1,
                                  "why": "test"})
    for m in spec["per_layer"]:
        m.pop("workloads", None)
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return root
