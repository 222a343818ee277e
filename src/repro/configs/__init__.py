"""Registry of assigned architectures (public ``--arch`` ids) -> ArchConfig."""
from __future__ import annotations

import dataclasses
import importlib

from repro.configs.base import (  # noqa: F401
    ArchConfig,
    InputShape,
    INPUT_SHAPES,
    LayerSpec,
    MambaCfg,
    MoECfg,
    XLSTMCfg,
    validate,
)

# public id -> module name
_ARCH_MODULES = {
    "phi3-mini-3.8b": "phi3_mini_3_8b",
    "hubert-xlarge": "hubert_xlarge",
    "qwen2.5-14b": "qwen2_5_14b",
    "dbrx-132b": "dbrx_132b",
    "xlstm-125m": "xlstm_125m",
    "xlstm-7-1-125m": "xlstm_7_1_125m",
    "internlm2-20b": "internlm2_20b",
    "qwen3-moe-235b-a22b": "qwen3_moe_235b_a22b",
    "internvl2-26b": "internvl2_26b",
    "gemma3-4b": "gemma3_4b",
    "jamba-v0.1-52b": "jamba_v0_1_52b",
    # the paper's own evaluation model (serverless benchmarks)
    "bert-large": "bert_large",
}

ARCH_IDS = [k for k in _ARCH_MODULES if k != "bert-large"]


def get_config(arch_id: str) -> ArchConfig:
    if arch_id not in _ARCH_MODULES:
        raise KeyError(f"unknown arch {arch_id!r}; known: {sorted(_ARCH_MODULES)}")
    mod = importlib.import_module(f"repro.configs.{_ARCH_MODULES[arch_id]}")
    cfg = mod.CONFIG
    validate(cfg)
    return cfg


def all_configs() -> dict:
    return {aid: get_config(aid) for aid in ARCH_IDS}


SPELLINGS = "<arch>, <arch>@reduced[<L>] or <arch>@depth<L>"


def resolve_arch(spelling: str) -> ArchConfig:
    """ArchConfig for a model spelling — the one parser of model ids.

    ``<arch>``            the published configuration;
    ``<arch>@reduced``    the CPU-sized smoke variant (``ArchConfig.reduced``),
    ``<arch>@reduced<L>`` the same at ``L`` layers;
    ``<arch>@depth<L>``   every published width, cut to ``L`` layers (at least
                          one whole period, at most the published depth).

    Raises ``KeyError`` for an unknown arch or a malformed cut, so callers
    that resolve model ids keep one failure type."""
    base, sep, cut = spelling.partition("@")
    if base not in ARCH_IDS:
        raise KeyError(f"unknown arch {base!r} in {spelling!r}; archs: "
                       f"{sorted(ARCH_IDS)}; spellings: {SPELLINGS}")
    cfg = get_config(base)
    if not sep:
        return cfg
    for kind in ("reduced", "depth"):
        if cut.startswith(kind):
            depth = cut[len(kind):]
            break
    else:
        raise KeyError(f"unknown cut {cut!r} in {spelling!r}; "
                       f"spellings: {SPELLINGS}")
    if kind == "reduced":
        cfg = cfg.reduced()
        if not depth:
            return cfg
    if not depth.isdigit():
        raise KeyError(f"malformed cut {cut!r} in {spelling!r}: the depth "
                       f"must be a positive integer ({SPELLINGS})")
    n_layers = int(depth)
    if kind == "depth" and not cfg.period_len <= n_layers <= cfg.n_layers:
        raise KeyError(
            f"{spelling!r}: depth must keep one whole period and at most the "
            f"published {cfg.n_layers} layers (period {cfg.period_len})")
    if n_layers < 1:
        raise KeyError(f"{spelling!r}: depth must be at least 1")
    return dataclasses.replace(cfg, n_layers=n_layers)
