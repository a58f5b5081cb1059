"""Architecture registry: 10 assigned archs, full + reduced (smoke) configs.

get_config(name, sparse=True)  -> full-size ModelConfig at published widths
get_reduced(name)              -> CPU-runnable reduced config, same family
                                  structure (pattern, mixers, MoE, enc-dec)
cut_layers(cfg, n)             -> cfg keeping its first n decoder layers,
                                  every width unchanged
"""
from __future__ import annotations

import dataclasses
import importlib

from repro.configs.base import (  # noqa: F401
    SHAPES,
    AttnConfig,
    Block,
    BottleneckStage,
    CNNConfig,
    ConvSpec,
    DenseStage,
    FFNConfig,
    MambaConfig,
    ModelConfig,
    MoEConfig,
    RWKVConfig,
    ShapeConfig,
    SparsityConfig,
)

ARCHS: tuple[str, ...] = (
    "chameleon-34b",
    "codeqwen1.5-7b",
    "internlm2-20b",
    "yi-9b",
    "gemma3-27b",
    "rwkv6-3b",
    "whisper-medium",
    "deepseek-v2-236b",
    "deepseek-v2-lite-16b",
    "jamba-v0.1-52b",
)

_MODULES = {
    "chameleon-34b": "chameleon_34b",
    "codeqwen1.5-7b": "codeqwen1_5_7b",
    "internlm2-20b": "internlm2_20b",
    "yi-9b": "yi_9b",
    "gemma3-27b": "gemma3_27b",
    "rwkv6-3b": "rwkv6_3b",
    "whisper-medium": "whisper_medium",
    "deepseek-v2-236b": "deepseek_v2_236b",
    "deepseek-v2-lite-16b": "deepseek_v2_lite_16b",
    "jamba-v0.1-52b": "jamba_v0_1_52b",
}

# shapes each arch skips, with the reason recorded in DESIGN.md §6
SHAPE_SKIPS: dict[str, dict[str, str]] = {
    name: {"long_500k": "full attention is quadratic at 524k prefill; "
                        "no sub-quadratic path"}
    for name in ARCHS
    if name not in ("rwkv6-3b", "jamba-v0.1-52b")
}
SHAPE_SKIPS.setdefault("rwkv6-3b", {})
SHAPE_SKIPS.setdefault("jamba-v0.1-52b", {})


def _mod(name: str):
    return importlib.import_module(f"repro.configs.{_MODULES[name]}")


def get_config(name: str, sparse: bool = True) -> ModelConfig:
    return _mod(name).config(sparse=sparse)


def get_reduced(name: str, sparse: bool = True) -> ModelConfig:
    return _mod(name).reduced(sparse=sparse)


def cut_layers(cfg: ModelConfig, n: int) -> ModelConfig:
    """``cfg`` cut to its first ``n`` decoder layers (whole repeating
    periods), every width as published — how a full config is sized to
    one chip."""
    plan, left = [], n
    for entry, rep in cfg.plan:
        period = len(entry) if isinstance(entry, tuple) else 1
        take = min(rep, left // period)
        if take:
            plan.append((entry, take))
            left -= take * period
    if left or not plan:
        raise ValueError(
            f"{cfg.name}: cannot cut to {n} layers in whole periods of "
            f"its plan")
    return dataclasses.replace(cfg, plan=tuple(plan))


def runnable_shapes(name: str) -> list[str]:
    return [s for s in SHAPES if s not in SHAPE_SKIPS.get(name, {})]


DEFAULT_SPARSITY = SparsityConfig()  # 2:4 compressed, targets ffn/attn_proj/expert


def sparsity_or_none(sparse: bool) -> SparsityConfig | None:
    return DEFAULT_SPARSITY if sparse else None


# ---------------------------------------------------------------------------
# CNN registry (the paper's evaluation workload: conv layers -> im2col GEMMs)
# ---------------------------------------------------------------------------

CNN_ARCHS: tuple[str, ...] = ("resnet50", "densenet121")

# every conv family is sparsified (the paper prunes all conv layers);
# the stem stays dense — its K = 3*kh*kw contraction is not M-divisible.
DEFAULT_CNN_SPARSITY = SparsityConfig(targets=("conv", "proj"))


def cnn_sparsity_or_none(sparse: bool) -> SparsityConfig | None:
    return DEFAULT_CNN_SPARSITY if sparse else None


def _cnn_mod(name: str):
    if name not in CNN_ARCHS:
        raise KeyError(f"unknown CNN {name!r}; known: {CNN_ARCHS}")
    return importlib.import_module(f"repro.configs.{name}")


def get_cnn_config(name: str, sparse: bool = True) -> CNNConfig:
    return _cnn_mod(name).config(sparse=sparse)


def get_cnn_reduced(name: str, sparse: bool = True) -> CNNConfig:
    return _cnn_mod(name).reduced(sparse=sparse)
