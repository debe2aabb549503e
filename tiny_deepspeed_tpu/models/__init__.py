# Copyright 2026 tiny-deepspeed-tpu authors
# SPDX-License-Identifier: Apache-2.0

"""Model zoo: GPT-2 family (parity with reference example/model.py) plus the
MoE, Llama, EvaByte and MiMo families (beyond the reference, SURVEY §2.20) — all
built on the same op layer, stacked-block scan, and engine surface."""

from .gpt2 import GPTConfig, GPT2Model, GPT2_PRESETS
from .moe import MoEConfig, MoEGPT, MOE_PRESETS
from .llama import LlamaConfig, LlamaModel, LLAMA_PRESETS
from .evabyte import EvaByteConfig, EvaByteModel, EVABYTE_PRESETS
from .mimo import MiMoConfig, MiMoModel, MIMO_PRESETS

# one flat preset namespace across families (tiny / gpt2-* / llama-* /
# moe-* / evabyte-* / mimo-*)
ALL_PRESETS = {**GPT2_PRESETS, **LLAMA_PRESETS, **MOE_PRESETS,
               **EVABYTE_PRESETS, **MIMO_PRESETS}


def build_model(name_or_cfg):
    """Model instance from a preset name or config; the family is inferred
    from the config type (single construction point for every entry
    surface: examples, bench, generate)."""
    cfg = (ALL_PRESETS[name_or_cfg] if isinstance(name_or_cfg, str)
           else name_or_cfg)
    if isinstance(cfg, MiMoConfig):
        return MiMoModel(cfg)
    if isinstance(cfg, EvaByteConfig):  # a LlamaConfig too: asked first
        return EvaByteModel(cfg)
    if isinstance(cfg, LlamaConfig):
        return LlamaModel(cfg)
    if isinstance(cfg, MoEConfig):
        return MoEGPT(cfg)
    return GPT2Model(cfg)


__all__ = [
    "GPTConfig", "GPT2Model", "GPT2_PRESETS",
    "MoEConfig", "MoEGPT", "MOE_PRESETS",
    "LlamaConfig", "LlamaModel", "LLAMA_PRESETS",
    "EvaByteConfig", "EvaByteModel", "EVABYTE_PRESETS",
    "MiMoConfig", "MiMoModel", "MIMO_PRESETS",
    "ALL_PRESETS", "build_model",
]
