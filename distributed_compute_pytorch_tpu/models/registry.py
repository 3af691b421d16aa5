"""Model registry — maps CLI names to model builders.

The reference has exactly one hard-wired model (``main.py:20-45``); the
framework's ladder (BASELINE.md configs 0-4) needs a zoo.
"""

from __future__ import annotations

import dataclasses
from typing import Any


def _transformer_config(cfg_cls, default_cfg, kw: dict):
    """Shared preset + override plumbing for the transformer configs."""
    preset = kw.pop("preset", None)
    if preset in (None, "full", "base", "small"):
        cfg = default_cfg
    elif preset == "tiny":
        cfg = cfg_cls.tiny()
    else:
        raise ValueError(
            f"unknown {cfg_cls.__name__} preset {preset!r}; "
            f"expected 'tiny' or None")
    return dataclasses.replace(cfg, **kw)


def build_model(name: str, **kw: Any):
    if name == "convnet":
        from distributed_compute_pytorch_tpu.models.convnet import ConvNet
        return ConvNet(**kw)
    if name in ("resnet18", "resnet50"):
        from distributed_compute_pytorch_tpu.models.resnet import ResNet
        return ResNet.build(name, **kw)
    if name == "bert":
        from distributed_compute_pytorch_tpu.models.bert import BertMLM, BertConfig
        return BertMLM(_transformer_config(BertConfig, BertConfig(), kw))
    if name == "gpt2":
        from distributed_compute_pytorch_tpu.models.gpt2 import GPT2, GPT2Config
        return GPT2(_transformer_config(GPT2Config, GPT2Config.small(), kw))
    if name == "moe":
        from distributed_compute_pytorch_tpu.models.moe import (
            MoETransformerConfig, MoETransformerLM)
        return MoETransformerLM(_transformer_config(
            MoETransformerConfig, MoETransformerConfig(), kw))
    if name == "llama":
        from distributed_compute_pytorch_tpu.models.llama import (
            LlamaConfig, LlamaLM)
        return LlamaLM(_transformer_config(LlamaConfig, LlamaConfig(), kw))
    if name == "hybrid":
        from distributed_compute_pytorch_tpu.models.hybrid import (
            HybridConfig, HybridLM)
        return HybridLM(_transformer_config(HybridConfig, HybridConfig(),
                                            kw))
    raise ValueError(f"unknown model {name!r}")
