"""Flagship model families (reference parity: torchvision ResNet-18/50 and
HF GPT-2 125M — SURVEY.md §2.7 [reconstructed]).

TPU-first: NHWC layouts (XLA's native conv layout on TPU), bf16 compute with
fp32 params/reductions via a dtype policy, static shapes, and module trees
whose parameter paths match the sharding-rule engine in
``pytorch_distributed_tpu.parallel``.
"""

from pytorch_distributed_tpu.models.resnet import (
    ResNet,
    resnet18,
    resnet34,
    resnet50,
    resnet101,
)
from pytorch_distributed_tpu.models.gpt2 import GPT2, GPT2Config, gpt2_125m
from pytorch_distributed_tpu.models.xing4 import Xing4, Xing4Config
from pytorch_distributed_tpu.models.exaone_moe import ExaoneMoE, ExaoneMoEConfig
from pytorch_distributed_tpu.models.kimi_linear import KimiLinear, KimiLinearConfig

__all__ = [
    "ResNet",
    "resnet18",
    "resnet34",
    "resnet50",
    "resnet101",
    "GPT2",
    "GPT2Config",
    "gpt2_125m",
    "Xing4",
    "Xing4Config",
    "ExaoneMoE",
    "ExaoneMoEConfig",
    "KimiLinear",
    "KimiLinearConfig",
]
