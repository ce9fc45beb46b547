"""Model zoo.  The workload families the reference trains with its collectives
(SURVEY §2.4: VGG16 DDP, ViT, GPT-2, MoE, elastic ResNet image
classification: ``vgg``, ``vit``, ``gpt2`` with ``gpt2_generate``, ``moe``,
``resnet``, and the toy ``mlp``), and the decoder blocks of five published
language models, each written from its ``config.json``: ``trinity``
(Trinity-Mini), ``kimi_linear`` (Kimi-Linear), ``joyai_flash``
(JoyAI-LLM-Flash), ``granite_hybrid`` (Granite 4.0-H), ``phi4_flash``
(Phi-4-mini-flash-reasoning), over what they share in ``lm``.  All are flax
modules shaped for TPU execution: bf16 matmuls on the MXU, static shapes,
remat-friendly blocks."""

from adapcc_tpu.models.mlp import MLP
from adapcc_tpu.models.gpt2 import GPT2, GPT2Config
from adapcc_tpu.models.resnet import ResNet, ResNet18, ResNet34, ResNet50

__all__ = ["MLP", "GPT2", "GPT2Config", "ResNet", "ResNet18", "ResNet34", "ResNet50"]
