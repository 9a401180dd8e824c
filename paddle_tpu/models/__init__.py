"""paddle_tpu.models — flagship model zoo.

The reference ships torchvision-style models under python/paddle/vision/models
and LLM recipes live out-of-tree (PaddleNLP); here the LLM family is in-tree
because it is the benchmark flagship (BASELINE.md: Llama-3-8B pretraining).
"""

from paddle_tpu.models.llama import (LlamaAttention, LlamaConfig,
                                     LlamaDecoderLayer, LlamaForCausalLM,
                                     LlamaMLP, LlamaModel)
from paddle_tpu.models.gpt import (GPTConfig, GPTDecoderLayer, GPTForCausalLM,
                                   GPTModel)
from paddle_tpu.models.moe_llm import (MoEConfig, MoEDecoderLayer,
                                       MoEForCausalLM, MoEModel)
from paddle_tpu.models.hybrid import (HybridConfig, HybridDecoderLayer,
                                      HybridForCausalLM, HybridModel,
                                      KDAMixer, Mamba2Mixer)
from paddle_tpu.models.dit import DiT, DiTBlock, DiTConfig
from paddle_tpu.models.ernie import (ErnieConfig, ErnieForCausalLM,
                                     ErnieForMaskedLM,
                                     ErnieForSequenceClassification,
                                     ErnieModel, ernie45_moe_config)

__all__ = ["LlamaConfig", "LlamaAttention", "LlamaMLP", "LlamaDecoderLayer",
           "LlamaModel", "LlamaForCausalLM",
           "GPTConfig", "GPTDecoderLayer", "GPTModel", "GPTForCausalLM",
           "MoEConfig", "MoEDecoderLayer", "MoEModel", "MoEForCausalLM",
           "HybridConfig", "Mamba2Mixer", "KDAMixer", "HybridDecoderLayer",
           "HybridModel", "HybridForCausalLM",
           "DiTConfig", "DiTBlock", "DiT",
           "ErnieConfig", "ErnieModel", "ErnieForSequenceClassification",
           "ErnieForMaskedLM", "ErnieForCausalLM", "ernie45_moe_config"]
