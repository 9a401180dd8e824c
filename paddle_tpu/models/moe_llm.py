"""MoE decoder LM — DeepSeekMoE / Qwen2-MoE shape.

Reference parity: the reference's MoE stack is ``incubate.distributed.
models.moe.MoELayer`` (moe_layer.py:261) + global_scatter/global_gather
all-to-all; BASELINE.md lists DeepSeekMoE / Qwen2-MoE as target configs.

Architecture (both families share it): Llama-style attention + RMSNorm
blocks where the dense SwiGLU MLP is replaced by a routed expert bank
(fine-grained experts, top-k routing) PLUS always-on shared experts
(DeepSeekMoE §3 / Qwen2-MoE): out = shared_mlp(x) + moe(x).  Expert
parallelism comes from the ``ep`` axis in the expert-stacked weights
(distributed/moe.py); aux load-balance losses accumulate on the model.

**Training only.**  ``MoEDecoderLayer.forward(x, rope_cos, rope_sin)`` and
``MoEForCausalLM.forward(input_ids)`` take no cache, so the serving engine
cannot run them, and their routed experts are ``ExpertFFN``'s two biased
matrices under a capacity bound (``MoEConfig.capacity_factor``: a token
over capacity is dropped, which a served token may never be).  The served
expert layer is ``distributed.moe.GatedExpertLayer`` (gated, bias-free,
dropless, told which experts it holds), used by ``models/hybrid.py``; it
is the only gated routed-expert implementation, and the shared expert
here is ``LlamaMLP``.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax.numpy as jnp

from paddle_tpu.distributed.moe import MoELayer, ExpertFFN
from paddle_tpu.models.llama import (LlamaAttention, LlamaConfig, LlamaMLP)
from paddle_tpu.nn import functional as F
from paddle_tpu.nn.common_layers import Embedding, Linear
from paddle_tpu.nn.layer import Layer
from paddle_tpu.nn.norm_layers import RMSNorm
from paddle_tpu.ops import manipulation as M

__all__ = ["MoEConfig", "MoEDecoderLayer", "MoEModel", "MoEForCausalLM"]


@dataclasses.dataclass
class MoEConfig:
    vocab_size: int = 32000
    hidden_size: int = 2048
    intermediate_size: int = 5632        # dense/shared-expert MLP width
    moe_intermediate_size: int = 1408    # per routed expert width
    num_hidden_layers: int = 24
    num_attention_heads: int = 16
    num_key_value_heads: Optional[int] = None
    num_experts: int = 64
    num_experts_per_tok: int = 6
    num_shared_experts: int = 2
    first_k_dense_replace: int = 1       # leading dense layers (DeepSeek)
    capacity_factor: float = 1.25        # training only: tokens over an
    # expert's capacity are dropped (GShard); the served layer has none
    aux_loss_alpha: float = 0.001
    max_position_embeddings: int = 4096
    rms_norm_eps: float = 1e-5
    rope_theta: float = 10000.0
    # "einsum" (GSPMD lowers to a2a under ep sharding), "index"
    # (gather/scatter fast path for single-program / dp-only runs),
    # "ragged" (dropless sort + lax.ragged_dot grouped matmul, zero
    # padding — single-program), "all_to_all"/"all_to_all_index"
    # (explicit shard_map exchange over mesh's ep axis; _index builds the
    # send buffers with the O(T·k·d) scatter instead of the one-hot einsum)
    dispatch_mode: str = "einsum"
    mesh: object = None                  # required by the all_to_all modes
    dtype: str = "float32"

    def __post_init__(self):
        if self.num_key_value_heads is None:
            self.num_key_value_heads = self.num_attention_heads

    def as_llama(self) -> LlamaConfig:
        return LlamaConfig(
            vocab_size=self.vocab_size, hidden_size=self.hidden_size,
            intermediate_size=self.intermediate_size,
            num_hidden_layers=self.num_hidden_layers,
            num_attention_heads=self.num_attention_heads,
            num_key_value_heads=self.num_key_value_heads,
            max_position_embeddings=self.max_position_embeddings,
            rms_norm_eps=self.rms_norm_eps, rope_theta=self.rope_theta,
            dtype=self.dtype)

    @property
    def head_dim(self):
        return self.hidden_size // self.num_attention_heads

    @staticmethod
    def qwen2_moe_a2_7b():
        return MoEConfig(vocab_size=151936, hidden_size=2048,
                         intermediate_size=5632, moe_intermediate_size=1408,
                         num_hidden_layers=24, num_attention_heads=16,
                         num_experts=60, num_experts_per_tok=4,
                         num_shared_experts=4, first_k_dense_replace=0,
                         dtype="bfloat16")

    @staticmethod
    def deepseek_moe_16b():
        return MoEConfig(vocab_size=102400, hidden_size=2048,
                         intermediate_size=10944, moe_intermediate_size=1408,
                         num_hidden_layers=28, num_attention_heads=16,
                         num_experts=64, num_experts_per_tok=6,
                         num_shared_experts=2, first_k_dense_replace=1,
                         dtype="bfloat16")

    @staticmethod
    def tiny(**over):
        cfg = dict(vocab_size=256, hidden_size=64, intermediate_size=128,
                   moe_intermediate_size=64, num_hidden_layers=2,
                   num_attention_heads=4, num_key_value_heads=2,
                   num_experts=4, num_experts_per_tok=2,
                   num_shared_experts=1, first_k_dense_replace=1,
                   max_position_embeddings=128, capacity_factor=2.0)
        cfg.update(over)
        return MoEConfig(**cfg)


class _SharedMLP(LlamaMLP):
    """Always-on shared expert(s): one SwiGLU of width
    num_shared_experts * moe_intermediate_size (DeepSeekMoE shared-expert
    isolation)."""

    def __init__(self, config: MoEConfig):
        shared = config.as_llama()
        shared.intermediate_size = (config.num_shared_experts
                                    * config.moe_intermediate_size)
        super().__init__(shared)


class MoEDecoderLayer(Layer):
    def __init__(self, config: MoEConfig, dense: bool = False):
        super().__init__(dtype=config.dtype)
        lc = config.as_llama()
        self.input_layernorm = RMSNorm(config.hidden_size,
                                       epsilon=config.rms_norm_eps)
        self.self_attn = LlamaAttention(lc)
        self.post_attention_layernorm = RMSNorm(config.hidden_size,
                                                epsilon=config.rms_norm_eps)
        self.is_dense = dense
        if dense:
            self.mlp = LlamaMLP(lc)
        else:
            self.shared_mlp = _SharedMLP(config)
            self.moe = MoELayer(
                d_model=config.hidden_size,
                num_experts=config.num_experts,
                d_hidden=config.moe_intermediate_size,
                gate="naive", top_k=config.num_experts_per_tok,
                capacity_factor=config.capacity_factor,
                dispatch_mode=config.dispatch_mode, mesh=config.mesh)

    def forward(self, x, rope_cos, rope_sin):
        x = x + self.self_attn(self.input_layernorm(x), rope_cos, rope_sin)
        h = self.post_attention_layernorm(x)
        if self.is_dense:
            return x + self.mlp(h)
        return x + self.shared_mlp(h) + self.moe(h)


class MoEModel(Layer):
    def __init__(self, config: MoEConfig):
        super().__init__(dtype=config.dtype)
        self.config = config
        self.embed_tokens = Embedding(config.vocab_size, config.hidden_size)
        self.layers = []
        for i in range(config.num_hidden_layers):
            layer = MoEDecoderLayer(config,
                                    dense=i < config.first_k_dense_replace)
            self.add_sublayer(f"layers_{i}", layer)
            self.layers.append(layer)
        self.norm = RMSNorm(config.hidden_size, epsilon=config.rms_norm_eps)
        cos, sin = F.rotary_freqs(config.head_dim,
                                  config.max_position_embeddings,
                                  base=config.rope_theta)
        self.register_buffer("rope_cos", cos, persistable=False)
        self.register_buffer("rope_sin", sin, persistable=False)
        if config.dtype != "float32":
            self.astype(config.dtype)
            self.rope_cos._set_data(cos)
            self.rope_sin._set_data(sin)

    def forward(self, input_ids):
        x = self.embed_tokens(input_ids)
        for layer in self.layers:
            x = layer(x, self.rope_cos, self.rope_sin)
        return self.norm(x)

    def aux_loss(self):
        """Sum of the last forward's per-layer load-balance losses."""
        total = None
        for layer in self.layers:
            if not layer.is_dense and layer.moe.aux_loss is not None:
                total = layer.moe.aux_loss if total is None \
                    else total + layer.moe.aux_loss
        return total


class MoEForCausalLM(Layer):
    def __init__(self, config: MoEConfig):
        super().__init__(dtype=config.dtype)
        self.config = config
        self.model = MoEModel(config)
        self.lm_head = Linear(config.hidden_size, config.vocab_size,
                              bias_attr=False)

    def forward(self, input_ids):
        return self.lm_head(self.model(input_ids))

    def loss(self, input_ids, labels):
        """Fused lm-head CE walked a chunk of rows at a time (the [T, V]
        fp32 logits are never materialized — same objective path as
        Llama) + alpha *
        load-balance aux (reference: gate loss added in moe/utils)."""
        h = self.model(input_ids)
        d = h.shape[-1]
        ce = F.fused_linear_cross_entropy(
            M.reshape(h, [-1, d]), self.lm_head.weight,
            M.reshape(labels, [-1]))
        aux = self.model.aux_loss()
        if aux is not None:
            from paddle_tpu.core.dispatch import unwrap, wrap_like
            ce_raw = unwrap(ce) + self.config.aux_loss_alpha * unwrap(aux)
            return wrap_like(ce_raw) if hasattr(ce, "_data") else ce_raw
        return ce

    @staticmethod
    def partition_specs(config, dp_axis="dp", tp_axis="tp", fsdp_axis=None,
                        ep_axis="ep"):
        """Llama rules for attention/shared MLP + expert-stacked weights on
        the ep axis (GSPMD turns the dispatch einsum into the reference's
        global_scatter all_to_all)."""
        from jax.sharding import PartitionSpec as P
        from paddle_tpu.models.llama import LlamaForCausalLM
        rules = LlamaForCausalLM.partition_specs(
            config, dp_axis=dp_axis, tp_axis=tp_axis, fsdp_axis=fsdp_axis)
        rules.update({
            ".moe.experts.w1": P(ep_axis, fsdp_axis, tp_axis),
            ".moe.experts.w2": P(ep_axis, tp_axis, fsdp_axis),
            ".moe.experts.b1": P(ep_axis, tp_axis),
            ".moe.experts.b2": P(ep_axis, None),
            ".moe.gate.gate": P(),
        })
        return rules

    @staticmethod
    def spec_for(name, rules):
        from paddle_tpu.models.llama import LlamaForCausalLM
        return LlamaForCausalLM.spec_for(name, rules)
