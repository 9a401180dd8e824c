"""ERNIE family — Baidu's native Paddle models, both generations.

Reference substrate: ERNIE is the model family the reference frames its
fused stacks around — ``fused_multi_transformer_op.cu`` (the stacked
fused encoder the ERNIE 3.0 serving path runs on) and the fleet MoE stack
for ERNIE 4.5.  Two sub-families matter to a Paddle user:

* **ErnieModel / ErnieForSequenceClassification / ErnieForMaskedLM** —
  the ERNIE 3.0-style bidirectional encoder (the NLU workhorse:
  ernie-3.0-medium-zh etc.).  Post-LayerNorm transformer encoder with
  learned position + token-type embeddings and a tanh pooler — the same
  topology the reference fuses into fused_multi_transformer.  TPU-native:
  the stack is plain Layers; XLA fuses the (QKV matmul → bias → softmax →
  context) chain the CUDA op fuses by hand.
* **ErnieForCausalLM** — the ERNIE 4.5-style decoder: heterogeneous MoE
  (shared + fine-grained routed experts, GQA, RoPE, RMSNorm, SwiGLU),
  structurally the MoEModel stack with ERNIE 4.5's public shape numbers
  (21B-A3B: 28 layers, d=2560, 20q/4kv heads, 64 experts top-6 + 2
  shared).  Expert parallelism, aux losses, and sharding rules come from
  the shared MoE substrate (distributed/moe.py).
"""

from __future__ import annotations

import dataclasses
from typing import Optional

from paddle_tpu.models.moe_llm import MoEConfig, MoEForCausalLM
from paddle_tpu.nn import functional as F
from paddle_tpu.nn.common_layers import Dropout, Embedding, Linear
from paddle_tpu.nn.layer import Layer
from paddle_tpu.nn.norm_layers import LayerNorm
from paddle_tpu.ops import creation as C
from paddle_tpu.ops import manipulation as M

__all__ = ["ErnieConfig", "ErnieModel", "ErnieForSequenceClassification",
           "ErnieForMaskedLM", "ErnieForCausalLM", "ernie45_moe_config"]


@dataclasses.dataclass
class ErnieConfig:
    """ERNIE 3.0 encoder shape (ernie-3.0-medium-zh defaults)."""
    vocab_size: int = 40000
    hidden_size: int = 768
    num_hidden_layers: int = 6
    num_attention_heads: int = 12
    intermediate_size: int = 3072
    hidden_act: str = "gelu"
    hidden_dropout_prob: float = 0.1
    attention_probs_dropout_prob: float = 0.1
    max_position_embeddings: int = 2048
    type_vocab_size: int = 4
    layer_norm_eps: float = 1e-12
    pad_token_id: int = 0
    initializer_range: float = 0.02   # reference init_weights normal std
    dtype: str = "float32"

    @staticmethod
    def tiny(**over):
        cfg = dict(vocab_size=128, hidden_size=32, num_hidden_layers=2,
                   num_attention_heads=4, intermediate_size=64,
                   max_position_embeddings=64, type_vocab_size=2,
                   hidden_dropout_prob=0.0, attention_probs_dropout_prob=0.0)
        cfg.update(over)
        return ErnieConfig(**cfg)


class _ErnieSelfAttention(Layer):
    def __init__(self, c: ErnieConfig):
        super().__init__(dtype=c.dtype)
        self.num_heads = c.num_attention_heads
        self.head_dim = c.hidden_size // c.num_attention_heads
        self.qkv = Linear(c.hidden_size, 3 * c.hidden_size)
        self.out = Linear(c.hidden_size, c.hidden_size)
        self.dropout = Dropout(c.attention_probs_dropout_prob)

    def forward(self, x, attn_mask=None):
        b, s = x.shape[0], x.shape[1]
        qkv = M.reshape(self.qkv(x), [b, s, 3, self.num_heads,
                                      self.head_dim])
        q, k, v = M.unbind(qkv, axis=2)                 # [b,s,h,d] each
        out = F.scaled_dot_product_attention(
            q, k, v, attn_mask=attn_mask, is_causal=False)
        return self.out(M.reshape(out, [b, s, -1]))


class _ErnieEncoderLayer(Layer):
    """Post-LN encoder block — the topology fused_multi_transformer_op.cu
    executes as one fused kernel chain per layer."""

    def __init__(self, c: ErnieConfig):
        super().__init__(dtype=c.dtype)
        self.self_attn = _ErnieSelfAttention(c)
        self.norm1 = LayerNorm(c.hidden_size, epsilon=c.layer_norm_eps)
        self.fc1 = Linear(c.hidden_size, c.intermediate_size)
        self.fc2 = Linear(c.intermediate_size, c.hidden_size)
        self.norm2 = LayerNorm(c.hidden_size, epsilon=c.layer_norm_eps)
        self.dropout = Dropout(c.hidden_dropout_prob)
        self.act = getattr(F, c.hidden_act)

    def forward(self, x, attn_mask=None):
        x = self.norm1(x + self.dropout(self.self_attn(x, attn_mask)))
        return self.norm2(x + self.dropout(self.fc2(self.act(self.fc1(x)))))


class ErnieModel(Layer):
    """ERNIE 3.0 encoder with pooler (reference user API:
    paddlenlp.transformers.ErnieModel over the fused stack)."""

    def __init__(self, config: ErnieConfig):
        super().__init__(dtype=config.dtype)
        c = self.config = config
        self.word_embeddings = Embedding(c.vocab_size, c.hidden_size)
        self.position_embeddings = Embedding(c.max_position_embeddings,
                                             c.hidden_size)
        self.token_type_embeddings = Embedding(c.type_vocab_size,
                                               c.hidden_size)
        self.embed_norm = LayerNorm(c.hidden_size, epsilon=c.layer_norm_eps)
        self.embed_dropout = Dropout(c.hidden_dropout_prob)
        # reference init_weights: every embedding table is
        # Normal(0, initializer_range).  nn.Embedding's paddle-parity
        # default is N(0, 1) (drawn from the seeded stream) — scale it,
        # keeping seed-reproducibility, or tied-embedding MLM logits run
        # ~1/initializer_range too hot at init
        for emb in (self.word_embeddings, self.position_embeddings,
                    self.token_type_embeddings):
            emb.weight._set_data(emb.weight._data * c.initializer_range)
        self.layers = []
        for i in range(c.num_hidden_layers):
            layer = _ErnieEncoderLayer(c)
            self.add_sublayer(f"layers_{i}", layer)
            self.layers.append(layer)
        self.pooler = Linear(c.hidden_size, c.hidden_size)

    def forward(self, input_ids, token_type_ids=None, attn_mask=None):
        s = input_ids.shape[1]
        pos = C.arange(s, dtype="int64")
        x = self.word_embeddings(input_ids) \
            + self.position_embeddings(pos)
        if token_type_ids is None:
            token_type_ids = input_ids * 0
        x = x + self.token_type_embeddings(token_type_ids)
        x = self.embed_dropout(self.embed_norm(x))
        for layer in self.layers:
            x = layer(x, attn_mask)
        pooled = F.tanh(self.pooler(x[:, 0]))
        return x, pooled


class ErnieForSequenceClassification(Layer):
    def __init__(self, config: ErnieConfig, num_classes: int = 2):
        super().__init__(dtype=config.dtype)
        self.ernie = ErnieModel(config)
        self.dropout = Dropout(config.hidden_dropout_prob)
        self.classifier = Linear(config.hidden_size, num_classes)

    def forward(self, input_ids, token_type_ids=None, attn_mask=None):
        _, pooled = self.ernie(input_ids, token_type_ids, attn_mask)
        return self.classifier(self.dropout(pooled))

    def loss(self, input_ids, labels, token_type_ids=None):
        return F.cross_entropy(self(input_ids, token_type_ids), labels)


class ErnieForMaskedLM(Layer):
    """Pretraining head: tied-embedding masked-LM logits (ERNIE's
    knowledge-masking pretraining objective runs on this head)."""

    def __init__(self, config: ErnieConfig):
        super().__init__(dtype=config.dtype)
        self.ernie = ErnieModel(config)
        self.transform = Linear(config.hidden_size, config.hidden_size)
        self.norm = LayerNorm(config.hidden_size,
                              epsilon=config.layer_norm_eps)

    def _features(self, input_ids, token_type_ids=None, attn_mask=None):
        """Encoder + MLM head transform — the single home forward and
        loss share (the head feeds either the tied-logits matmul or the
        fused CE)."""
        h, _ = self.ernie(input_ids, token_type_ids, attn_mask)
        return self.norm(F.gelu(self.transform(h)))

    def forward(self, input_ids, token_type_ids=None, attn_mask=None):
        from paddle_tpu.ops import linalg as L
        h = self._features(input_ids, token_type_ids, attn_mask)
        return L.matmul(h, self.ernie.word_embeddings.weight,
                        transpose_y=True)

    def loss(self, input_ids, labels, ignore_index: int = -100):
        """Masked-token CE via the fused lm-head+CE, walked a chunk of
        rows at a time — the [T, V] fp32 logits are never materialized
        (same memory trick as the Llama objective; positions with
        label==ignore_index, the unmasked 85%, contribute neither loss
        nor gradient)."""
        h = self._features(input_ids)
        d = h.shape[-1]
        return F.fused_linear_cross_entropy(
            M.reshape(h, [-1, d]),
            self.ernie.word_embeddings.weight.t(),
            M.reshape(labels, [-1]), ignore_index=ignore_index)


# -- ERNIE 4.5: heterogeneous-MoE decoder -------------------------------------

def ernie45_moe_config(**over) -> MoEConfig:
    """ERNIE-4.5-21B-A3B public shape: 28 layers, d=2560, 20 q heads /
    4 kv heads, 64 routed experts top-6 + 2 shared, expert ffn 1536."""
    cfg = dict(vocab_size=103424, hidden_size=2560,
               intermediate_size=12288, moe_intermediate_size=1536,
               num_hidden_layers=28, num_attention_heads=20,
               num_key_value_heads=4, num_experts=64,
               num_experts_per_tok=6, num_shared_experts=2,
               first_k_dense_replace=1, max_position_embeddings=131072,
               rope_theta=500000.0, dtype="bfloat16")
    cfg.update(over)
    return MoEConfig(**cfg)


class ErnieForCausalLM(MoEForCausalLM):
    """ERNIE 4.5 text decoder = the shared heterogeneous-MoE substrate
    with ERNIE's shape.  Train step, expert parallelism (ep axis), aux
    load-balance loss, and GSPMD rules are inherited — the reference
    reaches the same reuse through incubate.distributed.models.moe."""

    def __init__(self, config: Optional[MoEConfig] = None, **over):
        super().__init__(config or ernie45_moe_config(**over))
