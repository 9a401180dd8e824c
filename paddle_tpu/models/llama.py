"""Llama-family decoder-only transformer — the flagship pretraining model.

The reference has no in-tree Llama; its LLM recipe is the fleet 4-D hybrid
stack applied to transformer blocks (SURVEY.md §3.3) built from
ColumnParallelLinear / RowParallelLinear (fleet/layers/mpu/mp_layers.py:173,343)
and fused attention ops.  Here the model is a plain nn.Layer stack whose
parallelism comes from GSPMD sharding annotations (`partition_specs`), not
parallel-layer classes: under pjit, XLA inserts the same collectives the
reference issues by hand (mp_allreduce after row-parallel matmul, etc.).

TPU-native choices:
  * [batch, seq, heads, head_dim] layout; QKV as single wide matmuls (MXU).
  * fp32 RoPE + fp32 softmax accumulation inside bf16 training.
  * GQA via jnp broadcast-repeat of KV heads (free under XLA fusion).
  * weights stay [in, out] so tp sharding is a PartitionSpec on one axis.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from paddle_tpu.nn import functional as F
from paddle_tpu.nn.common_layers import Embedding, Linear
from paddle_tpu.nn.layer import Layer
from paddle_tpu.nn.norm_layers import RMSNorm
from paddle_tpu.ops import manipulation as M

__all__ = ["LlamaConfig", "LlamaAttention", "LlamaMLP", "LlamaDecoderLayer",
           "LlamaModel", "LlamaForCausalLM"]


@dataclasses.dataclass
class LlamaConfig:
    vocab_size: int = 32000
    hidden_size: int = 4096
    intermediate_size: int = 11008
    num_hidden_layers: int = 32
    num_attention_heads: int = 32
    num_key_value_heads: Optional[int] = None  # None → MHA
    max_position_embeddings: int = 4096
    rms_norm_eps: float = 1e-5
    rope_theta: float = 10000.0
    tie_word_embeddings: bool = False
    # sparse_embed=True gives the embedding a SelectedRows-style
    # RowSparseGrad in EAGER training (rows-touched optimizer update, no
    # dense [vocab, d] grad — core/sparse_grad.py); the jitted TrainStep
    # path keeps dense grads (XLA fuses its scatter-add)
    sparse_embed: bool = False
    dtype: str = "float32"
    # a config may state its head size (q_proj is then heads x head_dim
    # wide whatever hidden_size is); None: hidden_size / heads
    head_dim: Optional[int] = None

    def __post_init__(self):
        if self.num_key_value_heads is None:
            self.num_key_value_heads = self.num_attention_heads
        if self.head_dim is None:
            self.head_dim = self.hidden_size // self.num_attention_heads

    @staticmethod
    def llama3_8b():
        return LlamaConfig(
            vocab_size=128256, hidden_size=4096, intermediate_size=14336,
            num_hidden_layers=32, num_attention_heads=32,
            num_key_value_heads=8, max_position_embeddings=8192,
            rope_theta=500000.0, dtype="bfloat16")

    @staticmethod
    def tiny(**over):
        cfg = dict(vocab_size=256, hidden_size=64, intermediate_size=128,
                   num_hidden_layers=2, num_attention_heads=4,
                   num_key_value_heads=2, max_position_embeddings=128)
        cfg.update(over)
        return LlamaConfig(**cfg)


class LlamaAttention(Layer):
    """Grouped-query attention.  Beside ``LlamaConfig``'s keys a config
    may state ``position_embedding_type`` / ``attention_multiplier``
    (below), ``qk_norm`` (a learned-gain RMSNorm over each query and key
    head, before the rotary turn) and ``attention_gate`` (the output
    times the sigmoid of a fifth projection ``gate_proj``, ``hidden ->
    heads x head_dim``, before ``o_proj``).  A layer may differ from its
    config in two things, given here: ``window`` (key j is seen by query
    t iff ``0 <= t - j < window``; None: everything before it) and
    ``rotary`` (None: the config's)."""

    def __init__(self, config: LlamaConfig, window: Optional[int] = None,
                 rotary: Optional[bool] = None):
        super().__init__(dtype=config.dtype)
        c = config
        self.num_heads = c.num_attention_heads
        self.num_kv_heads = c.num_key_value_heads
        self.head_dim = c.head_dim
        # two things a config may state and LlamaConfig does not: no
        # rotary positions (``position_embedding_type`` "nope") and the
        # score scale (``attention_multiplier``; None: 1 / sqrt(head_dim)).
        # A stated scale is folded into q before the cache, so the paged
        # kernel, the flash kernel and sdpa run unchanged
        self.rotary = getattr(c, "position_embedding_type",
                              "rope") != "nope" if rotary is None \
            else bool(rotary)
        self.window = int(window) if window else None
        scale = getattr(c, "attention_multiplier", None)
        self.q_scale = None if scale is None \
            else float(scale) * self.head_dim ** 0.5
        self.q_proj = Linear(c.hidden_size, self.num_heads * self.head_dim,
                             bias_attr=False)
        self.k_proj = Linear(c.hidden_size, self.num_kv_heads * self.head_dim,
                             bias_attr=False)
        self.v_proj = Linear(c.hidden_size, self.num_kv_heads * self.head_dim,
                             bias_attr=False)
        self.o_proj = Linear(self.num_heads * self.head_dim, c.hidden_size,
                             bias_attr=False)
        self.gate_proj = None
        if getattr(c, "attention_gate", False):
            self.gate_proj = Linear(c.hidden_size,
                                    self.num_heads * self.head_dim,
                                    bias_attr=False)
        self.q_norm = self.k_norm = None
        if getattr(c, "qk_norm", False):
            self.q_norm = RMSNorm(self.head_dim, epsilon=c.rms_norm_eps)
            self.k_norm = RMSNorm(self.head_dim, epsilon=c.rms_norm_eps)

    def forward(self, x, rope_cos, rope_sin, attn_mask=None, cache=None,
                position_offset=0):
        gate = None if self.gate_proj is None else self.gate_proj(x)
        return self.attend(self.q_proj(x), self.k_proj(x), self.v_proj(x),
                           rope_cos, rope_sin, attn_mask, cache,
                           position_offset, gate)

    def _out(self, out, gate, b, s):
        """The heads' outputs, gated where the layer has a gate, through
        ``o_proj``."""
        out = M.reshape(out, [b, s, self.num_heads * self.head_dim])
        if gate is not None:
            out = out * F.sigmoid(gate)
        return self.o_proj(out)

    def attend(self, q, k, v, rope_cos, rope_sin, attn_mask=None,
               cache=None, position_offset=0, gate=None):
        """Everything after the projections (head norms, RoPE, cache,
        sdpa, gate, o_proj) — split out so the decoder layer's fused
        rmsnorm+QKV path can feed projections straight from the Pallas
        kernel."""
        b, s = q.shape[0], q.shape[1]
        q = M.reshape(q, [b, s, self.num_heads, self.head_dim])
        k = M.reshape(k, [b, s, self.num_kv_heads, self.head_dim])
        v = M.reshape(v, [b, s, self.num_kv_heads, self.head_dim])
        if self.q_norm is not None:
            q, k = self.q_norm(q), self.k_norm(k)
        if self.rotary:
            q = F.apply_rotary_emb(q, rope_cos, rope_sin, position_offset)
            k = F.apply_rotary_emb(k, rope_cos, rope_sin, position_offset)
        if self.q_scale is not None:
            q = q * self.q_scale
        new_cache = None
        if cache is not None:
            from paddle_tpu.generation import (StaticCache,
                                               static_cache_attention)
            if self.window is not None and \
                    not hasattr(cache, "block_table"):
                raise NotImplementedError(
                    "a sliding window over a static or a growing cache: "
                    "the paged cache is the one that keeps a window")
            if isinstance(cache, StaticCache):
                # TPU decode path: fixed-size buffers + dynamic_update_slice
                # — one compiled step serves every position (the concat path
                # below grows shapes and recompiles per token)
                out, new_cache = static_cache_attention(
                    q, k, v, cache, position_offset, attn_mask)
                return self._out(out, gate, b, s), new_cache
            from paddle_tpu.inference.kv_cache import (PagedCache,
                                                       paged_cache_attention)
            if isinstance(cache, PagedCache):
                # paged serving path: KV lives in block pools addressed by
                # a per-row block table (prefix blocks shared COW across
                # requests); supports per-row offsets at s > 1, which is
                # what chunked prefill and batched speculative verify need
                out, new_cache = paged_cache_attention(
                    q, k, v, cache, position_offset, attn_mask,
                    window=self.window)
                return self._out(out, gate, b, s), new_cache
            pk, pv = cache
            k = M.concat([pk, k], axis=1)
            v = M.concat([pv, v], axis=1)
            new_cache = (k, v)
        # GQA k/v pass through at kv_heads width — the Pallas flash kernel
        # maps query heads onto kv heads in its grid (no repeat in HBM);
        # the XLA fallback repeats internally.
        # is_causal stays on for cached prefill too: the tril mask in sdpa
        # offsets by sk-sq, so a multi-token query over past KV is causal
        if self.window is not None:
            if attn_mask is not None:
                raise NotImplementedError(
                    "a sliding window under an attn_mask of the caller's")
            t = jnp.arange(s)
            attn_mask = (t[None, :] <= t[:, None]) & \
                (t[None, :] > t[:, None] - self.window)
        out = F.scaled_dot_product_attention(
            q, k, v, attn_mask=attn_mask, is_causal=(attn_mask is None))
        out = self._out(out, gate, b, s)
        if cache is not None:
            return out, new_cache
        return out


def _rows(shape):
    n = 1
    for dim in shape[:-1]:
        n *= int(dim)
    return n


def _fused_norm_qkv(layer, x):
    """(q, k, v) via the fused rmsnorm+QKV Pallas kernel when the
    PADDLE_TPU_FUSED_BLOCK knob and the shapes allow; None → caller
    takes the reference (unfused) path.  The routing decision happens
    at trace time, so PADDLE_TPU_FUSED_BLOCK=0 reproduces the previous
    jaxpr exactly."""
    from paddle_tpu.ops.pallas import fused_block as FB
    attn = layer.self_attn
    d = int(x.shape[-1])
    dq = attn.num_heads * attn.head_dim
    dkv = attn.num_kv_heads * attn.head_dim
    # weight-only quantized projections (quantization.serving) have no
    # fp .weight — the quant matmul kernel owns that path
    quanted = any(getattr(p, "quantized", False)
                  for p in (attn.q_proj, attn.k_proj, attn.v_proj))
    fused = not quanted and FB.fused_block_enabled() and \
        FB.fused_qkv_eligible(_rows(x.shape), d, dq, dkv, dkv, x.dtype)
    FB.record_path("rmsnorm_qkv", fused)
    if not fused:
        return None
    return F.fused_rmsnorm_qkv(
        x, layer.input_layernorm.weight, attn.q_proj.weight,
        attn.k_proj.weight, attn.v_proj.weight,
        epsilon=layer.input_layernorm._epsilon)


def _fused_decoder(layer, x, rope_cos, rope_sin):
    """The whole decoder block through the Pallas megakernel when the
    PADDLE_TPU_FUSED_BLOCK=decoder tier and the shapes allow; None →
    caller takes the per-segment/unfused path.  The routing decision
    happens at trace time, so every other knob value reproduces its
    previous jaxpr exactly.  The ``measured`` tier makes the same
    choice per shape from the measurement ledger: the megakernel routes
    only when it was measured fastest for this (b, s, d) on this
    backend (``FB.measured_tier_for``)."""
    from paddle_tpu.ops.pallas import fused_block as FB
    tier = FB.fused_block_tier()
    if tier not in ("decoder", "measured"):
        return None
    b, s, d = int(x.shape[0]), int(x.shape[1]), int(x.shape[2])
    if tier == "measured" and \
            FB.measured_tier_for((b, s, d), x.dtype) != "decoder":
        return None
    attn, mlp = layer.self_attn, layer.mlp
    projs = (attn.q_proj, attn.k_proj, attn.v_proj, attn.o_proj,
             mlp.gate_proj, mlp.up_proj, mlp.down_proj)
    quanted = any(getattr(p, "quantized", False) for p in projs)
    dq = attn.num_heads * attn.head_dim
    dkv = attn.num_kv_heads * attn.head_dim
    f = None if quanted else int(mlp.gate_proj.weight.shape[-1])
    fused = (not quanted and int(rope_cos.shape[0]) >= s and
             FB.fused_decoder_eligible(b, s, d, dq, dkv, attn.head_dim,
                                       f, x.dtype))
    FB.record_path("decoder_block", fused)
    if not fused:
        return None
    return F.fused_decoder_block(
        x, layer.input_layernorm.weight, attn.q_proj.weight,
        attn.k_proj.weight, attn.v_proj.weight, rope_cos, rope_sin,
        attn.o_proj.weight, layer.post_attention_layernorm.weight,
        mlp.gate_proj.weight, mlp.up_proj.weight, mlp.down_proj.weight,
        num_heads=attn.num_heads, num_kv_heads=attn.num_kv_heads,
        epsilon=layer.input_layernorm._epsilon)


class LlamaMLP(Layer):
    """SwiGLU: down(silu(gate(x)) * up(x)) — routed through the fused
    Pallas MLP kernel (hidden intermediate VMEM-resident) behind
    PADDLE_TPU_FUSED_BLOCK; reference matmul chain otherwise."""

    def __init__(self, config: LlamaConfig):
        super().__init__(dtype=config.dtype)
        c = config
        self.gate_proj = Linear(c.hidden_size, c.intermediate_size,
                                bias_attr=False)
        self.up_proj = Linear(c.hidden_size, c.intermediate_size,
                              bias_attr=False)
        self.down_proj = Linear(c.intermediate_size, c.hidden_size,
                                bias_attr=False)

    def forward(self, x):
        from paddle_tpu.ops.pallas import fused_block as FB
        d = int(x.shape[-1])
        quanted = any(getattr(p, "quantized", False)
                      for p in (self.gate_proj, self.up_proj,
                                self.down_proj))
        f = int(self.gate_proj.qweight.shape[-1]) if quanted \
            else int(self.gate_proj.weight.shape[-1])
        fused = not quanted and FB.fused_block_enabled() and \
            FB.fused_mlp_eligible(_rows(x.shape), d, f, x.dtype)
        FB.record_path("mlp", fused)
        if fused:
            return F.fused_mlp(x, self.gate_proj.weight,
                               self.up_proj.weight, self.down_proj.weight)
        return self.down_proj(F.silu(self.gate_proj(x)) * self.up_proj(x))


class LlamaDecoderLayer(Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__(dtype=config.dtype)
        self.input_layernorm = RMSNorm(config.hidden_size,
                                       epsilon=config.rms_norm_eps)
        self.self_attn = LlamaAttention(config)
        self.post_attention_layernorm = RMSNorm(config.hidden_size,
                                                epsilon=config.rms_norm_eps)
        self.mlp = LlamaMLP(config)

    def forward(self, x, rope_cos, rope_sin, attn_mask=None, cache=None,
                position_offset=0):
        # whole-block megakernel tier: the no-cache, offset-0, causal
        # form (training and full prefill) can run the entire block as
        # one Pallas pass — eligible shapes only, decided at trace time
        if cache is None and attn_mask is None and \
                isinstance(position_offset, int) and position_offset == 0:
            y = _fused_decoder(self, x, rope_cos, rope_sin)
            if y is not None:
                return y
        # the scope names are what a device trace is read by (``attn``:
        # norm + QKV + rope + attention + output projection; ``mlp``:
        # norm + MLP), on the backward operations too
        # (``transpose(jvp(attn))``); each takes its residual add
        with jax.named_scope("attn"):
            qkv = _fused_norm_qkv(self, x)
            if qkv is not None:
                h = self.self_attn.attend(*qkv, rope_cos, rope_sin,
                                          attn_mask, cache,
                                          position_offset)
            else:
                h = self.self_attn(self.input_layernorm(x), rope_cos,
                                   rope_sin, attn_mask, cache,
                                   position_offset)
            new_cache = None
            if cache is not None:
                h, new_cache = h
            # NOT the fused Pallas rms_norm_residual: measured in-model
            # (bench.py v5e) the custom-kernel call is a fusion barrier
            # that costs ~2 MFU points vs letting XLA fuse the chain
            # (0.491 vs 0.514) even though the kernel wins 1.38x in
            # isolation
            x = x + h
        with jax.named_scope("mlp"):
            x = x + self.mlp(self.post_attention_layernorm(x))
        if cache is not None:
            return x, new_cache
        return x


class LlamaModel(Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__(dtype=config.dtype)
        self.config = config
        self.embed_tokens = Embedding(config.vocab_size, config.hidden_size,
                                      sparse=config.sparse_embed)
        self.layers = []
        for i in range(config.num_hidden_layers):
            layer = LlamaDecoderLayer(config)
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
            # RoPE tables stay fp32 (applied in fp32 regardless)
            self.rope_cos._set_data(cos)
            self.rope_sin._set_data(sin)

    def forward(self, input_ids, attn_mask=None, caches=None,
                position_offset=0):
        with jax.named_scope("embed"):
            x = self.embed_tokens(input_ids)
        new_caches = [] if caches is not None else None
        for i, layer in enumerate(self.layers):
            cache = caches[i] if caches is not None else None
            x = layer(x, self.rope_cos, self.rope_sin, attn_mask, cache,
                      position_offset)
            if caches is not None:
                x, c = x
                new_caches.append(c)
        with jax.named_scope("lm_head_ce"):     # final norm, head, loss
            x = self.norm(x)
        if caches is not None:
            return x, new_caches
        return x


class LlamaForCausalLM(Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__(dtype=config.dtype)
        self.config = config
        self.model = LlamaModel(config)
        if config.tie_word_embeddings:
            self.lm_head = None
        else:
            self.lm_head = Linear(config.hidden_size, config.vocab_size,
                                  bias_attr=False)

    def forward(self, input_ids, attn_mask=None, caches=None,
                position_offset=0):
        h = self.model(input_ids, attn_mask, caches, position_offset)
        new_caches = None
        if caches is not None:
            h, new_caches = h
        with jax.named_scope("lm_head_ce"):
            if self.lm_head is None:
                from paddle_tpu.ops import linalg as L
                logits = L.matmul(h, self.model.embed_tokens.weight,
                                  transpose_y=True)
            else:
                logits = self.lm_head(h)
        if caches is not None:
            return logits, new_caches
        return logits

    def generate(self, input_ids, generation_config=None, **kwargs):
        """Compiled KV-cache decoding (paddle_tpu.generation.generate)."""
        from paddle_tpu.generation import generate as _gen
        return _gen(self, input_ids, generation_config, **kwargs)

    def loss(self, input_ids, labels):
        """Next-token cross-entropy via the fused lm-head+CE, walked a
        chunk of rows at a time: the [T, V] fp32 logits are never
        materialized, which is what bounds single-chip batch size, and
        under differentiation the same walk makes dh and dW (reference
        role: fused c_softmax_with_cross_entropy)."""
        h = self.model(input_ids)
        d = h.shape[-1]
        with jax.named_scope("lm_head_ce"):
            w = self.model.embed_tokens.weight.t() \
                if self.lm_head is None else self.lm_head.weight
            return F.fused_linear_cross_entropy(
                M.reshape(h, [-1, d]), w, M.reshape(labels, [-1]))

    # -- GSPMD sharding rules -------------------------------------------------
    @staticmethod
    def partition_specs(config: LlamaConfig, dp_axis="dp", tp_axis="tp",
                        fsdp_axis=None):
        """{state_dict name pattern → PartitionSpec} for a (dp, tp) mesh.

        Megatron mapping expressed as shardings (the reference does this with
        ColumnParallelLinear/RowParallelLinear classes,
        fleet/layers/mpu/mp_layers.py:173,343): q/k/v/gate/up are
        column-parallel (shard the output dim on tp), o/down are row-parallel
        (shard the input dim), embedding + lm_head shard the vocab dim.
        fsdp_axis additionally shards the other weight axis (ZeRO-3 at rest).
        """
        from jax.sharding import PartitionSpec as P
        col = P(fsdp_axis, tp_axis)     # [in, out] weight, shard out
        row = P(tp_axis, fsdp_axis)     # [in, out] weight, shard in
        rules = {
            "model.embed_tokens.weight": P(tp_axis, fsdp_axis),
            "lm_head.weight": col,
            ".q_proj.weight": col,
            ".k_proj.weight": col,
            ".v_proj.weight": col,
            ".o_proj.weight": row,
            ".gate_proj.weight": col,
            ".up_proj.weight": col,
            ".down_proj.weight": row,
            "norm.weight": P(),
            "layernorm.weight": P(),
            # rope tables are non-persistable buffers: they never appear in
            # state_dict/params — they are baked into the jaxpr as constants
        }
        return rules

    @staticmethod
    def spec_for(name, rules):
        from jax.sharding import PartitionSpec as P
        for pat, spec in rules.items():
            if name.endswith(pat) or pat in name:
                return spec
        return P()
