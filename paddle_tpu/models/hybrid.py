"""The layer-pattern decoder for serving: a layer is a token mixer and a
feed-forward, each of a kind the config names.

    x <- x + r * mixer(rms(x))     ``layer_types[i]``: ``mamba`` (Mamba-2),
                                   ``kda`` (Kimi Delta Attention),
                                   ``attention`` (grouped-query) or
                                   ``latent_attention`` (MLA)
    x <- x + r * ffn(rms(x))       ``ffn_types[i]``: ``experts`` (a router
                                   over gated experts beside a shared
                                   expert) or ``dense`` (one gated MLP)

with ``r = residual_multiplier``, or, where the config states
``sandwich_norm``, with four norms a layer and the sum taken after the
second of each pair:

    x <- x + rms_post(mixer(rms_in(x)))     x <- x + rms_post(ffn(rms_pre(x)))

Four published families are configurations of it: ``granitemoehybrid``
(IBM Granite 4.0-H: Mamba-2 and attention without rotary positions,
experts everywhere, multipliers, a tied head), ``sarvam_mla`` (latent
attention with YaRN rotary positions, a leading dense layer before expert
layers whose router takes sigmoid scores and a choice bias, an untied
head), ``kimi_linear`` (three KDA layers to one of latent attention
without rotary positions, the same dense layer, router and head) and
``afmoe`` (Arcee Trinity: grouped-query attention with a norm on every
query and key head and a sigmoid gate on its output, three layers that
see a sliding window and turn rotary positions to one that sees
everything and turns none, sandwich norms, the same router).

The embedding's output is scaled by ``embedding_multiplier``, the head is
the embedding when ``tie_word_embeddings`` and the logits are divided by
``logits_scaling``.  ``attention`` is ``LlamaAttention`` with what the
config states: a head size (``head_dim``), rotary positions or none
(``position_embedding_type``; a layer at a time where ``layer_rotary``
says so), a score scale (``attention_multiplier``), head norms
(``qk_norm``), an output gate (``attention_gate``), and a layer at a time
a sliding window (``layer_windows``; the serving engine keeps such
layers' keys and values in a block group of their own, a ring a request:
``inference/kv_cache.py``).
``latent_attention`` is ``models/latent_attention.py``.  The expert layer
is ``distributed.moe.GatedExpertLayer``, told which experts this chip
holds and its router's rule.  The Mamba-2 mathematics is
``ops/mamba2.py``, the gated delta rule's ``ops/kda.py``.

The model serves and does not train (no scan backward, no auxiliary
loss).  ``forward(input_ids, attn_mask, caches, position_offset)`` is the
serving engine's signature: ``caches[i]`` is a ``PagedCache`` for an
attention layer (over a latent pool for ``latent_attention``) and a
``SlotState`` for a recurrent layer (``mamba``, ``kda``), and one more
entry after the layers', a ``StepInfo``, says which rows are real and
collects the expert layers' counts.  Device operations carry the scopes
``embed``, ``ssm`` (a recurrent layer's norm + mixer + residual; the
delta rule's recurrence alone is ``kda`` inside it), ``attn``, ``moe``
(norm + router + routed + shared + residual), ``mlp`` (norm + dense MLP +
residual) and ``lm_head_ce``.
"""

from __future__ import annotations

import contextlib
import dataclasses
import types
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from paddle_tpu.core.dispatch import unwrap
from paddle_tpu.distributed.moe import GatedExpertLayer
from paddle_tpu.models.latent_attention import LatentAttention
from paddle_tpu.models.llama import LlamaAttention, LlamaMLP, \
    _fused_norm_qkv
from paddle_tpu.nn.common_layers import Embedding, Linear
from paddle_tpu.nn.layer import Layer
from paddle_tpu.nn.norm_layers import RMSNorm
from paddle_tpu.ops import kda, mamba2

__all__ = ["HybridConfig", "Mamba2Mixer", "KDAMixer", "HybridDecoderLayer",
           "HybridModel", "HybridForCausalLM"]

RECURRENT = ("mamba", "kda")    # layer kinds that keep per-slot state


@dataclasses.dataclass
class HybridConfig:
    vocab_size: int = 100352
    hidden_size: int = 4096
    num_hidden_layers: int = 40
    # "mamba" | "kda" | "attention" | "latent_attention" a layer
    layer_types: Tuple[str, ...] = ()
    ffn_types: Tuple[str, ...] = ()         # "experts" | "dense" a layer
    num_attention_heads: int = 32
    num_key_value_heads: int = 8
    head_dim: Optional[int] = None          # stated; None: hidden / heads
    intermediate_size: int = 768            # one routed expert's width
    shared_intermediate_size: int = 1536
    dense_intermediate_size: int = 0        # a "dense" layer's MLP width
    num_local_experts: int = 72             # the router's width
    num_experts_per_tok: int = 10
    held_experts: Optional[Tuple[int, ...]] = None  # ids here; None: all
    # the router's rule (distributed/moe.py): "softmax_topk" | "sigmoid_bias"
    router_rule: str = "softmax_topk"
    routed_scaling_factor: float = 1.0
    # latent attention: what a token caches is kv_lora_rank + rope values
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # latent attention's two norms; grouped-query attention's norm on
    # each query and key head
    qk_norm: bool = False
    attention_gate: bool = False    # attention's output x sigmoid(W_g h)
    # a layer's sliding window in positions (0: it sees everything) and
    # whether it turns rotary positions; empty: no window anywhere, and
    # ``position_embedding_type`` for every layer
    layer_windows: Tuple[int, ...] = ()
    layer_rotary: Tuple[bool, ...] = ()
    # x + rms(mixer(rms(x))): a second norm on each branch's output
    sandwich_norm: bool = False
    rope_theta: float = 10000.0
    rope_scaling: Optional[dict] = None     # YaRN (latent attention)
    tie_word_embeddings: bool = True
    mamba_n_heads: int = 128
    mamba_d_head: int = 64
    mamba_d_state: int = 128
    mamba_d_conv: int = 4
    mamba_n_groups: int = 1
    mamba_chunk_size: int = 256
    mamba_conv_bias: bool = True
    kda_n_heads: int = 0                    # a "kda" layer: heads of
    kda_head_dim: int = 0                   # kda_head_dim keys and values
    kda_d_conv: int = 4
    embedding_multiplier: float = 12.0
    attention_multiplier: float = 0.0078125
    residual_multiplier: float = 0.22
    logits_scaling: float = 16.0
    position_embedding_type: str = "nope"
    max_position_embeddings: int = 131072
    rms_norm_eps: float = 1e-5
    dtype: str = "float32"

    def __post_init__(self):
        self.layer_types = tuple(self.layer_types) or \
            ("attention",) * self.num_hidden_layers
        self.ffn_types = tuple(self.ffn_types) or \
            ("experts",) * self.num_hidden_layers
        if len(self.layer_types) != self.num_hidden_layers or \
                set(self.layer_types) - {*RECURRENT, "attention",
                                         "latent_attention"}:
            raise ValueError(f"layer_types {self.layer_types} do not name "
                             f"{self.num_hidden_layers} mamba / kda / "
                             f"attention / latent_attention layers")
        if len(self.ffn_types) != self.num_hidden_layers or \
                set(self.ffn_types) - {"experts", "dense"}:
            raise ValueError(f"ffn_types {self.ffn_types} do not name "
                             f"{self.num_hidden_layers} experts / dense "
                             f"layers")
        if self.mamba_n_groups != 1:
            raise NotImplementedError("Mamba-2 with more than one B/C group")
        if self.position_embedding_type not in ("nope", "rope"):
            raise NotImplementedError(
                f"position_embedding_type "
                f"{self.position_embedding_type!r}: rotary over the whole "
                f"head (rope) or none (nope); partial rotary is not served")
        self.layer_windows = tuple(int(w or 0) for w in self.layer_windows) \
            or (0,) * self.num_hidden_layers
        self.layer_rotary = tuple(bool(r) for r in self.layer_rotary) or \
            (self.position_embedding_type == "rope",) \
            * self.num_hidden_layers
        for name in ("layer_windows", "layer_rotary"):
            if len(getattr(self, name)) != self.num_hidden_layers:
                raise ValueError(f"{name} {getattr(self, name)} does not "
                                 f"name {self.num_hidden_layers} layers")
        if any(w and k != "attention"
               for w, k in zip(self.layer_windows, self.layer_types)):
            raise NotImplementedError(
                "a sliding window on a layer that is not grouped-query "
                "attention")
        if len({w for w in self.layer_windows if w}) > 1:
            raise NotImplementedError(
                f"sliding windows of several sizes {self.layer_windows}: "
                f"the engine keeps one window group, of one ring size")
        if self.sandwich_norm and self.residual_multiplier != 1.0:
            raise ValueError("sandwich_norm with a residual_multiplier: "
                             "a published family states one or the other")
        kinds = set(self.layer_types)
        if {"attention", "latent_attention"} <= kinds:
            raise NotImplementedError(
                "attention and latent_attention layers in one model: the "
                "engine builds one kind of block pool")
        if "latent_attention" in kinds and not (
                self.kv_lora_rank and self.qk_rope_head_dim
                and self.qk_nope_head_dim and self.v_head_dim):
            raise ValueError("latent_attention needs kv_lora_rank, "
                             "qk_nope_head_dim, qk_rope_head_dim and "
                             "v_head_dim")
        if "kda" in kinds and not (self.kda_n_heads and self.kda_head_dim):
            raise ValueError("a kda layer needs kda_n_heads and "
                             "kda_head_dim")
        if "dense" in self.ffn_types and not self.dense_intermediate_size:
            raise ValueError("a dense layer needs dense_intermediate_size")
        if self.head_dim is None:
            self.head_dim = self.hidden_size // self.num_attention_heads
        if self.held_experts is not None:
            self.held_experts = tuple(self.held_experts)

    @property
    def latent_row(self):
        """Values a token leaves in a latent-attention layer's cache:
        the latent and the shared rotary key (0 without such layers)."""
        if "latent_attention" not in self.layer_types:
            return 0
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def mamba_d_inner(self):
        return self.mamba_n_heads * self.mamba_d_head

    @property
    def mamba_conv_dim(self):
        return self.mamba_d_inner + \
            2 * self.mamba_n_groups * self.mamba_d_state

    @staticmethod
    def tiny(**over):
        cfg = dict(vocab_size=128, hidden_size=64, num_hidden_layers=3,
                   layer_types=("mamba", "attention", "mamba"),
                   num_attention_heads=4, num_key_value_heads=2,
                   intermediate_size=32, shared_intermediate_size=48,
                   num_local_experts=8, num_experts_per_tok=2,
                   mamba_n_heads=8, mamba_d_head=16, mamba_d_state=16,
                   mamba_chunk_size=8, max_position_embeddings=512)
        cfg.update(over)
        return HybridConfig(**cfg)


class _Conv1d(Layer):
    """The depthwise convolution's leaves: weight [d_conv, channels]
    (row d_conv - 1 multiplies the current position), bias [channels]."""

    def __init__(self, kernel, channels, bias):
        super().__init__()
        self.weight = self.create_parameter([kernel, channels])
        self.bias = self.create_parameter([channels], is_bias=True) \
            if bias else None


def _state_in(state, info, B, shapes, dtype):
    """(conv tail, recurrent state) a span of ``B`` rows starts from:
    zeros without ``state`` (a ``SlotState``), slot ``info.slot``'s for a
    B == 1 prefill chunk, else row b is slot b."""
    if state is None:
        return (jnp.zeros((B,) + tuple(shapes[0]), dtype),
                jnp.zeros((B,) + tuple(shapes[1]), jnp.float32))
    if info is not None and info.slot is not None:
        return tuple(jax.lax.dynamic_index_in_dim(
            unwrap(a), info.slot, 0, keepdims=True) for a in state)
    return unwrap(state.conv), unwrap(state.ssm)


def _state_out(state, info, tail, h):
    """``state`` with the span's tail and recurrent state written back
    where ``_state_in`` read them."""
    from paddle_tpu.inference.kv_cache import SlotState
    if info is not None and info.slot is not None:
        return SlotState(*(jax.lax.dynamic_update_index_in_dim(
            unwrap(old), new[0], info.slot, 0)
            for old, new in zip(state, (tail, h))))
    return SlotState(tail, h)


class Mamba2Mixer(Layer):
    """``[z | xBC | dt] = in_proj(u)``; ``xBC = silu(conv(xBC) + b)``;
    ``dt = softplus(dt + dt_bias)``; the recurrence of ``ops/mamba2.py``
    a head; ``y = rms_w((h C + D x) * silu(z))`` (gate before norm);
    ``out_proj(y)``.  With ``state`` (a ``SlotState``) the recurrence
    starts from the slot's state and the new one comes back; positions
    at or past ``info.valid`` touch neither the tail nor the state."""

    def __init__(self, c: HybridConfig):
        super().__init__(dtype=c.dtype)
        self.heads, self.head_dim = c.mamba_n_heads, c.mamba_d_head
        self.d_state, self.chunk = c.mamba_d_state, c.mamba_chunk_size
        self.d_inner, self.conv_dim = c.mamba_d_inner, c.mamba_conv_dim
        self.in_proj = Linear(c.hidden_size,
                              self.d_inner + self.conv_dim + self.heads,
                              bias_attr=False)
        self.conv1d = _Conv1d(c.mamba_d_conv, self.conv_dim,
                              c.mamba_conv_bias)
        self.dt_bias = self.create_parameter([self.heads], is_bias=True)
        self.A_log = self.create_parameter([self.heads], is_bias=True)
        self.D = self.create_parameter([self.heads], is_bias=True)
        self.norm = RMSNorm(self.d_inner, epsilon=c.rms_norm_eps)
        self.out_proj = Linear(self.d_inner, c.hidden_size, bias_attr=False)

    def state_shapes(self):
        """(conv tail, SSM state) of one slot, without the slot axis."""
        return ((self.conv1d.weight.shape[0] - 1, self.conv_dim),
                (self.heads, self.head_dim, self.d_state))

    def forward(self, u, state=None, info=None):
        f32 = jnp.float32
        u = unwrap(u)
        B, S = u.shape[0], u.shape[1]
        H, P, N = self.heads, self.head_dim, self.d_state
        z, xbc, dt = jnp.split(
            unwrap(self.in_proj(u)),
            [self.d_inner, self.d_inner + self.conv_dim], axis=-1)
        valid = jnp.full((B,), S, jnp.int32) if info is None \
            else info.valid.astype(jnp.int32)
        tail, h0 = _state_in(state, info, B, self.state_shapes(), u.dtype)
        bias = self.conv1d.bias
        xbc, tail = mamba2.causal_conv(
            xbc, tail, unwrap(self.conv1d.weight),
            None if bias is None else unwrap(bias), valid)
        xbc = jax.nn.silu(xbc)                              # float32
        x, Bm, Cm = jnp.split(xbc, [self.d_inner, self.d_inner + N], -1)
        x = x.reshape(B, S, H, P)
        real = jnp.arange(S)[None] < valid[:, None]         # [B, S]
        dt = jax.nn.softplus(dt.astype(f32)
                             + unwrap(self.dt_bias).astype(f32))
        dt = jnp.where(real[..., None], dt, 0.0)
        A = -jnp.exp(unwrap(self.A_log).astype(f32))
        if S == 1:
            y, h = mamba2.ssm_step(x[:, 0], dt[:, 0], A, Bm[:, 0],
                                   Cm[:, 0], h0)
            y = y[:, None]
        else:
            Q = min(self.chunk, S)
            pad = (-S) % Q
            padded = [jnp.pad(a, [(0, 0), (0, pad)] + [(0, 0)]
                              * (a.ndim - 2)) for a in (x, dt, Bm, Cm)]
            y, h = mamba2.ssd_scan(padded[0], padded[1], A, padded[2],
                                   padded[3], h0, Q)
            y = y[:, :S]
        y = y + unwrap(self.D).astype(f32)[:, None] * x
        y = y.reshape(B, S, self.d_inner) * jax.nn.silu(z.astype(f32))
        y = y * jax.lax.rsqrt(jnp.mean(y * y, -1, keepdims=True)
                              + self.norm._epsilon) \
            * unwrap(self.norm.weight).astype(f32)
        out = unwrap(self.out_proj(y.astype(u.dtype)))
        if state is None:
            return out
        return out, _state_out(state, info, tail, h)


class KDAMixer(Layer):
    """Kimi Delta Attention, ``H`` heads of ``d`` keys and values:
    ``[q | k | v | f | g | b] = in_proj(u)``; ``[q | k | v] = silu(conv(
    .))`` (depthwise, causal, no bias); ``q <- q / |q| d^-1/2``, ``k <- k
    / |k|`` a head; the log-decay ``a = -exp(A_log) softplus(f_proj(f) +
    dt_bias)`` a channel of the keys; ``beta = sigmoid(b)`` a head; the
    recurrence of ``ops/kda.py`` a head; ``y = rms_w(o) sigmoid(g_proj(
    g))`` a head; ``o_proj(y)``.  ``f`` and ``g`` are the two low-rank
    gates' inner values, ``d`` wide.  State and masking as
    ``Mamba2Mixer``'s: a ``SlotState`` of the convolution's tail over the
    ``q | k | v`` channels and the ``[H, d, d]`` float32 state."""

    CHUNK = 64      # positions a step of the scan over chunks

    def __init__(self, c: HybridConfig):
        super().__init__(dtype=c.dtype)
        self.heads, self.head_dim = c.kda_n_heads, c.kda_head_dim
        self.d_inner = self.heads * self.head_dim
        self.eps = c.rms_norm_eps
        self.in_proj = Linear(
            c.hidden_size,
            3 * self.d_inner + 2 * self.head_dim + self.heads,
            bias_attr=False)
        self.conv1d = _Conv1d(c.kda_d_conv, 3 * self.d_inner, False)
        self.f_proj = Linear(self.head_dim, self.d_inner, bias_attr=False)
        self.g_proj = Linear(self.head_dim, self.d_inner, bias_attr=False)
        self.dt_bias = self.create_parameter([self.d_inner], is_bias=True)
        self.A_log = self.create_parameter([self.heads], is_bias=True)
        self.o_norm = RMSNorm(self.head_dim, epsilon=c.rms_norm_eps)
        self.o_proj = Linear(self.d_inner, c.hidden_size, bias_attr=False)

    def state_shapes(self):
        """(conv tail, delta-rule state) of one slot, without the slot
        axis."""
        return ((self.conv1d.weight.shape[0] - 1, 3 * self.d_inner),
                (self.heads, self.head_dim, self.head_dim))

    def forward(self, u, state=None, info=None):
        f32 = jnp.float32
        u = unwrap(u)
        B, S = u.shape[0], u.shape[1]
        H, D, P = self.heads, self.head_dim, self.d_inner
        qkv, f, g, b = jnp.split(unwrap(self.in_proj(u)),
                                 [3 * P, 3 * P + D, 3 * P + 2 * D], axis=-1)
        valid = jnp.full((B,), S, jnp.int32) if info is None \
            else info.valid.astype(jnp.int32)
        tail, S0 = _state_in(state, info, B, self.state_shapes(), u.dtype)
        qkv, tail = mamba2.causal_conv(qkv, tail,
                                       unwrap(self.conv1d.weight), None,
                                       valid)
        q, k, v = (x.reshape(B, S, H, D) for x in
                   jnp.split(jax.nn.silu(qkv), 3, axis=-1))     # float32
        q = q * jax.lax.rsqrt(jnp.sum(q * q, -1, keepdims=True) + 1e-6) \
            * D ** -0.5
        k = k * jax.lax.rsqrt(jnp.sum(k * k, -1, keepdims=True) + 1e-6)
        a = jax.nn.softplus(unwrap(self.f_proj(f)).astype(f32)
                            + unwrap(self.dt_bias).astype(f32))
        a = -jnp.exp(unwrap(self.A_log).astype(f32))[:, None] \
            * a.reshape(B, S, H, D)
        beta = jax.nn.sigmoid(b.astype(f32))
        real = (jnp.arange(S)[None] < valid[:, None])[..., None]
        a = jnp.where(real[..., None], a, 0.0)
        beta = jnp.where(real, beta, 0.0)
        with jax.named_scope("kda"):
            if S == 1:
                o, h = kda.kda_step(q[:, 0], k[:, 0], v[:, 0], a[:, 0],
                                    beta[:, 0], S0)
                o = o[:, None]
            else:
                o, h = kda.kda_scan(q, k, v, a, beta, S0,
                                    min(self.CHUNK, S))
        o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True)
                              + self.eps) \
            * unwrap(self.o_norm.weight).astype(f32)
        y = o.reshape(B, S, P) * jax.nn.sigmoid(
            unwrap(self.g_proj(g)).astype(f32))
        out = unwrap(self.o_proj(y.astype(u.dtype)))
        if state is None:
            return out
        return out, _state_out(state, info, tail, h)


class _SharedExpert(Layer):
    """The expert every token passes: the routed experts' gated form at
    its own width, ``output(silu(g) * u)`` with ``[g | u] = input(h)``."""

    def __init__(self, d_model, width):
        super().__init__()
        self.input_linear = Linear(d_model, 2 * width, bias_attr=False)
        self.output_linear = Linear(width, d_model, bias_attr=False)

    def forward(self, h):
        g, u = jnp.split(unwrap(self.input_linear(h)), 2, axis=-1)
        return unwrap(self.output_linear(jax.nn.silu(g) * u))


class HybridDecoderLayer(Layer):
    def __init__(self, c: HybridConfig, kind: str, ffn: str = "experts",
                 window: int = 0, rotary: Optional[bool] = None):
        super().__init__(dtype=c.dtype)
        self.kind, self.ffn = kind, ffn
        self.residual = float(c.residual_multiplier)
        self.input_layernorm = RMSNorm(c.hidden_size,
                                       epsilon=c.rms_norm_eps)
        if kind == "mamba":
            self.mamba = Mamba2Mixer(c)
        elif kind == "kda":
            self.kda = KDAMixer(c)
        elif kind == "latent_attention":
            self.self_attn = LatentAttention(c)
        else:
            self.self_attn = LlamaAttention(c, window, rotary)
        # with windows in the model, a layer's attention carries a scope
        # of its kind inside ``attn``
        self.attn_scope = None if not any(c.layer_windows) else \
            "attn_window" if window else "attn_full"
        self.post_attention_layernorm = RMSNorm(c.hidden_size,
                                                epsilon=c.rms_norm_eps)
        # the sandwich form: ``post_attention_layernorm`` is then the norm
        # of attention's output, and the feed-forward has a pair of its own
        self.pre_mlp_layernorm = self.post_mlp_layernorm = None
        if c.sandwich_norm:
            if kind != "attention":
                raise NotImplementedError(
                    f"sandwich norms around a {kind} layer")
            self.pre_mlp_layernorm = RMSNorm(c.hidden_size,
                                             epsilon=c.rms_norm_eps)
            self.post_mlp_layernorm = RMSNorm(c.hidden_size,
                                              epsilon=c.rms_norm_eps)
        if ffn == "dense":
            self.mlp = LlamaMLP(types.SimpleNamespace(
                dtype=c.dtype, hidden_size=c.hidden_size,
                intermediate_size=c.dense_intermediate_size))
        else:
            self.block_sparse_moe = GatedExpertLayer(
                c.hidden_size, c.intermediate_size, c.num_local_experts,
                c.num_experts_per_tok, held=c.held_experts, dtype=c.dtype,
                rule=c.router_rule, scaling=c.routed_scaling_factor)
            self.shared_mlp = _SharedExpert(c.hidden_size,
                                            c.shared_intermediate_size)

    def forward(self, x, attn_mask=None, cache=None, position_offset=0,
                info=None, rope=(None, None)):
        """-> (x, the layer's new cache or None, the expert layer's
        counts: zeros for a dense layer)."""
        x = unwrap(x)
        new_cache = None
        if self.kind == "latent_attention":
            if attn_mask is not None:
                raise NotImplementedError(
                    "latent attention under an attn_mask: its paths are "
                    "causal over the cached context only")
            with jax.named_scope("attn"):
                h = self.self_attn(self.input_layernorm(x), cache,
                                   position_offset)
                if cache is not None:
                    h, new_cache = h
                x = x + self.residual * h.astype(x.dtype)
        elif self.kind in RECURRENT:
            with jax.named_scope("ssm"):
                h = getattr(self, self.kind)(self.input_layernorm(x),
                                             cache, info)
                if cache is not None:
                    h, new_cache = h
                x = x + self.residual * h.astype(x.dtype)
        else:
            with jax.named_scope("attn"):
                with jax.named_scope(self.attn_scope) if self.attn_scope \
                        else contextlib.nullcontext():
                    # a gate's fifth projection reads the normed input:
                    # such a layer takes plain matmuls, not the fused
                    # norm + QKV kernel
                    qkv = _fused_norm_qkv(self, x) \
                        if self.self_attn.gate_proj is None else None
                    if qkv is not None:
                        h = self.self_attn.attend(*qkv, *rope, attn_mask,
                                                  cache, position_offset)
                    else:
                        h = self.self_attn(self.input_layernorm(x), *rope,
                                           attn_mask, cache,
                                           position_offset)
                    if cache is not None:
                        h, new_cache = h
                if self.pre_mlp_layernorm is not None:
                    h = self.post_attention_layernorm(h)
                x = x + self.residual * unwrap(h).astype(x.dtype)
        sandwich = self.pre_mlp_layernorm is not None
        pre = self.pre_mlp_layernorm if sandwich \
            else self.post_attention_layernorm
        if self.ffn == "dense":
            with jax.named_scope("mlp"):
                h = self.mlp(pre(x))
                if sandwich:
                    h = self.post_mlp_layernorm(h)
                x = x + self.residual * unwrap(h).astype(x.dtype)
            return x, new_cache, jnp.zeros((3,), jnp.int32)
        with jax.named_scope("moe"):
            h = unwrap(pre(x))
            real = None if info is None else \
                jnp.arange(x.shape[1])[None] < info.valid[:, None]
            y, counts = self.block_sparse_moe(h, real)
            y = y + self.shared_mlp(h).astype(jnp.float32)
            if sandwich:
                y = unwrap(self.post_mlp_layernorm(y))
            x = x + (self.residual * y).astype(x.dtype)
        return x, new_cache, counts


class HybridModel(Layer):
    def __init__(self, config: HybridConfig):
        super().__init__(dtype=config.dtype)
        self.config = config
        self.embed_tokens = Embedding(config.vocab_size, config.hidden_size)
        self.layers = []
        for i, kind in enumerate(config.layer_types):
            layer = HybridDecoderLayer(config, kind, config.ffn_types[i],
                                       config.layer_windows[i],
                                       config.layer_rotary[i])
            self.add_sublayer(f"layers_{i}", layer)
            self.layers.append(layer)
        self.norm = RMSNorm(config.hidden_size, epsilon=config.rms_norm_eps)
        # grouped-query attention with rotary positions reads LlamaModel's
        # float32 tables (latent attention computes its own from the
        # positions); kept off the layer tree, so a dtype cast, a
        # state_dict and a LazyGuard build never see them
        self._rope = (None, None)
        if any(r and k == "attention" for r, k in
               zip(config.layer_rotary, config.layer_types)):
            from paddle_tpu.nn import functional as F
            self._rope = tuple(unwrap(t) for t in F.rotary_freqs(
                config.head_dim, config.max_position_embeddings,
                base=config.rope_theta))
        if config.dtype != "float32":
            self.astype(config.dtype)

    def forward(self, input_ids, attn_mask=None, caches=None,
                position_offset=0):
        from paddle_tpu.inference.kv_cache import StepInfo
        L = len(self.layers)
        info = None
        if caches is not None and len(caches) > L:
            info = caches[L]
        with jax.named_scope("embed"):
            x = unwrap(self.embed_tokens(input_ids))
            x = x * jnp.asarray(self.config.embedding_multiplier, x.dtype)
        new_caches = [] if caches is not None else None
        counts = jnp.zeros((3,), jnp.int32)
        for i, layer in enumerate(self.layers):
            x, c, n = layer(x, attn_mask,
                            None if caches is None else caches[i],
                            position_offset, info, self._rope)
            counts = counts + n
            if caches is not None:
                new_caches.append(c)
        with jax.named_scope("lm_head_ce"):
            x = unwrap(self.norm(x))
        if caches is None:
            return x
        if info is not None:
            new_caches.append(StepInfo(info.valid, info.slot, counts))
        return x, new_caches


class HybridForCausalLM(Layer):
    """``HybridModel`` under its head (the embedding when tied).  The
    serving engine asks a model three things, apart:
    ``slot_state_shapes`` (its recurrent layers' state),
    ``routed_expert_layers`` (how many layers add to
    ``StepInfo.moe_counts``), ``config.latent_row`` (the width of a
    latent-attention layer's cached row: a latent pool, not K and V) and
    ``attention_windows`` (which attention layers keep a window: a block
    group of their own)."""

    def __init__(self, config: HybridConfig):
        super().__init__(dtype=config.dtype)
        self.config = config
        self.model = HybridModel(config)
        self.lm_head = None if config.tie_word_embeddings else \
            Linear(config.hidden_size, config.vocab_size, bias_attr=False)
        if self.lm_head is not None and config.dtype != "float32":
            self.lm_head.astype(config.dtype)

    def slot_state_shapes(self):
        """[(conv tail, recurrent state)] a recurrent layer, in layer
        order, without the slot axis."""
        return [getattr(layer, layer.kind).state_shapes()
                for layer in self.model.layers if layer.kind in RECURRENT]

    def attention_windows(self):
        """[window in positions, or 0] an attention layer (of either
        kind), in layer order: which of the engine's block groups holds
        the layer's cache."""
        c = self.config
        return [w for w, k in zip(c.layer_windows, c.layer_types)
                if k.endswith("attention")]

    def routed_expert_layers(self) -> int:
        """Layers that route over experts."""
        return self.config.ffn_types.count("experts")

    def forward(self, input_ids, attn_mask=None, caches=None,
                position_offset=0):
        h = self.model(input_ids, attn_mask, caches, position_offset)
        new_caches = None
        if caches is not None:
            h, new_caches = h
        with jax.named_scope("lm_head_ce"):
            if self.lm_head is None:
                logits = jnp.einsum(
                    "bsd,vd->bsv", h, unwrap(self.model.embed_tokens.weight),
                    preferred_element_type=jnp.float32)
            else:
                logits = jnp.einsum(
                    "bsd,dv->bsv", h, unwrap(self.lm_head.weight),
                    preferred_element_type=jnp.float32)
            logits = logits / self.config.logits_scaling
        if caches is not None:
            return logits, new_caches
        return logits
