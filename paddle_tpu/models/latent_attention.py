"""Latent attention (multi-head latent attention, the DeepSeek-V2/V3
family's ``MLA``) as a layer of the layer-pattern model.

With ``h = rms(x)``, ``H`` heads, a latent of ``kv_lora_rank`` and head
widths ``nope`` / ``rope`` / ``v``:

    q = W_q h -> [H, nope + rope];  q <- rms_g(q) a head (``qk_norm``)
    q = [q_n | q_r];  q_r <- rotate(q_r)
    [c | k_r] = W_kva h;  c <- rms_g(c);  k_r <- rotate(k_r)   one k_r
    [k_n | v] = W_kvb c -> H x (nope + v)                      for all heads
    score = (q_n . k_n + q_r . k_r) * scale;  causal softmax;  o = P v
    out = W_o o

What a token leaves in the cache is ``[c | k_r]``: ``kv_lora_rank +
rope`` values a layer (``HybridConfig.latent_row``), held by a latent
``PagedKVPool``; ``kv_cache.latent_cache_attention`` attends it — decode
absorbed (``W_kvb`` folded into the query and the output, one Pallas call
over the block table), a prefill chunk over the context it can see.
Without a cache the layer attends expanded, every position at once.

Rotary positions are "rotate halves" over the ``rope`` dims, with
YaRN-scaled frequencies when the config's ``rope_scaling`` asks
(:func:`yarn_inv_freq`, :func:`yarn_mscale`); cos and sin are computed
from the positions, so no table bounds ``max_len``.  A config whose
``position_embedding_type`` is ``nope`` turns nothing: ``q_r`` and
``k_r`` are 64 more dims of the score, and the cached row and the
absorbed kernel are what they were.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from paddle_tpu.core.dispatch import unwrap
from paddle_tpu.inference.kv_cache import (PagedCache,
                                           latent_cache_attention,
                                           query_positions)
from paddle_tpu.nn.common_layers import Linear
from paddle_tpu.nn.layer import Layer
from paddle_tpu.nn.norm_layers import RMSNorm

__all__ = ["LatentAttention", "yarn_inv_freq", "yarn_mscale",
           "latent_score_scale", "rotate_halves"]


def yarn_mscale(factor: float, mscale: float) -> float:
    """``m(a) = 0.1 a ln(factor) + 1`` (1 at or under factor 1)."""
    if factor <= 1.0 or not mscale:
        return 1.0
    return 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(dim: int, theta: float, scaling=None):
    """The ``dim // 2`` rotary frequencies ``theta^(-2j/dim)``, YaRN-
    interpolated when ``scaling`` (the config's ``rope_scaling``:
    ``factor``, ``original_max_position_embeddings``, ``beta_fast``,
    ``beta_slow``) says so: frequencies that turn more than ``beta_fast``
    times over the original context are kept, those that turn less than
    ``beta_slow`` times are divided by ``factor``, a linear ramp
    between.  float32 ``[dim // 2]``."""
    j = np.arange(dim // 2, dtype=np.float64)
    freq = theta ** (-2.0 * j / dim)
    if not scaling or float(scaling.get("factor", 1.0)) == 1.0:
        return freq.astype(np.float32)
    kind = scaling.get("type", scaling.get("rope_type"))
    if kind not in ("yarn", "deepseek_yarn"):
        raise NotImplementedError(f"rope_scaling type {kind!r}")
    factor = float(scaling["factor"])
    original = float(scaling["original_max_position_embeddings"])

    def corr(turns):
        return dim * math.log(original / (turns * 2 * math.pi)) \
            / (2 * math.log(theta))

    lo = max(math.floor(corr(float(scaling.get("beta_fast", 32)))), 0)
    hi = min(math.ceil(corr(float(scaling.get("beta_slow", 1)))), dim - 1)
    ramp = np.clip((j - lo) / max(hi - lo, 1e-3), 0.0, 1.0)
    return ((freq / factor) * ramp + freq * (1.0 - ramp)).astype(np.float32)


def latent_score_scale(qk_head_dim: int, scaling=None) -> float:
    """``qk_head_dim^-1/2 * m(mscale_all_dim)^2``: the family folds the
    YaRN attention factor into the softmax scale."""
    m = 1.0
    if scaling and scaling.get("mscale_all_dim"):
        m = yarn_mscale(float(scaling.get("factor", 1.0)),
                        float(scaling["mscale_all_dim"]))
    return qk_head_dim ** -0.5 * m * m


def rotate_halves(x, positions, inv_freq, mscale: float = 1.0):
    """x ``[b, s, heads, dim]`` rotated by ``positions`` ``[b, s]``
    (pairs are (j, j + dim/2)), in float32, cast back."""
    ang = positions[..., None].astype(jnp.float32) * inv_freq
    cos = (jnp.cos(ang) * mscale)[:, :, None, :]
    sin = (jnp.sin(ang) * mscale)[:, :, None, :]
    half = x.shape[-1] // 2
    x32 = x.astype(jnp.float32)
    x1, x2 = x32[..., :half], x32[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1).astype(x.dtype)


def _rms(x, gain, eps):
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, -1, keepdims=True) + eps)
    return (y * unwrap(gain).astype(jnp.float32)).astype(x.dtype)


class LatentAttention(Layer):
    def __init__(self, c):
        super().__init__(dtype=c.dtype)
        self.heads = c.num_attention_heads
        self.rank = c.kv_lora_rank
        self.nope, self.rope = c.qk_nope_head_dim, c.qk_rope_head_dim
        self.v_dim = c.v_head_dim
        self.eps = c.rms_norm_eps
        qk = self.nope + self.rope
        scaling = c.rope_scaling
        self.rotary = getattr(c, "position_embedding_type",
                              "rope") != "nope"
        self.inv_freq = yarn_inv_freq(self.rope, c.rope_theta, scaling)
        self.mscale = 1.0
        if scaling and float(scaling.get("factor", 1.0)) != 1.0:
            f = float(scaling["factor"])
            self.mscale = yarn_mscale(f, float(scaling.get("mscale", 1))) \
                / yarn_mscale(f, float(scaling.get("mscale_all_dim", 0)))
        self.scale = latent_score_scale(qk, scaling)
        self.q_proj = Linear(c.hidden_size, self.heads * qk,
                             bias_attr=False)
        self.kv_a_proj_with_mqa = Linear(c.hidden_size,
                                         self.rank + self.rope,
                                         bias_attr=False)
        self.kv_b_proj = Linear(self.rank,
                                self.heads * (self.nope + self.v_dim),
                                bias_attr=False)
        self.o_proj = Linear(self.heads * self.v_dim, c.hidden_size,
                             bias_attr=False)
        # learned-gain norms: one over each head's query, one over the
        # latent; none on the expanded keys (they must stay linear in c
        # for the absorbed form)
        self.q_norm = RMSNorm(qk, epsilon=c.rms_norm_eps) \
            if c.qk_norm else None
        self.kv_a_layernorm = RMSNorm(self.rank, epsilon=c.rms_norm_eps)

    def forward(self, x, cache=None, position_offset=0):
        x = unwrap(x)
        B, S = x.shape[0], x.shape[1]
        H, rank, nope = self.heads, self.rank, self.nope
        q = unwrap(self.q_proj(x)).reshape(B, S, H, -1)
        if self.q_norm is not None:
            q = _rms(q, self.q_norm.weight, self.eps)
        ckr = unwrap(self.kv_a_proj_with_mqa(x))
        c = _rms(ckr[..., :rank], self.kv_a_layernorm.weight, self.eps)
        k_r = ckr[..., rank:]
        if self.rotary:
            pos = query_positions(position_offset, B, S)
            q = jnp.concatenate(
                [q[..., :nope], rotate_halves(q[..., nope:], pos,
                                              self.inv_freq, self.mscale)],
                -1)
            k_r = rotate_halves(k_r[:, :, None], pos, self.inv_freq,
                                self.mscale)[:, :, 0]
        w_kvb = unwrap(self.kv_b_proj.weight)
        new_cache = None
        if cache is not None:
            if not isinstance(cache, PagedCache) or cache.v is not None:
                raise TypeError(
                    "latent attention is served from a latent "
                    "PagedKVPool (PagedKVPool(..., latent=True)); got "
                    f"{type(cache).__name__}")
            out, new_cache = latent_cache_attention(
                q, jnp.concatenate([c, k_r], -1), cache, position_offset,
                w_kvb, rank=rank, nope=nope, scale=self.scale)
            out = unwrap(out)
        else:
            kv = unwrap(self.kv_b_proj(c)).reshape(B, S, H, -1)
            k = jnp.concatenate(
                [kv[..., :nope],
                 jnp.broadcast_to(k_r[:, :, None], (B, S, H, self.rope))],
                axis=-1)
            s = jnp.einsum("bshn,bthn->bhst", q, k,
                           preferred_element_type=jnp.float32) * self.scale
            s = jnp.where(jnp.arange(S)[:, None] >= jnp.arange(S)[None],
                          s, -1e30)
            out = jnp.einsum("bhst,bthv->bshv",
                             jax.nn.softmax(s, -1).astype(x.dtype),
                             kv[..., nope:])
        out = unwrap(self.o_proj(out.reshape(B, S, H * self.v_dim)))
        if cache is not None:
            return out, new_cache
        return out
