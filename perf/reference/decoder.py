"""Plain reference: a pre-norm decoder-only transformer in jax.numpy.

RMSNorm, rotary positions (half-rotation), grouped-query causal attention,
SwiGLU, untied head — the equations of MistralForCausalLM and
InternLM2ForCausalLM (whose fused ``wqkv`` is the same mathematics as
separate q/k/v).  float32 throughout, ``jax.default_matmul_precision(
"highest")`` set by the caller, no kernels, no cache, no batching tricks.
It imports nothing of the program and takes nothing the program made.

Departures from a textbook forward, for memory only: attention walks the
queries in blocks, the training loss walks the head in blocks of rows and
re-computes each layer in the backward pass (``jax.checkpoint``).  None
changes a value beyond float32 rounding.

``precision`` selects the lower-precision *control* (never the reference):
``"int8"`` rounds both operands of every matrix product to 8-bit integers
(weights a scale per output column, activations a scale per row), which is
what computing the model in int8 means; gradients pass straight through.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

Q_BLOCK = 1024      # attention query block
CE_BLOCK = 2048     # loss rows per block


def _q8(x, axis):
    """Symmetric 8-bit rounding along ``axis``, straight-through."""
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    scale = jnp.where(scale == 0, 1.0, scale)
    q = jnp.clip(jnp.round(x / scale), -127, 127) * scale
    return x + jax.lax.stop_gradient(q - x)


def matmul(x, w, precision):
    if precision == "int8":
        x, w = _q8(x, -1), _q8(w, 0)
    elif precision == "bfloat16":
        x, w = (a + jax.lax.stop_gradient(
            a.astype(jnp.bfloat16).astype(jnp.float32) - a) for a in (x, w))
    elif precision != "float32":
        raise ValueError(f"unknown precision {precision!r}")
    return x @ w


def rms_norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def rope_tables(cfg, positions):
    hd = cfg.get("head_dim") or \
        cfg["hidden_size"] // cfg["num_attention_heads"]
    inv = cfg["rope_theta"] ** (-jnp.arange(0, hd, 2, dtype=jnp.float32)
                                / hd)
    ang = positions.astype(jnp.float32)[:, None] * inv[None]
    return jnp.cos(ang), jnp.sin(ang)


def rope(x, cos, sin):
    """x [b, s, heads, hd]; rotate (x1, x2) halves."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    c, s = cos[None, :, None, :], sin[None, :, None, :]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


def attention(q, k, v):
    """Causal softmax attention, q [b, s, h, hd], k/v [b, s, kv, hd]."""
    b, s, h, hd = q.shape
    rep = h // k.shape[2]
    k = jnp.repeat(k, rep, axis=2)
    v = jnp.repeat(v, rep, axis=2)
    out = []
    for lo in range(0, s, Q_BLOCK):
        hi = min(lo + Q_BLOCK, s)
        sc = jnp.einsum("bqhd,bkhd->bhqk", q[:, lo:hi], k[:, :hi]) \
            / jnp.sqrt(jnp.float32(hd))
        mask = jnp.arange(lo, hi)[:, None] >= jnp.arange(hi)[None]
        sc = jnp.where(mask[None, None], sc, -jnp.inf)
        out.append(jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(sc, -1),
                              v[:, :hi]))
    return jnp.concatenate(out, 1)


def layer(x, w, cfg, cos, sin, prefix, precision="float32"):
    """One decoder block; ``w`` maps leaf names (with ``prefix``) to
    float32 arrays, matrices stored [in, out]."""
    b, s, d = x.shape
    h, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    eps = cfg["rms_norm_eps"]
    mm = functools.partial(matmul, precision=precision)
    y = rms_norm(x, w[prefix + "input_layernorm.weight"], eps)
    q = mm(y, w[prefix + "self_attn.q_proj.weight"]).reshape(b, s, h, -1)
    k = mm(y, w[prefix + "self_attn.k_proj.weight"]).reshape(b, s, kv, -1)
    v = mm(y, w[prefix + "self_attn.v_proj.weight"]).reshape(b, s, kv, -1)
    a = attention(rope(q, cos, sin), rope(k, cos, sin), v)
    x = x + mm(a.reshape(b, s, -1), w[prefix + "self_attn.o_proj.weight"])
    y = rms_norm(x, w[prefix + "post_attention_layernorm.weight"], eps)
    gate = mm(y, w[prefix + "mlp.gate_proj.weight"])
    up = mm(y, w[prefix + "mlp.up_proj.weight"])
    return x + mm(jax.nn.silu(gate) * up, w[prefix + "mlp.down_proj.weight"])


def hidden(w, cfg, ids, precision="float32", remat=False):
    """Final-norm hidden states [b, s, d] for token ids [b, s]."""
    cos, sin = rope_tables(cfg, jnp.arange(ids.shape[1]))
    x = w["model.embed_tokens.weight"][ids]
    for i in range(cfg["num_hidden_layers"]):
        f = functools.partial(layer, cfg=cfg, cos=cos, sin=sin,
                              prefix=f"model.layers_{i}.",
                              precision=precision)
        x = (jax.checkpoint(f) if remat else f)(x, w)
    return rms_norm(x, w["model.norm.weight"], cfg["rms_norm_eps"])


def logits(w, cfg, ids, precision="float32"):
    return matmul(hidden(w, cfg, ids, precision), w["lm_head.weight"],
                  precision)


def loss(w, cfg, ids, labels, precision="float32"):
    """Mean next-token cross-entropy over every position."""
    h = hidden(w, cfg, ids, precision, remat=True)
    h = h.reshape(-1, h.shape[-1])
    y = labels.reshape(-1)

    @jax.checkpoint
    def block(hb, yb):
        lg = matmul(hb, w["lm_head.weight"], precision)
        return jnp.sum(jax.nn.logsumexp(lg, -1)
                       - jnp.take_along_axis(lg, yb[:, None], 1)[:, 0])

    total = 0.0
    for lo in range(0, h.shape[0], CE_BLOCK):
        total = total + block(h[lo:lo + CE_BLOCK], y[lo:lo + CE_BLOCK])
    return total / h.shape[0]


def adamw(p, g, m, v, step, *, lr, beta1, beta2, eps, weight_decay):
    """Decoupled-decay Adam (Loshchilov & Hutter), bias-corrected."""
    m = beta1 * m + (1 - beta1) * g
    v = beta2 * v + (1 - beta2) * g * g
    upd = (m / (1 - beta1 ** step)) / (jnp.sqrt(v / (1 - beta2 ** step))
                                      + eps)
    return p - lr * (upd + weight_decay * p), m, v
