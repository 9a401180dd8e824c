"""Attention functionals.

Parity targets: python/paddle/nn/functional/flash_attention.py (reference
routes to _C_ops.flash_attn, a CUDA kernel) and scaled_dot_product_attention.
TPU-native: the hot path routes to a Pallas flash-attention kernel when on
TPU (paddle_tpu/ops/pallas/flash_attention.py); the reference XLA fallback
(below) is used on CPU and for odd shapes — XLA fuses it well regardless.

Layout convention is paddle's: [batch, seq, num_heads, head_dim].
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from paddle_tpu.core.dispatch import eager_op


def _sdpa_reference(q, k, v, attn_mask=None, dropout_p=0.0, is_causal=False,
                    scale=None, dropout_key=None):
    # GQA/MQA: this path materializes s×s scores anyway, so repeating KV
    # costs nothing extra (the Pallas path never repeats)
    if k.shape[2] != q.shape[2]:
        rep = q.shape[2] // k.shape[2]
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    # [b, s, h, d] → [b, h, s, d]
    qt = jnp.swapaxes(q, 1, 2)
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
    d = q.shape[-1]
    scale = scale if scale is not None else 1.0 / (d ** 0.5)
    # fp32 softmax accumulation (TPU numerics practice for bf16 inputs)
    scores = jnp.einsum("bhqd,bhkd->bhqk", qt, kt,
                        preferred_element_type=jnp.float32) * scale
    if is_causal:
        sq, sk = scores.shape[-2], scores.shape[-1]
        causal = jnp.tril(jnp.ones((sq, sk), bool), k=sk - sq)
        scores = jnp.where(causal, scores, -1e30)
    if attn_mask is not None:
        if attn_mask.dtype == jnp.bool_:
            scores = jnp.where(attn_mask, scores, -1e30)
        else:
            scores = scores + attn_mask.astype(scores.dtype)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    if dropout_p > 0.0 and dropout_key is not None:
        keep = jax.random.bernoulli(dropout_key, 1.0 - dropout_p, probs.shape)
        probs = jnp.where(keep, probs / (1.0 - dropout_p),
                          jnp.zeros((), probs.dtype)).astype(probs.dtype)
    out = jnp.einsum("bhqk,bhkd->bhqd", probs, vt)
    return jnp.swapaxes(out, 1, 2)


def _use_pallas(q_shape, head_dim):
    import jax as _j
    if _j.default_backend() != "tpu":
        return False
    # pallas kernel wants lane-aligned head_dim and block-aligned seq
    # (block sizes of >=128 and seq % block == 0).  Even at sequence
    # lengths where XLA's fused dense attention is FASTER in isolation
    # (below ~4k on v5e), flash is what lets the training step fit: the
    # dense path materializes the [b, h, s, s] score tensor per layer and
    # the remat policy keeps those dot outputs live (at the bench model's
    # shapes the dense variant fails to even compile on a 16 GB chip).
    # The forward's block sizes are autotuned (ops/pallas/autotune.py);
    # at 8k+ flash also wins outright (6.4x).
    return head_dim % 128 == 0 and q_shape[1] >= 128 and \
        q_shape[1] % 128 == 0


@eager_op
def scaled_dot_product_attention(query, key, value, attn_mask=None,
                                 dropout_p=0.0, is_causal=False,
                                 training=True, scale=None):
    use_dropout = dropout_p > 0.0 and training
    hd = query.shape[-1]
    if (attn_mask is None and not use_dropout
            and query.shape[1] == key.shape[1]
            and hd in (32, 64)
            and query.shape[1] >= 1024
            and _use_pallas(query.shape[:-1] + (128,), 128)):
        # lane-alignment shim for BERT/ERNIE-class head_dim: zero-pad the
        # head dim to 128 and slice the output back — numerically EXACT
        # (zero pads contribute nothing to q@k^T or probs@v; the softmax
        # scale pins to the true head_dim) and autodiff slices the pad
        # grads away.  Costs extra MXU lanes but keeps the O(s) memory
        # of flash.  Gated to seq >= 1024: below that, XLA's dense
        # attention is FASTER on v5e (measured: ERNIE b64 s512 padded
        # flash 0.188 MFU vs dense 0.265 at b32) and the [b,h,s,s] probs
        # it saves are still affordable; at long seq flash is both the
        # memory story and the speed story.
        pad = [(0, 0)] * 3 + [(0, 128 - hd)]
        qp, kp, vp = (jnp.pad(t, pad) for t in (query, key, value))
        out = scaled_dot_product_attention(
            qp, kp, vp, attn_mask=None, dropout_p=0.0,
            is_causal=is_causal, training=training,
            scale=scale if scale is not None else hd ** -0.5)
        return out[..., :hd]
    if attn_mask is None and not use_dropout and \
            query.shape[1] == key.shape[1] and \
            _use_pallas(query.shape, query.shape[-1]):
        # no try/except: a lowering break in the flagship kernel must
        # surface, not silently fall back (round-1 lesson).
        import functools

        from paddle_tpu.ops.pallas import mesh
        from paddle_tpu.ops.pallas.flash_attention import flash_attention
        attn = functools.partial(flash_attention, causal=is_causal,
                                 scale=scale)
        if mesh.current() is not None:
            # inside a sharded step: per shard of batch and heads
            return mesh.over_batch_and_heads(attn, query, key, value)
        return attn(query, key, value)
    dk = None
    if use_dropout:
        from paddle_tpu.core import functional as _cf
        from paddle_tpu.core import state as _cs
        dk = _cf.next_functional_key("dropout")
        if dk is None:
            dk = _cs.next_key()
    return _sdpa_reference(query, key, value, attn_mask, dropout_p,
                           is_causal, scale, dropout_key=dk)


@eager_op
def flash_attention(query, key, value, dropout=0.0, causal=False,
                    return_softmax=False, fixed_seed_offset=None,
                    rng_name="", training=True):
    """paddle.nn.functional.flash_attention parity: returns (out, softmax)."""
    out = None
    if _use_pallas(query.shape, query.shape[-1]):
        try:
            from paddle_tpu.ops.pallas.flash_attention import flash_attention \
                as _fa
            out = _fa(query, key, value, causal=causal)
        except Exception:
            out = None
    if out is None:
        out = _sdpa_reference(query, key, value, None, dropout, causal)
    return out, None


@eager_op
def flash_attn_unpadded(query, key, value, cu_seqlens_q, cu_seqlens_k,
                        max_seqlen_q, max_seqlen_k, scale=None, dropout=0.0,
                        causal=False, return_softmax=False, training=True):
    """Variable-length packed attention (reference:
    nn/functional/flash_attention.py flash_attn_unpadded — FlashAttention's
    varlen kernel over cu_seqlens-packed sequences).

    q/k/v: [total_tokens, num_heads, head_dim] with sequences concatenated;
    cu_seqlens_*: [batch+1] int32 prefix offsets.  TPU-native realisation:
    segment-id block masking over the packed token axis — XLA fuses the
    mask into the attention matmuls, and cross-sequence pairs are masked
    exactly like the reference kernel skips them.  Memory is O(total^2)
    (dense scores) — fine for packed batches up to a few thousand tokens;
    larger packs should run the Pallas flash path with segment ids.
    Causal masking is bottom-right aligned (flash-attn >= 2.1 varlen
    semantics).  Returns (out, softmax).
    """
    tq, h, d = query.shape
    tk = key.shape[0]
    if scale is None:
        scale = 1.0 / (d ** 0.5)
    # segment id of each packed token: seg[i] = #offsets <= i  (tokens past
    # the last offset land in segment batch+1 == padding, matching nothing)
    pos_q = jnp.arange(tq)
    pos_k = jnp.arange(tk)
    seg_q = jnp.searchsorted(cu_seqlens_q.astype(jnp.int32), pos_q,
                             side="right")
    seg_k = jnp.searchsorted(cu_seqlens_k.astype(jnp.int32), pos_k,
                             side="right")
    # position within the sequence (for causal masking)
    start_q = cu_seqlens_q[jnp.clip(seg_q - 1, 0, None)]
    start_k = cu_seqlens_k[jnp.clip(seg_k - 1, 0, None)]
    rel_q = pos_q - start_q
    rel_k = pos_k - start_k

    mask = seg_q[:, None] == seg_k[None, :]
    if causal:
        # bottom-right alignment (flash-attn >= 2.1 varlen semantics):
        # when a sequence has fewer queries than keys (decode with cache),
        # the last query aligns with the last key.  The shift is per
        # SEQUENCE, gathered onto each query token via its segment id.
        seq_len_q = cu_seqlens_q[1:] - cu_seqlens_q[:-1]   # [batch]
        seq_len_k = cu_seqlens_k[1:] - cu_seqlens_k[:-1]
        nb = seq_len_q.shape[0]
        shift = (seq_len_k - seq_len_q)[jnp.clip(seg_q - 1, 0, nb - 1)]
        mask = mask & ((rel_q + shift)[:, None] >= rel_k[None, :])

    qf = query.astype(jnp.float32) * scale
    scores = jnp.einsum("qhd,khd->hqk", qf, key.astype(jnp.float32))
    scores = jnp.where(mask[None, :, :], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    if dropout > 0.0 and training:
        from paddle_tpu.core import state as _cs
        keyr = _cs.next_key()
        keep = jax.random.bernoulli(keyr, 1.0 - dropout, probs.shape)
        probs = probs * keep / (1.0 - dropout)
    out = jnp.einsum("hqk,khd->qhd", probs, value.astype(jnp.float32))
    out = out.astype(query.dtype)
    return (out, probs if return_softmax else None)


def rotary_freqs(head_dim, max_position, base=10000.0, dtype=jnp.float32):
    """Precompute RoPE cos/sin tables, each [max_position, head_dim//2]."""
    inv = 1.0 / (base ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                          / head_dim))
    t = jnp.arange(max_position, dtype=jnp.float32)
    freqs = jnp.outer(t, inv)
    return jnp.cos(freqs).astype(dtype), jnp.sin(freqs).astype(dtype)


@eager_op
def apply_rotary_emb(x, cos, sin, position_offset=0):
    """Rotary position embedding, Llama/NeoX half-rotation convention.

    x: [batch, seq, heads, head_dim]; cos/sin: [max_pos, head_dim//2] tables
    from rotary_freqs.  position_offset shifts positions (decode w/ KV cache).
    Computed in fp32 then cast back (TPU bf16 numerics practice).
    """
    seq = x.shape[1]
    if isinstance(position_offset, int) and position_offset + seq > cos.shape[0]:
        raise ValueError(
            f"RoPE table overflow: positions [{position_offset}, "
            f"{position_offset + seq}) exceed table length {cos.shape[0]} "
            f"(max_position_embeddings)")
    if getattr(position_offset, "ndim", 0) == 1:
        # per-row offsets [B] (continuous-batching decode: every slot sits
        # at its own position) — gather per-(row, step) tables.  NOTE:
        # traced offsets can't be range-checked here; an out-of-table
        # position CLAMPS to the last row (jax gather semantics) instead
        # of raising like the scalar path — drivers must bound positions
        # against the table (ContinuousBatchingEngine validates max_len
        # at construction)
        pos = position_offset[:, None] + jnp.arange(seq)[None]   # [B, s]
        cos = cos[pos][:, :, None, :]                            # [B,s,1,h]
        sin = sin[pos][:, :, None, :]
    else:
        cos = jax.lax.dynamic_slice_in_dim(cos, position_offset, seq, 0)
        sin = jax.lax.dynamic_slice_in_dim(sin, position_offset, seq, 0)
        cos = cos[None, :, None, :]
        sin = sin[None, :, None, :]
    half = x.shape[-1] // 2
    x32 = x.astype(jnp.float32)
    x1, x2 = x32[..., :half], x32[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


__all__ = ["scaled_dot_product_attention", "flash_attention",
           "flash_attn_unpadded", "rotary_freqs", "apply_rotary_emb"]
