"""Sequence / context parallelism: ring attention + Ulysses (DeepSpeed-style).

**New capability — no reference port.** SURVEY.md §5.7 verified the reference
has NO sequence parallelism (grep over the snapshot); its long-context story
is flash attention + recompute.  This module is designed TPU-first:

* **Ring attention** (`ring_attention`): the sequence dim is sharded on the
  ``sp`` mesh axis; each device keeps its Q shard and rotates K/V shards
  around the ring with ``lax.ppermute`` (one ICI hop per step), folding each
  incoming block into a running online-softmax — so peak memory is
  O(seq/sp) and the N² score matrix never materialises anywhere.  Two
  per-hop folds are available: the dense online-softmax math (default —
  exact at any head_dim) and a **flash-backed** fold (``impl="flash"`` or
  ``PADDLE_TPU_RING_FLASH=1``) that runs the flash-attention Pallas kernel
  on each incoming K/V shard and merges hops by log-sum-exp, so the local
  score matrix never materialises either — O(seq/sp) total memory, which is
  what lets seq ≫ 2048 train across chips.  Causal hops resolve by ring
  position (``lax.switch``): the diagonal hop runs the kernel's causal
  path, earlier shards run full attention, later shards are skipped.
* **Striped ring attention** (`striped_ring_attention`): tokens are laid
  out round-robin (local slot j ↔ global j·sp + rank), so under a causal
  mask every hop carries an (almost) equal triangle of work instead of
  rank 0 idling — the Striped Attention load-balance fix.  The per-hop
  causal mask reduces to ``j_q >= j_k`` (diagonal-inclusive when
  rank ≥ source, strict otherwise); fully-masked rows are guarded so the
  fold never folds ``exp(0)`` garbage.
* **Ulysses** (`ulysses_attention`): ``all_to_all`` swaps the head dim for
  the sequence dim (heads must divide sp), runs dense/flash attention on
  full sequences of the local heads, and swaps back.  Two all_to_alls per
  layer vs sp ppermutes — better when heads ≥ sp and ICI all_to_all
  bandwidth is good (within a pod).

Masking is dtype-aware (:func:`mask_value`: half of ``finfo.min`` for the
score dtype, so two masked scores can never sum past the representable
range) and the fold guards rows that have seen no real key yet —
``exp(mask - mask) == 1`` used to pollute the accumulator whenever a hop
was fully masked before any real hop, which plain causal ring ordering
happens to avoid (hop 0 is always the diagonal) but striped layouts and
padded tails do not.

Both are plain differentiable JAX (ppermute/all_to_all have transposes), so
jax.grad through a shard_map'd call gives the distributed backward.
"""

from __future__ import annotations

import functools
import os
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from paddle_tpu.distributed.communication import axis_size as _axis_size

__all__ = ["ring_attention", "striped_ring_attention", "ulysses_attention",
           "make_ring_attention", "make_striped_ring_attention",
           "make_ulysses_attention", "ring_flash_enabled", "mask_value"]

_NEG_INF = -1e30   # legacy floor; real masking routes through mask_value()


def mask_value(dtype=jnp.float32) -> float:
    """Dtype-aware large-negative mask score: half of ``finfo.min`` for
    the dtype the scores are computed in, so the sum of two masked
    scores (or mask + finite score) stays representable — ``-1e30``
    overflows to ``-inf`` the moment bf16/fp16 score math touches it."""
    return float(jnp.finfo(jnp.dtype(dtype)).min) / 2


def ring_flash_enabled() -> bool:
    """``PADDLE_TPU_RING_FLASH=1`` makes the flash-backed per-hop fold
    the default ``ring_attention`` implementation."""
    raw = os.environ.get("PADDLE_TPU_RING_FLASH")
    return raw is not None and raw.strip().lower() in ("1", "true", "yes",
                                                       "on")


def _overlap_state():
    """(overlap_enabled, counter_inc) — PR 15's ppermute-before-fold
    trace-time routing, shared by every ring variant."""
    from paddle_tpu.distributed.sharding import (overlap_enabled,
                                                 overlap_path_counter)
    on = overlap_enabled()
    if on:
        overlap_path_counter().labels(path="ring_exchange").inc()
    return on


def ring_attention(q, k, v, axis_name: str = "sp", causal: bool = False,
                   scale: Optional[float] = None,
                   impl: Optional[str] = None):
    """Blockwise ring attention INSIDE shard_map.

    q, k, v: local shards [batch, seq_local, heads, head_dim]; the global
    sequence is the concatenation over the sp axis in rank order.
    ``impl``: "dense" (online-softmax fold, exact at any head_dim),
    "flash" (per-hop flash-attention kernel + lse merge — O(seq/sp)
    memory, needs flash-legal shapes), or None → the
    PADDLE_TPU_RING_FLASH knob (off → dense, the previous program).
    Returns the local output shard [batch, seq_local, heads, head_dim].
    """
    if impl is None:
        impl = "flash" if ring_flash_enabled() else "dense"
    if impl not in ("dense", "flash"):
        raise ValueError(f"unknown ring impl {impl!r}")
    if impl == "flash":
        return _ring_flash(q, k, v, axis_name=axis_name, causal=causal,
                           scale=scale)
    return _ring_dense(q, k, v, axis_name=axis_name, causal=causal,
                       scale=scale, striped=False)


def striped_ring_attention(q, k, v, axis_name: str = "sp",
                           causal: bool = True,
                           scale: Optional[float] = None):
    """Striped ring attention INSIDE shard_map: local slot j holds
    global token ``j * sp + rank`` (callers stripe the sequence:
    ``x[:, rank::sp]``), which balances the causal triangle across hops
    — with the contiguous layout, hop i attends src > rank to nothing
    while rank sp-1 does full work.  The per-hop mask is
    ``j_q >= j_k + (rank < src)``: diagonal-inclusive when the query
    rank is at or past the source rank, strict otherwise."""
    return _ring_dense(q, k, v, axis_name=axis_name, causal=causal,
                       scale=scale, striped=True)


def _ring_dense(q, k, v, *, axis_name, causal, scale, striped):
    sp = _axis_size(axis_name)
    idx = lax.axis_index(axis_name)
    b, s, h, d = q.shape
    if scale is None:
        scale = 1.0 / (d ** 0.5)

    # GQA: broadcast kv heads
    if k.shape[2] != h:
        rep = h // k.shape[2]
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)

    qf = q.astype(jnp.float32)
    neg = mask_value(jnp.float32)                      # scores are fp32
    local = jnp.arange(s)
    q_pos = idx * s + local                            # global q positions

    perm = [(i, (i + 1) % sp) for i in range(sp)]
    from paddle_tpu.robustness import fault_point
    # dead-ring-peer drill: fires as the K/V rotation is laid out — the
    # trace fails loudly (never a silent wrong answer) and nothing is
    # cached, so clearing the fault restores the path on the next call
    fault_point("sp.ring_peer", axis=axis_name, sp=int(sp), impl="dense")

    # async ring exchange (ISSUE 15): with PADDLE_TPU_COLLECTIVE_OVERLAP
    # the rotation is issued BEFORE the fold — the ppermute has no data
    # dependency on this step's softmax/matmuls, so an async-collective
    # scheduler streams the next K/V shard in under the current fold's
    # compute instead of paying the ICI hop at the step boundary.
    # Trace-time routing: knob off keeps the exact previous program.
    overlap = _overlap_state()

    def step(carry, i):
        o, m, l, k_cur, v_cur = carry
        src = (idx - i) % sp                           # owner of current kv
        if overlap:
            # issue the rotation first: comm rides under the fold below
            k_nxt = lax.ppermute(k_cur, axis_name, perm)
            v_nxt = lax.ppermute(v_cur, axis_name, perm)
        scores = jnp.einsum("bqhd,bkhd->bhqk", qf,
                            k_cur.astype(jnp.float32)) * scale
        if causal:
            if striped:
                # local slot j is global j*sp + rank: strict triangle
                # against sources this rank has not yet passed
                strict = (idx < src).astype(local.dtype)
                mask = local[:, None] >= (local[None, :] + strict)
            else:
                k_pos = src * s + local
                mask = q_pos[:, None] >= k_pos[None, :]    # [sq, sk]
            scores = jnp.where(mask[None, None], scores, neg)
        m_cur = jnp.max(scores, axis=-1, keepdims=True)   # [b,h,q,1]
        m_new = jnp.maximum(m, m_cur)
        # rows that have seen no real key keep m_new at the mask floor;
        # without the guard exp(mask - mask) == 1 folds garbage rows in
        # (plain causal ordering dodges this — hop 0 is the diagonal —
        # striped layouts and padded tails do not)
        alive = m_new > neg * 0.5
        p = jnp.where(alive, jnp.exp(scores - m_new), 0.0)
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=-1, keepdims=True)
        pv = jnp.einsum("bhqk,bkhd->bhqd", p, v_cur.astype(jnp.float32))
        o_new = o * corr + pv
        if not overlap:
            # rotate kv to the next rank (skip after the last fold)
            k_nxt = lax.ppermute(k_cur, axis_name, perm)
            v_nxt = lax.ppermute(v_cur, axis_name, perm)
        return (o_new, m_new, l_new, k_nxt, v_nxt), None

    from paddle_tpu.distributed.communication import pvary_like
    # accumulators must vary over EVERY manual axis the kv blocks vary
    # over (not just the ring axis) — on an (sp, tp) mesh the heads are
    # tp-sharded and the carry types must agree across scan steps
    o0 = pvary_like(jnp.zeros((b, h, s, d), jnp.float32), qf)
    m0 = pvary_like(jnp.full((b, h, s, 1), neg, jnp.float32), qf)
    l0 = pvary_like(jnp.zeros((b, h, s, 1), jnp.float32), qf)
    (o, m, l, _, _), _ = lax.scan(step, (o0, m0, l0, k, v),
                                  jnp.arange(sp))
    safe_l = jnp.where(l > 0, l, 1.0)
    out = (o / safe_l).astype(q.dtype)                 # [b,h,s,d]
    return jnp.swapaxes(out, 1, 2)                     # [b,s,h,d]


def _flash_blocks(s: int) -> int:
    """Largest flash block that tiles the local sequence."""
    for c in (128, 64, 32, 16, 8):
        if s % c == 0 and s >= c:
            return c
    return s


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash_hop_core(q, k, v, scale, causal, blk, interpret):
    """(out, lse) of one flash hop ([b, h, s, d] operands), with a VJP
    that accepts cotangents for BOTH outputs — the ring fold weights
    each hop by its lse, so dlse is structurally nonzero (the raw
    pallas_call has no autodiff rule, and the stock flash custom VJP
    discards lse)."""
    return _flash_hop_fwd(q, k, v, scale, causal, blk, interpret)[0]


def _flash_hop_fwd(q, k, v, scale, causal, blk, interpret):
    from paddle_tpu.ops.pallas.flash_attention import _fwd_pallas
    o, lse = _fwd_pallas(q, k, v, scale=scale, causal=causal,
                         block_q=blk, block_k=blk, interpret=interpret)
    o = o.astype(jnp.float32)
    return (o, lse), (q, k, v, o, lse)


def _flash_hop_bwd(scale, causal, blk, interpret, res, ct):
    # softmax-through-lse backward: with p = exp(s - lse) the combined
    # cotangent is ds = p ⊙ (dp − delta + dlse·1ᵀ) — the dlse term is
    # exactly the softmax jacobian of the log-normalizer.  Recomputes
    # the [s, s] score block per hop in fp32 (same memory class as the
    # dense ring backward).
    q, k, v, o, lse = res
    do, dlse = ct
    qf = q.astype(jnp.float32)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    dof = do.astype(jnp.float32)
    s = jnp.einsum("bhqd,bhkd->bhqk", qf, kf) * scale
    p = jnp.exp(s - lse[..., None])
    if causal:
        n = q.shape[2]
        pos = jnp.arange(n)
        mask = pos[:, None] >= pos[None, :]
        p = jnp.where(mask[None, None], p, 0.0)
    delta = jnp.sum(dof * o, axis=-1)                  # [b, h, s]
    dp = jnp.einsum("bhqd,bhkd->bhqk", dof, vf)
    ds = p * (dp - delta[..., None] + dlse[..., None])
    dq = scale * jnp.einsum("bhqk,bhkd->bhqd", ds, kf)
    dk = scale * jnp.einsum("bhqk,bhqd->bhkd", ds, qf)
    dv = jnp.einsum("bhqk,bhqd->bhkd", p, dof)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


_flash_hop_core.defvjp(_flash_hop_fwd, _flash_hop_bwd)


def _lse_fold(o1, l1, o2, l2):
    """Merge two normalized attention partials by log-sum-exp:
    ``o = o1·exp(l1-l) + o2·exp(l2-l)`` with ``l = logaddexp(l1, l2)``.
    ``-inf`` lse (a skipped/fully-masked partial) contributes exactly
    zero weight — guarded so ``-inf - -inf`` never makes a NaN."""
    l_new = jnp.logaddexp(l1, l2)
    safe = jnp.where(jnp.isfinite(l_new), l_new, 0.0)
    w1 = jnp.where(jnp.isfinite(l1), jnp.exp(l1 - safe), 0.0)
    w2 = jnp.where(jnp.isfinite(l2), jnp.exp(l2 - safe), 0.0)
    return o1 * w1[..., None] + o2 * w2[..., None], l_new


def _ring_flash(q, k, v, *, axis_name, causal, scale):
    """Per-hop flash fold: each incoming K/V shard runs through the
    flash-attention Pallas kernel (out + lse) and hops merge by
    log-sum-exp — the [s_local, s_local] score matrix never exists, so
    ring memory is O(seq/sp) end to end.  Causal hops route by ring
    position: the diagonal hop (src == rank) is the kernel's causal
    path (local positions align), earlier shards (src < rank) are fully
    visible, later shards are skipped without touching the MXU."""
    sp = _axis_size(axis_name)
    idx = lax.axis_index(axis_name)
    b, s, h, d = q.shape
    if scale is None:
        scale = 1.0 / (d ** 0.5)
    if k.shape[2] != h:
        rep = h // k.shape[2]
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)

    interpret = jax.default_backend() != "tpu"
    blk = _flash_blocks(s)
    qT = jnp.swapaxes(q, 1, 2)                         # [b, h, s, d]

    def flash_hop(k_cur, v_cur, hop_causal):
        return _flash_hop_core(qT, jnp.swapaxes(k_cur, 1, 2),
                               jnp.swapaxes(v_cur, 1, 2), scale,
                               hop_causal, blk, interpret)

    perm = [(i, (i + 1) % sp) for i in range(sp)]
    from paddle_tpu.robustness import fault_point
    fault_point("sp.ring_peer", axis=axis_name, sp=int(sp), impl="flash")
    overlap = _overlap_state()

    def step(carry, i):
        o, l, k_cur, v_cur = carry
        src = (idx - i) % sp
        if overlap:
            k_nxt = lax.ppermute(k_cur, axis_name, perm)
            v_nxt = lax.ppermute(v_cur, axis_name, perm)
        if causal:
            def diag(k_, v_):
                return flash_hop(k_, v_, True)

            def full(k_, v_):
                return flash_hop(k_, v_, False)

            def skip(k_, v_):
                return jnp.zeros_like(o), jnp.full_like(l, -jnp.inf)

            case = jnp.where(src == idx, 0, jnp.where(src < idx, 1, 2))
            o_h, l_h = lax.switch(case, (diag, full, skip), k_cur, v_cur)
        else:
            o_h, l_h = flash_hop(k_cur, v_cur, False)
        o_new, l_new = _lse_fold(o, l, o_h, l_h)
        if not overlap:
            k_nxt = lax.ppermute(k_cur, axis_name, perm)
            v_nxt = lax.ppermute(v_cur, axis_name, perm)
        return (o_new, l_new, k_nxt, v_nxt), None

    from paddle_tpu.distributed.communication import pvary_like
    o0 = pvary_like(jnp.zeros((b, h, s, d), jnp.float32), q)
    l0 = pvary_like(jnp.full((b, h, s), -jnp.inf, jnp.float32), q)
    (o, _, _, _), _ = lax.scan(step, (o0, l0, k, v), jnp.arange(sp))
    return jnp.swapaxes(o.astype(q.dtype), 1, 2)       # [b,s,h,d]


def ulysses_attention(q, k, v, axis_name: str = "sp", causal: bool = False,
                      scale: Optional[float] = None,
                      attn_fn=None):
    """Ulysses sequence parallelism INSIDE shard_map.

    q, k, v: local shards [batch, seq_local, heads, head_dim]; heads must be
    divisible by the sp axis size.  all_to_all to [batch, seq_global,
    heads_local, head_dim], run full attention per local head, swap back.
    `attn_fn(q, k, v, causal, scale)` defaults to the XLA sdpa; pass the
    flash kernel for long sequences.
    """
    sp = _axis_size(axis_name)
    b, s, h, d = q.shape
    if h % sp:
        raise ValueError(f"heads {h} not divisible by sp={sp}")

    def swap_in(x):   # [b, s_l, h, d] -> [b, s_g, h_l, d]
        return lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1,
                              tiled=True)

    def swap_out(x):  # [b, s_g, h_l, d] -> [b, s_l, h, d]
        return lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2,
                              tiled=True)

    qg, kg, vg = swap_in(q), swap_in(k), swap_in(v)
    if attn_fn is None:
        from paddle_tpu.nn.functional.attention import _sdpa_reference
        out = _sdpa_reference(qg, kg, vg, is_causal=causal, scale=scale)
    else:
        out = attn_fn(qg, kg, vg, causal=causal, scale=scale)
    return swap_out(out)


def _wrap_shard_map(fn, mesh, axis_name, seq_axis=1, kernel=False):
    from jax.sharding import PartitionSpec as P

    from paddle_tpu.distributed.communication import shard_map
    spec = [None, None, None, None]
    spec[seq_axis] = axis_name
    spec = P(*spec)
    if kernel:
        from paddle_tpu.ops.pallas.mesh import shard_map_kernel
        return shard_map_kernel(fn, mesh, (spec, spec, spec), spec)
    return shard_map(fn, mesh=mesh, in_specs=(spec, spec, spec),
                     out_specs=spec)


def make_ring_attention(mesh, axis_name: str = "sp", causal: bool = False,
                        scale=None, impl: Optional[str] = None):
    """Top-level entry: global [b, seq, h, d] arrays sharded on `axis_name`
    → shard_map'd ring attention."""
    fn = functools.partial(ring_attention, axis_name=axis_name,
                           causal=causal, scale=scale, impl=impl)
    flash = impl == "flash" or (impl is None and ring_flash_enabled())
    return _wrap_shard_map(lambda q, k, v: fn(q, k, v), mesh, axis_name,
                           kernel=flash)


def make_striped_ring_attention(mesh, axis_name: str = "sp",
                                causal: bool = True, scale=None):
    """Top-level entry for the striped layout.  Operands must already be
    striped (global token j·sp + rank at local slot j — e.g.
    ``x[:, rank::sp]`` gathered per shard); outputs come back in the
    same striped layout."""
    fn = functools.partial(striped_ring_attention, axis_name=axis_name,
                           causal=causal, scale=scale)
    return _wrap_shard_map(lambda q, k, v: fn(q, k, v), mesh, axis_name)


def make_ulysses_attention(mesh, axis_name: str = "sp",
                           causal: bool = False, scale=None, attn_fn=None):
    fn = functools.partial(ulysses_attention, axis_name=axis_name,
                           causal=causal, scale=scale, attn_fn=attn_fn)
    return _wrap_shard_map(lambda q, k, v: fn(q, k, v), mesh, axis_name)
