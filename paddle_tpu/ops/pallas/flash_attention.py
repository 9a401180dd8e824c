"""Flash attention — Pallas TPU kernel.

Replaces the reference's CUDA flash-attn integration
(python/paddle/nn/functional/flash_attention.py → _C_ops.flash_attn,
kernels under paddle/phi/kernels/gpu/flash_attn_*) with a TPU-native
blockwise kernel:

* forward: online-softmax over K/V blocks streamed HBM→VMEM by the grid
  pipeline; scores/accumulators live in VMEM scratch in fp32; the MXU does
  the two matmuls per block.  Saves per-row logsumexp for the backward.
  GQA/MQA is handled in the grid itself: the K/V BlockSpec index map sends
  q-head h to kv-head h // (hq // hk), so KV tiles are fetched once per
  group instead of materializing repeated heads in HBM.
* backward: blockwise recompute from the saved logsumexp (flash-attention-2
  style) expressed in JAX with grouped-GQA einsums and left to XLA to fuse —
  dQ/dK/dV each come from one scan over blocks, so backward memory is
  O(seq·block), not O(seq²), and dK/dV sum over the query group without
  ever materializing repeated KV.

Mosaic legality notes (the round-1 kernel broke here): every output block's
last two dims must be (divisible by 8, divisible by 128) or equal to the
array dims.  The logsumexp is therefore emitted as [b, h, nq, 1, block_q]
— block (1,1,1,1,block_q) is legal because the trailing two dims equal the
array's — and reshaped to [b, h, s] outside the kernel.

Layout: [batch, seq, heads, head_dim] (paddle convention) at the API;
kernels see [batch, heads, seq, head_dim].
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from jax.experimental.pallas import tpu as pltpu

__all__ = ["flash_attention", "flash_bwd_env"]


def flash_bwd_env():
    """Backward-implementation override from the environment:
    ``PADDLE_TPU_FLASH_BWD=1`` forces the Pallas dq/dkv kernels, ``0``
    the blockwise-jax recompute; unset → None (autotuner / call site
    decides).  ``PT_FLASH_PALLAS_BWD`` is honored as a legacy alias."""
    raw = os.environ.get("PADDLE_TPU_FLASH_BWD",
                         os.environ.get("PT_FLASH_PALLAS_BWD"))
    if raw is None:
        return None
    return raw.strip().lower() in ("1", "true", "yes", "on")


def _bwd_path_counter():
    from paddle_tpu.observability import default_registry
    return default_registry().counter(
        "paddle_tpu_flash_bwd_path_total",
        "flash-attention backward implementation chosen at trace time",
        labelnames=("path",))

_NEG_INF = -1e30


def _out_struct(shape, dtype, *operands):
    """An ``out_shape`` that varies over every manual mesh axis its
    operands vary over — ``pallas_call`` under ``shard_map`` demands the
    ``vma`` of each output (outside ``shard_map`` the set is empty)."""
    vma = frozenset().union(*(jax.typeof(x).vma for x in operands))
    return jax.ShapeDtypeStruct(shape, dtype, vma=vma)


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref,
                acc_ref, m_ref, l_ref, *, block_q, block_k, scale, causal):
    """Grid: (batch, q_heads, num_q_blocks, num_k_blocks); the k axis is the
    innermost (sequential) dim, so VMEM scratch carries the online-softmax
    state across k blocks."""
    qi = pl.program_id(2)
    kj = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(kj == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    def _compute():
        q = q_ref[0, 0]                                # [bq, d]
        k = k_ref[0, 0]                                # [bk, d]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # [bq, bk]

        if causal:
            q_pos = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            k_pos = kj * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(q_pos >= k_pos, s, _NEG_INF)

        m_prev = m_ref[:]                              # [bq, 1]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)                         # [bq, bk]
        correction = jnp.exp(m_prev - m_new)           # [bq, 1]
        l_new = correction * l_ref[:] + jnp.sum(p, axis=-1, keepdims=True)
        pv = jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[0, 0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)        # [bq, d]
        acc_ref[:] = acc_ref[:] * correction + pv
        m_ref[:] = m_new
        l_ref[:] = l_new

    if causal:
        # whole block above the diagonal → nothing to do
        @pl.when(kj * block_k <= qi * block_q + (block_q - 1))
        def _():
            _compute()
    else:
        _compute()

    @pl.when(kj == nk - 1)
    def _finalize():
        l = l_ref[:]
        safe_l = jnp.where(l > 0, l, 1.0)
        o_ref[0, 0] = (acc_ref[:] / safe_l).astype(o_ref.dtype)
        lse = m_ref[:] + jnp.log(safe_l)               # [bq, 1]
        lse_ref[0, 0, 0] = lse.reshape(1, block_q)


def _fwd_pallas(q, k, v, *, scale, causal, block_q, block_k,
                interpret=False):
    """q: [b, hq, s, d]; k,v: [b, hk, s, d] → (out [b, hq, s, d],
    lse [b, hq, s] fp32)."""
    b, hq, s, d = q.shape
    hk = k.shape[1]
    rep = hq // hk
    nq = pl.cdiv(s, block_q)
    nk = pl.cdiv(s, block_k)

    kernel = functools.partial(
        _fwd_kernel, block_q=block_q, block_k=block_k, scale=scale,
        causal=causal)

    scratch = [
        pltpu.VMEM((block_q, d), jnp.float32),
        pltpu.VMEM((block_q, 1), jnp.float32),
        pltpu.VMEM((block_q, 1), jnp.float32),
    ]

    params = {}
    if not interpret:
        params["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"))

    out, lse5 = pl.pallas_call(
        kernel,
        grid=(b, hq, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d),
                         lambda b_, h, i, j: (b_, h, i, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda b_, h, i, j: (b_, h // rep, j, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda b_, h, i, j: (b_, h // rep, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_q, d),
                         lambda b_, h, i, j: (b_, h, i, 0)),
            pl.BlockSpec((1, 1, 1, 1, block_q),
                         lambda b_, h, i, j: (b_, h, i, 0, 0)),
        ],
        out_shape=[
            _out_struct((b, hq, s, d), q.dtype, q, k, v),
            _out_struct((b, hq, nq, 1, block_q), jnp.float32, q, k, v),
        ],
        scratch_shapes=scratch,
        name="flash_fwd",
        interpret=interpret,
        **params,
    )(q, k, v)
    return out, lse5.reshape(b, hq, s)


# -- backward: Pallas kernels (flash-attn-2 equations) -----------------------
#
# Both kernels work in TRANSPOSED score space (s_T[k, q] instead of
# s[q, k]): the per-ROW softmax statistics (lse, delta) then enter as
# [1, block_q] row vectors that broadcast over the k dimension with no
# in-kernel transpose/relayout, and every contraction is a dot_general the
# MXU handles directly.

def _bwd_dq_kernel(q_ref, k_ref, v_ref, g_ref, lse_ref, delta_ref, dq_ref,
                   acc_ref, *, block_q, block_k, scale, causal):
    """Grid: (b, hq, nq, nk); k inner — dq accumulates across k blocks."""
    qi = pl.program_id(2)
    kj = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(kj == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    def _compute():
        q = q_ref[0, 0]                                 # [bq, d]
        k = k_ref[0, 0]                                 # [bk, d]
        v = v_ref[0, 0]
        g = g_ref[0, 0]
        lse_row = lse_ref[0, 0, 0]                      # [1, bq]
        delta_row = delta_ref[0, 0, 0]                  # [1, bq]

        s_t = jax.lax.dot_general(
            k, q, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # [bk, bq]
        if causal:
            k_pos = kj * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_k, block_q), 0)
            q_pos = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_k, block_q), 1)
            s_t = jnp.where(q_pos >= k_pos, s_t, _NEG_INF)
        p_t = jnp.exp(s_t - lse_row)                    # [bk, bq]
        dp_t = jax.lax.dot_general(
            v, g, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)         # [bk, bq]
        ds_t = p_t * (dp_t - delta_row) * scale
        # dq[q, d] = sum_k ds_T[k, q] * k[k, d]
        acc_ref[:] += jax.lax.dot_general(
            ds_t, k, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    if causal:
        @pl.when(kj * block_k <= qi * block_q + (block_q - 1))
        def _():
            _compute()
    else:
        _compute()

    @pl.when(kj == nk - 1)
    def _flush():
        dq_ref[0, 0] = acc_ref[:].astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, g_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, dk_acc, dv_acc, *, block_q, block_k,
                    nq, rep, scale, causal):
    """Grid: (b, hk, nk, rep*nq); inner axis walks every (group head,
    q block) pair — dk/dv accumulate over the whole query group, so
    repeated KV heads are never materialized (GQA)."""
    kj = pl.program_id(2)
    t = pl.program_id(3)
    nt = pl.num_programs(3)
    qi = t % nq

    @pl.when(t == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    def _compute():
        q = q_ref[0, 0]                                 # [bq, d]
        k = k_ref[0, 0]                                 # [bk, d]
        v = v_ref[0, 0]
        g = g_ref[0, 0]
        lse_row = lse_ref[0, 0, 0]                      # [1, bq]
        delta_row = delta_ref[0, 0, 0]

        s_t = jax.lax.dot_general(
            k, q, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # [bk, bq]
        if causal:
            k_pos = kj * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_k, block_q), 0)
            q_pos = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_k, block_q), 1)
            s_t = jnp.where(q_pos >= k_pos, s_t, _NEG_INF)
        p_t = jnp.exp(s_t - lse_row)
        # dv[k, d] = sum_q p_T[k, q] * g[q, d]
        dv_acc[:] += jax.lax.dot_general(
            p_t.astype(jnp.float32), g.astype(jnp.float32),
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp_t = jax.lax.dot_general(
            v, g, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds_t = p_t * (dp_t - delta_row) * scale
        # dk[k, d] = sum_q ds_T[k, q] * q[q, d]
        dk_acc[:] += jax.lax.dot_general(
            ds_t, q.astype(jnp.float32), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    if causal:
        @pl.when(kj * block_k <= qi * block_q + (block_q - 1))
        def _():
            _compute()
    else:
        _compute()

    @pl.when(t == nt - 1)
    def _flush():
        dk_ref[0, 0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[:].astype(dv_ref.dtype)


def _bwd_pallas(res, g, *, scale, causal, block_q, block_k, interpret):
    """Pallas flash backward: dq from one kernel (k inner), dk/dv from a
    second (query-group inner).  lse/delta ride as [b,hq,nq,1,bq] so each
    q block's statistics arrive as a [1, bq] row vector."""
    q, k, v, out, lse = res      # q,out [b,hq,s,d]; k,v [b,hk,s,d]
    b, hq, s, d = q.shape
    hk = k.shape[1]
    rep = hq // hk
    nq = pl.cdiv(s, block_q)
    nk = pl.cdiv(s, block_k)

    delta = jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1)                              # [b, hq, s]
    lse5 = lse.reshape(b, hq, nq, 1, block_q)
    delta5 = delta.reshape(b, hq, nq, 1, block_q)

    params = {}
    if not interpret:
        params["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"))

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, block_q=block_q, block_k=block_k,
                          scale=scale, causal=causal),
        grid=(b, hq, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d),
                         lambda b_, h, i, j: (b_, h, i, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda b_, h, i, j: (b_, h // rep, j, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda b_, h, i, j: (b_, h // rep, j, 0)),
            pl.BlockSpec((1, 1, block_q, d),
                         lambda b_, h, i, j: (b_, h, i, 0)),
            pl.BlockSpec((1, 1, 1, 1, block_q),
                         lambda b_, h, i, j: (b_, h, i, 0, 0)),
            pl.BlockSpec((1, 1, 1, 1, block_q),
                         lambda b_, h, i, j: (b_, h, i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, d),
                               lambda b_, h, i, j: (b_, h, i, 0)),
        out_shape=_out_struct((b, hq, s, d), q.dtype, q, k, v, g),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        name="flash_bwd_dq",
        interpret=interpret,
        **params,
    )(q, k, v, g, lse5, delta5)

    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, block_q=block_q,
                          block_k=block_k, nq=nq, rep=rep, scale=scale,
                          causal=causal),
        grid=(b, hk, nk, rep * nq),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d),
                         lambda b_, g_, j, t: (b_, g_ * rep + t // nq,
                                               t % nq, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda b_, g_, j, t: (b_, g_, j, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda b_, g_, j, t: (b_, g_, j, 0)),
            pl.BlockSpec((1, 1, block_q, d),
                         lambda b_, g_, j, t: (b_, g_ * rep + t // nq,
                                               t % nq, 0)),
            pl.BlockSpec((1, 1, 1, 1, block_q),
                         lambda b_, g_, j, t: (b_, g_ * rep + t // nq,
                                               t % nq, 0, 0)),
            pl.BlockSpec((1, 1, 1, 1, block_q),
                         lambda b_, g_, j, t: (b_, g_ * rep + t // nq,
                                               t % nq, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_k, d),
                         lambda b_, g_, j, t: (b_, g_, j, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda b_, g_, j, t: (b_, g_, j, 0)),
        ],
        out_shape=[
            _out_struct((b, hk, s, d), k.dtype, q, k, v, g),
            _out_struct((b, hk, s, d), v.dtype, q, k, v, g),
        ],
        scratch_shapes=[pltpu.VMEM((block_k, d), jnp.float32),
                        pltpu.VMEM((block_k, d), jnp.float32)],
        name="flash_bwd_dkv",
        interpret=interpret,
        **params,
    )(q, k, v, g, lse5, delta5)
    return dq, dk, dv


# -- backward: blockwise recompute in JAX (flash-attn-2 equations) -----------

def _bwd_blockwise(res, g, *, scale, causal, block_k):
    """Memory-efficient backward: scan over K/V blocks; recompute P from
    q,k and the saved logsumexp.  Grouped-GQA einsums keep KV at hk heads;
    dK/dV sum over the query group (r axis) inside the contraction.  All
    matmuls MXU-shaped; XLA fuses the elementwise chain."""
    q, k, v, out, lse = res      # q,out [b,hq,s,d]; k,v [b,hk,s,d]
    b, hq, s, d = q.shape
    hk = k.shape[1]
    rep = hq // hk
    g = g.astype(jnp.float32)
    qf = q.astype(jnp.float32).reshape(b, hk, rep, s, d)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    of = out.astype(jnp.float32)
    gg = g.reshape(b, hk, rep, s, d)
    lse_g = lse.reshape(b, hk, rep, s)

    # delta_i = sum_d(dO * O) — rowwise (flash-attn-2 eq. 4)
    delta = jnp.sum(g * of, axis=-1).reshape(b, hk, rep, s)

    nk = s // block_k
    kb = kf.reshape(b, hk, nk, block_k, d)
    vb = vf.reshape(b, hk, nk, block_k, d)

    q_pos = jnp.arange(s)

    def one_block(j):
        kj = kb[:, :, j]                               # [b, hk, bk, d]
        vj = vb[:, :, j]
        sij = jnp.einsum("bgrqd,bgkd->bgrqk", qf, kj) * scale
        if causal:
            k_pos = j * block_k + jnp.arange(block_k)
            mask = q_pos[:, None] >= k_pos[None, :]
            sij = jnp.where(mask[None, None, None], sij, _NEG_INF)
        pij = jnp.exp(sij - lse_g[..., None])          # [b,g,r,q,bk]
        dv_j = jnp.einsum("bgrqk,bgrqd->bgkd", pij, gg)
        dp = jnp.einsum("bgrqd,bgkd->bgrqk", gg, vj)
        ds = pij * (dp - delta[..., None]) * scale
        dq_contrib = jnp.einsum("bgrqk,bgkd->bgrqd", ds, kj)
        dk_j = jnp.einsum("bgrqk,bgrqd->bgkd", ds, qf)
        return dq_contrib, dk_j, dv_j

    def scan_body(dq_acc, j):
        dq_c, dk_j, dv_j = one_block(j)
        return dq_acc + dq_c, (dk_j, dv_j)

    dq, (dks, dvs) = jax.lax.scan(scan_body, jnp.zeros_like(qf),
                                  jnp.arange(nk))
    dq = dq.reshape(b, hq, s, d)
    dk = jnp.moveaxis(dks, 0, 2).reshape(b, hk, s, d)
    dv = jnp.moveaxis(dvs, 0, 2).reshape(b, hk, s, d)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _flash_core(q, k, v, scale, causal, block_q, block_k, interpret,
                pallas_bwd):
    out, _ = _flash_fwd(q, k, v, scale, causal, block_q, block_k,
                        interpret, pallas_bwd)
    return out


def _flash_fwd(q, k, v, scale, causal, block_q, block_k, interpret,
               pallas_bwd):
    out, lse = _fwd_pallas(q, k, v, scale=scale, causal=causal,
                           block_q=block_q, block_k=block_k,
                           interpret=interpret)
    return out, (q, k, v, out, lse)


def _flash_bwd(scale, causal, block_q, block_k, interpret, pallas_bwd,
               res, g):
    if pallas_bwd:
        return _bwd_pallas(res, g, scale=scale, causal=causal,
                           block_q=block_q, block_k=block_k,
                           interpret=interpret)
    return _bwd_blockwise(res, g, scale=scale, causal=causal,
                          block_k=block_k)


_flash_core.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(q, k, v, causal: bool = False, scale=None,
                    block_q: int = None, block_k: int = None,
                    interpret: bool = None, pallas_bwd: bool = None,
                    autotune: bool = None):
    """q: [batch, seq, heads, head_dim]; k,v: [batch, seq, kv_heads,
    head_dim] (paddle layout).  Requires seq divisible by the block sizes
    (callers pad; the model stack keeps seq a multiple of 128 for MXU
    efficiency anyway) and heads % kv_heads == 0.

    block_q/block_k — and the backward implementation, when
    ``pallas_bwd`` is left None — default to the autotuner's cached
    choice on TPU (measured once per shape, persisted — reference analog:
    phi/kernels/autotune/auto_tune_base.h); elsewhere min(128, s) blocks
    and the Pallas backward.  ``pallas_bwd=False`` forces the
    blockwise-jax backward, True the Pallas dq/dkv kernels."""
    b, s, h, d = q.shape
    hk = k.shape[2]
    if h % hk:
        raise ValueError(f"q heads {h} not a multiple of kv heads {hk}")
    if scale is None:
        scale = 1.0 / (d ** 0.5)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    if autotune is None:
        autotune = not interpret
    if pallas_bwd is None:
        pallas_bwd = flash_bwd_env()
    if block_q is None or block_k is None or pallas_bwd is None:
        if autotune and not interpret:
            from paddle_tpu.ops.pallas.autotune import flash_block_sizes
            bq_t, bk_t, pb_t = flash_block_sizes(
                b, s, h, hk, d, str(q.dtype), bool(causal),
                pallas_bwd=pallas_bwd)
            block_q = block_q or bq_t
            block_k = block_k or bk_t
            if pallas_bwd is None:
                pallas_bwd = pb_t
        else:
            block_q = block_q or min(128, s)
            block_k = block_k or min(128, s)
            if pallas_bwd is None:
                pallas_bwd = True
    block_q = min(block_q, s)
    block_k = min(block_k, s)
    if s % block_q or s % block_k:
        raise ValueError(f"seq {s} must be divisible by block sizes "
                         f"({block_q},{block_k})")

    # trace-time telemetry: which backward this compile will run
    _bwd_path_counter().labels(
        path="pallas" if pallas_bwd else "blockwise").inc()

    def to_bhsd(x):
        return jnp.swapaxes(x, 1, 2)

    out = _flash_core(to_bhsd(q), to_bhsd(k), to_bhsd(v), float(scale),
                      bool(causal), block_q, block_k, bool(interpret),
                      bool(pallas_bwd))
    return jnp.swapaxes(out, 1, 2)


# ---------------------------------------------------------------------------
# static verification (analysis/kernel_verify) — the fwd / bwd-dq /
# bwd-dkv pallas_calls described as KernelSpecs, same grids and index
# maps the real calls install.


def _fwd_verify_spec(b, s, h, hk, d, bq, bk, dtype):
    from paddle_tpu.analysis import kernel_verify as kv
    rep = h // hk
    nq, nk = s // bq, s // bk
    q4 = (b, h, s, d)
    kv4 = (b, hk, s, d)
    return kv.KernelSpec(
        name="flash_fwd", grid=(b, h, nq, nk),
        args=[
            kv.ArgSpec("q", q4, (1, 1, bq, d),
                       lambda b_, h_, i, j: (b_, h_, i, 0), dtype),
            kv.ArgSpec("k", kv4, (1, 1, bk, d),
                       lambda b_, h_, i, j: (b_, h_ // rep, j, 0), dtype),
            kv.ArgSpec("v", kv4, (1, 1, bk, d),
                       lambda b_, h_, i, j: (b_, h_ // rep, j, 0), dtype),
            kv.ArgSpec("o", q4, (1, 1, bq, d),
                       lambda b_, h_, i, j: (b_, h_, i, 0), dtype,
                       is_output=True),
            kv.ArgSpec("lse", (b, h, nq, 1, bq), (1, 1, 1, 1, bq),
                       lambda b_, h_, i, j: (b_, h_, i, 0, 0), "float32",
                       is_output=True),
        ],
        scratch=[kv.ScratchSpec("acc", (bq, d), "float32"),
                 kv.ScratchSpec("m", (bq, 1), "float32"),
                 kv.ScratchSpec("l", (bq, 1), "float32")],
        dimension_semantics=("parallel", "parallel", "parallel",
                             "arbitrary"),
        needs_fp32_acc=True,
        where=f"flash_fwd[b={b} s={s} h={h}/{hk} d={d} bq={bq} bk={bk} "
              f"{dtype}]")


def _bwd_dq_verify_spec(b, s, h, hk, d, bq, bk, dtype):
    from paddle_tpu.analysis import kernel_verify as kv
    rep = h // hk
    nq, nk = s // bq, s // bk
    q4, kv4, stat5 = (b, h, s, d), (b, hk, s, d), (b, h, nq, 1, bq)
    qmap = lambda b_, h_, i, j: (b_, h_, i, 0)
    kmap = lambda b_, h_, i, j: (b_, h_ // rep, j, 0)
    smap = lambda b_, h_, i, j: (b_, h_, i, 0, 0)
    return kv.KernelSpec(
        name="flash_bwd_dq", grid=(b, h, nq, nk),
        args=[
            kv.ArgSpec("q", q4, (1, 1, bq, d), qmap, dtype),
            kv.ArgSpec("k", kv4, (1, 1, bk, d), kmap, dtype),
            kv.ArgSpec("v", kv4, (1, 1, bk, d), kmap, dtype),
            kv.ArgSpec("g", q4, (1, 1, bq, d), qmap, dtype),
            kv.ArgSpec("lse", stat5, (1, 1, 1, 1, bq), smap, "float32"),
            kv.ArgSpec("delta", stat5, (1, 1, 1, 1, bq), smap, "float32"),
            kv.ArgSpec("dq", q4, (1, 1, bq, d), qmap, dtype,
                       is_output=True),
        ],
        scratch=[kv.ScratchSpec("acc", (bq, d), "float32")],
        dimension_semantics=("parallel", "parallel", "parallel",
                             "arbitrary"),
        needs_fp32_acc=True,
        where=f"flash_bwd_dq[b={b} s={s} h={h}/{hk} d={d} bq={bq} "
              f"bk={bk} {dtype}]")


def _bwd_dkv_verify_spec(b, s, h, hk, d, bq, bk, dtype):
    from paddle_tpu.analysis import kernel_verify as kv
    rep = h // hk
    nq, nk = s // bq, s // bk
    q4, kv4, stat5 = (b, h, s, d), (b, hk, s, d), (b, h, nq, 1, bq)
    qmap = lambda b_, g_, j, t: (b_, g_ * rep + t // nq, t % nq, 0)
    kmap = lambda b_, g_, j, t: (b_, g_, j, 0)
    smap = lambda b_, g_, j, t: (b_, g_ * rep + t // nq, t % nq, 0, 0)
    return kv.KernelSpec(
        name="flash_bwd_dkv", grid=(b, hk, nk, rep * nq),
        args=[
            kv.ArgSpec("q", q4, (1, 1, bq, d), qmap, dtype),
            kv.ArgSpec("k", kv4, (1, 1, bk, d), kmap, dtype),
            kv.ArgSpec("v", kv4, (1, 1, bk, d), kmap, dtype),
            kv.ArgSpec("g", q4, (1, 1, bq, d), qmap, dtype),
            kv.ArgSpec("lse", stat5, (1, 1, 1, 1, bq), smap, "float32"),
            kv.ArgSpec("delta", stat5, (1, 1, 1, 1, bq), smap, "float32"),
            kv.ArgSpec("dk", kv4, (1, 1, bk, d), kmap, dtype,
                       is_output=True),
            kv.ArgSpec("dv", kv4, (1, 1, bk, d), kmap, dtype,
                       is_output=True),
        ],
        scratch=[kv.ScratchSpec("dk_acc", (bk, d), "float32"),
                 kv.ScratchSpec("dv_acc", (bk, d), "float32")],
        dimension_semantics=("parallel", "parallel", "parallel",
                             "arbitrary"),
        needs_fp32_acc=True,
        where=f"flash_bwd_dkv[b={b} s={s} h={h}/{hk} d={d} bq={bq} "
              f"bk={bk} {dtype}]")


def verify_static(b, s, h, hk, d, dtype="bfloat16", causal=True,
                  block_q=None, block_k=None, parts=("fwd", "bwd")):
    """Static Mosaic-legality findings for the flash kernels at this
    shape/config.  ``parts`` selects fwd and/or the two Pallas backward
    kernels; defaults mirror :func:`flash_attention`'s non-autotuned
    block choice (min(128, s))."""
    from paddle_tpu.analysis import kernel_verify as kv
    del causal  # masking happens in-kernel; the layout is causal-agnostic
    dtype = str(dtype)
    bq = min(int(block_q or min(128, s)), s)
    bk = min(int(block_k or min(128, s)), s)
    diags = []
    if "fwd" in parts:
        diags += kv.verify_kernel(_fwd_verify_spec(b, s, h, hk, d, bq, bk,
                                                   dtype))
    if "bwd" in parts:
        diags += kv.verify_kernel(_bwd_dq_verify_spec(b, s, h, hk, d, bq,
                                                      bk, dtype))
        diags += kv.verify_kernel(_bwd_dkv_verify_spec(b, s, h, hk, d, bq,
                                                       bk, dtype))
    return diags
