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
* backward: two Pallas kernels that recompute the probabilities from the
  saved logsumexp (flash-attention-2 style).  dk/dv take a k tile a grid
  step and walk the q tiles of the whole query group inside the kernel
  (GQA without repeated heads); dq takes a q tile and walks the k tiles.
  The walked side sits in VMEM a span at a time and the walk's bounds
  are run-time scalars, so a causal call neither multiplies nor copies a
  tile above the diagonal.  Backward memory is O(seq), and the tiles
  follow a rule read from the shapes (``bwd_tiles``), not a sweep.

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

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from jax.experimental.pallas import tpu as pltpu

__all__ = ["flash_attention", "bwd_tiles"]

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


# -- backward: two Pallas kernels (flash-attn-2 equations) -------------------
#
# One side of the score tile is the grid's (a k tile for dk/dv, a q tile
# for dq); the other side is WALKED inside the kernel, a tile at a time,
# over a span of rows that the grid pipeline keeps in VMEM.  The walk's
# bounds are run-time scalars read from the tile's position, so a causal
# call multiplies no tile above the diagonal, masks only the tiles the
# diagonal crosses, and — the span's index map clamps to the last span
# the diagonal reaches — copies none either.  Every product runs on the
# MXU with operands of the inputs' dtype and float32 accumulation (``p``
# and ``ds`` are cast for it, as the forward casts ``p``); the softmax
# statistics, ``p``, ``dp - delta`` and the accumulators stay float32, and
# the softmax scale meets the float32 accumulators once, at the flush.
#
# dk/dv work in TRANSPOSED score space (s_T[k, q]): the per-row
# statistics enter as [1, tile] rows that broadcast down the k dimension
# and ``p_T @ g`` / ``ds_T @ q`` are plain products.  dq works in score
# space proper (``ds @ k`` is then plain too) and turns its two rows of
# statistics into columns once a grid step.

_BWD_TILE = 512                # rows of a score tile, either side
_BWD_SPAN_BYTES = 8 << 20      # the walked side's blocks, double-buffered


def bwd_tiles(s, d, rep, itemsize):
    """``(tile, q_span, k_span)`` of the backward, read from the shapes
    the call sees.  ``tile``: the largest of 512 / 256 / 128 rows that
    divides ``s`` (a shorter sequence is one tile): at 512 a tile's
    products are microseconds of MXU against a loop step's overhead, and
    its float32 ``[tile, tile]`` intermediates (1 MiB each) still leave
    the blocks room inside Mosaic's 16 MiB.  A span is as many whole tiles
    of the walked side as ``_BWD_SPAN_BYTES`` holds double-buffered — q
    and g of the ``rep`` heads of a group for dk/dv, k and v for dq: a
    span that is the whole sequence is copied once a head, a shorter one
    once a grid step."""
    tile = next((t for t in (_BWD_TILE, 256, 128) if s % t == 0), s)

    def span(row_bytes):
        n = max(1, min(s // tile, _BWD_SPAN_BYTES // (row_bytes * tile)))
        while (s // tile) % n:
            n -= 1
        return n * tile

    return (tile, span(2 * 2 * rep * d * itemsize),
            span(2 * 2 * d * itemsize))


def _dkv_maps(tile_k, q_span, causal, xp=jnp):
    """Index maps of the dk/dv kernel's grid ``(b, kv head, k tile,
    q span)``: ``(walked, own, stat)``.  A causal k tile starts at the
    span that holds its diagonal; earlier grid steps name that span too,
    so nothing is copied for them."""
    def first(j, m):
        if not causal:
            return m
        return xp.maximum(m, (j * tile_k) // q_span)
    return (lambda b_, g_, j, m: (b_, g_, first(j, m), 0),
            lambda b_, g_, j, m: (b_, g_, j, 0),
            lambda b_, g_, j, m: (b_, g_, first(j, m), 0, 0))


def _dq_maps(rep, tile_q, k_span, causal, xp=jnp):
    """Index maps of the dq kernel's grid ``(b, q head, q tile, k
    span)``: ``(own, walked, stat)``.  A causal q tile ends at the span
    that holds its diagonal; later grid steps name that span too."""
    def last(i, m):
        if not causal:
            return m
        return xp.minimum(m, ((i + 1) * tile_q - 1) // k_span)
    return (lambda b_, h, i, m: (b_, h, i, 0),
            lambda b_, h, i, m: (b_, h // rep, last(i, m), 0),
            lambda b_, h, i, m: (b_, h, i, 0, 0))


def _walk(lo, hi, step):
    """``step(i)`` for ``i`` in ``[lo, hi)``: a loop whose bounds are
    run-time scalars and whose state lives in refs."""
    def body(i, carry):
        step(i)
        return carry
    jax.lax.fori_loop(lo, hi, body, 0)


def _positions(rows, cols, row0, col0):
    """``(row position, column position)`` of a ``[rows, cols]`` tile
    whose corner sits at ``(row0, col0)``."""
    shape = (rows, cols)
    return (row0 + jax.lax.broadcasted_iota(jnp.int32, shape, 0),
            col0 + jax.lax.broadcasted_iota(jnp.int32, shape, 1))


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, g_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, dk_acc, dv_acc, *, tile_q, tile_k,
                    q_span, rep, scale, causal):
    """Grid ``(b, kv head, k tile, q span)``; the q span's tiles of every
    head of the group are walked here, dk/dv accumulating over the whole
    query group, so repeated KV heads are never materialized (GQA)."""
    j = pl.program_id(2)
    m = pl.program_id(3)
    n_t = q_span // tile_q
    k0 = j * tile_k

    @pl.when(m == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    k = k_ref[0, 0]                                     # [tk, d]
    v = v_ref[0, 0]

    def tile(r, t, masked):
        rows = pl.ds(pl.multiple_of(t * tile_q, tile_q), tile_q)
        q = q_ref[0, r, rows, :]                        # [tq, d]
        g = g_ref[0, r, rows, :]
        s_t = jax.lax.dot_general(
            k, q, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # [tk, tq]
        if masked:
            k_pos, q_pos = _positions(tile_k, tile_q, k0,
                                      m * q_span + t * tile_q)
            s_t = jnp.where(q_pos >= k_pos, s_t, _NEG_INF)
        p_t = jnp.exp(s_t - lse_ref[0, r, t])           # rows [1, tq]
        # dv[k, d] = sum_q p_T[k, q] * g[q, d]
        dv_acc[:] += jax.lax.dot_general(
            p_t.astype(g.dtype), g, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp_t = jax.lax.dot_general(
            v, g, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)         # [tk, tq]
        ds_t = p_t * (dp_t - delta_ref[0, r, t])
        # dk[k, d] = scale * sum_q ds_T[k, q] * q[q, d]
        dk_acc[:] += jax.lax.dot_general(
            ds_t.astype(q.dtype), q, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    if causal:
        # first tile the diagonal reaches; first tile wholly below it
        t0 = m * n_t
        lo = jnp.clip(k0 // tile_q - t0, 0, n_t)
        full = jnp.clip((k0 + tile_k + tile_q - 2) // tile_q - t0, lo, n_t)
    else:
        lo = full = 0

    def head(r):
        if causal:
            _walk(lo, full, lambda t: tile(r, t, True))
        _walk(full, n_t, lambda t: tile(r, t, False))

    _walk(0, rep, head)

    @pl.when(m == pl.num_programs(3) - 1)
    def _flush():
        dk_ref[0, 0] = (dk_acc[:] * scale).astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[:].astype(dv_ref.dtype)


def _column(row_ref, tile):
    """A ``[1, tile]`` row of statistics as a ``[tile, 1]`` column."""
    return jnp.transpose(jnp.broadcast_to(row_ref[0, 0, 0],
                                          (128, tile)))[:, :1]


def _bwd_dq_kernel(q_ref, k_ref, v_ref, g_ref, lse_ref, delta_ref, dq_ref,
                   acc_ref, *, tile_q, tile_k, k_span, scale, causal):
    """Grid ``(b, q head, q tile, k span)``; the k span's tiles are
    walked here and dq accumulates across them and across spans."""
    i = pl.program_id(2)
    m = pl.program_id(3)
    n_u = k_span // tile_k
    q0 = i * tile_q

    @pl.when(m == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0]                                     # [tq, d]
    g = g_ref[0, 0]
    lse = _column(lse_ref, tile_q)                      # [tq, 1]
    delta = _column(delta_ref, tile_q)

    def tile(u, masked):
        rows = pl.ds(pl.multiple_of(u * tile_k, tile_k), tile_k)
        k = k_ref[0, 0, rows, :]                        # [tk, d]
        v = v_ref[0, 0, rows, :]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # [tq, tk]
        if masked:
            q_pos, k_pos = _positions(tile_q, tile_k, q0,
                                      m * k_span + u * tile_k)
            s = jnp.where(q_pos >= k_pos, s, _NEG_INF)
        p = jnp.exp(s - lse)
        dp = jax.lax.dot_general(
            g, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)         # [tq, tk]
        ds = p * (dp - delta)
        # dq[q, d] = scale * sum_k ds[q, k] * k[k, d]
        acc_ref[:] += jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    if causal:
        # tiles wholly below the diagonal, then those it crosses
        u0 = m * n_u
        full = jnp.clip((q0 + 1) // tile_k - u0, 0, n_u)
        hi = jnp.clip((q0 + tile_q - 1) // tile_k + 1 - u0, full, n_u)
    else:
        full = hi = n_u

    _walk(0, full, lambda u: tile(u, False))
    if causal:
        _walk(full, hi, lambda u: tile(u, True))

    @pl.when(m == pl.num_programs(3) - 1)
    def _flush():
        dq_ref[0, 0] = (acc_ref[:] * scale).astype(dq_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "scale", "causal", "tile_q", "tile_k", "q_span", "k_span", "interpret"))
def _bwd_pallas(q, k, v, out, lse, g, *, scale, causal, interpret,
                tile_q=None, tile_k=None, q_span=None, k_span=None):
    """(dq, dk, dv) from the two kernels.  q, out, g: [b, hq, s, d]; k, v:
    [b, hk, s, d]; lse: [b, hq, s].  Tiles and spans come from
    :func:`bwd_tiles` unless a test names them.  Behind ONE jit: a step
    whose layers call it at identical shapes traces it once and lowers one
    body of each kernel that the layers share."""
    b, hq, s, d = q.shape
    hk = k.shape[1]
    rep = hq // hk
    tile, qs, ks = bwd_tiles(s, d, rep, q.dtype.itemsize)
    tile_q, tile_k = tile_q or tile, tile_k or tile
    q_span, k_span = q_span or max(qs, tile_q), k_span or max(ks, tile_k)

    # delta_i = sum_d(dO * O) — rowwise (flash-attn-2 eq. 4); it and lse
    # ride as [b, hq, s / tile_q, 1, tile_q]: a q tile's statistics are a
    # lane-dense row
    delta = jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1)
    stat5 = (b, hq, s // tile_q, 1, tile_q)
    lse5, delta5 = lse.reshape(stat5), delta.reshape(stat5)

    params = {}
    if not interpret:
        params["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"))

    own, walked, stat = _dq_maps(rep, tile_q, k_span, causal)
    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, tile_q=tile_q, tile_k=tile_k,
                          k_span=k_span, scale=scale, causal=causal),
        grid=(b, hq, s // tile_q, s // k_span),
        in_specs=[
            pl.BlockSpec((1, 1, tile_q, d), own),
            pl.BlockSpec((1, 1, k_span, d), walked),
            pl.BlockSpec((1, 1, k_span, d), walked),
            pl.BlockSpec((1, 1, tile_q, d), own),
            pl.BlockSpec((1, 1, 1, 1, tile_q), stat),
            pl.BlockSpec((1, 1, 1, 1, tile_q), stat),
        ],
        out_specs=pl.BlockSpec((1, 1, tile_q, d), own),
        out_shape=_out_struct((b, hq, s, d), q.dtype, q, k, v, g),
        scratch_shapes=[pltpu.VMEM((tile_q, d), jnp.float32)],
        name="flash_bwd_dq",
        interpret=interpret,
        **params,
    )(q, k, v, g, lse5, delta5)

    walked, own, stat = _dkv_maps(tile_k, q_span, causal)
    n_t = q_span // tile_q
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, tile_q=tile_q, tile_k=tile_k,
                          q_span=q_span, rep=rep, scale=scale,
                          causal=causal),
        grid=(b, hk, s // tile_k, s // q_span),
        in_specs=[
            pl.BlockSpec((1, rep, q_span, d), walked),
            pl.BlockSpec((1, 1, tile_k, d), own),
            pl.BlockSpec((1, 1, tile_k, d), own),
            pl.BlockSpec((1, rep, q_span, d), walked),
            pl.BlockSpec((1, rep, n_t, 1, tile_q), stat),
            pl.BlockSpec((1, rep, n_t, 1, tile_q), stat),
        ],
        out_specs=[pl.BlockSpec((1, 1, tile_k, d), own),
                   pl.BlockSpec((1, 1, tile_k, d), own)],
        out_shape=[
            _out_struct((b, hk, s, d), k.dtype, q, k, v, g),
            _out_struct((b, hk, s, d), v.dtype, q, k, v, g),
        ],
        scratch_shapes=[pltpu.VMEM((tile_k, d), jnp.float32),
                        pltpu.VMEM((tile_k, d), jnp.float32)],
        name="flash_bwd_dkv",
        interpret=interpret,
        **params,
    )(q, k, v, g, lse5, delta5)
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash_core(q, k, v, scale, causal, block_q, block_k, interpret):
    out, _ = _flash_fwd(q, k, v, scale, causal, block_q, block_k,
                        interpret)
    return out


def _flash_fwd(q, k, v, scale, causal, block_q, block_k, interpret):
    out, lse = _fwd_pallas(q, k, v, scale=scale, causal=causal,
                           block_q=block_q, block_k=block_k,
                           interpret=interpret)
    return out, (q, k, v, out, lse)


def _flash_bwd(scale, causal, block_q, block_k, interpret, res, g):
    return _bwd_pallas(*res, g, scale=scale, causal=causal,
                       interpret=interpret)


_flash_core.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(q, k, v, causal: bool = False, scale=None,
                    block_q: int = None, block_k: int = None,
                    interpret: bool = None, autotune: bool = None):
    """q: [batch, seq, heads, head_dim]; k,v: [batch, seq, kv_heads,
    head_dim] (paddle layout).  Requires seq divisible by the block sizes
    (callers pad; the model stack keeps seq a multiple of 128 for MXU
    efficiency anyway) and heads % kv_heads == 0.

    block_q/block_k are the FORWARD's blocks and default to the
    autotuner's cached choice on TPU (measured once per shape, persisted
    — reference analog: phi/kernels/autotune/auto_tune_base.h); elsewhere
    min(128, s).  The backward's tiles follow :func:`bwd_tiles`."""
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
    if block_q is None or block_k is None:
        if autotune and not interpret:
            from paddle_tpu.ops.pallas.autotune import flash_block_sizes
            bq_t, bk_t = flash_block_sizes(
                b, s, h, hk, d, str(q.dtype), bool(causal))
            block_q = block_q or bq_t
            block_k = block_k or bk_t
        else:
            block_q = block_q or min(128, s)
            block_k = block_k or min(128, s)
    block_q = min(block_q, s)
    block_k = min(block_k, s)
    if s % block_q or s % block_k:
        raise ValueError(f"seq {s} must be divisible by block sizes "
                         f"({block_q},{block_k})")

    def to_bhsd(x):
        return jnp.swapaxes(x, 1, 2)

    out = _flash_core(to_bhsd(q), to_bhsd(k), to_bhsd(v), float(scale),
                      bool(causal), block_q, block_k, bool(interpret))
    return jnp.swapaxes(out, 1, 2)

# ---------------------------------------------------------------------------
# static verification (analysis/kernel_verify) — the fwd / bwd-dq /
# bwd-dkv pallas_calls described as KernelSpecs, same grids and (for the
# backward, the very same) index maps the real calls install.


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


def _bwd_verify_specs(b, s, h, hk, d, dtype, causal):
    """The dq and dk/dv launches at the rule's tiles and spans, through
    the index maps the real calls install."""
    import numpy as np

    from paddle_tpu.analysis import kernel_verify as kv
    rep = h // hk
    tile, q_span, k_span = bwd_tiles(s, d, rep, kv.itemsize(dtype))
    n_t = q_span // tile
    q4, kv4, stat5 = (b, h, s, d), (b, hk, s, d), (b, h, s // tile, 1, tile)
    sem = ("parallel", "parallel", "parallel", "arbitrary")
    where = (f"[b={b} s={s} h={h}/{hk} d={d} tile={tile} "
             f"spans={q_span}/{k_span} {dtype}]")

    own, walked, stat = _dq_maps(rep, tile, k_span, causal, xp=np)
    dq = kv.KernelSpec(
        name="flash_bwd_dq", grid=(b, h, s // tile, s // k_span),
        args=[
            kv.ArgSpec("q", q4, (1, 1, tile, d), own, dtype),
            kv.ArgSpec("k", kv4, (1, 1, k_span, d), walked, dtype),
            kv.ArgSpec("v", kv4, (1, 1, k_span, d), walked, dtype),
            kv.ArgSpec("g", q4, (1, 1, tile, d), own, dtype),
            kv.ArgSpec("lse", stat5, (1, 1, 1, 1, tile), stat, "float32"),
            kv.ArgSpec("delta", stat5, (1, 1, 1, 1, tile), stat, "float32"),
            kv.ArgSpec("dq", q4, (1, 1, tile, d), own, dtype,
                       is_output=True),
        ],
        scratch=[kv.ScratchSpec("acc", (tile, d), "float32")],
        dimension_semantics=sem, needs_fp32_acc=True,
        where="flash_bwd_dq" + where)

    walked, own, stat = _dkv_maps(tile, q_span, causal, xp=np)
    dkv = kv.KernelSpec(
        name="flash_bwd_dkv", grid=(b, hk, s // tile, s // q_span),
        args=[
            kv.ArgSpec("q", q4, (1, rep, q_span, d), walked, dtype),
            kv.ArgSpec("k", kv4, (1, 1, tile, d), own, dtype),
            kv.ArgSpec("v", kv4, (1, 1, tile, d), own, dtype),
            kv.ArgSpec("g", q4, (1, rep, q_span, d), walked, dtype),
            kv.ArgSpec("lse", stat5, (1, rep, n_t, 1, tile), stat,
                       "float32"),
            kv.ArgSpec("delta", stat5, (1, rep, n_t, 1, tile), stat,
                       "float32"),
            kv.ArgSpec("dk", kv4, (1, 1, tile, d), own, dtype,
                       is_output=True),
            kv.ArgSpec("dv", kv4, (1, 1, tile, d), own, dtype,
                       is_output=True),
        ],
        scratch=[kv.ScratchSpec("dk_acc", (tile, d), "float32"),
                 kv.ScratchSpec("dv_acc", (tile, d), "float32")],
        dimension_semantics=sem, needs_fp32_acc=True,
        where="flash_bwd_dkv" + where)
    return dq, dkv


def verify_static(b, s, h, hk, d, dtype="bfloat16", causal=True,
                  block_q=None, block_k=None, parts=("fwd", "bwd")):
    """Static Mosaic-legality findings for the flash kernels at this
    shape/config.  ``parts`` selects the forward (at ``block_q`` /
    ``block_k``, defaulting like :func:`flash_attention`'s non-autotuned
    choice, min(128, s)) and/or the two backward kernels (at the tiles
    :func:`bwd_tiles` gives this shape)."""
    from paddle_tpu.analysis import kernel_verify as kv
    dtype = str(dtype)
    bq = min(int(block_q or min(128, s)), s)
    bk = min(int(block_k or min(128, s)), s)
    diags = []
    if "fwd" in parts:
        diags += kv.verify_kernel(_fwd_verify_spec(b, s, h, hk, d, bq, bk,
                                                   dtype))
    if "bwd" in parts:
        for spec in _bwd_verify_specs(b, s, h, hk, d, dtype, causal):
            diags += kv.verify_kernel(spec)
    return diags
