"""Latent attention (MLA) over a paged latent cache.

A latent-attention layer caches one row a token a layer: the compressed
latent ``c`` (``kv_lora_rank`` wide) followed by the one rotary key all
heads share (``qk_rope_head_dim`` wide).  Per-head keys and values are
products of the latent, ``[k_n | v] = W_kvb c``, and the same scores can
be had two ways:

* **absorbed** — ``q_l = q_n W_uk``, ``score = [q_l | q_r] . [c | k_r]``,
  ``o = (P c) W_uv``: every query head against ONE key row whose first
  ``kv_lora_rank`` values are also the value.  No ``k_n`` / ``v`` is ever
  built; a cached pair costs 2 h (row + rank) FLOPs.
* **expanded** — ``k_n`` and ``v`` are rebuilt from the cached latents
  (2 rank h (nope + v) FLOPs a context token) and a pair costs
  2 h (nope + rope + v): four times fewer than absorbed.

Decode (one query a row, the whole context) wants the absorbed form:
:func:`latent_decode_attention`, a Pallas kernel over the block table in
the image of ``paged_attention.py`` (rows in a sequential grid, the pool
left in HBM, a chunk of live blocks DMA'd into one of two VMEM buffers
while the other is multiplied, run-time trip counts).  A prefill chunk
(hundreds of queries) is :func:`latent_chunk_attention`: a walk over the
tiles of context **the chunk can see** — a run-time trip count, so a
chunk at position 1 k costs a thirty-second of one at 32 k whatever
``max_len`` is — in the expanded form (the chip read the absorbed walk
2.0-2.1 x slower at 2 k, 8 k and 32 k of context: PERF.md).  The path
taken is counted at trace time
(``paddle_tpu_latent_attention_path_total``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["latent_decode_attention", "latent_decode_eligible",
           "latent_chunk_attention", "record_path"]

_NEG_INF = -1e30
# tokens of context a decode chunk / a prefill tile holds
_DECODE_CHUNK_TOKENS = 512
_PREFILL_TILE_TOKENS = 512


def record_path(path: str):
    """Trace-time path counter: ``decode_kernel`` | ``chunk_expanded``."""
    try:
        from paddle_tpu.observability import default_registry
        default_registry().counter(
            "paddle_tpu_latent_attention_path_total",
            "latent-attention implementation chosen at trace time",
            labelnames=("path",)).labels(path=path).inc()
    except Exception:  # pragma: no cover - telemetry must never trace-fail
        pass


def latent_decode_eligible(rank: int, block_size: int, dtype) -> bool:
    """The decode kernel: a TPU, a lane-aligned latent (the value is the
    row's first ``rank`` lanes) and whole packed sublane tiles a block."""
    if jax.default_backend() != "tpu":
        return False
    if jnp.dtype(dtype) not in (jnp.dtype(jnp.float32),
                                jnp.dtype(jnp.bfloat16)):
        return False
    return rank % 128 == 0 and \
        block_size % (32 // jnp.dtype(dtype).itemsize) == 0


def _decode_kernel(bt_ref, len_ref, q_ref, pool_hbm, o_ref, buf, sem,
                   slot_ref, *, rank):
    """Grid (rows,), sequential; ``paged_attention._decode_kernel`` with
    one stream and one key row a token: row b walks
    ``cdiv(lengths[b], T)`` chunks, a chunk's live blocks copied HBM ->
    VMEM a block a DMA into one of two buffers while the other is
    multiplied.  ``q`` is the absorbed query ``[h, width]`` (scale
    folded in); the value of a cached row is its first ``rank`` lanes."""
    b = pl.program_id(0)
    rows = pl.num_programs(0)
    bs = pool_hbm.shape[1]
    _, T, _ = buf.shape
    C = T // bs
    heads = q_ref.shape[1]

    def each_copy(row, i, slot, do):
        live = jnp.minimum(pl.cdiv(len_ref[row], bs) - i * C, C)

        def block(c, carry):
            do(pltpu.make_async_copy(
                pool_hbm.at[bt_ref[row, i * C + c]],
                buf.at[slot, pl.ds(c * bs, bs)], sem.at[slot]))
            return carry

        jax.lax.fori_loop(0, live, block, 0)

    def start(row, i, slot):
        each_copy(row, i, slot, lambda cp: cp.start())

    @pl.when(b == 0)
    def _first():
        # a buffer's dead tail meets a zero probability: keep it finite
        buf[...] = jnp.zeros_like(buf)
        slot_ref[0] = 0
        start(0, 0, 0)

    plen = len_ref[b]
    n = jnp.maximum(pl.cdiv(plen, T), 1)
    slot0 = slot_ref[0]
    q = q_ref[0]                                        # [h, width]

    def chunk(i, carry):
        m_prev, l_prev, acc = carry
        slot = (slot0 + i) % 2

        @pl.when(i + 1 < n)
        def _next_chunk():
            start(b, i + 1, 1 - slot)

        @pl.when((i + 1 == n) & (b + 1 < rows))
        def _next_row():
            start(b + 1, 0, 1 - slot)

        each_copy(b, i, slot, lambda cp: cp.wait())
        k = buf[slot]                                   # [T, width]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)         # [h, T]
        col = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        live = (i * T + col) < plen
        s = jnp.where(live, s, _NEG_INF)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.where(live, jnp.exp(s - m_new), 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_new = corr * l_prev + jnp.sum(p, axis=-1, keepdims=True)
        pv = jax.lax.dot_general(
            p.astype(k.dtype), k[:, :rank], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)         # [h, rank]
        return m_new, l_new, acc * corr + pv

    _, l, acc = jax.lax.fori_loop(0, n, chunk, (
        jnp.full((heads, 1), _NEG_INF, jnp.float32),
        jnp.zeros((heads, 1), jnp.float32),
        jnp.zeros((heads, rank), jnp.float32)))
    slot_ref[0] = (slot0 + n) % 2
    o_ref[0] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


@functools.lru_cache(maxsize=None)
def _kernel(rank):
    return functools.partial(_decode_kernel, rank=rank)


@functools.partial(jax.jit, static_argnames=("rank", "chunk", "interpret"))
def _latent_decode(q, pool, block_table, lengths, *, rank, chunk,
                   interpret):
    """The ``pallas_call`` behind ONE jit: the layers of a program share
    one kernel body."""
    B, h, width = q.shape
    _, bs, _ = pool.shape
    T = chunk * bs
    params = {}
    if not interpret:
        params["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("arbitrary",))
    return pl.pallas_call(
        _kernel(rank),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B,),
            in_specs=[pl.BlockSpec((1, h, width),
                                   lambda b, bt, ln: (b, 0, 0)),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((1, h, rank),
                                   lambda b, bt, ln: (b, 0, 0)),
            scratch_shapes=[pltpu.VMEM((2, T, width), pool.dtype),
                            pltpu.SemaphoreType.DMA((2,)),
                            pltpu.SMEM((1,), jnp.int32)]),
        out_shape=jax.ShapeDtypeStruct((B, h, rank), q.dtype),
        name="latent_attention",
        interpret=interpret,
        **params,
    )(block_table.astype(jnp.int32), lengths.astype(jnp.int32), q, pool)


def latent_decode_attention(q, pool, block_table, lengths, rank,
                            interpret=None):
    """Single-token absorbed latent attention through the block table.

    q: ``[B, heads, width]``, the absorbed query ``[q_n W_uk | q_r]``
    with the score scale folded in; pool: ``[num_blocks, block_size,
    width]``, a token's ``[c | k_r]``; block_table ``[B, max_blocks]``;
    row b attends positions ``< lengths[b]`` (its own row already
    written).  Returns ``P c``: ``[B, heads, rank]``, still to meet
    ``W_uv``."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    bs = pool.shape[1]
    chunk = max(1, min(_DECODE_CHUNK_TOKENS // bs, block_table.shape[1]))
    return _latent_decode(q, pool, block_table, lengths, rank=int(rank),
                          chunk=chunk, interpret=bool(interpret))


def latent_chunk_attention(q, pool, block_table, qpos, w_kvb, *, rank,
                           nope, scale):
    """Causal latent attention of ``S`` queries a row over the context
    they can see, a tile of cached rows at a time, in the expanded form.

    q ``[B, S, h, nope + rope]`` (rotary part rotated); pool
    ``[num_blocks, block_size, >= rank + rope]`` (a row's lanes past
    ``rank + rope`` are padding) with this dispatch's rows already
    written; block_table ``[B, max_blocks]``; qpos ``[B, S]``
    the queries' positions; w_kvb ``[rank, h * (nope + v)]``.  Walks
    ``cdiv(max(qpos) + 1, tile)`` tiles — a run-time trip count — with
    an online softmax in float32: a tile's ``k_n`` and ``v`` rebuilt from
    its latents, q.k width nope + rope against v width ``v``.  Returns
    ``[B, S, h, v]``."""
    record_path("chunk_expanded")
    with jax.named_scope("latent_chunk_attention"):
        return _chunk_attention(q, pool, block_table, qpos, w_kvb, rank,
                                nope, scale)


def _chunk_attention(q, pool, block_table, qpos, w_kvb, rank, nope, scale):
    B, S, h, qk = q.shape
    bs, width = pool.shape[1], rank + qk - nope
    wb = w_kvb.reshape(rank, h, -1)
    vdim = wb.shape[-1] - nope
    cb = max(1, min(_PREFILL_TILE_TOKENS // bs, block_table.shape[1]))
    tile = cb * bs
    mb = block_table.shape[1]
    bt = jnp.pad(block_table, ((0, 0), (0, (-mb) % cb)))
    n = (jnp.max(qpos) + tile) // tile
    f32 = jnp.float32
    q = (q.astype(f32) * scale).astype(q.dtype)

    def body(i, carry):
        m_prev, l_prev, acc = carry
        blocks = jax.lax.dynamic_slice_in_dim(bt, i * cb, cb, axis=1)
        rows = pool[blocks].reshape(B, tile, -1)[..., :width]
        kpos = i * tile + jnp.arange(tile)
        live = kpos[None, None, None, :] <= qpos[:, None, :, None]
        kv = jnp.einsum("btc,chn->bthn", rows[..., :rank], wb,
                        preferred_element_type=f32).astype(rows.dtype)
        k = jnp.concatenate(
            [kv[..., :nope],
             jnp.broadcast_to(rows[:, :, None, rank:],
                              (B, tile, h, width - rank))], axis=-1)
        s = jnp.einsum("bshn,bthn->bhst", q, k, preferred_element_type=f32)
        val = kv[..., nope:]
        s = jnp.where(live, s, _NEG_INF)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.where(live, jnp.exp(s - m_new), 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_new = corr * l_prev + jnp.sum(p, axis=-1, keepdims=True)
        pv = jnp.einsum("bhst,bthv->bhsv", p.astype(val.dtype), val,
                        preferred_element_type=f32)
        return m_new, l_new, acc * corr + pv

    _, l, acc = jax.lax.fori_loop(0, n, body, (
        jnp.full((B, h, S, 1), _NEG_INF, f32),
        jnp.zeros((B, h, S, 1), f32),
        jnp.zeros((B, h, S, vdim), f32)))
    out = acc / jnp.maximum(l, 1e-30)                   # [B, h, S, v]
    return jnp.swapaxes(out, 1, 2).astype(q.dtype)
