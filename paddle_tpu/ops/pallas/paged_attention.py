"""Paged decode attention — Pallas TPU kernel over block-table KV pools.

The serving engine's paged KV cache (``inference/kv_cache.py``) stores
each sequence's keys/values as fixed-size token blocks scattered through
``[num_blocks, block_size, kv_heads, head_dim]`` pools, addressed by a
per-row block table.  The XLA fallback gathers the whole logical table
back to HBM-contiguous form every step — correct, but it re-materializes
``max_len`` rows per layer per token.  This kernel reads the pools
**in place** and its work is what is live: the grid is over rows, the
pools stay in HBM (``memory_space=pl.ANY``), the block table and the
lengths ride in as scalar prefetch, and row b walks
``cdiv(lengths[b], chunk)`` iterations of a run-time-bounded loop.  An
iteration takes a chunk of C consecutive table entries: one
``make_async_copy`` per live physical block of K and of V into a VMEM
buffer ``[C·block_size, kv_heads, head_dim]``, started together and
waited together, with two buffers so the next chunk (or the next row's
first) is in flight while this one is multiplied; an online-softmax
state in fp32 walks the chunks.  A table entry past a row's last live
block is never read, so a long table costs a short row nothing (the
grid of one block a step that this replaces paid a step for every
entry, live or not).

C comes from the shapes the call sees (``chunk_blocks``): whole blocks
within 256 tokens whose four buffers fit a VMEM budget.  The
``pallas_call`` sits behind one ``jax.jit``, so a program whose layers
call it at identical shapes lowers ONE kernel body (the Pallas -> Mosaic
lowering is paid on every start, warm compile cache or not), and its
trip counts are run-time scalars, so there is one decode program
whatever the live lengths.

GQA is handled in-kernel without a transpose: a chunk is multiplied as
``[T·kv_heads, hd]`` rows against all q heads at once and the mask keeps
each q head's own kv head.

Eligibility mirrors the flash kernel's Mosaic constraints: TPU backend,
lane-aligned ``head_dim % 128 == 0``, sublane-aligned
``block_size % 8 == 0``, kv heads that fill 32-bit sublane words.
Elsewhere the engine's ``jnp.take`` gather fallback runs
(``paddle_tpu_paged_attention_path_total{path=...}`` records the
trace-time choice).  ``PADDLE_TPU_PAGED_ATTN=0`` forces the fallback.
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from jax.experimental.pallas import tpu as pltpu

__all__ = ["paged_decode_attention", "paged_chunk_attention",
           "paged_decode_eligible", "paged_attention_env", "record_path"]

_NEG_INF = -1e30


def paged_attention_env():
    """``PADDLE_TPU_PAGED_ATTN``: 1 forces the Pallas kernel (still
    TPU-only), 0 forces the gather fallback, unset → auto (kernel when
    eligible)."""
    raw = os.environ.get("PADDLE_TPU_PAGED_ATTN")
    if raw is None:
        return None
    return raw.strip().lower() in ("1", "true", "yes", "on")


def paged_decode_eligible(head_dim: int, block_size: int, dtype,
                          pool=None) -> bool:
    """Trace-time routing decision for the decode (s == 1) path.
    ``pool`` (the K pool, where the caller has it): a block is DMA'd as
    ``[block_size, kv_heads, head_dim]`` and Mosaic slices an HBM array
    on whole 32-bit sublane words only, so the kv heads must fill them
    (one bf16 kv head does not)."""
    env = paged_attention_env()
    if env is False:
        return False
    if jax.default_backend() != "tpu":
        return False
    if jnp.dtype(dtype) not in (jnp.dtype(jnp.float32),
                                jnp.dtype(jnp.bfloat16)):
        return False
    if pool is not None and \
            pool.shape[2] * jnp.dtype(pool.dtype).itemsize % 4:
        return False
    return head_dim % 128 == 0 and block_size % 8 == 0


def record_path(path: str):
    """Trace-time path counter (pallas | fallback) — BENCH trajectories
    attribute serving wins to the exact attention implementation."""
    try:
        from paddle_tpu.observability import default_registry
        default_registry().counter(
            "paddle_tpu_paged_attention_path_total",
            "paged-attention implementation chosen at trace time",
            labelnames=("path",)).labels(path=path).inc()
    except Exception:  # pragma: no cover - telemetry must never trace-fail
        pass


# One chunk of a row's KV is at most this many tokens: wide enough that
# the score tile fills whole lanes and a DMA burst amortises its issue,
# small enough that a short row wastes little masked compute.
_CHUNK_TOKENS = 256
# ... and the two K and two V chunk buffers stay under this much VMEM.
_CHUNK_VMEM_BYTES = 4 << 20


def chunk_blocks(block_size, kv_heads, head_dim, max_blocks, itemsize):
    """Physical blocks per chunk, from what the call can see: the most
    whole blocks within ``_CHUNK_TOKENS`` whose double-buffered K and V
    fit ``_CHUNK_VMEM_BYTES``, never more than the table holds."""
    per_block = 2 * 2 * block_size * kv_heads * head_dim * itemsize
    return max(1, min(_CHUNK_TOKENS // block_size,
                      _CHUNK_VMEM_BYTES // per_block, max_blocks))


def _scale_lanes(kv_heads):
    """Mosaic cannot slice an HBM array whose minor dim is under a lane
    width: an int8 pool's scale blocks ``[bs, kvh]`` reach the kernel
    with their kv heads padded to whole lanes."""
    return -(-kv_heads // 128) * 128


def _decode_kernel(bt_ref, len_ref, q_ref, *refs, scale, quant, window=None):
    """Grid (batch,), sequential.  Row b walks ``cdiv(lengths[b], T)``
    chunks of T = C·block_size tokens (with ``window``, a static count
    of positions, only from the chunk that holds position
    ``max(lengths[b] - window, 0)``: nothing older is copied, and the
    keys below that position are masked); a chunk's live blocks are copied
    HBM -> VMEM by the kernel's own DMAs (one per physical block, named
    by the block table), into one of two buffers, so the next chunk —
    this row's, or the next row's first — is in flight while this one
    is multiplied.  ``slot_ref`` carries the buffer parity from row to
    row.

    A chunk is multiplied as it lies, ``[T·kvh, hd]`` with a token's kv
    heads in consecutive rows: every q head meets every (token, kv head)
    row in one matmul and the mask keeps a q head's own kv head, so no
    K or V tile is transposed; the masked probabilities are exact zeros
    in the second matmul.  Quantized pools dequantize AT THE LOAD: the
    int8 chunk and its ``[T, kvh]`` scales widen in VMEM registers — the
    fp16/bf16 KV never exists in HBM."""
    if quant:
        (k_hbm, v_hbm, ks_hbm, vs_hbm, o_ref,
         kbuf, vbuf, ksbuf, vsbuf, sem, slot_ref) = refs
        streams = ((k_hbm, kbuf), (v_hbm, vbuf),
                   (ks_hbm, ksbuf), (vs_hbm, vsbuf))
    else:
        k_hbm, v_hbm, o_ref, kbuf, vbuf, sem, slot_ref = refs
        streams = ((k_hbm, kbuf), (v_hbm, vbuf))
    b = pl.program_id(0)
    rows = pl.num_programs(0)
    bs = k_hbm.shape[1]
    _, T, kv_heads, head_dim = kbuf.shape
    C = T // bs
    heads = q_ref.shape[1]
    group = heads // kv_heads

    # without a window every branch below is the Python of the kernel
    # as it was: its jaxpr, and so its lowering, do not change
    def low(row):
        """The first position ``row`` sees under the window."""
        return jnp.maximum(len_ref[row] - window, 0)

    def each_copy(row, i, slot, do):
        """``do`` every DMA of chunk i of ``row``: live blocks only."""
        live = jnp.minimum(pl.cdiv(len_ref[row], bs) - i * C, C)
        first = 0 if window is None else \
            jnp.maximum(low(row) // bs - i * C, 0)

        def block(c, carry):
            blk = bt_ref[row, i * C + c]
            for pool, buf in streams:
                do(pltpu.make_async_copy(
                    pool.at[blk], buf.at[slot, pl.ds(c * bs, bs)],
                    sem.at[slot]))
            return carry

        jax.lax.fori_loop(first, live, block, 0)

    def start(row, i, slot):
        each_copy(row, i, slot, lambda cp: cp.start())

    @pl.when(b == 0)
    def _first():
        # a dead tail of a buffer is masked out of the scores, but its
        # values still meet a zero probability: they must be finite
        for _, buf in streams:
            buf[...] = jnp.zeros_like(buf)
        slot_ref[0] = 0
        start(0, 0 if window is None else low(0) // T, 0)

    plen = len_ref[b]                     # valid tokens in this row
    # a zero-length row still takes its turn, so the buffer parity and
    # the prefetch chain never skip a row
    n = jnp.maximum(pl.cdiv(plen, T), 1)
    if window is not None:
        lo = low(b)
        i0 = lo // T
        n = n - i0                        # chunks from the window's first
    slot0 = slot_ref[0]
    q = q_ref[0]                                       # [h, hd]

    def chunk(j, carry):
        m_prev, l_prev, acc = carry
        slot = (slot0 + j) % 2
        i = j if window is None else i0 + j

        @pl.when(j + 1 < n)
        def _next_chunk():
            start(b, i + 1, 1 - slot)

        @pl.when((j + 1 == n) & (b + 1 < rows))
        def _next_row():
            if window is None:
                start(b + 1, 0, 1 - slot)
            else:
                start(b + 1, low(b + 1) // T, 1 - slot)

        each_copy(b, i, slot, lambda cp: cp.wait())
        k, v = kbuf[slot], vbuf[slot]                  # [T, kvh, hd]
        if quant:
            ks = ksbuf[slot][:, :kv_heads][..., None]  # [T, kvh, 1]
            vs = vsbuf[slot][:, :kv_heads][..., None]
            k = (k.astype(jnp.float32) * ks).astype(q.dtype)
            v = (v.astype(jnp.float32) * vs).astype(q.dtype)
        k = k.reshape(T * kv_heads, head_dim)
        v = v.reshape(T * kv_heads, head_dim)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # [h, T*kvh]
        # column c is token c // kvh of the chunk under kv head c % kvh
        col = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        row = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        kpos = i * T + col // kv_heads
        live = (kpos < plen) & ((col % kv_heads) == (row // group))
        if window is not None:
            live = live & (kpos >= lo)
        s = jnp.where(live, s, _NEG_INF)

        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.where(live, jnp.exp(s - m_new), 0.0)   # [h, T*kvh]
        corr = jnp.exp(m_prev - m_new)
        l_new = corr * l_prev + jnp.sum(p, axis=-1, keepdims=True)
        pv = jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)        # [h, hd]
        return m_new, l_new, acc * corr + pv

    _, l, acc = jax.lax.fori_loop(0, n, chunk, (
        jnp.full((heads, 1), _NEG_INF, jnp.float32),
        jnp.zeros((heads, 1), jnp.float32),
        jnp.zeros((heads, head_dim), jnp.float32)))
    slot_ref[0] = (slot0 + n) % 2
    o_ref[0] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


@functools.lru_cache(maxsize=None)
def _kernel(scale, quant, window=None):
    """One kernel object per static configuration, so jax's trace cache
    sees the same function at every call site and in every program."""
    return functools.partial(_decode_kernel, scale=scale, quant=quant,
                             window=window)


@functools.partial(jax.jit, static_argnames=("scale", "chunk", "interpret",
                                             "window"))
def _paged_decode(q, k_pool, v_pool, block_table, lengths, k_scale, v_scale,
                  *, scale, chunk, interpret, window=None):
    """The ``pallas_call`` behind ONE jit: a program that calls it at
    identical shapes from every layer traces it once and lowers one
    kernel body that the layers share."""
    B, h, hd = q.shape
    _, bs, kvh, _ = k_pool.shape
    quant = k_scale is not None
    T = chunk * bs

    row = pl.BlockSpec((1, h, hd), lambda b, bt, ln: (b, 0, 0))
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    operands = [q, k_pool, v_pool]
    scratch = [pltpu.VMEM((2, T, kvh, hd), k_pool.dtype),
               pltpu.VMEM((2, T, kvh, hd), v_pool.dtype)]
    if quant:
        lanes = _scale_lanes(kvh)
        pad = ((0, 0), (0, 0), (0, lanes - kvh))
        operands += [jnp.pad(k_scale.astype(jnp.float32), pad),
                     jnp.pad(v_scale.astype(jnp.float32), pad)]
        scratch += [pltpu.VMEM((2, T, lanes), jnp.float32),
                    pltpu.VMEM((2, T, lanes), jnp.float32)]
    scratch += [pltpu.SemaphoreType.DMA((2,)), pltpu.SMEM((1,), jnp.int32)]

    params = {}
    if not interpret:
        # sequential: the buffer parity and the prefetched first chunk
        # pass from one row to the next
        params["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("arbitrary",))

    return pl.pallas_call(
        _kernel(scale, quant, window),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B,),
            in_specs=[row] + [hbm] * (len(operands) - 1),
            out_specs=row,
            scratch_shapes=scratch),
        out_shape=jax.ShapeDtypeStruct((B, h, hd), q.dtype),
        name="paged_attention",
        interpret=interpret,
        **params,
    )(block_table.astype(jnp.int32), lengths.astype(jnp.int32),
      *operands)


def paged_decode_attention(q, k_pool, v_pool, block_table, lengths,
                           scale=None, interpret=None,
                           k_scale=None, v_scale=None, window=None):
    """Single-token paged attention.

    q: ``[B, heads, head_dim]`` (the step's one query row per sequence,
    RoPE already applied); k_pool/v_pool:
    ``[num_blocks, block_size, kv_heads, head_dim]``; block_table:
    ``[B, max_blocks]`` int32 (scratch block 0 beyond a row's
    allocation); lengths: ``[B]`` int32 — row b attends positions
    ``< lengths[b]`` (the current token's KV must already be written).
    ``k_scale/v_scale`` (``[num_blocks, block_size, kv_heads]`` fp32)
    mark an int8-quantized pool: a block's scales ride the same DMAs
    and dequantize at the load.  Table entries past a row's last live
    block are never read.  ``window`` (a static count, None: none): row
    b attends positions ``lengths[b] - window <= . < lengths[b]`` only,
    and no table entry before the block of the first is read — what a
    sliding-window layer's ring table (one physical block under several
    logical entries) needs.  Returns ``[B, heads, hd]``."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    hd = q.shape[-1]
    _, bs, kvh, _ = k_pool.shape
    if scale is None:
        scale = 1.0 / (hd ** 0.5)
    chunk = chunk_blocks(bs, kvh, hd, block_table.shape[1],
                         jnp.dtype(k_pool.dtype).itemsize)
    return _paged_decode(q, k_pool, v_pool, block_table, lengths, k_scale,
                         v_scale, scale=float(scale), chunk=chunk,
                         interpret=bool(interpret),
                         window=None if window is None else int(window))


# A tile of the walk below, by measurement on a v5e at 48 heads and a
# 512-query chunk (PERF.md, PR 46): at 512 keys XLA fuses the mask, the
# exponent and the running maximum into the two matmuls' neighbours and
# the walk reads 47 % of its arithmetic's peak; at 1024 a window layer's
# walk is 8 % slower (its tiles overhang a window of 4096 further) and a
# full layer's within 3 % either way; at 2048 the fusion is lost (9 %,
# the chunk 1.6 x slower) and a tile's float32 scores are 200 MB.
_WALK_TILE_TOKENS = 512


def paged_chunk_attention(q, k_pool, v_pool, block_table, qpos, scale=None,
                          window=None):
    """Causal grouped-query attention of ``S`` queries a row over the
    paged context they can see, a tile of whole blocks at a time with an
    online softmax in float32: the ``[S, max_len]`` scores never exist.

    q ``[B, S, heads, hd]``; pools ``[num_blocks, block_size, kv_heads,
    hd]`` with this dispatch's keys and values already written;
    block_table ``[B, max_blocks]``; qpos ``[B, S]`` the queries'
    positions.  The walk runs from the tile of the oldest position any
    query sees — 0, or ``min(qpos) - window + 1`` under a ``window`` — to
    the tile of ``max(qpos)``: run-time trip counts, one program whatever
    the context.  Under a window key ``j`` is seen by query ``t`` iff
    ``0 <= t - j < window``, and no older table entry is read (a ring
    table may name one physical block under several logical entries).
    Plain XLA: it runs on every backend.  Returns ``[B, S, heads, hd]``."""
    with jax.named_scope("paged_chunk_attention"):
        B, S, h, hd = q.shape
        _, bs, kvh, _ = k_pool.shape
        g = h // kvh
        mb = block_table.shape[1]
        cb = max(1, min(_WALK_TILE_TOKENS // bs, mb))
        tile = cb * bs
        bt = jnp.pad(block_table, ((0, 0), (0, (-mb) % cb)))
        f32 = jnp.float32
        if scale is None:
            scale = hd ** -0.5
        qg = (q.astype(f32) * scale).astype(q.dtype).reshape(B, S, kvh, g, hd)
        last = jnp.minimum((jnp.max(qpos) + tile) // tile, bt.shape[1] // cb)
        first = 0 if window is None else \
            jnp.maximum(jnp.min(qpos) - (window - 1), 0) // tile
        qp = qpos[:, None, None, :, None]               # [B, 1, 1, S, 1]

        def body(i, carry):
            m_prev, l_prev, acc = carry
            blocks = jax.lax.dynamic_slice_in_dim(bt, i * cb, cb, axis=1)
            k = k_pool[blocks].reshape(B, tile, kvh, hd)
            v = v_pool[blocks].reshape(B, tile, kvh, hd)
            kpos = i * tile + jnp.arange(tile)
            live = kpos <= qp                           # [B, 1, 1, S, tile]
            if window is not None:
                live = live & (kpos > qp - window)
            s = jnp.einsum("bskgd,btkd->bkgst", qg, k,
                           preferred_element_type=f32)
            s = jnp.where(live, s, _NEG_INF)
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.where(live, jnp.exp(s - m_new), 0.0)
            corr = jnp.exp(m_prev - m_new)
            l_new = corr * l_prev + jnp.sum(p, axis=-1, keepdims=True)
            pv = jnp.einsum("bkgst,btkd->bkgsd", p.astype(v.dtype), v,
                            preferred_element_type=f32)
            return m_new, l_new, acc * corr + pv

        _, l, acc = jax.lax.fori_loop(first, last, body, (
            jnp.full((B, kvh, g, S, 1), _NEG_INF, f32),
            jnp.zeros((B, kvh, g, S, 1), f32),
            jnp.zeros((B, kvh, g, S, hd), f32)))
        out = acc / jnp.maximum(l, 1e-30)               # [B, kvh, g, S, hd]
        return jnp.moveaxis(out, 3, 1).reshape(B, S, h, hd).astype(q.dtype)


# ---------------------------------------------------------------------------
# static verification (analysis/kernel_verify)


def verify_static(B, h, hd, kvh, bs, nb, mb, dtype="bfloat16",
                  quant=False):
    """Static Mosaic-legality findings for the paged decode kernel: a
    grid over rows, the pools left in HBM and copied a physical block a
    DMA into the two chunk buffers, which are the kernel's VMEM.  The
    block-table scalar-prefetch operand is synthesized (row b's logical
    block j lives at physical block ``(b*mb + j) % nb``) and every row
    is full, so the pool maps evaluate concretely over every copy the
    walk can issue."""
    import numpy as np
    from paddle_tpu.analysis import kernel_verify as kv
    dtype = str(dtype)
    bt = (np.arange(B, dtype=np.int32)[:, None] * mb
          + np.arange(mb, dtype=np.int32)[None, :]) % nb
    lengths = np.full((B,), mb * bs, dtype=np.int32)
    pool_dtype = "int8" if quant else dtype
    T = bs * chunk_blocks(bs, kvh, hd, mb, kv.itemsize(pool_dtype))
    pool4 = (nb, bs, kvh, hd)
    pool_map = lambda b, j, bt, ln: (bt[b, j], 0, 0, 0)
    row_map = lambda b, bt, ln: (b, 0, 0)
    args = [
        kv.ArgSpec("q", (B, h, hd), (1, h, hd), row_map, dtype),
        kv.ArgSpec("k_pool", pool4, (1, bs, kvh, hd), pool_map,
                   pool_dtype, dma_grid=(mb,)),
        kv.ArgSpec("v_pool", pool4, (1, bs, kvh, hd), pool_map,
                   pool_dtype, dma_grid=(mb,)),
    ]
    scratch = [kv.ScratchSpec("k_chunks", (2, T, kvh, hd), pool_dtype),
               kv.ScratchSpec("v_chunks", (2, T, kvh, hd), pool_dtype)]
    if quant:
        lanes = _scale_lanes(kvh)
        scale_map = lambda b, j, bt, ln: (bt[b, j], 0, 0)
        args += [
            kv.ArgSpec("k_scale", (nb, bs, lanes), (1, bs, lanes),
                       scale_map, "float32", dma_grid=(mb,)),
            kv.ArgSpec("v_scale", (nb, bs, lanes), (1, bs, lanes),
                       scale_map, "float32", dma_grid=(mb,)),
        ]
        scratch += [kv.ScratchSpec("k_scale_chunks", (2, T, lanes),
                                   "float32"),
                    kv.ScratchSpec("v_scale_chunks", (2, T, lanes),
                                   "float32")]
    args.append(kv.ArgSpec("o", (B, h, hd), (1, h, hd), row_map, dtype,
                           is_output=True))
    spec = kv.KernelSpec(
        name="paged_decode", grid=(B,), args=args, scratch=scratch,
        dimension_semantics=("arbitrary",),
        scalar_prefetch=(bt, lengths),
        # the softmax state and the accumulator are loop-carried fp32
        # values (preferred_element_type), not scratch
        needs_fp32_acc=True, acc_inline=True,
        where=f"paged_decode[B={B} h={h}/{kvh} hd={hd} bs={bs} nb={nb} "
              f"mb={mb} chunk={T} {dtype}{' int8-kv' if quant else ''}]")
    return kv.verify_kernel(spec)
