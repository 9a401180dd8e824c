"""Paged decode attention — Pallas TPU kernel over block-table KV pools.

The serving engine's paged KV cache (``inference/kv_cache.py``) stores
each sequence's keys/values as fixed-size token blocks scattered through
``[num_blocks, block_size, kv_heads, head_dim]`` pools, addressed by a
per-row block table.  The XLA fallback gathers the whole logical table
back to HBM-contiguous form every step — correct, but it re-materializes
``max_len`` rows per layer per token.  This kernel reads the pools
**in place**: the block table rides in as scalar prefetch
(``PrefetchScalarGridSpec``), the K/V ``BlockSpec`` index maps chase it
(``bt[b, j]`` picks the physical block each grid step DMAs), and an
online-softmax accumulator in VMEM scratch walks the sequence's logical
blocks.  Nothing is gathered; blocks past the row's length are skipped
entirely (``pl.when``), so decode reads exactly the live KV bytes.

GQA is handled in-kernel: q heads reshape to ``[kv_heads, group, hd]``
and both matmuls run batched over kv heads, so KV blocks stream once per
group (the same trick the flash kernel plays in its grid).

Eligibility mirrors the flash kernel's Mosaic constraints: TPU backend,
lane-aligned ``head_dim % 128 == 0``, sublane-aligned
``block_size % 8 == 0``.  Elsewhere the engine's ``jnp.take`` gather
fallback runs (``paddle_tpu_paged_attention_path_total{path=...}``
records the trace-time choice).  ``PADDLE_TPU_PAGED_ATTN=0`` forces the
fallback.
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from jax.experimental.pallas import tpu as pltpu

__all__ = ["paged_decode_attention", "paged_decode_eligible",
           "paged_attention_env", "record_path"]

_NEG_INF = -1e30


def paged_attention_env():
    """``PADDLE_TPU_PAGED_ATTN``: 1 forces the Pallas kernel (still
    TPU-only), 0 forces the gather fallback, unset → auto (kernel when
    eligible)."""
    raw = os.environ.get("PADDLE_TPU_PAGED_ATTN")
    if raw is None:
        return None
    return raw.strip().lower() in ("1", "true", "yes", "on")


def paged_decode_eligible(head_dim: int, block_size: int, dtype) -> bool:
    """Trace-time routing decision for the decode (s == 1) path."""
    env = paged_attention_env()
    if env is False:
        return False
    if jax.default_backend() != "tpu":
        return False
    if jnp.dtype(dtype) not in (jnp.dtype(jnp.float32),
                                jnp.dtype(jnp.bfloat16)):
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


def _decode_kernel(bt_ref, len_ref, q_ref, k_ref, v_ref, o_ref,
                   acc_ref, m_ref, l_ref, *, block_size, kv_heads, group,
                   head_dim, scale, ks_ref=None, vs_ref=None):
    """Grid (batch, max_blocks); the block axis is innermost/sequential so
    VMEM scratch carries the online-softmax state across a row's blocks.
    Quantized pools (``ks_ref/vs_ref`` given) dequantize AT THE BLOCK
    LOAD: the int8 tile and its ``[bs, kvh]`` scales widen in VMEM
    registers — the fp16/bf16 KV never exists in HBM."""
    b = pl.program_id(0)
    j = pl.program_id(1)
    nb = pl.num_programs(1)

    @pl.when(j == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    plen = len_ref[b]                     # valid tokens in this row

    @pl.when(j * block_size < plen)
    def _compute():
        q = q_ref[0].reshape(kv_heads, group, head_dim)
        if ks_ref is not None:
            ks = jnp.swapaxes(ks_ref[0], 0, 1)[..., None]  # [kvh, bs, 1]
            vs = jnp.swapaxes(vs_ref[0], 0, 1)[..., None]
            k = (jnp.swapaxes(k_ref[0], 0, 1).astype(jnp.float32)
                 * ks).astype(q.dtype)                 # [kvh, bs, hd]
            v = (jnp.swapaxes(v_ref[0], 0, 1).astype(jnp.float32)
                 * vs).astype(q.dtype)
        else:
            k = jnp.swapaxes(k_ref[0], 0, 1)           # [kvh, bs, hd]
            v = jnp.swapaxes(v_ref[0], 0, 1)           # [kvh, bs, hd]
        s = jax.lax.dot_general(
            q, k, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32) * scale  # [kvh, g, bs]
        kpos = j * block_size + jax.lax.broadcasted_iota(
            jnp.int32, (kv_heads, group, block_size), 2)
        s = jnp.where(kpos < plen, s, _NEG_INF)

        m_prev = m_ref[:]                              # [kvh, g, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)                         # [kvh, g, bs]
        corr = jnp.exp(m_prev - m_new)
        l_ref[:] = corr * l_ref[:] + jnp.sum(p, axis=-1, keepdims=True)
        pv = jax.lax.dot_general(
            p.astype(v.dtype), v, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)        # [kvh, g, hd]
        acc_ref[:] = acc_ref[:] * corr + pv
        m_ref[:] = m_new

    @pl.when(j == nb - 1)
    def _finish():
        safe_l = jnp.maximum(l_ref[:], 1e-30)
        out = (acc_ref[:] / safe_l).reshape(
            kv_heads * group, head_dim)
        o_ref[0] = out.astype(o_ref.dtype)


def _decode_kernel_quant(bt_ref, len_ref, q_ref, k_ref, v_ref, ks_ref,
                         vs_ref, o_ref, acc_ref, m_ref, l_ref, **kw):
    """Positional adapter: the quantized variant's extra scale inputs
    sit between the pools and the output in pallas_call order."""
    _decode_kernel(bt_ref, len_ref, q_ref, k_ref, v_ref, o_ref,
                   acc_ref, m_ref, l_ref, ks_ref=ks_ref, vs_ref=vs_ref,
                   **kw)


def paged_decode_attention(q, k_pool, v_pool, block_table, lengths,
                           scale=None, interpret=None,
                           k_scale=None, v_scale=None):
    """Single-token paged attention.

    q: ``[B, heads, head_dim]`` (the step's one query row per sequence,
    RoPE already applied); k_pool/v_pool:
    ``[num_blocks, block_size, kv_heads, head_dim]``; block_table:
    ``[B, max_blocks]`` int32 (scratch block 0 beyond a row's
    allocation); lengths: ``[B]`` int32 — row b attends positions
    ``< lengths[b]`` (the current token's KV must already be written).
    ``k_scale/v_scale`` (``[num_blocks, block_size, kv_heads]`` fp32)
    mark an int8-quantized pool: blocks dequantize at the load, chased
    by the same block-table index maps.  Returns ``[B, heads, hd]``."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    B, h, hd = q.shape
    nb, bs, kvh, _ = k_pool.shape
    mb = block_table.shape[1]
    group = h // kvh
    if scale is None:
        scale = 1.0 / (hd ** 0.5)
    quant = k_scale is not None

    kw = dict(block_size=bs, kv_heads=kvh, group=group, head_dim=hd,
              scale=scale)
    kernel = functools.partial(
        _decode_kernel_quant if quant else _decode_kernel, **kw)

    in_specs = [
        pl.BlockSpec((1, h, hd), lambda b, j, bt, ln: (b, 0, 0)),
        pl.BlockSpec((1, bs, kvh, hd),
                     lambda b, j, bt, ln: (bt[b, j], 0, 0, 0)),
        pl.BlockSpec((1, bs, kvh, hd),
                     lambda b, j, bt, ln: (bt[b, j], 0, 0, 0)),
    ]
    operands = [q, k_pool, v_pool]
    if quant:
        in_specs += [
            pl.BlockSpec((1, bs, kvh),
                         lambda b, j, bt, ln: (bt[b, j], 0, 0)),
            pl.BlockSpec((1, bs, kvh),
                         lambda b, j, bt, ln: (bt[b, j], 0, 0)),
        ]
        operands += [k_scale.astype(jnp.float32),
                     v_scale.astype(jnp.float32)]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, mb),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, h, hd), lambda b, j, bt, ln: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((kvh, group, hd), jnp.float32),
            pltpu.VMEM((kvh, group, 1), jnp.float32),
            pltpu.VMEM((kvh, group, 1), jnp.float32),
        ],
    )

    params = {}
    if not interpret:
        params["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"))

    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, h, hd), q.dtype),
        name="paged_attention",
        interpret=interpret,
        **params,
    )(block_table.astype(jnp.int32), lengths.astype(jnp.int32),
      *operands)


# ---------------------------------------------------------------------------
# static verification (analysis/kernel_verify)


def verify_static(B, h, hd, kvh, bs, nb, mb, dtype="bfloat16",
                  quant=False):
    """Static Mosaic-legality findings for the paged decode kernel.
    The block-table scalar-prefetch operand is synthesized (row b's
    logical block j lives at physical block ``(b*mb + j) % nb``) so the
    pool index maps evaluate concretely over the whole (B, mb) grid."""
    import numpy as np
    from paddle_tpu.analysis import kernel_verify as kv
    dtype = str(dtype)
    group = h // kvh
    bt = (np.arange(B, dtype=np.int32)[:, None] * mb
          + np.arange(mb, dtype=np.int32)[None, :]) % nb
    lengths = np.full((B,), mb * bs, dtype=np.int32)
    pool4 = (nb, bs, kvh, hd)
    pool_map = lambda b, j, bt, ln: (bt[b, j], 0, 0, 0)
    row_map = lambda b, j, bt, ln: (b, 0, 0)
    args = [
        kv.ArgSpec("q", (B, h, hd), (1, h, hd), row_map, dtype),
        kv.ArgSpec("k_pool", pool4, (1, bs, kvh, hd), pool_map,
                   "int8" if quant else dtype),
        kv.ArgSpec("v_pool", pool4, (1, bs, kvh, hd), pool_map,
                   "int8" if quant else dtype),
    ]
    if quant:
        scale_map = lambda b, j, bt, ln: (bt[b, j], 0, 0)
        args += [
            kv.ArgSpec("k_scale", (nb, bs, kvh), (1, bs, kvh), scale_map,
                       "float32"),
            kv.ArgSpec("v_scale", (nb, bs, kvh), (1, bs, kvh), scale_map,
                       "float32"),
        ]
    args.append(kv.ArgSpec("o", (B, h, hd), (1, h, hd), row_map, dtype,
                           is_output=True))
    spec = kv.KernelSpec(
        name="paged_decode", grid=(B, mb), args=args,
        scratch=[kv.ScratchSpec("acc", (kvh, group, hd), "float32"),
                 kv.ScratchSpec("m", (kvh, group, 1), "float32"),
                 kv.ScratchSpec("l", (kvh, group, 1), "float32")],
        dimension_semantics=("parallel", "arbitrary"),
        scalar_prefetch=(bt, lengths),
        needs_fp32_acc=True,
        scale_pairs=[("k_scale", "k_pool"),
                     ("v_scale", "v_pool")] if quant else [],
        where=f"paged_decode[B={B} h={h}/{kvh} hd={hd} bs={bs} nb={nb} "
              f"mb={mb} {dtype}{' int8-kv' if quant else ''}]")
    return kv.verify_kernel(spec)
