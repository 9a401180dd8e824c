"""Fused softmax-cross-entropy — Pallas TPU kernel, vocab-blockwise.

Reference parity: the fused softmax_with_cross_entropy kernels
(phi/kernels/fusion, c_softmax_with_cross_entropy) — the op the memory
roofline says dominates the tail of an LM train step when left to XLA:
``log_softmax`` materializes a full fp32 ``[tokens, vocab]`` array in HBM
and the one-hot backward reads it again.  Here neither survives:

* forward: vocab blocks stream HBM→VMEM; an online max/logsumexp (the
  flash-attention trick applied along the class axis) and the gathered
  gold logit live in VMEM scratch as ``[block_t, 1]`` fp32 columns.  Only
  the per-token loss and logsumexp (``[T, 1]`` each) are written back.
* backward: embarrassingly parallel over (token, vocab) blocks — each
  block recomputes its probabilities from the saved logsumexp and writes
  ``(p - onehot) * g`` straight in the input dtype.  The only
  ``[T, V]``-sized arrays in the whole fwd+bwd are the caller's logits
  and their cotangent, both in the caller's dtype (bf16 in training).

Distinct from ``F.fused_linear_cross_entropy`` (which owns the lm-head
matmul: it walks chunks of rows against the whole head, so only one
``[rows, vocab]`` chunk of logits ever exists, and makes dh and dW in the
same visit): this kernel takes logits that already exist and removes the
fp32 softmax intermediate — it is the automatic fast path under plain
``F.cross_entropy``.

Mosaic legality (see flash_attention.py): per-token columns ride as
``[T, 1]`` arrays with ``(block_t, 1)`` blocks — trailing dims
(multiple-of-8, 1) match the array, same shape trick the fused rmsnorm
uses for its inverse-rms output.

Env knobs:
  PADDLE_TPU_FUSED_CE=1|0   force-enable (interpret off-TPU) / disable;
                            unset = auto (TPU backend only)
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from jax.experimental.pallas import tpu as pltpu

__all__ = ["fused_softmax_cross_entropy", "fused_ce_enabled",
           "fused_ce_eligible"]

_NEG_INF = -1e30


def fused_ce_enabled() -> bool:
    """Routing gate: env wins, else auto = TPU backend only, outside a
    sharded step (interpret mode off-TPU is for tests, not the hot
    path)."""
    env = os.environ.get("PADDLE_TPU_FUSED_CE", "").strip().lower()
    if env in ("0", "false", "off", "no"):
        return False
    if env in ("1", "true", "on", "yes"):
        return True
    if jax.default_backend() != "tpu":
        return False
    from paddle_tpu.ops.pallas import mesh
    if mesh.current() is not None:
        # sharded logits: XLA cannot partition a Mosaic kernel
        mesh.record_route("fused_ce", "xla")
        return False
    return True


def fused_ce_eligible(t: int, v: int) -> bool:
    """Shape gate: the vocab axis must tile the 128-lane VPU; tokens pad
    to the row block inside the wrapper, so any T works."""
    return v >= 128 and v % 128 == 0 and t >= 1


# -- forward -----------------------------------------------------------------

def _fwd_kernel(x_ref, lbl_ref, loss_ref, lse_ref, m_ref, s_ref, gold_ref,
                *, block_v):
    """Grid: (token_blocks, vocab_blocks); the vocab axis is innermost
    (sequential) so VMEM scratch carries the online-softmax state."""
    vj = pl.program_id(1)
    nv = pl.num_programs(1)

    @pl.when(vj == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        s_ref[:] = jnp.zeros_like(s_ref)
        gold_ref[:] = jnp.zeros_like(gold_ref)

    x = x_ref[:].astype(jnp.float32)                   # [bt, bv]
    bt = x.shape[0]
    col = vj * block_v + jax.lax.broadcasted_iota(
        jnp.int32, (bt, block_v), 1)
    m_prev = m_ref[:]                                  # [bt, 1]
    m_new = jnp.maximum(m_prev, jnp.max(x, axis=1, keepdims=True))
    s_ref[:] = s_ref[:] * jnp.exp(m_prev - m_new) + \
        jnp.sum(jnp.exp(x - m_new), axis=1, keepdims=True)
    m_ref[:] = m_new
    hit = col == lbl_ref[:]                            # [bt, bv]
    gold_ref[:] += jnp.sum(jnp.where(hit, x, 0.0), axis=1, keepdims=True)

    @pl.when(vj == nv - 1)
    def _finalize():
        lse = m_ref[:] + jnp.log(s_ref[:])
        lse_ref[:] = lse
        loss_ref[:] = lse - gold_ref[:]


def _fwd_pallas(x, lbl_col, *, block_t, block_v, interpret):
    """x: [T, V]; lbl_col: [T, 1] int32 → (loss [T, 1], lse [T, 1]) fp32."""
    t, v = x.shape
    nt = t // block_t
    nv = v // block_v

    params = {}
    if not interpret:
        params["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"))

    return pl.pallas_call(
        functools.partial(_fwd_kernel, block_v=block_v),
        grid=(nt, nv),
        in_specs=[
            pl.BlockSpec((block_t, block_v), lambda i, j: (i, j)),
            pl.BlockSpec((block_t, 1), lambda i, j: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_t, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((block_t, 1), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((t, 1), jnp.float32),
            jax.ShapeDtypeStruct((t, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_t, 1), jnp.float32),
            pltpu.VMEM((block_t, 1), jnp.float32),
            pltpu.VMEM((block_t, 1), jnp.float32),
        ],
        name="fused_ce_fwd",
        interpret=interpret,
        **params,
    )(x, lbl_col)


# -- backward ----------------------------------------------------------------

def _bwd_kernel(x_ref, lbl_ref, lse_ref, g_ref, dx_ref, *, block_v):
    """Grid: (token_blocks, vocab_blocks), fully parallel — each block is
    self-contained given the saved logsumexp."""
    vj = pl.program_id(1)
    x = x_ref[:].astype(jnp.float32)                   # [bt, bv]
    bt = x.shape[0]
    p = jnp.exp(x - lse_ref[:])
    col = vj * block_v + jax.lax.broadcasted_iota(
        jnp.int32, (bt, block_v), 1)
    onehot = (col == lbl_ref[:]).astype(jnp.float32)
    dx_ref[:] = ((p - onehot) * g_ref[:]).astype(dx_ref.dtype)


def _bwd_pallas(x, lbl_col, lse, g_col, *, block_t, block_v, interpret):
    t, v = x.shape
    nt = t // block_t
    nv = v // block_v

    params = {}
    if not interpret:
        params["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"))

    return pl.pallas_call(
        functools.partial(_bwd_kernel, block_v=block_v),
        grid=(nt, nv),
        in_specs=[
            pl.BlockSpec((block_t, block_v), lambda i, j: (i, j)),
            pl.BlockSpec((block_t, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((block_t, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((block_t, 1), lambda i, j: (i, 0)),
        ],
        out_specs=pl.BlockSpec((block_t, block_v), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((t, v), x.dtype),
        name="fused_ce_bwd",
        interpret=interpret,
        **params,
    )(x, lbl_col, lse, g_col)


# -- differentiable core -----------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def _ce_core(x, lbl_col, block_t, block_v, interpret):
    loss, _ = _fwd_pallas(x, lbl_col, block_t=block_t, block_v=block_v,
                          interpret=interpret)
    return loss[:, 0]


def _ce_core_fwd(x, lbl_col, block_t, block_v, interpret):
    loss, lse = _fwd_pallas(x, lbl_col, block_t=block_t, block_v=block_v,
                            interpret=interpret)
    return loss[:, 0], (x, lbl_col, lse)


def _ce_core_bwd(block_t, block_v, interpret, res, g):
    x, lbl_col, lse = res
    dx = _bwd_pallas(x, lbl_col, lse, g.astype(jnp.float32)[:, None],
                     block_t=block_t, block_v=block_v, interpret=interpret)
    return dx, None


_ce_core.defvjp(_ce_core_fwd, _ce_core_bwd)


def _default_blocks(t: int, v: int):
    """Heuristic fallback: biggest lane block that divides the vocab
    (more vocab per visit = fewer scratch rescales), 128 token rows."""
    block_v = 128
    for cand in (2048, 1024, 512, 256, 128):
        if v % cand == 0:
            block_v = cand
            break
    if v % block_v:
        # odd vocab (no power-of-two divisor >= 128): a non-dividing
        # block would leave uncovered columns — fall back to one whole-
        # vocab block (the verifier's coverage check catches regressions)
        block_v = v
    block_t = 128 if t >= 128 else max(8, -(-t // 8) * 8)
    return block_t, block_v


def fused_softmax_cross_entropy(logits, labels, block_t=None, block_v=None,
                                interpret=None, autotune=None):
    """Per-token ``-log_softmax(logits)[labels]`` without the ``[T, V]``
    fp32 intermediate.

    logits: [T, V] (flatten leading dims first; any float dtype — softmax
    math is fp32 per block); labels: [T] int, all in ``[0, V)`` (mask
    ignore_index to a safe class BEFORE calling; the cotangent you zero
    outside also zeroes the row's dlogits).  Returns fp32 [T].
    Differentiable wrt logits.
    """
    t, v = logits.shape
    if not fused_ce_eligible(t, v):
        raise ValueError(f"vocab {v} must be a multiple of 128")
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    if autotune is None:
        autotune = not interpret
    if block_t is None or block_v is None:
        if autotune and not interpret:
            from paddle_tpu.ops.pallas.autotune import ce_block_sizes
            bt_t, bv_t = ce_block_sizes(t, v, str(logits.dtype))
            block_t = block_t or bt_t
            block_v = block_v or bv_t
        else:
            bt_d, bv_d = _default_blocks(t, v)
            block_t = block_t or bt_d
            block_v = block_v or bv_d
    if v % block_v:
        raise ValueError(f"vocab {v} not divisible by block_v {block_v}")

    lbl = jnp.asarray(labels).astype(jnp.int32)
    # pad the token axis up to the row block; the pad/slice pair is
    # outside the custom vjp, so pad-row cotangents are exactly zero
    tp = -(-t // block_t) * block_t
    x = logits
    if tp != t:
        x = jnp.pad(x, ((0, tp - t), (0, 0)))
        lbl = jnp.pad(lbl, (0, tp - t))
    per_tok = _ce_core(x, lbl[:, None], int(block_t), int(block_v),
                       bool(interpret))
    return per_tok[:t]


# ---------------------------------------------------------------------------
# static verification (analysis/kernel_verify)


def _fwd_verify_spec(tp, v, bt, bv, dtype):
    from paddle_tpu.analysis import kernel_verify as kv
    nt, nv = tp // bt, v // bv
    col = lambda i, j: (i, 0)
    return kv.KernelSpec(
        name="fused_ce_fwd", grid=(nt, nv),
        args=[
            kv.ArgSpec("x", (tp, v), (bt, bv), lambda i, j: (i, j), dtype),
            kv.ArgSpec("lbl", (tp, 1), (bt, 1), col, "int32"),
            kv.ArgSpec("loss", (tp, 1), (bt, 1), col, "float32",
                       is_output=True),
            kv.ArgSpec("lse", (tp, 1), (bt, 1), col, "float32",
                       is_output=True),
        ],
        scratch=[kv.ScratchSpec("m", (bt, 1), "float32"),
                 kv.ScratchSpec("s", (bt, 1), "float32"),
                 kv.ScratchSpec("gold", (bt, 1), "float32")],
        dimension_semantics=("parallel", "arbitrary"),
        needs_fp32_acc=True,
        where=f"fused_ce_fwd[t={tp} v={v} bt={bt} bv={bv} {dtype}]")


def _bwd_verify_spec(tp, v, bt, bv, dtype):
    from paddle_tpu.analysis import kernel_verify as kv
    nt, nv = tp // bt, v // bv
    col = lambda i, j: (i, 0)
    return kv.KernelSpec(
        name="fused_ce_bwd", grid=(nt, nv),
        args=[
            kv.ArgSpec("x", (tp, v), (bt, bv), lambda i, j: (i, j), dtype),
            kv.ArgSpec("lbl", (tp, 1), (bt, 1), col, "int32"),
            kv.ArgSpec("lse", (tp, 1), (bt, 1), col, "float32"),
            kv.ArgSpec("g", (tp, 1), (bt, 1), col, "float32"),
            kv.ArgSpec("dx", (tp, v), (bt, bv), lambda i, j: (i, j),
                       dtype, is_output=True),
        ],
        dimension_semantics=("parallel", "parallel"),
        where=f"fused_ce_bwd[t={tp} v={v} bt={bt} bv={bv} {dtype}]")


def verify_static(t, v, dtype="float32", block_t=None, block_v=None):
    """Static Mosaic-legality findings for the fused cross-entropy
    (fwd + bwd pallas_calls) at this shape/config.  The token axis pads
    to the row block exactly like the wrapper does."""
    from paddle_tpu.analysis import kernel_verify as kv
    dtype = str(dtype)
    if block_t is None or block_v is None:
        bt_d, bv_d = _default_blocks(t, v)
        block_t = block_t or bt_d
        block_v = block_v or bv_d
    bt, bv = int(block_t), int(block_v)
    tp = -(-t // bt) * bt
    return (kv.verify_kernel(_fwd_verify_spec(tp, v, bt, bv, dtype))
            + kv.verify_kernel(_bwd_verify_spec(tp, v, bt, bv, dtype)))
