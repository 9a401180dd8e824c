"""Fused residual-add + RMSNorm — Pallas TPU kernel.

Reference parity: the fused norm ops the reference keeps in its fusion
layer (``fused_bias_residual_layernorm``, ``rms_norm`` under
paddle/phi/kernels/fusion/gpu) — one HBM round-trip for what XLA would
otherwise schedule as add → square → reduce → rsqrt → mul → mul chains
with the residual re-read.

Design: rows stream HBM→VMEM in (block_rows, d) tiles; the row-wise mean
square, rsqrt, scale and the residual sum all happen in one VMEM pass in
fp32; the kernel emits BOTH the normalized output and the residual sum
(the value the next block needs) plus the per-row inverse rms for the
backward.  Backward is plain jax (pure elementwise + a row reduction —
XLA fuses it into neighbors; the win here is the forward's memory
traffic).

Falls back to pure jax when the shape can't tile (d % 128, rows % 8) so
the API is total.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


__all__ = ["fused_rmsnorm"]


def _fwd_kernel(x_ref, res_ref, w_ref, y_ref, h_ref, inv_ref, *, eps,
                has_res):
    x = x_ref[:].astype(jnp.float32)
    if has_res:
        x = x + res_ref[:].astype(jnp.float32)
    ms = jnp.mean(x * x, axis=-1, keepdims=True)
    inv = jax.lax.rsqrt(ms + eps)                      # [br, 1]
    y = (x * inv) * w_ref[:].astype(jnp.float32)
    y_ref[:] = y.astype(y_ref.dtype)
    h_ref[:] = x.astype(h_ref.dtype)
    inv_ref[:] = inv


def _fwd_pallas(x2d, res2d, w, *, eps, block_rows, interpret):
    rows, d = x2d.shape
    nr = rows // block_rows
    has_res = res2d is not None
    kernel = functools.partial(_fwd_kernel, eps=eps, has_res=has_res)

    in_specs = [pl.BlockSpec((block_rows, d), lambda i: (i, 0))]
    args = [x2d]
    if has_res:
        in_specs.append(pl.BlockSpec((block_rows, d), lambda i: (i, 0)))
        args.append(res2d)
    else:
        # keep the kernel signature uniform: alias x as the (unread) res
        in_specs.append(pl.BlockSpec((block_rows, d), lambda i: (i, 0)))
        args.append(x2d)
    in_specs.append(pl.BlockSpec((1, d), lambda i: (0, 0)))
    args.append(w.reshape(1, d))

    y, h, inv = pl.pallas_call(
        kernel,
        grid=(nr,),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
            pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
            pl.BlockSpec((block_rows, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((rows, d), x2d.dtype),
            jax.ShapeDtypeStruct((rows, d), x2d.dtype),
            jax.ShapeDtypeStruct((rows, 1), jnp.float32),
        ],
        name="rmsnorm",
        interpret=interpret,
    )(*args)
    return y, h, inv


def _default_block_rows(rows, d, dtype):
    """Row-block heuristic.  VMEM budget: the block holds x, res, y, h
    (io dtype) plus ~3 fp32 working copies — keep it under ~8 MB."""
    import numpy as np
    per_row = d * (4 * np.dtype(dtype).itemsize + 3 * 4)
    budget = (8 << 20) // max(per_row, 1)
    for cand in (512, 256, 128, 64, 32, 16, 8):
        if cand <= budget and rows % cand == 0:
            return cand
    return 8


def _ref_fwd(x2d, res2d, w, eps):
    h = x2d.astype(jnp.float32)
    if res2d is not None:
        h = h + res2d.astype(jnp.float32)
    inv = jax.lax.rsqrt(jnp.mean(h * h, axis=-1, keepdims=True) + eps)
    y = (h * inv) * w.astype(jnp.float32)
    return y.astype(x2d.dtype), h.astype(x2d.dtype), inv


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _core(x2d, res2d, w, eps, has_res, use_pallas, interpret):
    return _fwd(x2d, res2d, w, eps, has_res, use_pallas, interpret)[0]


def _fwd(x2d, res2d, w, eps, has_res, use_pallas, interpret):
    r = res2d if has_res else None
    if use_pallas:
        rows, d = x2d.shape
        block_rows = _default_block_rows(rows, d, x2d.dtype)
        y, h, inv = _fwd_pallas(x2d, r, w, eps=eps, block_rows=block_rows,
                                interpret=interpret)
    else:
        y, h, inv = _ref_fwd(x2d, r, w, eps)
    return (y, h), (h, inv, w)


def _bwd(eps, has_res, use_pallas, interpret, saved, cts):
    gy, gh_extra = cts                 # cotangents of (y, h)
    h, inv, w = saved
    hf = h.astype(jnp.float32)
    g = gy.astype(jnp.float32)
    wf = w.astype(jnp.float32)
    d = hf.shape[-1]
    gw_row = g * wf                                        # [R, d]
    # dL/dh = inv * gw - h * inv^3 * mean(gw * h)
    dot = jnp.mean(gw_row * hf, axis=-1, keepdims=True)
    dh = inv * gw_row - hf * (inv ** 3) * dot
    if gh_extra is not None:
        dh = dh + gh_extra.astype(jnp.float32)
    dw = jnp.sum(g * hf * inv, axis=0).astype(w.dtype)
    dx = dh.astype(h.dtype)
    # no residual: res2d was an ALIAS of x2d (placeholder) — its cotangent
    # must be zero or the caller's x gradient double-counts
    dres = dx if has_res else jnp.zeros_like(dx)
    return dx, dres, dw


_core.defvjp(_fwd, _bwd)


def fused_rmsnorm(x, weight, residual=None, epsilon: float = 1e-5,
                  interpret: bool = None, use_pallas: bool = None):
    """y, h = fused_rmsnorm(x, w, residual): h = x (+ residual), y =
    RMSNorm(h) * w — one fused pass; ``h`` is the pre-norm sum the next
    residual branch consumes.

    x: [..., d]; weight: [d]; residual: same shape as x or None.
    """
    shape = x.shape
    d = shape[-1]
    x2d = x.reshape(-1, d)
    res2d = residual.reshape(-1, d) if residual is not None else None
    rows = x2d.shape[0]
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    if use_pallas is None:
        use_pallas = (d % 128 == 0) and (rows % 8 == 0)
    has_res = residual is not None
    if not has_res:
        res2d = x2d  # unread placeholder keeps the vjp signature stable
    y, h = _core(x2d, res2d, weight, float(epsilon), has_res,
                 bool(use_pallas), bool(interpret))
    return y.reshape(shape), h.reshape(shape)


# ---------------------------------------------------------------------------
# static verification (analysis/kernel_verify)


def verify_static(rows, d, dtype="float32", block_rows=None,
                  residual=True):
    """Static Mosaic-legality findings for the fused rmsnorm forward at
    this shape/config (the residual-add variant by default — it is a
    superset of the plain one's operand list)."""
    from paddle_tpu.analysis import kernel_verify as kv
    dtype = str(dtype)
    br = int(block_rows or _default_block_rows(rows, d, dtype))
    row = lambda i: (i, 0)
    args = [
        kv.ArgSpec("x", (rows, d), (br, d), row, dtype),
        kv.ArgSpec("res", (rows, d), (br, d), row, dtype),
        kv.ArgSpec("w", (1, d), (1, d), lambda i: (0, 0), dtype,
                   resident=True),
        kv.ArgSpec("y", (rows, d), (br, d), row, dtype, is_output=True),
        kv.ArgSpec("h", (rows, d), (br, d), row, dtype, is_output=True),
        kv.ArgSpec("inv", (rows, 1), (br, 1), row, "float32",
                   is_output=True),
    ]
    if not residual:
        args = [a for a in args if a.name != "res"]
    spec = kv.KernelSpec(
        name="rmsnorm_fwd", grid=(rows // br,), args=args,
        dimension_semantics=("parallel",),
        where=f"rmsnorm_fwd[rows={rows} d={d} br={br} {dtype}]")
    return kv.verify_kernel(spec)
