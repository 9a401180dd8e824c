"""Weight-only quantized matmul — int8/fp8 Pallas kernel, fused dequant.

The serving tentpole (ROADMAP item 4): decode is bandwidth-bound, and
bf16 weights are most of the bytes a decode step reads.  Weight-only
quantization stores every large 2-D weight as int8 (symmetric,
per-output-channel scale) or ``float8_e4m3fn`` and reads HALF (bf16) /
a QUARTER (fp32) of the weight bytes per matmul.  The kernel keeps the
fused-block discipline: the quantized weight block is DMA'd once,
up-converted in VMEM registers, multiplied on the MXU with an fp32
accumulator, and the per-channel scale multiply lands on that fp32
accumulator before the single cast to the io dtype — the dequantized
weight never exists in HBM.

Grid ``(token_blocks, out_blocks)``; K is unblocked (a ``[K, block_n]``
int8 weight tile at serving hidden sizes is well under VMEM), so each
grid step is one clean MXU contraction and the blocked result is
bitwise the unblocked one — which is why :func:`quant_matmul_reference`
(the jnp scale-multiply fallback, same op order) doubles as the
correctness oracle in interpret-mode tests.

Tile candidates are one more autotune axis (TVM-style, PAPERS.md):
``autotune.quant_block_sizes`` enumerates/benches ``(block_t,
block_n)`` through the persistent v2 cache, and the offline sweep CLI
(``python -m paddle_tpu.ops.pallas.autotune --sweep``) covers the
bench shapes for both wdtypes.

Routing is trace-time (``quant_matmul`` picks kernel vs fallback and
records ``paddle_tpu_quant_kernel_path_total{kernel,path}``), so
serving BENCH trajectories can attribute wins to the exact
implementation.  ``PADDLE_TPU_QUANT_MATMUL=0`` forces the fallback.
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from jax.experimental.pallas import tpu as pltpu

__all__ = ["quant_matmul", "quant_matmul_pallas", "quant_matmul_reference",
           "quant_matmul_eligible", "quant_matmul_env", "record_path",
           "weight_dtype", "QUANT_WEIGHT_DTYPES"]


def weight_dtype(mode: str):
    """The storage dtype of a quant mode: ``int8`` or ``fp8``
    (``float8_e4m3fn`` via ml_dtypes — jax's extended dtypes)."""
    if mode == "int8":
        return jnp.dtype(jnp.int8)
    if mode == "fp8":
        import ml_dtypes
        return jnp.dtype(ml_dtypes.float8_e4m3fn)
    raise ValueError(f"unknown quant mode {mode!r}; expected int8|fp8")


QUANT_WEIGHT_DTYPES = ("int8", "fp8")


def quant_matmul_env():
    """``PADDLE_TPU_QUANT_MATMUL``: 0 forces the jnp fallback, 1 forces
    the Pallas kernel (still TPU-only), unset → auto."""
    raw = os.environ.get("PADDLE_TPU_QUANT_MATMUL")
    if raw is None:
        return None
    return raw.strip().lower() in ("1", "true", "yes", "on")


def quant_matmul_eligible(t: int, k: int, n: int, x_dtype) -> bool:
    """Trace-time routing: TPU backend, lane-aligned K and N, token axis
    tiling the io dtype's sublane minimum (decode at tiny batch falls
    back — the fallback is bitwise-equivalent anyway)."""
    env = quant_matmul_env()
    if env is False:
        return False
    if jax.default_backend() != "tpu":
        return False
    s = str(jnp.dtype(x_dtype))
    q = 16 if ("bfloat16" in s or "float16" in s) else 8
    return t >= q and t % q == 0 and k % 128 == 0 and n % 128 == 0


def record_path(kernel: str, path: str):
    """Trace-time implementation counter — the quant analog of the
    fused-block / paged-attention path counters."""
    try:
        from paddle_tpu.observability import default_registry
        default_registry().counter(
            "paddle_tpu_quant_kernel_path_total",
            "quantized-kernel implementation chosen at trace time",
            labelnames=("kernel", "path")).labels(
            kernel=kernel, path=path).inc()
    except Exception:  # pragma: no cover - telemetry must never trace-fail
        pass


def _default_quant_blocks(t: int, n: int, xdtype=None):
    """Heuristic (block_t, block_n) when the autotune cache is cold.
    Always valid: falls back to degenerate blocks when a dim doesn't
    tile (interpret-mode tests at odd shapes).  ``xdtype`` (the io/
    activation dtype) restricts the row block to its sublane quantum
    (bf16/fp16 tiles pack 16 rows) so the choice Mosaic sees is never
    sublane-padded."""
    quantum = 16 if xdtype is not None and \
        ("bfloat16" in str(xdtype) or "float16" in str(xdtype)) else 8
    bt = None
    for c in (256, 128, 64, 32, 16, 8):   # quantum-aligned first
        if c % quantum == 0 and t >= c and t % c == 0:
            bt = c
            break
    if bt is None:                        # degenerate shapes: old ladder
        bt = 1
        for c in (256, 128, 64, 32, 16, 8):
            if t >= c and t % c == 0:
                bt = c
                break
    bn = n
    for c in (512, 256, 128):
        if n % c == 0:
            bn = c
            break
    return (bt, bn)


def _quant_kernel(x_ref, w_ref, s_ref, o_ref):
    """One (token, out) tile: up-convert the quantized weight block in
    VMEM, contract on the MXU with an fp32 accumulator, and fold the
    per-output-channel scale into that accumulator before the single
    cast to the io dtype."""
    x = x_ref[:]
    w = w_ref[:].astype(x.dtype)                  # dequant, in-register
    acc = jax.lax.dot_general(
        x, w, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    o_ref[:] = (acc * s_ref[:].astype(jnp.float32)).astype(o_ref.dtype)


def quant_matmul_pallas(x2d, qw, scale, *, block_t=None, block_n=None,
                        interpret=None, autotune=True):
    """``x2d [T, K] @ dequant(qw [K, N], scale [N]) -> [T, N]`` via the
    Pallas kernel.  ``scale`` is the per-output-channel multiplier."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    t, k = x2d.shape
    kk, n = qw.shape
    assert k == kk, (x2d.shape, qw.shape)
    if block_t is None or block_n is None:
        if autotune:
            from paddle_tpu.ops.pallas.autotune import quant_block_sizes
            bt, bn = quant_block_sizes(t, k, n, str(qw.dtype),
                                       str(x2d.dtype))
        else:
            bt, bn = _default_quant_blocks(t, n)
        block_t = block_t or bt
        block_n = block_n or bn
    if t % block_t or n % block_n:
        block_t, block_n = _default_quant_blocks(t, n)
    params = {}
    if not interpret:
        params["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"))
    return pl.pallas_call(
        _quant_kernel,
        grid=(t // block_t, n // block_n),
        in_specs=[
            pl.BlockSpec((block_t, k), lambda i, j: (i, 0)),
            pl.BlockSpec((k, block_n), lambda i, j: (0, j)),
            pl.BlockSpec((1, block_n), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((block_t, block_n), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((t, n), x2d.dtype),
        name="quant_matmul",
        interpret=interpret,
        **params,
    )(x2d, qw, scale.reshape(1, n))


def quant_matmul_reference(x2d, qw, scale):
    """The jnp scale-multiply fallback AND correctness oracle: identical
    op order to the kernel (up-convert to io dtype, fp32 MXU
    accumulation, per-channel scale on the accumulator, one final
    cast), so the two paths agree to blocked-vs-unblocked noise — zero
    at these shapes, since K is unblocked in the kernel."""
    w = qw.astype(x2d.dtype)
    acc = jax.lax.dot_general(
        x2d, w, (((x2d.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    return (acc * scale.reshape(-1).astype(jnp.float32)).astype(x2d.dtype)


def quant_matmul(x, qw, scale, *, mode: str = "int8", interpret=None):
    """Weight-only quantized matmul with trace-time routing.

    ``x``: ``[..., K]`` activations (any leading dims); ``qw``:
    ``[K, N]`` int8 / float8_e4m3fn; ``scale``: ``[N]`` (or ``[1, N]``)
    fp32 per-output-channel dequant scale.  Returns ``[..., N]`` in
    ``x.dtype``.  Routes to the Pallas kernel when eligible; the jnp
    fallback is numerically identical.
    """
    lead = x.shape[:-1]
    k = x.shape[-1]
    n = qw.shape[1]
    t = 1
    for d in lead:
        t *= int(d)
    kernel = f"matmul_{mode}"
    use_pallas = quant_matmul_eligible(t, int(k), int(n), x.dtype) \
        if interpret is None else True
    record_path(kernel, "pallas" if use_pallas else "fallback")
    if not use_pallas:
        return quant_matmul_reference(x, qw, scale)
    x2d = x.reshape(t, k)
    out = quant_matmul_pallas(x2d, qw, scale, interpret=interpret)
    return out.reshape(lead + (n,))


# ---------------------------------------------------------------------------
# static verification (analysis/kernel_verify)


def verify_static(t, k, n, wdtype="int8", xdtype="bfloat16",
                  block_t=None, block_n=None):
    """Static Mosaic-legality findings for the weight-only quantized
    matmul at this shape/config — includes the scale-operand shape
    agreement check (``scale`` lanes must track the weight tile)."""
    from paddle_tpu.analysis import kernel_verify as kv
    wdtype, xdtype = str(wdtype), str(xdtype)
    if block_t is None or block_n is None:
        bt_d, bn_d = _default_quant_blocks(t, n, xdtype)
        block_t = block_t or bt_d
        block_n = block_n or bn_d
    bt, bn = int(block_t), int(block_n)
    spec = kv.KernelSpec(
        name="quant_matmul", grid=(t // bt if bt else 0,
                                   n // bn if bn else 0),
        args=[
            kv.ArgSpec("x", (t, k), (bt, k), lambda i, j: (i, 0), xdtype),
            kv.ArgSpec("qw", (k, n), (k, bn), lambda i, j: (0, j),
                       wdtype),
            kv.ArgSpec("scale", (1, n), (1, bn), lambda i, j: (0, j),
                       "float32"),
            kv.ArgSpec("o", (t, n), (bt, bn), lambda i, j: (i, j),
                       xdtype, is_output=True),
        ],
        dimension_semantics=("parallel", "parallel"),
        needs_fp32_acc=True, acc_inline=True,
        scale_pairs=[("scale", "qw")],
        where=f"quant_matmul[t={t} k={k} n={n} {wdtype}/{xdtype} "
              f"bt={bt} bn={bn}]")
    return kv.verify_kernel(spec)
