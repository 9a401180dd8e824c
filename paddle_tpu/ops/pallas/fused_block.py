"""Transformer-block megakernels — fused rmsnorm+QKV and fused MLP.

Reference parity: the block-level fusion ops the reference keeps in
``phi/kernels/fusion`` (``fused_attention_op.cu`` front half,
``fused_bias_act`` / ``fused_gate_attention``); MPK-style
mega-kernelization (PAPERS.md) applied to the two segments PR 6's
roofline-gap attribution ranks highest once flash attention and the
fused lm-head CE are in place:

* ``fused_rmsnorm_qkv`` — RMSNorm statistics and the normalized
  activations are computed once per token block in VMEM and consumed by
  the q/k/v projections without ever round-tripping HBM.  The unfused
  lowering writes the normalized ``[T, d]`` activations and reads them
  back three times; here they live in a VMEM scratch for the lifetime
  of the token block.  Grid: (token_blocks, out_blocks) with the out
  axis walking q's, then k's, then v's column blocks — each weight
  block-spec clamps its index so a block is DMA'd exactly once.

* ``fused_mlp`` — SwiGLU (``down(silu(gate(x)) * up(x))``) with the
  ``[T, f]`` hidden intermediate VMEM-resident: the f axis is the inner
  grid dimension; each step computes a ``[bt, bf]`` gate/up tile, the
  activation product, and accumulates its contribution to the down
  projection into a ``[bt, d]`` fp32 scratch.  Neither ``gate(x)``,
  ``up(x)`` nor their product ever exists in HBM.  ``fused_ffn`` is the
  non-gated variant (``act(x@w1 + b1) @ w2 + b2``) for the classic
  Transformer encoder/decoder feed-forward.

The two per-segment kernels' blocks follow one rule read from the shape
(``_choose_blocks``): a token block tall enough that the weights are
read once, or at the chip's ridge where ``t`` does not fit the declared
VMEM — every token block walks all of the weights again.

All three carry custom VJPs: the backward recomputes the cheap
forward intermediates from the saved inputs (rmsnorm scale, gate/up
activations) in plain jax — XLA fuses those chains well, and the HBM
win lives in the forward, which inference/serving runs alone.

Numerics: norm statistics, activation math and all matmul
accumulation in fp32 (``preferred_element_type``) regardless of the
io dtype, mirroring the rest of the Pallas layer.

* ``fused_decoder_block`` — the whole-decoder-block megakernel (ISSUE
  15, MPK-style): ONE ``pallas_call`` runs rmsnorm → QKV projections →
  RoPE → causal flash attention (online softmax over VMEM-resident K/V)
  → output projection → residual add → post-attention rmsnorm → SwiGLU
  MLP → residual add.  The block-boundary activations (normalized x,
  q/k/v, attention output, pre-MLP hidden state) never round-trip HBM:
  a decoder block reads its input activations once and writes its
  output once.  The grid is (batch, token_blocks, inner) where the
  inner axis walks phases — projection column blocks, (head, k-block)
  attention folds, output-projection columns, MLP hidden blocks — and
  per-token-block state lives in VMEM scratch across the inner walk,
  with K/V rows for the WHOLE sequence carried in scratch across token
  blocks (causal attention only ever looks back).  That K/V residency
  is the VMEM budget: eligibility requires ``2·s·dkv`` io-dtype bytes
  plus the walked weight blocks to fit (~12 MB), so the kernel serves
  short/medium contexts and decode-sized rows; longer shapes fall back
  to the per-segment kernels above.  The custom VJP recomputes the
  block from its saved INPUTS (reference math + the flash blockwise
  backward) — block-boundary remat: training saves only x per layer
  instead of every intermediate.

Env knobs:
  PADDLE_TPU_FUSED_BLOCK=1|0      force-enable the per-segment kernels
                                  (interpret off-TPU) / disable;
                                  unset = auto (TPU backend only)
  PADDLE_TPU_FUSED_BLOCK=decoder  additionally route eligible llama
                                  decoder layers through the
                                  whole-block megakernel (per-segment
                                  kernels keep ineligible layers)
  PADDLE_TPU_FUSED_BLOCK=measured per-shape decision from the
                                  measurement ledger: an eligible
                                  decoder layer routes through the
                                  megakernel only when the ledger
                                  measured it faster than the
                                  per-segment path for that shape on
                                  this backend (no coverage -> the
                                  per-segment tier, i.e. auto)
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from jax.experimental.pallas import tpu as pltpu

__all__ = ["fused_rmsnorm_qkv", "fused_mlp", "fused_ffn",
           "fused_decoder_block", "fused_block_enabled",
           "fused_block_tier", "fused_decoder_enabled",
           "measured_tier_for",
           "fused_qkv_eligible", "fused_mlp_eligible",
           "fused_decoder_eligible", "decoder_vmem_bytes", "record_path",
           "SUPPORTED_ACTS"]

_ACT = {
    "silu": jax.nn.silu,
    # exact erf form — matches F.gelu (jax.nn.gelu defaults to tanh)
    "gelu": functools.partial(jax.nn.gelu, approximate=False),
    "relu": jax.nn.relu,
}
SUPPORTED_ACTS = tuple(_ACT)


def fused_block_tier() -> str:
    """The PADDLE_TPU_FUSED_BLOCK knob as a tier: ``"off"`` (reference
    lowering everywhere), ``"fused"`` (the PR-8 per-segment kernels —
    rmsnorm+QKV and MLP), ``"decoder"`` (additionally route eligible
    llama decoder layers through the whole-block megakernel).  Unset =
    auto: ``"fused"`` on a TPU backend outside a sharded step
    (``ops/pallas/mesh.py``), ``"off"`` elsewhere — the
    decoder tier is opt-in only, so existing knob values reproduce
    their previous jaxprs exactly.  ``"measured"`` resolves the
    decoder-vs-per-segment choice per shape from the measurement
    ledger (:func:`measured_tier_for`) instead of globally."""
    env = os.environ.get("PADDLE_TPU_FUSED_BLOCK", "").strip().lower()
    if env in ("0", "false", "off", "no"):
        return "off"
    if env == "decoder":
        return "decoder"
    if env == "measured":
        return "measured"
    if env in ("1", "true", "on", "yes"):
        return "fused"
    if jax.default_backend() != "tpu":
        return "off"
    from paddle_tpu.ops.pallas import mesh
    if mesh.current() is not None:
        # the step shards these kernels' weights, and XLA cannot
        # partition a Mosaic kernel: the XLA path, which it can
        mesh.record_route("fused_block", "xla")
        return "off"
    return "fused"


def fused_block_enabled() -> bool:
    """Routing gate: env wins, else auto = TPU backend only (interpret
    mode off-TPU is for tests, not the hot path)."""
    return fused_block_tier() != "off"


def fused_decoder_enabled() -> bool:
    """True only at the explicit ``PADDLE_TPU_FUSED_BLOCK=decoder``
    tier — never auto-on, so every pre-existing knob value keeps its
    exact previous lowering.  (The ``measured`` tier routes the
    megakernel per shape through :func:`measured_tier_for`, not through
    this global gate.)"""
    return fused_block_tier() == "decoder"


def measured_tier_for(shape, dtype) -> str:
    """The ``PADDLE_TPU_FUSED_BLOCK=measured`` decision for one decoder
    activation shape ``(b, s, d)``: which tier the measurement ledger
    recorded as fastest on THIS backend.

    The DeviceProfiler feeder tags every ``decoder_block`` /
    ``decoder_block_fused`` segment row with the fusion tier active
    when it was measured, so the three lowerings are distinct ledger
    populations: a sweep day that profiles under ``off``, ``1`` and
    ``decoder`` gives this function all three measurements to compare.
    Returns ``"decoder"``, ``"fused"`` or ``"off"`` — the fastest tier
    with coverage; without any coverage the answer is ``"fused"`` (the
    auto default), so an empty ledger makes ``measured`` behave exactly
    like the per-segment tier.

    Only the decoder-layer boundary consults this (megakernel vs
    per-segment routing, the decision with measured 10x+ spread); the
    per-segment kernels themselves stay enabled under ``measured`` as
    under auto."""
    dtype = str(dtype)
    times = {}
    try:
        from paddle_tpu.observability import calibration
        model = calibration.CalibratedCostModel()
        t = model.measured_for("decoder_block_fused", shape, dtype,
                               layout="tier=decoder")
        if t is not None:
            times["decoder"] = t
        for tier, op in (("fused", "decoder_block"),
                         ("off", "decoder_block")):
            t = model.measured_for(op, shape, dtype,
                                   layout=f"tier={tier}")
            if t is not None:
                times[tier] = t
    except Exception:
        return "fused"
    if not times:
        return "fused"
    return min(times, key=times.get)


def _row_quantum(dtype) -> int:
    """Min sublane tile: 8 rows for 4-byte dtypes, 16 for 16-bit."""
    s = str(dtype)
    return 16 if ("bfloat16" in s or "float16" in s) else 8


def fused_qkv_eligible(t: int, d: int, dq: int, dk: int, dv: int,
                       dtype="float32") -> bool:
    """Shape gate: feature dims must tile the 128-lane VPU/MXU; the
    token axis must tile the dtype's sublane minimum (serving decode
    with t = batch falls back to the reference path)."""
    q = _row_quantum(dtype)
    return (t >= q and t % q == 0 and d % 128 == 0 and
            dq % 128 == 0 and dk % 128 == 0 and dv % 128 == 0)


def fused_mlp_eligible(t: int, d: int, f: int, dtype="float32") -> bool:
    q = _row_quantum(dtype)
    return t >= q and t % q == 0 and d % 128 == 0 and f % 128 == 0


def _path_counter():
    from paddle_tpu.observability import default_registry
    return default_registry().counter(
        "paddle_tpu_fused_block_path_total",
        "fused-block kernel routing chosen at trace time",
        labelnames=("kernel", "path"))


def record_path(kernel: str, fused: bool):
    """Trace-time telemetry: which implementation this compile will run
    (same idiom as the flash-attention backward path counter)."""
    _path_counter().labels(
        kernel=kernel, path="fused" if fused else "reference").inc()


def _passes_counter():
    from paddle_tpu.observability import default_registry
    return default_registry().counter(
        "paddle_tpu_fused_block_weight_passes_total",
        "times a fused-block kernel walks its weights (rows over the "
        "token block chosen at trace time)",
        labelnames=("kernel", "passes"))


def record_weight_passes(kernel: str, t: int, block_t: int):
    """Trace-time telemetry beside :func:`record_path`: a grid of
    ``t / block_t`` token blocks reads every weight that many times."""
    _passes_counter().labels(kernel=kernel, passes=str(t // block_t)).inc()


# ---------------------------------------------------------------------------
# the block rule of the two per-segment kernels
# ---------------------------------------------------------------------------
#
# The grid is (token blocks, column blocks) with the weights' index maps
# following the column axis alone: every token block walks all of the
# weights again.  ``t / block_t`` passes cost ``passes * weight bytes /
# HBM bandwidth`` against ``flops / peak``, and a token block of r rows
# does ``2 r / itemsize`` operations a weight byte, so below the chip's
# ridge the kernel waits for its weights.  The rule reads the shape and
# nothing else.

# A v5e core has 128 MiB of VMEM and the compiler's own scope is 16: where
# the blocks need more, both calls ask for it (``vmem_limit_bytes``, and
# the verifier is told the same number: :func:`_vmem_limit`), never more
# than the limit; a block choice holds no more than the budget, which
# leaves the ask room for the float32 temporaries of a step.
_VMEM_LIMIT = 64 * (1 << 20)
_VMEM_BUDGET = 40 * (1 << 20)
# what the compiler's own scope holds with room to spare: the bound of the
# blocks as they were chosen before the calls asked for more, and of the
# calls that ask for nothing (a decode step's program is the one it was)
_COMPILER_SCOPE = 10 * (1 << 20)


def block_vmem_bytes(kernel: str, block_t: int, block_c: int, d: int,
                     itemsize: int, residuals: bool = False) -> int:
    """What a call of ``kernel`` ("mlp" | "qkv") holds in VMEM: the
    streamed blocks twice (the grid's pipeline double-buffers them), the
    float32 scratch once.  ``block_c`` is the hidden block of the MLP and
    the out block of the projections; ``residuals`` adds what the
    differentiated QKV call also writes."""
    rows = block_t * d
    weights = 6 * d * block_c * itemsize         # 3 weight blocks, 2x
    if kernel == "mlp":
        return (4 * rows * itemsize              # x and y, 2x each
                + rows * 4                       # fp32 accumulator
                + weights)
    saved = 2 * rows * itemsize + 8 * block_t    # xn and 1/rms out, 2x
    return (2 * rows * itemsize                  # x, 2x
            + rows * 4                           # fp32 normalized scratch
            + d * itemsize                       # the norm's weight, once
            + weights
            + 6 * block_t * block_c * itemsize   # 3 out blocks, 2x
            + (saved if residuals else 0))


def _vmem_limit(kernel, block_t, block_c, d, itemsize, residuals=False):
    """The scope a call asks the compiler for: ``None`` (its own) where
    that holds the blocks, else half again the working set in whole MiB
    (the float32 temporaries of a step), at most the limit.  What a call
    reserves the compiler cannot use around it: ``serve-rag``'s chunk read
    3.5 % slower with one call asking 64 MiB where 32 did (PERF.md
    section 6, PR 42)."""
    held = block_vmem_bytes(kernel, block_t, block_c, d, itemsize)
    if held < _COMPILER_SCOPE:
        return None
    held = block_vmem_bytes(kernel, block_t, block_c, d, itemsize, residuals)
    return min(_VMEM_LIMIT, -(-3 * held // (2 << 20)) << 20)


def _verify_scope(kernel, block_t, block_c, d, dtype, residuals=False):
    """The budget and limit a verifier spec of these blocks carries: what
    the call asks for, or the verifier's own where it asks for nothing."""
    from paddle_tpu.analysis import kernel_verify as kv
    limit = _vmem_limit(kernel, block_t, block_c, d,
                        jnp.dtype(dtype).itemsize, residuals)
    if limit is None:
        return dict(vmem_budget=kv.VMEM_BUDGET_BYTES,
                    vmem_limit=kv.VMEM_LIMIT_BYTES)
    return dict(vmem_budget=_VMEM_BUDGET, vmem_limit=limit)


def _ridge_rows(itemsize: int) -> int:
    """Rows of a token block at which a pass over the weights takes as
    long as its arithmetic (240 in a 16-bit type on a v5e)."""
    from paddle_tpu.analysis.passes.cost_model import (DEFAULT_HBM_BW,
                                                       DEFAULT_PEAK_FLOPS)
    return int(DEFAULT_PEAK_FLOPS / DEFAULT_HBM_BW * itemsize / 2)


def _scope_blocks(kernel, t, widths, d, dtype) -> list:
    """Every (token, column) block pair the compiler's own scope holds,
    widest column block first, then tallest token block."""
    itemsize = jnp.dtype(dtype).itemsize
    return [(bt, bc)
            for bc in (512, 256, 128) if not any(w % bc for w in widths)
            for bt in (512, 256, 128, 64, 32, 16, 8)
            if bt >= _row_quantum(dtype) and t % bt == 0
            and block_vmem_bytes(kernel, bt, bc, d, itemsize)
            < _COMPILER_SCOPE]


def _taller_blocks(kernel, t, bc, d, dtype) -> list:
    """Token blocks, shortest first, that the budget holds at column
    block ``bc`` and that read the weights once (``t`` itself) or stand
    at the ridge or above it."""
    itemsize = jnp.dtype(dtype).itemsize
    ridge = _ridge_rows(itemsize)
    return [bt for bt in sorted({256, 512, 1024, t})
            if t % bt == 0 and (bt == t or bt >= ridge)
            and block_vmem_bytes(kernel, bt, bc, d, itemsize)
            <= _VMEM_BUDGET]


def _choose_blocks(kernel, t, widths, d, dtype):
    """The first pair of :func:`_scope_blocks` where it reads the weights
    once or its token block stands at the ridge: such a shape keeps the
    blocks it had.  Otherwise the same column block under a taller token
    block — all of ``t`` where the budget holds it (one pass), else the
    shortest at the ridge or above."""
    scope = _scope_blocks(kernel, t, widths, d, dtype)
    bt, bc = scope[0] if scope else (_row_quantum(dtype), 128)
    if bt == t or bt >= _ridge_rows(jnp.dtype(dtype).itemsize):
        return bt, bc
    taller = _taller_blocks(kernel, t, bc, d, dtype)
    if t in taller:
        return t, bc
    return (taller[0] if taller else bt), bc


def _block_candidates(kernel, t, widths, d, dtype) -> list:
    """What a sweep is offered.  One pass over the weights is the
    arithmetic's own answer and is offered alone.  A shape of several
    passes is timed: the pairs of 64 rows and more that the compiler's
    scope holds (narrowest column block first, the order swept since
    these kernels had a sweep), the rule's own pair and its double."""
    best = _choose_blocks(kernel, t, widths, d, dtype)
    if best[0] == t:
        return [best]
    out = sorted((c for c in _scope_blocks(kernel, t, widths, d, dtype)
                  if c[0] >= 64), key=lambda c: (c[1], c[0]))
    taller = _taller_blocks(kernel, t, best[1], d, dtype)
    for bt in (best[0], 2 * best[0]):
        if (bt == best[0] or bt in taller) and (bt, best[1]) not in out:
            out.append((bt, best[1]))
    return out


# ---------------------------------------------------------------------------
# clamped index maps — the DMA-once idiom shared by the fused kernels
# and their static-verifier specs (analysis/kernel_verify checks the
# "each block DMAs exactly once per inner sweep" invariant concretely)
# ---------------------------------------------------------------------------

def _clamped(lo, n):
    """Weight-spec index map: clamp the walking axis into [lo, lo+n) so
    a block outside its phase re-uses the resident block (no DMA)."""
    return lambda i, j: (0, jnp.clip(j - lo, 0, n - 1))


def _clamped_out(lo, n):
    """Output-spec variant of :func:`_clamped` (row block tracks i)."""
    return lambda i, j: (i, jnp.clip(j - lo, 0, n - 1))


def _clamp3(lo, n):
    """Decoder-grid (batch, token, inner) variant of :func:`_clamped`."""
    return lambda bi, i, j: (0, jnp.clip(j - lo, 0, n - 1))


# ---------------------------------------------------------------------------
# fused rmsnorm + QKV projection
# ---------------------------------------------------------------------------

def _qkv_kernel(x_ref, wn_ref, wq_ref, wk_ref, wv_ref, *out_refs, eps, nq,
                nk, residuals):
    """Grid: (token_blocks, out_blocks); the out axis is innermost
    (sequential) so the normalized activations computed at j == 0 stay
    in VMEM scratch for every projection block of the token block.
    With ``residuals`` the normalized activations and the inverse rms
    are also emitted (once, at j == 0) for the custom VJP — the
    forward-only (inference) variant keeps the pure
    one-read/three-write form."""
    if residuals:
        q_ref, k_ref, v_ref, xn_out_ref, inv_ref, xn_ref = out_refs
    else:
        q_ref, k_ref, v_ref, xn_ref = out_refs
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _norm():
        xf = x_ref[:].astype(jnp.float32)
        ms = jnp.mean(xf * xf, axis=-1, keepdims=True)
        inv = jax.lax.rsqrt(ms + eps)
        xn_ref[:] = (xf * inv) * wn_ref[:].astype(jnp.float32)
        if residuals:
            xn_out_ref[:] = xn_ref[:].astype(xn_out_ref.dtype)
            inv_ref[:] = inv

    def _proj(w_ref, o_ref):
        o_ref[:] = jax.lax.dot_general(
            xn_ref[:].astype(w_ref.dtype), w_ref[:],
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32).astype(o_ref.dtype)

    @pl.when(j < nq)
    def _q():
        _proj(wq_ref, q_ref)

    @pl.when(jnp.logical_and(j >= nq, j < nq + nk))
    def _k():
        _proj(wk_ref, k_ref)

    @pl.when(j >= nq + nk)
    def _v():
        _proj(wv_ref, v_ref)


def _qkv_pallas(x2d, wn, wq, wk, wv, *, eps, block_t, block_o, interpret,
                residuals):
    t, d = x2d.shape
    dq, dk, dv = wq.shape[1], wk.shape[1], wv.shape[1]
    nt = t // block_t
    nq, nkb, nvb = dq // block_o, dk // block_o, dv // block_o

    # each weight/output spec clamps the out-axis index into its own
    # range (module-level _clamped/_clamped_out): while j walks another
    # projection's blocks the index map returns the previous value, so
    # Mosaic re-uses the resident block instead of issuing a DMA —
    # every block is fetched/flushed once
    out_specs = [
        pl.BlockSpec((block_t, block_o), _clamped_out(0, nq)),
        pl.BlockSpec((block_t, block_o), _clamped_out(nq, nkb)),
        pl.BlockSpec((block_t, block_o), _clamped_out(nq + nkb, nvb)),
    ]
    out_shape = [
        jax.ShapeDtypeStruct((t, dq), x2d.dtype),
        jax.ShapeDtypeStruct((t, dk), x2d.dtype),
        jax.ShapeDtypeStruct((t, dv), x2d.dtype),
    ]
    if residuals:
        out_specs += [pl.BlockSpec((block_t, d), lambda i, j: (i, 0)),
                      pl.BlockSpec((block_t, 1), lambda i, j: (i, 0))]
        out_shape += [jax.ShapeDtypeStruct((t, d), x2d.dtype),
                      jax.ShapeDtypeStruct((t, 1), jnp.float32)]

    params = {}
    if not interpret:
        params["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_vmem_limit("qkv", block_t, block_o, d,
                                         x2d.dtype.itemsize, residuals))

    return pl.pallas_call(
        functools.partial(_qkv_kernel, eps=eps, nq=nq, nk=nkb,
                          residuals=residuals),
        grid=(nt, nq + nkb + nvb),
        in_specs=[
            pl.BlockSpec((block_t, d), lambda i, j: (i, 0)),
            pl.BlockSpec((1, d), lambda i, j: (0, 0)),
            pl.BlockSpec((d, block_o), _clamped(0, nq)),
            pl.BlockSpec((d, block_o), _clamped(nq, nkb)),
            pl.BlockSpec((d, block_o), _clamped(nq + nkb, nvb)),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((block_t, d), jnp.float32)],
        name="rmsnorm_qkv",
        interpret=interpret,
        **params,
    )(x2d, wn.reshape(1, d), wq, wk, wv)


def _qkv_reference(x2d, wn, wq, wk, wv, eps, residuals=False):
    xf = x2d.astype(jnp.float32)
    inv = jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    xn = ((xf * inv) * wn.astype(jnp.float32)).astype(x2d.dtype)

    def proj(w):
        return jax.lax.dot_general(
            xn, w, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32).astype(x2d.dtype)

    out = (proj(wq), proj(wk), proj(wv))
    return out + (xn, inv) if residuals else out


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9))
def _qkv_core(x2d, wn, wq, wk, wv, eps, use_pallas, interpret,
              block_t, block_o):
    # primal (forward-only) path: no residual outputs — inference keeps
    # the pure one-read/three-write kernel
    if use_pallas:
        return tuple(_qkv_pallas(x2d, wn, wq, wk, wv, eps=eps,
                                 block_t=block_t, block_o=block_o,
                                 interpret=interpret, residuals=False))
    return _qkv_reference(x2d, wn, wq, wk, wv, eps)


def _qkv_fwd(x2d, wn, wq, wk, wv, eps, use_pallas, interpret,
             block_t, block_o):
    # differentiated path: the kernel additionally emits the normalized
    # activations and the inverse rms (flash-attention saved-lse style),
    # so the backward never recomputes the norm chain
    if use_pallas:
        q, k, v, xn, inv = _qkv_pallas(
            x2d, wn, wq, wk, wv, eps=eps, block_t=block_t,
            block_o=block_o, interpret=interpret, residuals=True)
    else:
        q, k, v, xn, inv = _qkv_reference(x2d, wn, wq, wk, wv, eps,
                                          residuals=True)
    return (q, k, v), (x2d, wn, wq, wk, wv, xn, inv)


def _qkv_bwd(eps, use_pallas, interpret, block_t, block_o, res, cts):
    # mixed-precision discipline matches what autodiff of the unfused
    # chain produces: matmuls accumulate fp32 on the MXU but cotangents
    # materialize in the io dtype (bf16 in training) — only the fused
    # rmsnorm-backward elementwise chain runs fp32, and XLA fuses it
    x2d, wn, wq, wk, wv, xn, inv = res
    dq, dk, dv = cts
    dt = x2d.dtype
    wnf = wn.astype(jnp.float32)

    def back(g, w):                                     # g @ w.T
        return jax.lax.dot_general(g, w, (((1,), (1,)), ((), ())))

    def wgrad(g):                                       # xn.T @ g, fp32
        return jax.lax.dot_general(
            xn, g, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    dxn = (back(dq, wq) + back(dk, wk) + back(dv, wv)) \
        .astype(jnp.float32)                            # [T, d]
    dwq = wgrad(dq).astype(wq.dtype)
    dwk = wgrad(dk).astype(wk.dtype)
    dwv = wgrad(dv).astype(wv.dtype)
    xf = x2d.astype(jnp.float32)
    xhat = xf * inv                                     # saved inv: no
    dwn = jnp.sum(dxn * xhat, axis=0).astype(wn.dtype)  # stat recompute
    # rmsnorm backward (same equations as ops/pallas/rmsnorm.py):
    # dx = inv * g - x * inv^3 * mean(g * x), with g = dxn * w
    gx = dxn * wnf
    dot = jnp.mean(gx * xf, axis=-1, keepdims=True)
    dx = (inv * gx - xf * (inv ** 3) * dot).astype(dt)
    return dx, dwn, dwq, dwk, dwv


_qkv_core.defvjp(_qkv_fwd, _qkv_bwd)


def _default_qkv_blocks(t, d, dq, dk, dv, dtype):
    """(block_t, block_o) by the block rule (:func:`_choose_blocks`)."""
    return _choose_blocks("qkv", t, (dq, dk, dv), d, dtype)


def fused_rmsnorm_qkv(x, norm_weight, wq, wk, wv, epsilon: float = 1e-5,
                      block_t: int = None, block_o: int = None,
                      interpret: bool = None, autotune: bool = None,
                      use_pallas: bool = None):
    """``q, k, v = (rmsnorm(x) * norm_weight) @ (wq | wk | wv)`` in one
    fused pass — the normalized activations never round-trip HBM.

    x: [..., d]; norm_weight: [d]; wq/wk/wv: [d, dq/dk/dv] (paddle
    [in, out] layout).  Returns projections with x's leading dims.
    Differentiable wrt every array input.  Ineligible shapes fall back
    to reference math inside the same custom VJP (the API is total)."""
    shape = x.shape
    d = shape[-1]
    x2d = x.reshape(-1, d)
    t = x2d.shape[0]
    dq, dk, dv = int(wq.shape[-1]), int(wk.shape[-1]), int(wv.shape[-1])
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    if use_pallas is None:
        use_pallas = fused_qkv_eligible(t, d, dq, dk, dv, x.dtype)
    if autotune is None:
        autotune = not interpret
    if use_pallas and (block_t is None or block_o is None):
        if autotune and not interpret:
            from paddle_tpu.ops.pallas.autotune import qkv_block_sizes
            bt, bo = qkv_block_sizes(t, d, dq, dk, dv, str(x.dtype))
        else:
            bt, bo = _default_qkv_blocks(t, d, dq, dk, dv, str(x.dtype))
        block_t = block_t or bt
        block_o = block_o or bo
    if use_pallas and (t % block_t or dq % block_o or dk % block_o
                       or dv % block_o):
        raise ValueError(
            f"shapes t={t} dq={dq} dk={dk} dv={dv} not divisible by "
            f"blocks ({block_t}, {block_o})")
    if use_pallas:
        record_weight_passes("rmsnorm_qkv", t, block_t)
    q, k, v = _qkv_core(x2d, norm_weight, wq, wk, wv, float(epsilon),
                        bool(use_pallas), bool(interpret),
                        int(block_t or 0), int(block_o or 0))
    lead = shape[:-1]
    return (q.reshape(*lead, dq), k.reshape(*lead, dk),
            v.reshape(*lead, dv))


# ---------------------------------------------------------------------------
# fused MLP (gated SwiGLU and plain act+bias feed-forward)
# ---------------------------------------------------------------------------

def _mlp_kernel(*refs, act, gated, has_bias):
    """Grid: (token_blocks, hidden_blocks); the hidden (f) axis is the
    innermost (sequential) dim — each step materializes only a
    [bt, bf] tile of the hidden activations in VMEM and folds it into
    the fp32 down-projection accumulator."""
    if gated:
        x_ref, wg_ref, wu_ref, wd_ref, y_ref, acc_ref = refs
        bu_ref = bd_ref = None
    else:
        x_ref, wu_ref, wd_ref, bu_ref, bd_ref, y_ref, acc_ref = refs
        wg_ref = None
    j = pl.program_id(1)
    nf = pl.num_programs(1)

    @pl.when(j == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    xb = x_ref[:]
    u = jax.lax.dot_general(
        xb, wu_ref[:], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)              # [bt, bf]
    if has_bias:
        u = u + bu_ref[:].astype(jnp.float32)
    if gated:
        g = jax.lax.dot_general(
            xb, wg_ref[:], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        h = _ACT[act](g) * u
    else:
        h = _ACT[act](u)
    acc_ref[:] += jax.lax.dot_general(
        h.astype(wd_ref.dtype), wd_ref[:], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)              # [bt, d]

    @pl.when(j == nf - 1)
    def _finalize():
        out = acc_ref[:]
        if has_bias:
            out = out + bd_ref[:].astype(jnp.float32)
        y_ref[:] = out.astype(y_ref.dtype)


def _mlp_pallas(x2d, weights, biases, *, act, gated, block_t, block_f,
                interpret):
    t, d = x2d.shape
    f = weights[-2].shape[1] if gated else weights[0].shape[1]
    nt = t // block_t
    nf = f // block_f

    in_specs = [pl.BlockSpec((block_t, d), lambda i, j: (i, 0))]
    args = [x2d]
    for w in weights[:-1]:                               # gate/up: [d, f]
        in_specs.append(pl.BlockSpec((d, block_f), lambda i, j: (0, j)))
        args.append(w)
    in_specs.append(pl.BlockSpec((block_f, d), lambda i, j: (j, 0)))
    args.append(weights[-1])                             # down: [f, d]
    if biases is not None:
        b1, b2 = biases
        in_specs.append(pl.BlockSpec((1, block_f), lambda i, j: (0, j)))
        args.append(b1.reshape(1, f))
        in_specs.append(pl.BlockSpec((1, d), lambda i, j: (0, 0)))
        args.append(b2.reshape(1, d))

    params = {}
    if not interpret:
        params["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_vmem_limit("mlp", block_t, block_f, d,
                                         x2d.dtype.itemsize))

    return pl.pallas_call(
        functools.partial(_mlp_kernel, act=act, gated=gated,
                          has_bias=biases is not None),
        grid=(nt, nf),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((block_t, d), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((t, d), x2d.dtype),
        scratch_shapes=[pltpu.VMEM((block_t, d), jnp.float32)],
        name="fused_mlp",
        interpret=interpret,
        **params,
    )(*args)


def _dot(a, b, contract):
    return jax.lax.dot_general(a, b, (contract, ((), ())),
                               preferred_element_type=jnp.float32)


def _mlp_gated_reference(x2d, wg, wu, wd, act):
    g = _dot(x2d, wg, ((1,), (0,)))
    u = _dot(x2d, wu, ((1,), (0,)))
    h = (_ACT[act](g) * u).astype(x2d.dtype)
    return _dot(h, wd, ((1,), (0,))).astype(x2d.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8))
def _mlp_gated_core(x2d, wg, wu, wd, act, use_pallas, interpret,
                    block_t, block_f):
    return _mlp_gated_fwd(x2d, wg, wu, wd, act, use_pallas, interpret,
                          block_t, block_f)[0]


def _mlp_gated_fwd(x2d, wg, wu, wd, act, use_pallas, interpret,
                   block_t, block_f):
    if use_pallas:
        y = _mlp_pallas(x2d, (wg, wu, wd), None, act=act, gated=True,
                        block_t=block_t, block_f=block_f,
                        interpret=interpret)
    else:
        y = _mlp_gated_reference(x2d, wg, wu, wd, act)
    return y, (x2d, wg, wu, wd)


def _mlp_gated_bwd(act, use_pallas, interpret, block_t, block_f, res, dy):
    # recompute in the io dtype (matmuls still accumulate fp32 on the
    # MXU) — the materialized [T, f] intermediates cost the same HBM
    # bytes autodiff of the unfused bf16 chain would spend
    x2d, wg, wu, wd = res
    dt = x2d.dtype

    def dot_t(a, b, contract):      # io-dtype out, fp32 MXU accumulate
        return jax.lax.dot_general(a, b, (contract, ((), ())))

    g = dot_t(x2d, wg, ((1,), (0,)))                    # recompute
    u = dot_t(x2d, wu, ((1,), (0,)))
    s, act_vjp = jax.vjp(_ACT[act], g)
    h = s * u
    dh = dot_t(dy, wd, ((1,), (1,)))                    # [T, f]
    dwd = _dot(h, dy, ((0,), (0,))).astype(wd.dtype)
    du = dh * s
    dg = act_vjp(dh * u)[0].astype(dt)
    dx = dot_t(dg, wg, ((1,), (1,))) + dot_t(du, wu, ((1,), (1,)))
    dwg = _dot(x2d, dg, ((0,), (0,))).astype(wg.dtype)
    dwu = _dot(x2d, du, ((0,), (0,))).astype(wu.dtype)
    return dx.astype(dt), dwg, dwu, dwd


_mlp_gated_core.defvjp(_mlp_gated_fwd, _mlp_gated_bwd)


def _ffn_reference(x2d, w1, b1, w2, b2, act):
    u = _dot(x2d, w1, ((1,), (0,))) + b1.astype(jnp.float32)
    h = _ACT[act](u).astype(x2d.dtype)
    y = _dot(h, w2, ((1,), (0,))) + b2.astype(jnp.float32)
    return y.astype(x2d.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9))
def _ffn_core(x2d, w1, b1, w2, b2, act, use_pallas, interpret,
              block_t, block_f):
    return _ffn_fwd(x2d, w1, b1, w2, b2, act, use_pallas, interpret,
                    block_t, block_f)[0]


def _ffn_fwd(x2d, w1, b1, w2, b2, act, use_pallas, interpret,
             block_t, block_f):
    if use_pallas:
        y = _mlp_pallas(x2d, (w1, w2), (b1, b2), act=act, gated=False,
                        block_t=block_t, block_f=block_f,
                        interpret=interpret)
    else:
        y = _ffn_reference(x2d, w1, b1, w2, b2, act)
    return y, (x2d, w1, b1, w2, b2)


def _ffn_bwd(act, use_pallas, interpret, block_t, block_f, res, dy):
    x2d, w1, b1, w2, b2 = res
    dt = x2d.dtype
    u = (_dot(x2d, w1, ((1,), (0,))) + b1.astype(jnp.float32)).astype(dt)
    h, act_vjp = jax.vjp(_ACT[act], u)
    dh = jax.lax.dot_general(dy, w2,
                             ((((1,), (1,))), ((), ()))).astype(dt)
    dw2 = _dot(h, dy, ((0,), (0,))).astype(w2.dtype)
    db2 = jnp.sum(dy.astype(jnp.float32), axis=0).astype(b2.dtype)
    du = act_vjp(dh)[0].astype(dt)
    dx = _dot(du, w1, ((1,), (1,))).astype(dt)
    dw1 = _dot(x2d, du, ((0,), (0,))).astype(w1.dtype)
    db1 = jnp.sum(du.astype(jnp.float32), axis=0).astype(b1.dtype)
    return dx, dw1, db1, dw2, db2


_ffn_core.defvjp(_ffn_fwd, _ffn_bwd)


def _default_mlp_blocks(t, d, f, dtype):
    """(block_t, block_f) by the block rule (:func:`_choose_blocks`)."""
    return _choose_blocks("mlp", t, (f,), d, dtype)


def _mlp_blocks(kernel, t, d, f, dtype, block_t, block_f, interpret,
                autotune):
    if block_t is None or block_f is None:
        if autotune and not interpret:
            from paddle_tpu.ops.pallas.autotune import mlp_block_sizes
            bt, bf = mlp_block_sizes(t, d, f, dtype)
        else:
            bt, bf = _default_mlp_blocks(t, d, f, dtype)
        block_t = block_t or bt
        block_f = block_f or bf
    if t % block_t or f % block_f:
        raise ValueError(f"shapes t={t} f={f} not divisible by blocks "
                         f"({block_t}, {block_f})")
    record_weight_passes(kernel, t, block_t)
    return int(block_t), int(block_f)


def fused_mlp(x, w_gate, w_up, w_down, activation: str = "silu",
              block_t: int = None, block_f: int = None,
              interpret: bool = None, autotune: bool = None,
              use_pallas: bool = None):
    """``y = (act(x @ w_gate) * (x @ w_up)) @ w_down`` with the [T, f]
    hidden intermediate VMEM-resident (SwiGLU when ``activation='silu'``).

    x: [..., d]; w_gate/w_up: [d, f]; w_down: [f, d].  Differentiable
    wrt every array input; ineligible shapes take reference math inside
    the same custom VJP."""
    if activation not in _ACT:
        raise ValueError(f"unsupported activation {activation!r}; "
                         f"expected one of {SUPPORTED_ACTS}")
    shape = x.shape
    d = shape[-1]
    x2d = x.reshape(-1, d)
    t = x2d.shape[0]
    f = int(w_up.shape[-1])
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    if use_pallas is None:
        use_pallas = fused_mlp_eligible(t, d, f, x.dtype)
    if autotune is None:
        autotune = not interpret
    if use_pallas:
        block_t, block_f = _mlp_blocks("mlp", t, d, f, str(x.dtype),
                                       block_t, block_f, interpret,
                                       autotune)
    y = _mlp_gated_core(x2d, w_gate, w_up, w_down, str(activation),
                        bool(use_pallas), bool(interpret),
                        int(block_t or 0), int(block_f or 0))
    return y.reshape(shape)


# ---------------------------------------------------------------------------
# whole-decoder-block megakernel (ISSUE 15)
# ---------------------------------------------------------------------------
#
# Inner-axis phase layout for grid (batch, token_blocks, inner):
#
#   [0, nqc)            q-projection column blocks (+ RoPE, into q scratch)
#   [nqc, nqc+nkc)      k/v-projection column blocks (+ RoPE on k), written
#                       into the sequence-wide K/V scratch at this token
#                       block's rows — later token blocks read them back
#                       for causal attention without any HBM traffic
#   [B0, B0+nh*nt)      attention: (head, k-block) pairs, online softmax
#                       in fp32 scratch; k-blocks past the causal frontier
#                       are pl.when-skipped
#   [C0, C0+no)         output-projection column blocks + residual add
#   [D0, D0+nf)         post-attention rmsnorm (at the first step) and the
#                       SwiGLU MLP hidden blocks folded into an fp32
#                       down-projection accumulator; the final step adds
#                       the residual and emits the block's single output
#
# Numerics mirror the unfused chain: every intermediate that the unfused
# lowering materializes in the io dtype is cast to the io dtype at the
# same point in-register (norm outputs, roped q/k, v, attention output,
# o-proj output); statistics, softmax and matmul accumulation stay fp32.

_DECODER_VMEM_BUDGET = 12 * (1 << 20)


def decoder_vmem_bytes(s, d, dq, dkv, hd, f, bt, bo, bf, dtype) -> int:
    """VMEM working set of the whole-block kernel, computed by the
    SHARED verifier footprint model (``analysis/kernel_verify``): the
    sequence-wide K/V scratch dominates; walked weight/io blocks are
    double-buffered by the grid pipeline, constant-map norm weights are
    resident.  Because the eligibility gate and ``lint --kernels`` both
    read this one model, their verdicts can never disagree."""
    from paddle_tpu.analysis.kernel_verify import footprint_bytes
    return footprint_bytes(
        _decoder_verify_spec(1, s, d, dq, dkv, hd, f, bt, bo, bf, dtype))


def _default_decoder_blocks(s, d, dq, dkv, hd, f, dtype):
    """First (block_t, block_o, block_f) — widest out/hidden blocks
    first, then tallest token block — whose working set fits the VMEM
    budget; None when nothing fits (the eligibility gate)."""
    q = _row_quantum(dtype)
    bts = [b for b in (256, 128, 64, 32, 16, 8) if b >= q]
    for bo in (512, 256, 128):
        if bo % hd or dq % bo or dkv % bo or d % bo:
            continue
        for bf in (512, 256, 128):
            if f % bf:
                continue
            for bt in bts:
                if s % bt:
                    continue
                if decoder_vmem_bytes(s, d, dq, dkv, hd, f, bt, bo, bf,
                                      dtype) < _DECODER_VMEM_BUDGET:
                    return bt, bo, bf
    return None


def fused_decoder_eligible(b, s, d, dq, dkv, hd, f, dtype="float32") -> bool:
    """Shape gate for the whole-block kernel: lane-tileable feature
    dims, whole 128-aligned heads (RoPE and the per-head attention
    slices walk head boundaries), a flash-legal sequence for the VJP
    recompute, and a (bt, bo, bf) choice inside the VMEM budget."""
    q = _row_quantum(dtype)
    if s < q or s % q:
        return False
    if s % min(128, s):                 # flash blocks in the backward
        return False
    if d % 128 or dq % 128 or dkv % 128 or f % 128:
        return False
    if hd <= 0 or hd % 128 or dq % hd or dkv % hd:
        return False
    if (dq // hd) % (dkv // hd):        # GQA: q heads per kv head
        return False
    return _default_decoder_blocks(s, d, dq, dkv, hd, f,
                                   str(dtype)) is not None


def _decoder_kernel(x_ref, wn1_ref, wq_ref, wk_ref, wv_ref, cos_ref,
                    sin_ref, wo_ref, wn2_ref, wg_ref, wu_ref, wd_ref,
                    y_ref, xn_scr, q_scr, k_scr, v_scr, attn_scr, x2_scr,
                    m_scr, l_scr, acc_scr, yacc_scr, *, eps, nh, nkvh,
                    hd, bt, bo, bf, nqc, nkc, nt, no, nf):
    i = pl.program_id(1)
    j = pl.program_id(2)
    B0 = nqc + nkc
    C0 = B0 + nh * nt
    D0 = C0 + no
    hh = hd // 2
    rep = nh // nkvh
    scale = 1.0 / (hd ** 0.5)
    io_dt = y_ref.dtype

    def _rmsnorm_into(src_f32, wn_ref):
        inv = jax.lax.rsqrt(
            jnp.mean(src_f32 * src_f32, axis=-1, keepdims=True) + eps)
        xn_scr[:] = ((src_f32 * inv)
                     * wn_ref[:].astype(jnp.float32)).astype(xn_scr.dtype)

    @pl.when(j == 0)
    def _norm1():
        _rmsnorm_into(x_ref[0].astype(jnp.float32), wn1_ref)

    def _proj(w_ref):
        return jax.lax.dot_general(
            xn_scr[:], w_ref[:], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    def _rope_heads(blk_f32):
        """RoPE per whole head of a [bt, bo] projection block — the
        unfused chain quantizes projections to the io dtype before the
        fp32 rotation, so this does too."""
        cos = cos_ref[:].astype(jnp.float32)           # [bt, hd//2]
        sin = sin_ref[:].astype(jnp.float32)
        heads = []
        for h0 in range(bo // hd):
            gh = blk_f32[:, h0 * hd:(h0 + 1) * hd].astype(io_dt) \
                .astype(jnp.float32)
            x1, x2 = gh[:, :hh], gh[:, hh:]
            heads.append(jnp.concatenate(
                [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1))
        return jnp.concatenate(heads, axis=-1) if len(heads) > 1 \
            else heads[0]

    # -- phase A: projections + RoPE into scratch ---------------------------
    @pl.when(j < nqc)
    def _q_cols():
        q_scr[:, pl.ds(j * bo, bo)] = _rope_heads(_proj(wq_ref)) \
            .astype(q_scr.dtype)

    @pl.when(jnp.logical_and(j >= nqc, j < B0))
    def _kv_cols():
        jk = j - nqc
        rows = pl.ds(i * bt, bt)
        k_scr[rows, pl.ds(jk * bo, bo)] = _rope_heads(_proj(wk_ref)) \
            .astype(k_scr.dtype)
        v_scr[rows, pl.ds(jk * bo, bo)] = _proj(wv_ref).astype(v_scr.dtype)

    # -- phase B: causal flash attention over the VMEM-resident K/V --------
    @pl.when(jnp.logical_and(j >= B0, j < C0))
    def _attention():
        t = j - B0
        h = t // nt
        kj = t % nt

        @pl.when(kj == 0)
        def _init():
            acc_scr[:] = jnp.zeros_like(acc_scr)
            m_scr[:] = jnp.full_like(m_scr, _DEC_NEG_INF)
            l_scr[:] = jnp.zeros_like(l_scr)

        @pl.when(kj <= i)
        def _fold():
            qh = q_scr[:, pl.ds(h * hd, hd)]
            kvh = h // rep
            kb = k_scr[pl.ds(kj * bt, bt), pl.ds(kvh * hd, hd)]
            vb = v_scr[pl.ds(kj * bt, bt), pl.ds(kvh * hd, hd)]
            s_ = jax.lax.dot_general(
                qh, kb, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale   # [bt, bt]
            q_pos = i * bt + jax.lax.broadcasted_iota(
                jnp.int32, (bt, bt), 0)
            k_pos = kj * bt + jax.lax.broadcasted_iota(
                jnp.int32, (bt, bt), 1)
            s_ = jnp.where(q_pos >= k_pos, s_, _DEC_NEG_INF)
            m_prev = m_scr[:]
            m_new = jnp.maximum(m_prev, jnp.max(s_, axis=-1, keepdims=True))
            p = jnp.exp(s_ - m_new)
            corr = jnp.exp(m_prev - m_new)
            l_scr[:] = corr * l_scr[:] + jnp.sum(p, axis=-1, keepdims=True)
            pv = jax.lax.dot_general(
                p.astype(vb.dtype), vb, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            acc_scr[:] = acc_scr[:] * corr + pv
            m_scr[:] = m_new

        @pl.when(kj == i)                   # last visible block: finalize
        def _finalize():
            l = l_scr[:]
            safe_l = jnp.where(l > 0, l, 1.0)
            attn_scr[:, pl.ds(h * hd, hd)] = \
                (acc_scr[:] / safe_l).astype(attn_scr.dtype)

    # -- phase C: output projection + residual ------------------------------
    @pl.when(jnp.logical_and(j >= C0, j < D0))
    def _o_proj():
        jo = j - C0
        cols = pl.ds(jo * bo, bo)
        ob = jax.lax.dot_general(
            attn_scr[:], wo_ref[:], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        x2_scr[:, cols] = x_ref[0, :, cols] + ob.astype(io_dt)

    # -- phase D: post-attention norm + SwiGLU MLP + residual ---------------
    @pl.when(j == D0)
    def _norm2():
        _rmsnorm_into(x2_scr[:].astype(jnp.float32), wn2_ref)
        yacc_scr[:] = jnp.zeros_like(yacc_scr)

    @pl.when(j >= D0)
    def _mlp():
        xb = xn_scr[:]
        g = jax.lax.dot_general(
            xb, wg_ref[:], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        u = jax.lax.dot_general(
            xb, wu_ref[:], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        hgu = jax.nn.silu(g) * u
        yacc_scr[:] += jax.lax.dot_general(
            hgu.astype(wd_ref.dtype), wd_ref[:], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(j == D0 + nf - 1)
    def _emit():
        y_ref[0] = x2_scr[:] + yacc_scr[:].astype(io_dt)


_DEC_NEG_INF = -1e30


def _decoder_pallas(x, wn1, wq, wk, wv, cos, sin, wo, wn2, wg, wu, wd, *,
                    eps, nh, nkvh, bt, bo, bf, interpret):
    b, s, d = x.shape
    dq, dkv, f = wq.shape[1], wk.shape[1], wu.shape[1]
    hd = dq // nh
    nt = s // bt
    nqc, nkc = dq // bo, dkv // bo
    no, nf = d // bo, f // bf
    B0 = nqc + nkc
    C0 = B0 + nh * nt
    D0 = C0 + no
    inner = D0 + nf

    params = {}
    if not interpret:
        params["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"))

    return pl.pallas_call(
        functools.partial(_decoder_kernel, eps=eps, nh=nh, nkvh=nkvh,
                          hd=hd, bt=bt, bo=bo, bf=bf, nqc=nqc, nkc=nkc,
                          nt=nt, no=no, nf=nf),
        grid=(b, nt, inner),
        in_specs=[
            pl.BlockSpec((1, bt, d), lambda bi, i, j: (bi, i, 0)),
            pl.BlockSpec((1, d), lambda bi, i, j: (0, 0)),
            pl.BlockSpec((d, bo), _clamp3(0, nqc)),
            pl.BlockSpec((d, bo), _clamp3(nqc, nkc)),
            pl.BlockSpec((d, bo), _clamp3(nqc, nkc)),
            pl.BlockSpec((bt, hd // 2), lambda bi, i, j: (i, 0)),
            pl.BlockSpec((bt, hd // 2), lambda bi, i, j: (i, 0)),
            pl.BlockSpec((dq, bo), _clamp3(C0, no)),
            pl.BlockSpec((1, d), lambda bi, i, j: (0, 0)),
            pl.BlockSpec((d, bf), _clamp3(D0, nf)),
            pl.BlockSpec((d, bf), _clamp3(D0, nf)),
            pl.BlockSpec((bf, d),
                         lambda bi, i, j: (jnp.clip(j - D0, 0, nf - 1), 0)),
        ],
        out_specs=pl.BlockSpec((1, bt, d), lambda bi, i, j: (bi, i, 0)),
        out_shape=jax.ShapeDtypeStruct((b, s, d), x.dtype),
        scratch_shapes=[
            pltpu.VMEM((bt, d), x.dtype),       # xn (norm1, reused norm2)
            pltpu.VMEM((bt, dq), x.dtype),      # roped q
            pltpu.VMEM((s, dkv), x.dtype),      # K rows, whole sequence
            pltpu.VMEM((s, dkv), x.dtype),      # V rows, whole sequence
            pltpu.VMEM((bt, dq), x.dtype),      # attention output
            pltpu.VMEM((bt, d), x.dtype),       # post-attention residual
            pltpu.VMEM((bt, 1), jnp.float32),   # online-softmax max
            pltpu.VMEM((bt, 1), jnp.float32),   # online-softmax sum
            pltpu.VMEM((bt, hd), jnp.float32),  # per-head softmax acc
            pltpu.VMEM((bt, d), jnp.float32),   # MLP down accumulator
        ],
        name="fused_decoder",
        interpret=interpret,
        **params,
    )(x, wn1.reshape(1, d), wq, wk, wv, cos, sin, wo,
      wn2.reshape(1, d), wg, wu, wd)


def _rope_ref(x, cos, sin):
    """Reference RoPE on [b, s, heads, hd] with [s, hd//2] tables — the
    same half-rotation math as F.apply_rotary_emb at offset 0."""
    c = cos[None, :, None, :].astype(jnp.float32)
    s_ = sin[None, :, None, :].astype(jnp.float32)
    half = x.shape[-1] // 2
    xf = x.astype(jnp.float32)
    x1, x2 = xf[..., :half], xf[..., half:]
    return jnp.concatenate([x1 * c - x2 * s_, x2 * c + x1 * s_],
                           axis=-1).astype(x.dtype)


def _decoder_reference(x, wn1, wq, wk, wv, cos, sin, wo, wn2, wg, wu, wd,
                       *, eps, nh, nkvh):
    """The unfused decoder-block composition: rmsnorm → projections →
    RoPE → causal flash attention → o-proj → residual → rmsnorm →
    SwiGLU MLP → residual.  Differentiable end-to-end (flash's blockwise
    backward keeps memory O(s·block)) — both the ineligible-shape
    fallback of :func:`fused_decoder_block` and the recompute target of
    its block-boundary-remat VJP."""
    b, s, d = x.shape
    dq, dkv = wq.shape[1], wk.shape[1]
    hd = dq // nh
    x2d = x.reshape(-1, d)
    q, k, v = _qkv_reference(x2d, wn1, wq, wk, wv, eps)
    q = _rope_ref(q.reshape(b, s, nh, hd), cos, sin)
    k = _rope_ref(k.reshape(b, s, nkvh, hd), cos, sin)
    v = v.reshape(b, s, nkvh, hd)
    from paddle_tpu.ops.pallas.flash_attention import flash_attention
    blk = min(128, s)
    o = flash_attention(q, k, v, causal=True, block_q=blk, block_k=blk,
                        autotune=False)
    h = jax.lax.dot_general(
        o.reshape(-1, dq), wo, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32).astype(x.dtype)
    x2 = x + h.reshape(b, s, d)
    xf = x2.astype(jnp.float32)
    inv = jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps)
    xn2 = ((xf * inv) * wn2.astype(jnp.float32)).astype(x.dtype)
    y = _mlp_gated_reference(xn2.reshape(-1, d), wg, wu, wd, "silu")
    return x2 + y.reshape(b, s, d)


@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(12, 13, 14, 15, 16, 17, 18, 19))
def _decoder_core(x, wn1, wq, wk, wv, cos, sin, wo, wn2, wg, wu, wd,
                  eps, nh, nkvh, use_pallas, interpret, bt, bo, bf):
    if use_pallas:
        return _decoder_pallas(x, wn1, wq, wk, wv, cos, sin, wo, wn2,
                               wg, wu, wd, eps=eps, nh=nh, nkvh=nkvh,
                               bt=bt, bo=bo, bf=bf, interpret=interpret)
    return _decoder_reference(x, wn1, wq, wk, wv, cos, sin, wo, wn2,
                              wg, wu, wd, eps=eps, nh=nh, nkvh=nkvh)


def _decoder_fwd(x, wn1, wq, wk, wv, cos, sin, wo, wn2, wg, wu, wd,
                 eps, nh, nkvh, use_pallas, interpret, bt, bo, bf):
    y = _decoder_core(x, wn1, wq, wk, wv, cos, sin, wo, wn2, wg, wu, wd,
                      eps, nh, nkvh, use_pallas, interpret, bt, bo, bf)
    # block-boundary remat: save only the INPUTS — one activation tensor
    # per layer instead of the unfused chain's q/k/v/attention/hidden set
    return y, (x, wn1, wq, wk, wv, cos, sin, wo, wn2, wg, wu, wd)


def _decoder_bwd(eps, nh, nkvh, use_pallas, interpret, bt, bo, bf, res, dy):
    # recompute the block from its saved inputs in reference math and
    # differentiate that — the VJP of the unfused chain (flash keeps the
    # attention backward blockwise), costing one extra block forward but
    # no saved intermediates: the training memory story of the kernel
    def ref(*args):
        return _decoder_reference(*args, eps=eps, nh=nh, nkvh=nkvh)

    _, vjp = jax.vjp(ref, *res)
    return vjp(dy)


_decoder_core.defvjp(_decoder_fwd, _decoder_bwd)


def fused_decoder_block(x, norm1_weight, wq, wk, wv, rope_cos, rope_sin,
                        wo, norm2_weight, wg, wu, wd, *, num_heads: int,
                        num_kv_heads: int, epsilon: float = 1e-5,
                        block_t: int = None, block_o: int = None,
                        block_f: int = None, interpret: bool = None,
                        autotune: bool = None, use_pallas: bool = None):
    """One whole llama decoder block — rmsnorm → QKV → RoPE → causal
    attention → o-proj (+residual) → rmsnorm → SwiGLU MLP (+residual) —
    as a single Pallas pass whose boundary activations never round-trip
    HBM.

    x: [b, s, d]; rope_cos/rope_sin: [max_pos, head_dim//2] tables
    (rows [0, s) are used — the no-cache, offset-0 training/prefill
    form).  Weight layouts match the llama Linears ([in, out]).
    Differentiable wrt every array input via block-boundary remat;
    ineligible shapes take the unfused reference composition inside the
    same custom VJP (the API is total)."""
    if x.ndim != 3:
        raise ValueError(f"fused_decoder_block expects [b, s, d], got "
                         f"shape {tuple(x.shape)}")
    b, s, d = int(x.shape[0]), int(x.shape[1]), int(x.shape[2])
    dq, dkv, f = int(wq.shape[-1]), int(wk.shape[-1]), int(wu.shape[-1])
    nh, nkvh = int(num_heads), int(num_kv_heads)
    hd = dq // nh
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    if use_pallas is None:
        use_pallas = (int(rope_cos.shape[0]) >= s and
                      fused_decoder_eligible(b, s, d, dq, dkv, hd, f,
                                             x.dtype))
    if autotune is None:
        autotune = not interpret
    if use_pallas and (block_t is None or block_o is None
                       or block_f is None):
        if autotune and not interpret:
            from paddle_tpu.ops.pallas.autotune import decoder_block_sizes
            blocks = decoder_block_sizes(b, s, d, dq, dkv, hd, f,
                                         str(x.dtype))
        else:
            blocks = _default_decoder_blocks(s, d, dq, dkv, hd, f,
                                             str(x.dtype))
        if blocks is None:
            raise ValueError(
                f"no decoder block sizes fit the VMEM budget at "
                f"s={s} d={d} dkv={dkv} f={f}")
        block_t = block_t or blocks[0]
        block_o = block_o or blocks[1]
        block_f = block_f or blocks[2]
    if use_pallas and (s % block_t or dq % block_o or dkv % block_o
                       or d % block_o or f % block_f or block_o % hd):
        raise ValueError(
            f"shapes s={s} d={d} dq={dq} dkv={dkv} f={f} hd={hd} not "
            f"divisible by blocks ({block_t}, {block_o}, {block_f})")
    cos = jnp.asarray(rope_cos)[:s].astype(jnp.float32)
    sin = jnp.asarray(rope_sin)[:s].astype(jnp.float32)
    return _decoder_core(x, norm1_weight, wq, wk, wv, cos, sin, wo,
                         norm2_weight, wg, wu, wd, float(epsilon), nh,
                         nkvh, bool(use_pallas), bool(interpret),
                         int(block_t or 0), int(block_o or 0),
                         int(block_f or 0))


def fused_ffn(x, w1, w2, b1=None, b2=None, activation: str = "relu",
              block_t: int = None, block_f: int = None,
              interpret: bool = None, autotune: bool = None,
              use_pallas: bool = None):
    """``y = act(x @ w1 + b1) @ w2 + b2`` — the classic Transformer
    feed-forward, hidden intermediate VMEM-resident (non-gated variant
    of :func:`fused_mlp`).  ``b1``/``b2`` may be None."""
    if activation not in _ACT:
        raise ValueError(f"unsupported activation {activation!r}; "
                         f"expected one of {SUPPORTED_ACTS}")
    shape = x.shape
    d = shape[-1]
    x2d = x.reshape(-1, d)
    t = x2d.shape[0]
    f = int(w1.shape[-1])
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    if use_pallas is None:
        use_pallas = fused_mlp_eligible(t, d, f, x.dtype)
    if autotune is None:
        autotune = not interpret
    if use_pallas:
        block_t, block_f = _mlp_blocks("ffn", t, d, f, str(x.dtype),
                                       block_t, block_f, interpret,
                                       autotune)
    if b1 is None:
        b1 = jnp.zeros((f,), x2d.dtype)
    if b2 is None:
        b2 = jnp.zeros((int(w2.shape[-1]),), x2d.dtype)
    y = _ffn_core(x2d, w1, b1, w2, b2, str(activation),
                  bool(use_pallas), bool(interpret),
                  int(block_t or 0), int(block_f or 0))
    return y.reshape(shape)


# ---------------------------------------------------------------------------
# static verification (analysis/kernel_verify) — the fused kernels
# described as KernelSpecs so the Mosaic-legality model can check them
# without a chip.  The specs reuse the SAME index-map closures the
# pallas_calls install (_clamped/_clamped_out/_clamp3), so what the
# verifier sweeps is what Mosaic would see.
# ---------------------------------------------------------------------------

def _qkv_verify_spec(t, d, dq, dk, dv, bt, bo, dtype, residuals=True):
    from paddle_tpu.analysis import kernel_verify as kv
    nt = t // bt if bt else 0
    nq, nkb, nvb = dq // bo, dk // bo, dv // bo
    args = [
        kv.ArgSpec("x", (t, d), (bt, d), lambda i, j: (i, 0), dtype),
        kv.ArgSpec("wn", (1, d), (1, d), lambda i, j: (0, 0), dtype,
                   resident=True),
        kv.ArgSpec("wq", (d, dq), (d, bo), _clamped(0, nq), dtype,
                   dma_once=True),
        kv.ArgSpec("wk", (d, dk), (d, bo), _clamped(nq, nkb), dtype,
                   dma_once=True),
        kv.ArgSpec("wv", (d, dv), (d, bo), _clamped(nq + nkb, nvb), dtype,
                   dma_once=True),
        kv.ArgSpec("q", (t, dq), (bt, bo), _clamped_out(0, nq), dtype,
                   is_output=True),
        kv.ArgSpec("k", (t, dk), (bt, bo), _clamped_out(nq, nkb), dtype,
                   is_output=True),
        kv.ArgSpec("v", (t, dv), (bt, bo), _clamped_out(nq + nkb, nvb),
                   dtype, is_output=True),
    ]
    if residuals:
        args += [
            kv.ArgSpec("xn", (t, d), (bt, d), lambda i, j: (i, 0), dtype,
                       is_output=True),
            kv.ArgSpec("inv", (t, 1), (bt, 1), lambda i, j: (i, 0),
                       "float32", is_output=True),
        ]
    return kv.KernelSpec(
        name="fused_qkv", grid=(nt, nq + nkb + nvb), args=args,
        scratch=[kv.ScratchSpec("xn_scr", (bt, d), "float32")],
        dimension_semantics=("parallel", "arbitrary"),
        **_verify_scope("qkv", bt, bo, d, dtype, residuals),
        needs_fp32_acc=True,
        where=f"fused_qkv[t={t} d={d} dq={dq} dk={dk} dv={dv} "
              f"bt={bt} bo={bo} {dtype}]")


def verify_static_qkv(t, d, dq, dk, dv, dtype="float32", block_t=None,
                      block_o=None, residuals=True):
    """Static Mosaic-legality findings for the fused rmsnorm+QKV kernel
    at this shape/config (defaults = the heuristic blocks)."""
    from paddle_tpu.analysis import kernel_verify as kv
    if block_t is None or block_o is None:
        bt, bo = _default_qkv_blocks(t, d, dq, dk, dv, str(dtype))
        block_t = block_t or bt
        block_o = block_o or bo
    spec = _qkv_verify_spec(t, d, dq, dk, dv, int(block_t), int(block_o),
                            str(dtype), residuals=residuals)
    return kv.verify_kernel(spec)


def _mlp_verify_spec(t, d, f, bt, bf, dtype, gated=True):
    from paddle_tpu.analysis import kernel_verify as kv
    nt = t // bt if bt else 0
    nf = f // bf if bf else 0
    args = [
        kv.ArgSpec("x", (t, d), (bt, d), lambda i, j: (i, 0), dtype),
    ]
    wnames = ("wg", "wu") if gated else ("w1",)
    for w in wnames:
        args.append(kv.ArgSpec(w, (d, f), (d, bf),
                               lambda i, j: (0, j), dtype, dma_once=True))
    args.append(kv.ArgSpec("wd", (f, d), (bf, d),
                           lambda i, j: (j, 0), dtype, dma_once=True))
    args.append(kv.ArgSpec("y", (t, d), (bt, d), lambda i, j: (i, 0),
                           dtype, is_output=True))
    return kv.KernelSpec(
        name="fused_mlp" if gated else "fused_ffn",
        grid=(nt, nf), args=args,
        scratch=[kv.ScratchSpec("acc", (bt, d), "float32")],
        dimension_semantics=("parallel", "arbitrary"),
        **_verify_scope("mlp", bt, bf, d, dtype),
        needs_fp32_acc=True,
        where=f"fused_mlp[t={t} d={d} f={f} bt={bt} bf={bf} {dtype}]")


def verify_static_mlp(t, d, f, dtype="float32", block_t=None,
                      block_f=None, gated=True):
    """Static Mosaic-legality findings for the fused MLP/FFN kernel at
    this shape/config (defaults = the heuristic blocks)."""
    from paddle_tpu.analysis import kernel_verify as kv
    if block_t is None or block_f is None:
        bt, bf = _default_mlp_blocks(t, d, f, str(dtype))
        block_t = block_t or bt
        block_f = block_f or bf
    spec = _mlp_verify_spec(t, d, f, int(block_t), int(block_f),
                            str(dtype), gated=gated)
    return kv.verify_kernel(spec)


def _decoder_verify_spec(b, s, d, dq, dkv, hd, f, bt, bo, bf, dtype):
    from paddle_tpu.analysis import kernel_verify as kv
    dtype = str(dtype)
    nh, nkvh = dq // hd, dkv // hd
    nt = s // bt if bt else 0
    nqc, nkc = dq // bo, dkv // bo
    no, nf = d // bo, f // bf
    B0 = nqc + nkc
    C0 = B0 + nh * nt
    D0 = C0 + no
    inner = D0 + nf
    hh = hd // 2
    args = [
        kv.ArgSpec("x", (b, s, d), (1, bt, d),
                   lambda bi, i, j: (bi, i, 0), dtype),
        kv.ArgSpec("wn1", (1, d), (1, d), lambda bi, i, j: (0, 0), dtype,
                   resident=True),
        kv.ArgSpec("wq", (d, dq), (d, bo), _clamp3(0, nqc), dtype,
                   dma_once=True),
        kv.ArgSpec("wk", (d, dkv), (d, bo), _clamp3(nqc, nkc), dtype,
                   dma_once=True),
        kv.ArgSpec("wv", (d, dkv), (d, bo), _clamp3(nqc, nkc), dtype,
                   dma_once=True),
        kv.ArgSpec("cos", (s, hh), (bt, hh),
                   lambda bi, i, j: (i, 0), "float32"),
        kv.ArgSpec("sin", (s, hh), (bt, hh),
                   lambda bi, i, j: (i, 0), "float32"),
        kv.ArgSpec("wo", (dq, d), (dq, bo), _clamp3(C0, no), dtype,
                   dma_once=True),
        kv.ArgSpec("wn2", (1, d), (1, d), lambda bi, i, j: (0, 0), dtype,
                   resident=True),
        kv.ArgSpec("wg", (d, f), (d, bf), _clamp3(D0, nf), dtype,
                   dma_once=True),
        kv.ArgSpec("wu", (d, f), (d, bf), _clamp3(D0, nf), dtype,
                   dma_once=True),
        kv.ArgSpec("wd", (f, d), (bf, d),
                   lambda bi, i, j: (jnp.clip(j - D0, 0, nf - 1), 0),
                   dtype, dma_once=True),
        kv.ArgSpec("y", (b, s, d), (1, bt, d),
                   lambda bi, i, j: (bi, i, 0), dtype, is_output=True),
    ]
    kv_note = (f"K/V rows for the WHOLE sequence stay VMEM-resident "
               f"(s={s}, dkv={dkv})")
    scratch = [
        kv.ScratchSpec("xn", (bt, d), dtype),
        kv.ScratchSpec("q", (bt, dq), dtype),
        kv.ScratchSpec("k_seq", (s, dkv), dtype, seq_scaling=True,
                       note=kv_note),
        kv.ScratchSpec("v_seq", (s, dkv), dtype, seq_scaling=True,
                       note=kv_note),
        kv.ScratchSpec("attn", (bt, dq), dtype),
        kv.ScratchSpec("x2", (bt, d), dtype),
        kv.ScratchSpec("m", (bt, 1), "float32"),
        kv.ScratchSpec("l", (bt, 1), "float32"),
        kv.ScratchSpec("acc", (bt, hd), "float32"),
        kv.ScratchSpec("yacc", (bt, d), "float32"),
    ]
    return kv.KernelSpec(
        name="fused_decoder", grid=(b, nt, inner), args=args,
        scratch=scratch,
        dimension_semantics=("parallel", "arbitrary", "arbitrary"),
        vmem_budget=_DECODER_VMEM_BUDGET,
        needs_fp32_acc=True,
        lane_concat=(
            f"in-kernel RoPE concatenates rotated half-heads and "
            f"{bo // hd} head slice(s) along the last axis of a "
            f"[{bt}, {bo}] block (hd={hd})"),
        where=f"fused_decoder[b={b} s={s} d={d} dq={dq} dkv={dkv} "
              f"f={f} bt={bt} bo={bo} bf={bf} {dtype}]")


def verify_static_decoder(b, s, d, dq, dkv, hd, f, dtype="float32",
                          block_t=None, block_o=None, block_f=None):
    """Static Mosaic-legality findings for the whole-decoder-block
    megakernel at this shape/config.  Surfaces the two named Mosaic
    risks as WARNINGs (lane-axis RoPE concat, seq-scaling K/V scratch)
    and errors when no block choice fits the VMEM budget."""
    from paddle_tpu.analysis import kernel_verify as kv
    dtype = str(dtype)
    if block_t is None or block_o is None or block_f is None:
        blocks = _default_decoder_blocks(s, d, dq, dkv, hd, f, dtype)
        if blocks is None:
            diags = [kv._d(
                kv.Severity.ERROR, kv.VMEM_EXCEEDED,
                f"fused_decoder: no (block_t, block_o, block_f) choice "
                f"fits the {_DECODER_VMEM_BUDGET >> 20} MiB budget at "
                f"s={s} d={d} dkv={dkv} f={f} ({dtype})",
                where=f"fused_decoder[b={b} s={s} d={d} {dtype}]",
                hint="the 2*s*dkv K/V scratch dominates; shorten the "
                     "sequence or fall back to the per-segment kernels")]
            kv._record("fused_decoder", kv.verdict_of(diags))
            return diags
        block_t = block_t or blocks[0]
        block_o = block_o or blocks[1]
        block_f = block_f or blocks[2]
    spec = _decoder_verify_spec(b, s, d, dq, dkv, hd, f, int(block_t),
                                int(block_o), int(block_f), dtype)
    return kv.verify_kernel(spec)
