"""Grouped expert-matmul — all E experts' FFNs in one Pallas call.

The MoE tentpole (ROADMAP item 1): every dispatch path in
``distributed/moe.py`` funnels expert compute through a stacked-weight
FFN over ``[G, C, d]`` capacity-grouped token blocks (G groups, each
bound to expert ``g // (G // E)``; the einsum/index paths have G == E,
the all_to_all paths G == E_loc * n_shards source chunks).  Upstream
Paddle loops experts through gather/scatter collectives; the dense
einsum pair here already beats that, but it still spends full
``[E, C, d]`` HBM traffic on padding rows and re-reads activations
between the up- and down-projection.  This kernel runs the whole
grouped FFN as ONE ``pallas_call``:

* grid ``(G, C/block_c, h/block_f)`` with the hidden (f) axis innermost
  — only a ``[block_c, block_f]`` tile of the hidden activations ever
  exists, folded into an fp32 VMEM accumulator (the fused-MLP
  discipline, fused_block.py);
* per-group valid-row counts ride along as a ``[G, 1, 1]`` int32
  operand and ``pl.when`` skips capacity blocks with no routed tokens —
  under GShard capacity factors most tail blocks are empty, so skipped
  blocks cost neither MXU flops nor the w1/w2 HBM reads their grid
  steps would re-issue;
* rows past a group's count are zeroed (their combine weights are zero
  in every dispatch path, so MoE outputs are unchanged), which makes
  the kernel's semantics block-size independent and gives the jnp
  reference an exact contract to oracle against;
* custom VJP: backward is the plain-JAX masked einsum chain, left to
  XLA, with a ``float0`` cotangent for counts.

Routing is trace-time and OFF by default: ``PADDLE_TPU_GROUPED_MOE=1``
flips ``_expert_ffn`` to this kernel (interpret mode off-TPU); unset or
0 keeps the dense einsum pair with a byte-identical jaxpr (regression-
tested).  Block sizes are one more autotune-v2 axis
(``autotune.grouped_block_sizes``) and the static Mosaic-legality spec
is in the kernel-verify catalog via :func:`verify_static`.
"""

from __future__ import annotations

import functools
import os

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from jax.experimental.pallas import tpu as pltpu

__all__ = ["grouped_expert_ffn", "grouped_expert_ffn_pallas",
           "grouped_expert_ffn_reference", "grouped_moe_enabled",
           "grouped_ffn_eligible", "record_path"]


def grouped_moe_enabled() -> bool:
    """``PADDLE_TPU_GROUPED_MOE=1`` routes stacked-expert FFNs through
    the grouped Pallas kernel; unset/0 keeps the dense einsum pair (and
    its exact jaxpr)."""
    raw = os.environ.get("PADDLE_TPU_GROUPED_MOE")
    return raw is not None and raw.strip().lower() in ("1", "true", "yes",
                                                       "on")


def grouped_ffn_eligible(G: int, C: int, d: int, h: int, E: int) -> bool:
    """Structural + (on TPU) alignment gate for the grouped kernel.
    Off-TPU the kernel runs in interpret mode, where Mosaic tiling does
    not constrain shapes."""
    if E <= 0 or G % E:
        return False
    if jax.default_backend() != "tpu":
        return True
    return d % 128 == 0 and h % 128 == 0 and C >= 8


def record_path(path: str):
    """Trace-time implementation counter — the grouped-MoE analog of the
    quant/fused-block path counters."""
    try:
        from paddle_tpu.observability import default_registry
        default_registry().counter(
            "paddle_tpu_grouped_moe_path_total",
            "grouped expert-FFN implementation chosen at trace time",
            labelnames=("path",)).labels(path=path).inc()
    except Exception:  # pragma: no cover - telemetry must never trace-fail
        pass


def _default_grouped_blocks(C: int, d: int, h: int, dtype):
    """Heuristic (block_c, block_f) when the autotune cache is cold:
    widest hidden block, then the tallest capacity block whose working
    set (x/y/acc + double-buffered w1/w2 tiles) stays under ~10 MB of
    VMEM.  Degenerate dims fall back to spanning blocks (always
    Mosaic-legal: a block equal to the array dim needs no tiling)."""
    s = str(dtype)
    itemsize = 2 if ("bfloat16" in s or "float16" in s) else 4
    quantum = 16 if itemsize == 2 else 8
    bcs = [c for c in (512, 256, 128, 64, 32, 16, 8)
           if c % quantum == 0 and C % c == 0 and C >= c]
    if not bcs:
        bcs = [C]                       # spanning block — no sublane tiling
    bfs = [f for f in (512, 256, 128) if h % f == 0]
    if not bfs:
        bfs = [h]
    for bf in bfs:
        for bc in bcs:
            vmem = (2 * bc * d * itemsize        # x, double-buffered
                    + bc * d * 4                 # fp32 accumulator
                    + 2 * bc * d * itemsize      # y, double-buffered
                    + 4 * d * bf * itemsize)     # w1 + w2 tiles, 2x
            if vmem < 10 * (1 << 20):
                return bc, bf
    return bcs[-1], bfs[-1]


def _grouped_kernel(x_ref, w1_ref, b1_ref, w2_ref, b2_ref, cnt_ref, o_ref,
                    acc_ref, *, act, block_c):
    """One (group, capacity, hidden) tile.  The hidden axis is the
    innermost (sequential) grid dim; the fp32 accumulator in VMEM folds
    each ``[block_c, block_f]`` hidden tile into the down-projection.
    Capacity blocks past the group's routed-token count are skipped
    entirely (no MXU work, zeros written at finalize)."""
    i = pl.program_id(1)
    j = pl.program_id(2)
    nf = pl.num_programs(2)

    @pl.when(j == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    cnt = cnt_ref[0, 0, 0]
    rows = jax.lax.broadcasted_iota(jnp.int32, (block_c, 1), 0) \
        + i * block_c
    valid = rows < cnt

    @pl.when(i * block_c < cnt)
    def _compute():
        xb = x_ref[0]                                    # [bc, d]
        u = jax.lax.dot_general(
            xb, w1_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)          # [bc, bf]
        u = u + b1_ref[0].astype(jnp.float32)
        hb = jnp.where(valid, act(u), 0.0)               # mask pad rows
        acc_ref[:] += jax.lax.dot_general(
            hb.astype(x_ref.dtype), w2_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)          # [bc, d]

    @pl.when(j == nf - 1)
    def _finalize():
        out = acc_ref[:] + b2_ref[0].astype(jnp.float32)
        o_ref[0] = jnp.where(valid, out, 0.0).astype(o_ref.dtype)


def grouped_expert_ffn_pallas(x, w1, b1, w2, b2, counts, *, act,
                              block_c, block_f, interpret):
    """``[G, C, d] -> [G, C, d]`` grouped FFN via the Pallas kernel.
    ``counts [G]`` int32 bounds each group's valid-row prefix; rows past
    it come back exactly zero."""
    G, C, d = x.shape
    E, _, h = w1.shape
    rep = G // E
    nc = C // block_c
    nf = h // block_f

    params = {}
    if not interpret:
        params["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"))

    return pl.pallas_call(
        functools.partial(_grouped_kernel, act=act, block_c=block_c),
        grid=(G, nc, nf),
        in_specs=[
            pl.BlockSpec((1, block_c, d), lambda g, i, j: (g, i, 0)),
            pl.BlockSpec((1, d, block_f), lambda g, i, j: (g // rep, 0, j)),
            pl.BlockSpec((1, 1, block_f), lambda g, i, j: (g // rep, 0, j)),
            pl.BlockSpec((1, block_f, d), lambda g, i, j: (g // rep, j, 0)),
            pl.BlockSpec((1, 1, d), lambda g, i, j: (g // rep, 0, 0)),
            pl.BlockSpec((1, 1, 1), lambda g, i, j: (g, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_c, d), lambda g, i, j: (g, i, 0)),
        out_shape=jax.ShapeDtypeStruct((G, C, d), x.dtype),
        scratch_shapes=[pltpu.VMEM((block_c, d), jnp.float32)],
        name="grouped_matmul",
        interpret=interpret,
        **params,
    )(x, w1, b1.reshape(E, 1, h), w2, b2.reshape(E, 1, d),
      counts.reshape(G, 1, 1))


def grouped_expert_ffn_reference(x, w1, b1, w2, b2, counts=None, *,
                                 act=None):
    """The jnp oracle: same op order as the kernel (fp32 MXU
    accumulation, activation in fp32, one cast between the projections)
    with rows past ``counts`` zeroed — block-size independent, so the
    kernel must match it to blocked-accumulation noise."""
    act = act or jax.nn.gelu
    G, C, d = x.shape
    E, _, h = w1.shape
    rep = G // E
    xr = x.reshape(E, rep * C, d)
    u = jnp.einsum("ecd,edh->ech", xr, w1,
                   preferred_element_type=jnp.float32) + b1[:, None, :]
    hb = act(u).astype(x.dtype)
    y = jnp.einsum("ech,ehd->ecd", hb, w2,
                   preferred_element_type=jnp.float32) + b2[:, None, :]
    y = y.astype(x.dtype).reshape(G, C, d)
    if counts is not None:
        rows = jax.lax.broadcasted_iota(jnp.int32, (G, C), 1)
        y = jnp.where((rows < counts[:, None])[..., None], y, 0)
    return y


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8, 9))
def _grouped_core(x, w1, b1, w2, b2, counts, act, block_c, block_f,
                  interpret):
    return _grouped_fwd(x, w1, b1, w2, b2, counts, act, block_c, block_f,
                        interpret)[0]


def _grouped_fwd(x, w1, b1, w2, b2, counts, act, block_c, block_f,
                 interpret):
    y = grouped_expert_ffn_pallas(x, w1, b1, w2, b2, counts, act=act,
                                  block_c=block_c, block_f=block_f,
                                  interpret=interpret)
    return y, (x, w1, b1, w2, b2, counts)


def _grouped_bwd(act, block_c, block_f, interpret, res, dy):
    # recompute the masked einsum chain in plain JAX: rows past counts
    # carry zero cotangent and zero input, so padded slots contribute
    # nothing to any grad
    x, w1, b1, w2, b2, counts = res
    G, C, d = x.shape
    E = w1.shape[0]
    rep = G // E
    rows = jax.lax.broadcasted_iota(jnp.int32, (G, C), 1)
    valid = (rows < counts[:, None])[..., None]
    xm = jnp.where(valid, x, 0).reshape(E, rep * C, d)
    gy = jnp.where(valid, dy, 0).reshape(E, rep * C, d)
    u = jnp.einsum("ecd,edh->ech", xm, w1,
                   preferred_element_type=jnp.float32) + b1[:, None, :]
    s, act_vjp = jax.vjp(act, u)
    dh = jnp.einsum("ecd,ehd->ech", gy, w2,
                    preferred_element_type=jnp.float32)
    dw2 = jnp.einsum("ech,ecd->ehd", s.astype(x.dtype), gy,
                     preferred_element_type=jnp.float32).astype(w2.dtype)
    db2 = gy.astype(jnp.float32).sum(axis=1).astype(b2.dtype)
    du = act_vjp(dh)[0]
    dw1 = jnp.einsum("ecd,ech->edh", xm, du.astype(x.dtype),
                     preferred_element_type=jnp.float32).astype(w1.dtype)
    db1 = du.sum(axis=1).astype(b1.dtype)
    dx = jnp.einsum("ech,edh->ecd", du.astype(x.dtype), w1,
                    preferred_element_type=jnp.float32)
    dx = dx.reshape(G, C, d).astype(x.dtype)
    dcounts = np.zeros(counts.shape, dtype=jax.dtypes.float0)
    return dx, dw1, db1, dw2, db2, dcounts


_grouped_core.defvjp(_grouped_fwd, _grouped_bwd)


def grouped_expert_ffn(x, w1, b1, w2, b2, *, counts=None, act=None,
                       block_c=None, block_f=None, interpret=None,
                       autotune=True):
    """Grouped expert FFN with trace-time block selection.

    ``x``: ``[G, C, d]`` capacity-grouped tokens (group ``g`` belongs
    to expert ``g // (G // E)``); ``w1/b1/w2/b2``: stacked
    ``[E, d, h] / [E, h] / [E, h, d] / [E, d]`` expert weights;
    ``counts``: optional ``[G]`` int32 valid-row prefix per group (rows
    past it return exactly zero — their combine weights are zero in
    every MoE dispatch path).  Differentiable in x and the weights.
    """
    act = act or jax.nn.gelu
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    G, C, d = x.shape
    E, _, h = w1.shape
    if G % E:
        raise ValueError(f"group count {G} not divisible by experts {E}")
    if block_c is None or block_f is None:
        if autotune and not interpret:
            from paddle_tpu.ops.pallas.autotune import grouped_block_sizes
            bc, bf = grouped_block_sizes(G, C, d, h, str(x.dtype))
        else:
            bc, bf = _default_grouped_blocks(C, d, h, str(x.dtype))
        block_c = block_c or bc
        block_f = block_f or bf
    if C % block_c or h % block_f:
        block_c, block_f = _default_grouped_blocks(C, d, h, str(x.dtype))
    if counts is None:
        counts = jnp.full((G,), C, jnp.int32)
    return _grouped_core(x, w1, b1, w2, b2, counts.astype(jnp.int32),
                         act, int(block_c), int(block_f), bool(interpret))


# ---------------------------------------------------------------------------
# static verification (analysis/kernel_verify)


def verify_static(G, C, d, h, E=None, dtype="bfloat16", block_c=None,
                  block_f=None):
    """Static Mosaic-legality findings for the grouped expert-matmul at
    this shape/config — the counts operand travels as ``[G, 1, 1]`` with
    ``(1, 1, 1)`` blocks (trailing dims span the array, so no sublane
    tiling applies; the flash-lse layout trick)."""
    from paddle_tpu.analysis import kernel_verify as kv
    dtype = str(dtype)
    E = int(E or G)
    rep = max(1, G // E)
    if block_c is None or block_f is None:
        bc_d, bf_d = _default_grouped_blocks(C, d, h, dtype)
        block_c = block_c or bc_d
        block_f = block_f or bf_d
    bc, bf = int(block_c), int(block_f)
    spec = kv.KernelSpec(
        name="grouped_matmul",
        grid=(G, C // bc if bc else 0, h // bf if bf else 0),
        args=[
            kv.ArgSpec("x", (G, C, d), (1, bc, d),
                       lambda g, i, j: (g, i, 0), dtype),
            kv.ArgSpec("w1", (E, d, h), (1, d, bf),
                       lambda g, i, j: (g // rep, 0, j), dtype,
                       dma_once=True),
            kv.ArgSpec("b1", (E, 1, h), (1, 1, bf),
                       lambda g, i, j: (g // rep, 0, j), dtype),
            kv.ArgSpec("w2", (E, h, d), (1, bf, d),
                       lambda g, i, j: (g // rep, j, 0), dtype,
                       dma_once=True),
            kv.ArgSpec("b2", (E, 1, d), (1, 1, d),
                       lambda g, i, j: (g // rep, 0, 0), dtype),
            kv.ArgSpec("counts", (G, 1, 1), (1, 1, 1),
                       lambda g, i, j: (g, 0, 0), "int32"),
            kv.ArgSpec("o", (G, C, d), (1, bc, d),
                       lambda g, i, j: (g, i, 0), dtype, is_output=True),
        ],
        scratch=[kv.ScratchSpec("acc", (bc, d), "float32")],
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        needs_fp32_acc=True,
        where=f"grouped_matmul[G={G} C={C} d={d} h={h} E={E} "
              f"bc={bc} bf={bf} {dtype}]")
    return kv.verify_kernel(spec)
