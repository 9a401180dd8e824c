"""Static verifier for Pallas TPU kernels — Mosaic legality without a chip.

Interpret mode proves kernel *math*; it proves nothing about whether the
Mosaic compiler will accept the kernel's grid/BlockSpec/scratch layout on
real hardware.  Three cycles of Pallas work (fused segments, quant
matmul, the whole-decoder megakernel) shipped on interpret-mode parity
alone, with the Mosaic risks named in the ROADMAP left open.  This module
closes that gap with a *model* of the constraints Mosaic enforces at
lowering time, checked statically:

1. **VMEM footprint** — every streamed in/out block is double-buffered
   (Mosaic overlaps the next DMA with compute), scratch is resident, and
   scalar-prefetch operands live in SMEM/VMEM for the whole launch.  The
   modelled footprint must fit the per-core budget
   (``VMEM_BUDGET_BYTES``, soft) and the physical limit
   (``VMEM_LIMIT_BYTES``, hard).  This is the *shared* footprint model:
   ``ops/pallas/fused_block.decoder_vmem_bytes`` delegates here, so the
   megakernel's eligibility gate and the lint verdict cannot disagree.
2. **Tiling/layout legality** — last (lane) block dim must be a multiple
   of 128, second-minor (sublane) dim a multiple of the dtype tile
   quantum (fp32 8, bf16/fp16 16, int8/fp8 32) unless the block spans
   the full array dim (the ``[T, 1]`` column trick is legal).
3. **Index-map analysis** — every BlockSpec index map is *concretely
   evaluated over the full grid* (vectorized numpy/jnp, one call per
   map): out-of-bounds block reads, output blocks written by more than
   one grid point along a ``parallel`` axis (write race), uncovered
   output regions, blocks that don't divide the array, and — for args
   that declare the fused-block clamped-map invariant — inputs re-DMA'd
   more than once per inner sweep (``dma_once``).
4. **Dtype discipline** — MXU kernels must carry an fp32 accumulator
   (scratch or declared inline via ``preferred_element_type``); quant
   kernels' scale operands must agree in shape with the tensor they
   scale.

Known-unsupported Mosaic patterns are declared by the kernel's spec
builder and surfaced as findings: lane-axis ``jnp.concatenate`` (the
megakernel's in-kernel RoPE) and sequence-proportional VMEM scratch
(the megakernel's ``(s, d_kv)`` K/V scratch) — each a distinct WARNING
with the offending shape.

Entry points:

* ``verify_kernel(spec)`` — check one ``KernelSpec``, return findings.
* per-kernel ``verify_static(...)`` functions in each ``ops/pallas``
  module build specs and call ``verify_kernel``.
* ``catalog_report()`` — the whole kernel catalog at bench shapes;
  rendered by ``python -m paddle_tpu.analysis.lint --kernels``.
* ``candidate_ok(op, shape, cand)`` — autotune pruning hook: reject
  configs the verifier proves illegal before they are ever benchmarked.
* the registered ``kernel-verify`` analysis pass walks a traced program
  for ``pallas_call`` equations and verifies each one (opt-in via
  ``--passes kernel-verify``; not in ``DEFAULT_PASSES``).

Every verification outcome increments
``paddle_tpu_kernel_verify_total{kernel,verdict}``.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np
import jax
import jax.numpy as jnp

from paddle_tpu.analysis.diagnostics import Diagnostic, Severity
from paddle_tpu.analysis.passes import PassContext, register_pass
from paddle_tpu.analysis.tracing import walk_eqns

__all__ = [
    "ArgSpec", "ScratchSpec", "KernelSpec",
    "VMEM_LIMIT_BYTES", "VMEM_BUDGET_BYTES",
    "itemsize", "sublane_quantum", "block_bytes", "footprint_bytes",
    "verify_kernel", "verdict_of",
    "candidate_findings", "candidate_ok", "prune_candidates",
    "catalog_report", "render_catalog_table",
    "kernel_verify_pass",
    # finding codes
    "VMEM_EXCEEDED", "VMEM_OVER_BUDGET", "LANE_MISALIGNED",
    "SUBLANE_MISALIGNED", "BLOCK_INDIVISIBLE", "OOB_BLOCK", "WRITE_RACE",
    "OUTPUT_UNCOVERED", "REDUNDANT_DMA", "LANE_CONCAT", "SEQ_SCRATCH",
    "ACC_DTYPE", "SCALE_SHAPE", "MAP_UNEVALUATED",
]

PASS_ID = "kernel-verify"

# ---------------------------------------------------------------------------
# finding codes — every Diagnostic message starts with one of these, so
# tests and tooling can match findings without parsing prose.

VMEM_EXCEEDED = "VMEM_EXCEEDED"          # ERROR: footprint > physical VMEM
VMEM_OVER_BUDGET = "VMEM_OVER_BUDGET"    # WARNING: footprint > soft budget
LANE_MISALIGNED = "LANE_MISALIGNED"      # ERROR: lane dim % 128
SUBLANE_MISALIGNED = "SUBLANE_MISALIGNED"  # ERROR %8 / WARNING % quantum
BLOCK_INDIVISIBLE = "BLOCK_INDIVISIBLE"  # ERROR: shape % block != 0
OOB_BLOCK = "OOB_BLOCK"                  # ERROR: index map leaves the array
WRITE_RACE = "WRITE_RACE"                # ERROR: parallel axes share a block
OUTPUT_UNCOVERED = "OUTPUT_UNCOVERED"    # ERROR: output block never written
REDUNDANT_DMA = "REDUNDANT_DMA"          # WARNING: dma_once arg re-fetched
LANE_CONCAT = "LANE_CONCAT"              # WARNING: lane-axis concat hazard
SEQ_SCRATCH = "SEQ_SCRATCH"              # WARNING: seq-scaling VMEM scratch
ACC_DTYPE = "ACC_DTYPE"                  # WARNING: no fp32 MXU accumulator
SCALE_SHAPE = "SCALE_SHAPE"              # ERROR: quant scale shape mismatch
MAP_UNEVALUATED = "MAP_UNEVALUATED"      # INFO: index map not analysable

# Physical VMEM is ~16 MiB/core on v4/v5; the 12 MiB budget leaves
# headroom for Mosaic's own spills and semaphores.  The megakernel's
# eligibility gate (`fused_block._DECODER_VMEM_BUDGET`) must equal the
# budget — regression-tested in tests/test_kernel_verify.py.
VMEM_LIMIT_BYTES = 16 * (1 << 20)
VMEM_BUDGET_BYTES = 12 * (1 << 20)

# index maps are evaluated concretely over the whole grid; above this
# many grid points the index-map checks are skipped with an INFO finding
_MAX_GRID_POINTS = 1 << 19

_SUBLANE_QUANTUM = {
    "float32": 8, "int32": 8, "uint32": 8,
    "bfloat16": 16, "float16": 16,
    "int8": 32, "uint8": 32, "float8_e4m3fn": 32, "float8_e5m2": 32,
}


def itemsize(dtype) -> int:
    """Bytes per element; tolerant of string names incl. bf16/fp8."""
    try:
        return jnp.dtype(dtype).itemsize
    except Exception:
        return 4


def sublane_quantum(dtype) -> int:
    """Second-minor tile quantum Mosaic requires for this dtype."""
    try:
        name = str(jnp.dtype(dtype))
    except Exception:
        name = str(dtype)
    return _SUBLANE_QUANTUM.get(name, 8)


# ---------------------------------------------------------------------------
# spec model


@dataclasses.dataclass
class ArgSpec:
    """One pallas_call operand (input or output) with its BlockSpec.

    ``index_map`` is a callable taking one array per grid axis (plus any
    ``scalar_prefetch`` operands appended) and returning a tuple of
    block-index components — the same lambda the kernel hands to
    ``pl.BlockSpec``, evaluated vectorized over the whole grid.
    ``resident`` marks constant-map args that are fetched once and stay
    in VMEM (single-buffered in the footprint); ``dma_once`` opts into
    the fused-block clamped-map invariant check (each block DMA'd at
    most once per inner sweep).  ``dma_grid`` marks an operand the
    kernel leaves in HBM (``memory_space=pl.ANY``) and copies by hand,
    one ``block`` a DMA, into scratch the spec lists: it takes no VMEM
    of its own, and its index map is evaluated over the grid with the
    extents of the loops that issue the copies appended (``()`` when
    the map is not analysable)."""

    name: str
    shape: Tuple[int, ...]
    block: Tuple[int, ...]
    index_map: Optional[Callable] = None
    dtype: Any = "float32"
    is_output: bool = False
    dma_once: bool = False
    resident: bool = False
    dma_grid: Optional[Tuple[int, ...]] = None


@dataclasses.dataclass
class ScratchSpec:
    """One VMEM scratch allocation.  ``seq_scaling=True`` declares the
    shape grows with sequence length — a known seq-scaling hazard the
    verifier surfaces as a ``SEQ_SCRATCH`` warning."""

    name: str
    shape: Tuple[int, ...]
    dtype: Any = "float32"
    seq_scaling: bool = False
    note: str = ""


@dataclasses.dataclass
class KernelSpec:
    """A pallas_call launch, statically describable: grid, operands,
    scratch, dimension semantics, and declared hazards."""

    name: str
    grid: Tuple[int, ...]
    args: List[ArgSpec]
    scratch: List[ScratchSpec] = dataclasses.field(default_factory=list)
    #: "parallel" / "arbitrary" per grid axis; None = unknown (race
    #: analysis is skipped — revisits may be legal sequential accumulation)
    dimension_semantics: Optional[Tuple[str, ...]] = None
    #: numpy arrays appended to every index-map call (block tables etc.);
    #: their bytes count toward the footprint
    scalar_prefetch: Tuple = ()
    vmem_budget: int = VMEM_BUDGET_BYTES
    #: the scope the call asks the compiler for (``vmem_limit_bytes``),
    #: where it asks for more than the compiler's own
    vmem_limit: int = VMEM_LIMIT_BYTES
    #: MXU kernel that must accumulate in fp32.  acc_inline=True declares
    #: the accumulation happens in registers via preferred_element_type.
    needs_fp32_acc: bool = False
    acc_inline: bool = False
    #: declared lane-axis concatenate hazard (message detail), or None
    lane_concat: Optional[str] = None
    #: (scale_arg_name, tensor_arg_name) pairs for quant scale agreement
    scale_pairs: List[Tuple[str, str]] = dataclasses.field(
        default_factory=list)
    where: str = ""


def block_bytes(shape: Sequence[int], dtype) -> int:
    return int(np.prod([int(s) for s in shape], dtype=np.int64)) * \
        itemsize(dtype) if len(tuple(shape)) else itemsize(dtype)


def footprint_bytes(spec: KernelSpec) -> int:
    """Modelled VMEM bytes: streamed blocks ×2 (double-buffered DMA),
    resident/full-array blocks ×1, scratch ×1, scalar prefetch ×1;
    an operand the kernel DMAs by hand lands in scratch."""
    total = 0
    for a in spec.args:
        if a.dma_grid is not None:
            continue
        mult = 1 if (a.resident or tuple(a.block) == tuple(a.shape)) else 2
        total += mult * block_bytes(a.block, a.dtype)
    for s in spec.scratch:
        total += block_bytes(s.shape, s.dtype)
    for p in spec.scalar_prefetch:
        arr = np.asarray(p)
        total += arr.size * arr.itemsize
    return total


def _d(severity, code, msg, where="", hint=""):
    return Diagnostic(pass_id=PASS_ID, severity=severity,
                      message=f"{code}: {msg}", where=where, hint=hint)


# ---------------------------------------------------------------------------
# per-arg tiling legality


def _tile_diags(spec: KernelSpec, a: ArgSpec) -> List[Diagnostic]:
    out = []
    if len(a.block) < 2:
        return out
    lane, sub = int(a.block[-1]), int(a.block[-2])
    alane, asub = int(a.shape[-1]), int(a.shape[-2])
    if lane != alane and lane % 128:
        out.append(_d(
            Severity.ERROR, LANE_MISALIGNED,
            f"{spec.name}/{a.name}: lane (last) block dim {lane} is not a "
            f"multiple of 128 and does not span the array dim {alane}",
            where=spec.where,
            hint="Mosaic vector lanes are 128-wide; pick a lane block "
                 "that is a multiple of 128 or cover the whole dim"))
    q = sublane_quantum(a.dtype)
    if sub != asub and sub % q:
        if sub % 8:
            out.append(_d(
                Severity.ERROR, SUBLANE_MISALIGNED,
                f"{spec.name}/{a.name}: sublane block dim {sub} is not a "
                f"multiple of 8 (dtype {a.dtype} needs {q})",
                where=spec.where))
        else:
            out.append(_d(
                Severity.WARNING, SUBLANE_MISALIGNED,
                f"{spec.name}/{a.name}: sublane block dim {sub} is not a "
                f"multiple of the {a.dtype} tile quantum {q}; Mosaic pads "
                f"each tile to {q} rows",
                where=spec.where,
                hint=f"use a block with second-minor dim % {q} == 0"))
    for dim, (s, b) in enumerate(zip(a.shape, a.block)):
        if int(b) and int(s) % int(b):
            out.append(_d(
                Severity.ERROR, BLOCK_INDIVISIBLE,
                f"{spec.name}/{a.name}: dim {dim} of size {s} is not "
                f"divisible by block {b}",
                where=spec.where,
                hint="partial edge blocks are not modelled by this "
                     "kernel's grid; choose a dividing block"))
    return out


# ---------------------------------------------------------------------------
# index-map evaluation (vectorized over the whole grid)


def _grid_coords(grid: Tuple[int, ...]) -> np.ndarray:
    """[G, naxes] int64 grid coordinates in row-major (last axis
    innermost) order — the order Mosaic sweeps the grid."""
    mesh = np.meshgrid(*[np.arange(g, dtype=np.int64) for g in grid],
                       indexing="ij")
    return np.stack([m.reshape(-1) for m in mesh], axis=-1)


def _eval_map(a: ArgSpec, coords: np.ndarray,
              scalar_prefetch: Tuple) -> Optional[np.ndarray]:
    """Evaluate ``a.index_map`` once for every grid point; returns
    [G, ndim] block indices or None when the map can't be evaluated."""
    if a.index_map is None:
        return None
    G = coords.shape[0]
    args = [coords[:, d] for d in range(coords.shape[1])]
    args += [np.asarray(p) for p in scalar_prefetch]
    res = a.index_map(*args)
    if not isinstance(res, tuple):
        res = (res,)
    cols = []
    for comp in res:
        c = np.asarray(comp)
        if c.ndim == 0:
            c = np.full((G,), int(c), dtype=np.int64)
        cols.append(c.astype(np.int64))
    return np.stack(cols, axis=-1)


def _nblocks(a: ArgSpec) -> Tuple[int, ...]:
    return tuple(-(-int(s) // int(b)) if int(b) else 1
                 for s, b in zip(a.shape, a.block))


def _map_diags(spec: KernelSpec, a: ArgSpec, idx: np.ndarray,
               coords: np.ndarray) -> List[Diagnostic]:
    out = []
    nb = _nblocks(a)
    if idx.shape[1] != len(nb):
        out.append(_d(
            Severity.INFO, MAP_UNEVALUATED,
            f"{spec.name}/{a.name}: index map returned {idx.shape[1]} "
            f"components for a rank-{len(nb)} block", where=spec.where))
        return out

    # (1) out-of-bounds block reads/writes
    oob = False
    for dim in range(len(nb)):
        bad = np.flatnonzero((idx[:, dim] < 0) | (idx[:, dim] >= nb[dim]))
        if bad.size:
            g = bad[0]
            out.append(_d(
                Severity.ERROR, OOB_BLOCK,
                f"{spec.name}/{a.name}: index map sends grid point "
                f"{tuple(int(c) for c in coords[g])} to block index "
                f"{int(idx[g, dim])} on dim {dim} (valid range "
                f"[0, {nb[dim] - 1}])", where=spec.where,
                hint="clamp the map (jnp.clip) or shrink the grid"))
            oob = True
            break
    if oob:
        return out
    bid = np.ravel_multi_index(tuple(idx[:, d] for d in range(len(nb))), nb)

    if a.is_output:
        # (2) coverage: every output block written by at least one point
        total = int(np.prod(nb, dtype=np.int64))
        uniq = np.unique(bid)
        if uniq.size < total:
            missing = np.setdiff1d(
                np.arange(total, dtype=np.int64), uniq)[0]
            out.append(_d(
                Severity.ERROR, OUTPUT_UNCOVERED,
                f"{spec.name}/{a.name}: {total - uniq.size} of {total} "
                f"output blocks are never written (first missing block "
                f"{tuple(int(v) for v in np.unravel_index(missing, nb))})",
                where=spec.where))
        # (3) write race: two grid points that differ along a *parallel*
        # axis map to the same output block.  Revisits along sequential
        # ("arbitrary") axes are the legal accumulator-output pattern.
        if spec.dimension_semantics is not None:
            par = [i for i, s in enumerate(spec.dimension_semantics)
                   if s == "parallel"]
            order = np.argsort(bid, kind="stable")
            sb = bid[order]
            starts = np.flatnonzero(np.r_[True, sb[1:] != sb[:-1]])
            for ax in par:
                c = coords[order, ax]
                mx = np.maximum.reduceat(c, starts)
                mn = np.minimum.reduceat(c, starts)
                bad = np.flatnonzero(mx != mn)
                if bad.size:
                    blk = tuple(int(v) for v in
                                np.unravel_index(sb[starts[bad[0]]], nb))
                    out.append(_d(
                        Severity.ERROR, WRITE_RACE,
                        f"{spec.name}/{a.name}: output block {blk} is "
                        f"written by multiple grid points along parallel "
                        f"axis {ax}", where=spec.where,
                        hint="parallel grid axes may execute in any "
                             "order; only sequential axes may revisit "
                             "an output block"))
                    break
    elif a.dma_once and len(spec.grid) >= 1:
        # (4) the fused-block clamped-map invariant: within one inner
        # sweep (all grid axes fixed except the last), each distinct
        # block must be one contiguous run — a block reappearing after
        # the map moved away means Mosaic re-issues its DMA.
        inner = int(spec.grid[-1])
        outer = np.arange(coords.shape[0], dtype=np.int64) // max(inner, 1)
        change = np.r_[True, (bid[1:] != bid[:-1]) |
                       (outer[1:] != outer[:-1])]
        run_key = outer[change] * (int(bid.max()) + 1) + bid[change]
        n_runs = run_key.size
        n_uniq = np.unique(run_key).size
        if n_uniq != n_runs:
            out.append(_d(
                Severity.WARNING, REDUNDANT_DMA,
                f"{spec.name}/{a.name}: declared dma_once but "
                f"{n_runs - n_uniq} block fetch(es) repeat within an "
                f"inner grid sweep — the clamped-map single-DMA "
                f"invariant is broken", where=spec.where,
                hint="use a monotone clamped index map "
                     "(jnp.clip(j - lo, 0, n - 1)) so each block is one "
                     "contiguous run"))
    return out


# ---------------------------------------------------------------------------
# the core check


def verify_kernel(spec: KernelSpec,
                  record_metric: bool = True) -> List[Diagnostic]:
    """All static checks for one kernel launch; returns findings."""
    out: List[Diagnostic] = []

    fp = footprint_bytes(spec)
    if fp > spec.vmem_limit:
        out.append(_d(
            Severity.ERROR, VMEM_EXCEEDED,
            f"{spec.name}: modelled VMEM footprint {fp / (1 << 20):.1f} "
            f"MiB exceeds the {spec.vmem_limit >> 20} MiB scoped "
            f"per-core VMEM", where=spec.where,
            hint="shrink the blocks — double-buffered streams count "
                 "twice"))
    elif fp > spec.vmem_budget:
        out.append(_d(
            Severity.WARNING, VMEM_OVER_BUDGET,
            f"{spec.name}: modelled VMEM footprint {fp / (1 << 20):.1f} "
            f"MiB exceeds the {spec.vmem_budget >> 20} MiB soft budget",
            where=spec.where))

    for a in spec.args:
        out.extend(_tile_diags(spec, a))

    G = int(np.prod(spec.grid, dtype=np.int64)) if spec.grid else 0
    if G and G <= _MAX_GRID_POINTS:
        grid = tuple(int(g) for g in spec.grid)
        coords = _grid_coords(grid)
        for a in spec.args:
            if a.index_map is None:
                continue
            # a hand-DMA'd operand is walked by the kernel's own loops
            at = (_grid_coords(grid + tuple(a.dma_grid))
                  if a.dma_grid else coords)
            try:
                idx = _eval_map(a, at, spec.scalar_prefetch)
            except Exception as e:  # maps may need runtime-only values
                out.append(_d(
                    Severity.INFO, MAP_UNEVALUATED,
                    f"{spec.name}/{a.name}: index map could not be "
                    f"evaluated statically ({type(e).__name__}: {e})",
                    where=spec.where))
                continue
            if idx is not None:
                out.extend(_map_diags(spec, a, idx, at))
    elif G:
        out.append(_d(
            Severity.INFO, MAP_UNEVALUATED,
            f"{spec.name}: grid has {G} points (> {_MAX_GRID_POINTS}); "
            f"index-map analysis skipped", where=spec.where))

    # declared hazards + dtype discipline
    if spec.lane_concat:
        out.append(_d(
            Severity.WARNING, LANE_CONCAT,
            f"{spec.name}: in-kernel concatenate along the lane (last) "
            f"axis — {spec.lane_concat}", where=spec.where,
            hint="Mosaic lowers lane-axis concats through expensive "
                 "relayouts and rejects some shapes; prefer sublane-axis "
                 "layouts or separate stores"))
    for s in spec.scratch:
        if s.seq_scaling:
            note = s.note or "footprint grows linearly with s"
            out.append(_d(
                Severity.WARNING, SEQ_SCRATCH,
                f"{spec.name}/{s.name}: VMEM scratch {tuple(s.shape)} "
                f"({block_bytes(s.shape, s.dtype) / (1 << 20):.2f} MiB) "
                f"scales with sequence length — {note}", where=spec.where,
                hint="seq-scaling scratch caps the max sequence this "
                     "kernel can serve; consider streaming KV blocks"))
    if spec.needs_fp32_acc and not spec.acc_inline:
        has_f32 = any(str(jnp.dtype(s.dtype)) == "float32"
                      for s in spec.scratch)
        if not has_f32:
            out.append(_d(
                Severity.WARNING, ACC_DTYPE,
                f"{spec.name}: MXU kernel carries no fp32 accumulator "
                f"scratch", where=spec.where,
                hint="accumulate matmuls in float32 (scratch or "
                     "preferred_element_type) to avoid bf16 precision "
                     "collapse"))
    by_name = {a.name: a for a in spec.args}
    for scale_name, tensor_name in spec.scale_pairs:
        sa, ta = by_name.get(scale_name), by_name.get(tensor_name)
        if sa is None or ta is None:
            continue
        ok = (tuple(sa.block)[-1] == tuple(ta.block)[-1]
              or tuple(sa.block) == tuple(ta.block)[:-1])
        if not ok:
            out.append(_d(
                Severity.ERROR, SCALE_SHAPE,
                f"{spec.name}: scale operand {scale_name} block "
                f"{tuple(sa.block)} does not agree with {tensor_name} "
                f"block {tuple(ta.block)} (need matching last dim or "
                f"scale == tensor block minus last dim)",
                where=spec.where))

    if record_metric:
        _record(spec.name, verdict_of(out))
    return out


def verdict_of(diags: Sequence[Diagnostic]) -> str:
    if any(d.severity >= Severity.ERROR for d in diags):
        return "error"
    if any(d.severity == Severity.WARNING for d in diags):
        return "warning"
    return "ok"


def _record(kernel: str, verdict: str):
    try:
        from paddle_tpu.observability import default_registry
        default_registry().counter(
            "paddle_tpu_kernel_verify_total",
            "static kernel verification outcomes",
            labelnames=("kernel", "verdict")).labels(
                kernel=kernel, verdict=verdict).inc()
    except Exception:  # pragma: no cover - telemetry must never fail
        pass


# ---------------------------------------------------------------------------
# autotune pruning hooks


def candidate_findings(op: str, shape: Tuple, cand: Tuple
                       ) -> List[Diagnostic]:
    """Verify one autotune candidate config for one sweep shape.
    ``op``/``shape`` use the autotune sweep vocabulary
    (see ``ops/pallas/autotune.SWEEP_SHAPES``)."""
    if op == "flash":
        from paddle_tpu.ops.pallas import flash_attention as fa
        b, s, h, hk, d, dtype, causal = shape
        bq, bk = cand    # the forward's; the backward's tiles follow a
        # rule of the shape alone and cannot tell candidates apart
        return fa.verify_static(b, s, h, hk, d, dtype=dtype, causal=causal,
                                block_q=bq, block_k=bk, parts=("fwd",))
    if op == "fused_ce":
        from paddle_tpu.ops.pallas import cross_entropy as ce
        t, v, dtype = shape
        bt, bv = cand
        return ce.verify_static(t, v, dtype=dtype, block_t=bt, block_v=bv)
    if op == "fused_qkv":
        from paddle_tpu.ops.pallas import fused_block as fb
        t, d, dq, dk, dv, dtype = shape
        bt, bo = cand
        return fb.verify_static_qkv(t, d, dq, dk, dv, dtype=dtype,
                                    block_t=bt, block_o=bo)
    if op == "fused_mlp":
        from paddle_tpu.ops.pallas import fused_block as fb
        t, d, f, dtype = shape
        bt, bf = cand
        return fb.verify_static_mlp(t, d, f, dtype=dtype,
                                    block_t=bt, block_f=bf)
    if op == "fused_decoder":
        from paddle_tpu.ops.pallas import fused_block as fb
        b, s, d, dq, dkv, hd, f, dtype = shape
        bt, bo, bf = cand
        return fb.verify_static_decoder(b, s, d, dq, dkv, hd, f,
                                        dtype=dtype, block_t=bt,
                                        block_o=bo, block_f=bf)
    if op == "quant_matmul":
        from paddle_tpu.ops.pallas import quant_matmul as qm
        t, k, n, wdtype, xdtype = shape
        bt, bn = cand
        return qm.verify_static(t, k, n, wdtype=wdtype, xdtype=xdtype,
                                block_t=bt, block_n=bn)
    if op == "grouped_matmul":
        from paddle_tpu.ops.pallas import grouped_matmul as gm
        g, c, d, h, dtype = shape
        bc, bf = cand
        return gm.verify_static(g, c, d, h, dtype=dtype,
                                block_c=bc, block_f=bf)
    raise KeyError(f"unknown sweep op {op!r}")


def candidate_ok(op: str, shape: Tuple, cand: Tuple) -> bool:
    """True when the verifier finds no lowering-blocking issue: no ERROR
    finding, and no sublane misalignment (a config the eligibility gates
    would reject on-chip even though Mosaic would merely pad)."""
    for d in candidate_findings(op, shape, cand):
        if d.severity >= Severity.ERROR:
            return False
        if d.message.startswith(SUBLANE_MISALIGNED):
            return False
    return True


def prune_candidates(op: str, shape: Tuple, cands: Sequence[Tuple]
                     ) -> Tuple[List[Tuple], int]:
    """(valid_candidates, n_pruned).  Never returns an empty list: if
    every candidate is rejected the original set is returned with the
    full pruned count so callers can flag a wrongly-strict verifier (or
    a genuinely unservable shape) instead of crashing."""
    kept = []
    for c in cands:
        try:
            ok = candidate_ok(op, shape, c)
        except Exception:
            ok = True  # the verifier must never lose a benchmark
        if ok:
            kept.append(tuple(c))
    n_pruned = len(cands) - len(kept)
    if not kept:
        return [tuple(c) for c in cands], n_pruned
    return kept, n_pruned


# ---------------------------------------------------------------------------
# catalog: every shipped kernel at bench shapes


def _catalog_entries() -> List[Dict[str, Any]]:
    """(kernel, shape-desc, config-desc, thunk) rows covering the whole
    ops/pallas catalog at the autotune bench shapes."""
    from paddle_tpu.ops.pallas import autotune as at
    from paddle_tpu.ops.pallas import (
        cross_entropy as ce, flash_attention as fa, fused_block as fb,
        grouped_matmul as gm, paged_attention as pa, quant_matmul as qm,
        rmsnorm as rn)

    rows: List[Dict[str, Any]] = []

    def add(kernel, shape_desc, config_desc, thunk):
        rows.append(dict(kernel=kernel, shape=shape_desc,
                         config=config_desc, thunk=thunk))

    for b, s, h, hk, d, dtype, causal in at.SWEEP_SHAPES["flash"]:
        bq = bk = min(128, s)
        add("flash_fwd", f"b{b} s{s} h{h}/{hk} d{d} {dtype}",
            f"bq{bq} bk{bk}",
            lambda b=b, s=s, h=h, hk=hk, d=d, dtype=dtype, causal=causal:
            fa.verify_static(b, s, h, hk, d, dtype=dtype, causal=causal,
                             parts=("fwd",)))
        tile, q_span, k_span = fa.bwd_tiles(s, d, h // hk, itemsize(dtype))
        add("flash_bwd", f"b{b} s{s} h{h}/{hk} d{d} {dtype}",
            f"tile{tile} spans{q_span}/{k_span}",
            lambda b=b, s=s, h=h, hk=hk, d=d, dtype=dtype, causal=causal:
            fa.verify_static(b, s, h, hk, d, dtype=dtype, causal=causal,
                             parts=("bwd",)))
    for t, v, dtype in at.SWEEP_SHAPES["fused_ce"]:
        bt, bv = ce._default_blocks(t, v)
        add("fused_ce", f"t{t} v{v} {dtype}", f"bt{bt} bv{bv}",
            lambda t=t, v=v, dtype=dtype: ce.verify_static(t, v,
                                                           dtype=dtype))
    for rows_, d_, dtype in ((8192, 2048, "bfloat16"),
                             (8192, 4096, "bfloat16")):
        br = rn._default_block_rows(rows_, d_, dtype)
        add("rmsnorm", f"rows{rows_} d{d_} {dtype}", f"br{br}",
            lambda r=rows_, d=d_, dtype=dtype: rn.verify_static(
                r, d, dtype=dtype))
    for t, d, dq, dk, dv, dtype in at.SWEEP_SHAPES["fused_qkv"]:
        bt, bo = fb._default_qkv_blocks(t, d, dq, dk, dv, dtype)
        add("fused_qkv", f"t{t} d{d} q{dq} kv{dk} {dtype}",
            f"bt{bt} bo{bo}",
            lambda t=t, d=d, dq=dq, dk=dk, dv=dv, dtype=dtype:
            fb.verify_static_qkv(t, d, dq, dk, dv, dtype=dtype))
    for t, d, f, dtype in at.SWEEP_SHAPES["fused_mlp"]:
        bt, bf = fb._default_mlp_blocks(t, d, f, dtype)
        add("fused_mlp", f"t{t} d{d} f{f} {dtype}", f"bt{bt} bf{bf}",
            lambda t=t, d=d, f=f, dtype=dtype:
            fb.verify_static_mlp(t, d, f, dtype=dtype))
    for b, s, d, dq, dkv, hd, f, dtype in at.SWEEP_SHAPES["fused_decoder"]:
        blocks = fb._default_decoder_blocks(s, d, dq, dkv, hd, f, dtype)
        cfg = ("bt{} bo{} bf{}".format(*blocks) if blocks
               else "ineligible")
        add("fused_decoder", f"b{b} s{s} d{d} q{dq} kv{dkv} f{f} {dtype}",
            cfg,
            lambda b=b, s=s, d=d, dq=dq, dkv=dkv, hd=hd, f=f, dtype=dtype:
            fb.verify_static_decoder(b, s, d, dq, dkv, hd, f, dtype=dtype))
    for t, k, n, wdtype, xdtype in at.SWEEP_SHAPES["quant_matmul"]:
        bt, bn = qm._default_quant_blocks(t, n, xdtype)
        add("quant_matmul", f"t{t} k{k} n{n} {wdtype}/{xdtype}",
            f"bt{bt} bn{bn}",
            lambda t=t, k=k, n=n, w=wdtype, x=xdtype:
            qm.verify_static(t, k, n, wdtype=w, xdtype=x))
    for g, c, d_, h_, dtype in at.SWEEP_SHAPES["grouped_matmul"]:
        bc, bf_ = gm._default_grouped_blocks(c, d_, h_, dtype)
        add("grouped_matmul", f"g{g} c{c} d{d_} h{h_} {dtype}",
            f"bc{bc} bf{bf_}",
            lambda g=g, c=c, d=d_, h=h_, dtype=dtype:
            gm.verify_static(g, c, d, h, dtype=dtype))
    # a prefill chunk over the held experts of the benchmark's serve-rag,
    # serve-longctx, serve-reason and serve-mixed
    for t, k, h_, d_, f_, dtype in ((512, 10, 36, 4096, 768, "bfloat16"),
                                    (512, 8, 16, 4096, 2048, "bfloat16"),
                                    (512, 8, 64, 2304, 1024, "bfloat16"),
                                    (512, 4, 16, 3072, 3072, "bfloat16")):
        br, bf_ = gm.sorted_ffn_blocks(t, k, h_, d_, f_, dtype)
        add("sorted_gated_ffn", f"t{t} k{k} h{h_} d{d_} f{f_} {dtype}",
            f"br{br} bf{bf_}",
            lambda t=t, k=k, h=h_, d=d_, f=f_, dtype=dtype:
            gm.verify_static_sorted(t, k, h, d, f, dtype=dtype))
    for B, h, hd, kvh, bs, nb, mb, dtype, quant in (
            (8, 16, 128, 8, 16, 128, 16, "bfloat16", False),
            (8, 16, 128, 8, 16, 128, 16, "bfloat16", True),
            # the benchmark's serve-chat: 32 slots x 2576 tokens
            (32, 32, 128, 8, 16, 3073, 161, "bfloat16", False)):
        add("paged_decode",
            f"B{B} h{h}/{kvh} d{hd} bs{bs} {dtype}"
            + (" int8-kv" if quant else ""),
            f"nb{nb} mb{mb}",
            lambda B=B, h=h, hd=hd, kvh=kvh, bs=bs, nb=nb, mb=mb,
            dtype=dtype, quant=quant:
            pa.verify_static(B, h, hd, kvh, bs, nb, mb, dtype=dtype,
                             quant=quant))
    return rows


def catalog_report(entries: Optional[List[Dict[str, Any]]] = None
                   ) -> List[Dict[str, Any]]:
    """Run the verifier over the whole catalog; returns one row per
    kernel × bench shape with the findings attached."""
    rows = []
    for e in (entries if entries is not None else _catalog_entries()):
        try:
            diags = e["thunk"]()
        except Exception as exc:  # a broken spec builder is a finding too
            diags = [_d(Severity.ERROR, MAP_UNEVALUATED,
                        f"{e['kernel']}: verify_static raised "
                        f"{type(exc).__name__}: {exc}")]
        codes = sorted({d.message.split(":", 1)[0] for d in diags
                        if d.severity >= Severity.WARNING})
        rows.append(dict(
            kernel=e["kernel"], shape=e["shape"], config=e["config"],
            verdict=verdict_of(diags).upper(),
            errors=sum(d.severity >= Severity.ERROR for d in diags),
            warnings=sum(d.severity == Severity.WARNING for d in diags),
            codes=codes, diags=diags))
    return rows


def render_catalog_table(rows: List[Dict[str, Any]]) -> str:
    headers = ("kernel", "shape", "config", "verdict", "findings")
    table = [(r["kernel"], r["shape"], r["config"], r["verdict"],
              ",".join(r["codes"]) or "-") for r in rows]
    widths = [max(len(h), *(len(t[i]) for t in table)) if table else len(h)
              for i, h in enumerate(headers)]
    lines = ["  ".join(h.ljust(w) for h, w in zip(headers, widths))]
    lines.append("  ".join("-" * w for w in widths))
    for t in table:
        lines.append("  ".join(c.ljust(w) for c, w in zip(t, widths)))
    nerr = sum(r["errors"] for r in rows)
    nwarn = sum(r["warnings"] for r in rows)
    lines.append(f"{len(rows)} kernel configs verified — "
                 f"{nerr} error(s), {nwarn} warning(s)")
    return "\n".join(lines)


# ---------------------------------------------------------------------------
# the registered analysis pass: verify every pallas_call in a traced
# program.  Opt-in (not in DEFAULT_PASSES) like autoshard — programs with
# no Pallas kernels get nothing from it.


def _spec_from_eqn(eqn, where: str) -> Optional[KernelSpec]:
    from jax import core as jcore
    gm = eqn.params["grid_mapping"]
    grid = tuple(int(g) for g in gm.grid)
    num_in = int(gm.num_inputs)
    num_out = int(gm.num_outputs)
    bms = list(gm.block_mappings)

    def map_fn(cj):
        def call(*coords):
            f = lambda *idx: jcore.eval_jaxpr(cj.jaxpr, cj.consts, *idx)
            return tuple(jax.vmap(f)(*[jnp.asarray(c) for c in coords]))
        return call

    args = []
    for k, bm in enumerate(bms[:num_in + num_out]):
        sd = bm.array_aval
        # Blocked/Element dims carry their size; a Squeezed dim is 1
        block = tuple(int(getattr(b, "block_size", 1))
                      for b in bm.block_shape)
        cj = bm.index_map_jaxpr
        fn = (map_fn(cj)
              if len(cj.jaxpr.invars) == len(grid) else None)
        is_out = k >= num_in
        space = str(getattr(bm.transformed_block_aval, "memory_space", ""))
        args.append(ArgSpec(
            name=(f"out{k - num_in}" if is_out else f"in{k}"),
            shape=tuple(int(s) for s in sd.shape), block=block,
            index_map=fn, dtype=sd.dtype, is_output=is_out,
            dma_grid=() if space in ("any", "hbm") else None))

    scratch = []
    n_scratch = int(getattr(gm, "num_scratch_operands", 0))
    if n_scratch:
        inner = eqn.params.get("jaxpr")
        if inner is not None:
            for i, var in enumerate(inner.invars[-n_scratch:]):
                aval = var.aval
                shape = tuple(int(s) for s in getattr(aval, "shape", ()))
                dtype = getattr(aval, "dtype", jnp.float32)
                scratch.append(ScratchSpec(
                    name=f"scratch{i}", shape=shape, dtype=dtype))

    cp = eqn.params.get("compiler_params") or {}
    semantics = None
    if isinstance(cp, dict):
        semantics = (cp.get("mosaic") or {}).get("dimension_semantics")
    else:  # pragma: no cover - newer jax carries a params object
        semantics = getattr(cp, "dimension_semantics", None)

    # the scope the call asks the compiler for, where it asks
    mosaic = cp.get("mosaic_tpu") if hasattr(cp, "get") else cp
    limit = getattr(mosaic, "vmem_limit_bytes", None)

    name = str(eqn.params.get("name_and_src_info", "pallas_call"))
    name = name.split(" ")[0] or "pallas_call"
    return KernelSpec(name=name, grid=grid, args=args, scratch=scratch,
                      dimension_semantics=semantics,
                      vmem_limit=int(limit or VMEM_LIMIT_BYTES),
                      where=where)


@register_pass(PASS_ID)
def kernel_verify_pass(ctx: PassContext) -> List[Diagnostic]:
    budget = int(ctx.opt("kernel_verify_budget", VMEM_BUDGET_BYTES))
    out: List[Diagnostic] = []
    n = 0
    for eqn, path, _w in walk_eqns(ctx.jaxpr):
        if eqn.primitive.name != "pallas_call":
            continue
        if "pallas_call[" in path:
            continue  # don't double-count through the kernel's own jaxpr
        n += 1
        try:
            spec = _spec_from_eqn(eqn, where=path or "<top>")
        except Exception as e:
            out.append(_d(
                Severity.INFO, MAP_UNEVALUATED,
                f"pallas_call at {path or '<top>'} could not be modelled "
                f"({type(e).__name__}: {e})"))
            continue
        if spec is None:
            continue
        spec.vmem_budget = budget
        found = verify_kernel(spec)
        out.extend(found)
        out.append(_d(
            Severity.INFO, "KERNEL_VERIFIED",
            f"{spec.name}: grid={spec.grid} "
            f"footprint={footprint_bytes(spec) / (1 << 20):.2f} MiB "
            f"-> {verdict_of(found)}", where=spec.where))
    if n == 0:
        out.append(_d(
            Severity.INFO, MAP_UNEVALUATED,
            "no pallas_call equations in the traced program "
            "(off-TPU traces route kernels to reference fallbacks)"))
    return out
