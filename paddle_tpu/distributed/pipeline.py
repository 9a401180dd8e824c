"""Pipeline parallelism.

Reference parity: ``PipelineLayer``/``LayerDesc``/``SharedLayerDesc``
(fleet/meta_parallel/parallel_layers/pp_layers.py:240,56,76), segmentation
(``SegmentLayers`` pp_layers.py:92), the 1F1B runtime
(``PipelineParallel.forward_backward_pipeline``
meta_parallel/pipeline_parallel.py:188) and interleaved variant (:565,642),
P2P activations (pp_utils/p2p_communication.py).

TPU-native design: the reference runs one Python process per stage that
`send/recv`s activations over NCCL and hand-schedules
forward/backward interleaving.  Under single-controller SPMD the whole
schedule is ONE traced program: stage weights are stacked on a leading
[num_stages, ...] axis sharded over the ``pp`` mesh axis, and a
``lax.scan`` over schedule ticks moves activations between neighbouring
stages with ``lax.ppermute`` (XLA collective-permute — ICI point-to-point).
Because ppermute/scan are differentiable, ``jax.grad`` of the scanned loss
IS the pipelined backward — the compiler produces the reverse schedule that
the reference writes by hand, and rematerialisation (``jax.checkpoint`` on
the stage fn) gives the 1F1B-grade memory profile.

Scope note: the scanned schedule is GPipe-shaped (all forwards, then the
transposed backwards). 1F1B reorders the *runtime buffer lifetimes*, which
in the reference reduces live activations from O(M) to O(S); here the same
reduction comes from `remat='stage'` (save only stage boundaries, recompute
inside the backward scan), which is how praxis/maxtext express it on TPU.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from paddle_tpu.distributed.communication import axis_size as _axis_size, \
    vma_of as _vma_of
from paddle_tpu.jit.train_step import CompiledStepBase as _TrainStepBase
from paddle_tpu.nn.layer import Layer

__all__ = ["LayerDesc", "SharedLayerDesc", "SegmentLayers", "PipelineLayer",
           "spmd_pipeline", "build_1f1b_schedule", "pipeline_1f1b",
           "build_interleaved_schedule", "pipeline_interleaved",
           "PipelineTrainStep"]


class LayerDesc:
    """Deferred layer constructor (reference pp_layers.py:56)."""

    def __init__(self, layer_cls, *args, **kwargs):
        if not issubclass(layer_cls, Layer):
            raise TypeError(f"{layer_cls} must be a Layer subclass")
        self.layer_cls = layer_cls
        self.args = args
        self.kwargs = kwargs

    def build_layer(self) -> Layer:
        return self.layer_cls(*self.args, **self.kwargs)

    def __repr__(self):
        return f"LayerDesc({self.layer_cls.__name__})"


class SharedLayerDesc(LayerDesc):
    """Weight-tied layer appearing in several stages (reference
    pp_layers.py:76 — e.g. tied embedding/lm-head; the reference allreduces
    the shared grads across stages (:532); here the tied parameter is a
    single array the compiler sees twice, so its gradient contributions sum
    automatically)."""

    def __init__(self, key, layer_cls, *args, forward_func=None,
                 shared_weight_attr="weight", **kwargs):
        super().__init__(layer_cls, *args, **kwargs)
        self.layer_name = key
        self.forward_func = forward_func
        self.shared_weight_attr = shared_weight_attr


class SegmentLayers:
    """Split N layer descs into S contiguous stages (reference
    pp_layers.py:92): 'uniform' by count or 'param' by parameter volume."""

    def __init__(self, layers: Sequence, num_parts: int,
                 method: str = "uniform"):
        self.layers = list(layers)
        self.num_parts = num_parts
        self.method = method
        if len(self.layers) < num_parts:
            raise ValueError(
                f"{len(self.layers)} layers < {num_parts} stages")

    def do_segment(self) -> List[int]:
        """Returns stage boundaries, len == num_parts+1."""
        n, s = len(self.layers), self.num_parts
        if self.method == "uniform":
            base, rem = divmod(n, s)
            sizes = [base + (1 if i < rem else 0) for i in range(s)]
        elif self.method.startswith("layer:"):
            # weight by occurrences of a named layer class (reference
            # supports 'layer:TransformerLayer')
            name = self.method.split(":", 1)[1]
            weights = [1 if getattr(d, "layer_cls", type(d)).__name__ == name
                       else 0 for d in self.layers]
            sizes = self._balance(weights, s)
        elif self.method == "param":
            weights = []
            for d in self.layers:
                layer = d.build_layer() if isinstance(d, LayerDesc) else d
                weights.append(sum(int(np.prod(p.shape))
                                   for p in layer.parameters()) or 1)
            sizes = self._balance(weights, s)
        else:
            raise ValueError(f"unknown segment method {self.method}")
        bounds = [0]
        for sz in sizes:
            bounds.append(bounds[-1] + sz)
        return bounds

    @staticmethod
    def _balance(weights: List[int], s: int) -> List[int]:
        """Greedy prefix split minimising max stage weight."""
        total = sum(weights)
        target = total / s
        sizes, acc, count = [], 0.0, 0
        remaining_parts = s
        for i, w in enumerate(weights):
            acc += w
            count += 1
            remaining = len(weights) - i - 1
            if (acc >= target and remaining_parts > 1
                    and remaining >= remaining_parts - 1):
                sizes.append(count)
                acc, count = 0.0, 0
                remaining_parts -= 1
        sizes.append(count)
        while len(sizes) < s:
            sizes.append(0)
        return sizes


class PipelineLayer(Layer):
    """Stage-segmented model container (reference pp_layers.py:240).

    Single-controller SPMD holds ALL stages' weights (each sharded to its
    stage's devices by the pp dim of the stacked arrays), so unlike the
    reference there is no per-rank construction: ``forward`` runs the full
    serial stack (parity/eval path), and ``stage_layers(i)`` exposes the
    per-stage slices for the spmd schedule.
    """

    def __init__(self, layers: Sequence, num_stages: int,
                 topology=None, seg_method: str = "uniform",
                 recompute_interval: int = 0, name=None):
        super().__init__()
        self._descs = list(layers)
        self._num_stages = num_stages
        self.seg_method = seg_method
        self.recompute_interval = recompute_interval

        self.segment_bounds = SegmentLayers(
            self._descs, num_stages, seg_method).do_segment()

        from paddle_tpu.nn.common_layers import LayerList
        built: List[Layer] = []
        self._shared: dict = {}
        self._shared_fwd: dict = {}
        for d in self._descs:
            if isinstance(d, SharedLayerDesc):
                if d.layer_name in self._shared:
                    # reuse the first instance's weights: same Layer object
                    built.append(self._shared[d.layer_name])
                else:
                    layer = d.build_layer()
                    self._shared[d.layer_name] = layer
                    built.append(layer)
                self._shared_fwd[len(built) - 1] = d.forward_func
            elif isinstance(d, LayerDesc):
                built.append(d.build_layer())
            elif isinstance(d, Layer):
                built.append(d)
            else:
                raise TypeError(f"bad pipeline element {d!r}")
        self.run_function = LayerList(built)

    @property
    def num_stages(self) -> int:
        return self._num_stages

    def get_num_virtual_stages(self) -> int:
        return 1

    def stage_layers(self, stage: int) -> List[Layer]:
        lo, hi = self.segment_bounds[stage], self.segment_bounds[stage + 1]
        return list(self.run_function)[lo:hi]

    def forward(self, x):
        for i, layer in enumerate(self.run_function):
            fwd = self._shared_fwd.get(i)
            x = fwd(layer, x) if fwd is not None else layer(x)
        return x


# -- the SPMD schedule -------------------------------------------------------

def spmd_pipeline(stage_fn: Callable, stage_params: Any, microbatches,
                  *, num_microbatches: int, axis_name: str = "pp",
                  remat: bool = True):
    """Run a homogeneous-stage pipeline INSIDE an enclosing shard_map.

    Args:
      stage_fn: ``(params_for_this_stage, x) -> y`` — one stage's compute.
        Same jaxpr on every device (SPMD); per-stage behaviour comes from
        the params.
      stage_params: this device's slice of the stacked [S, ...] params
        (shard_map has already split the leading axis).
      microbatches: ``[M, mb, ...]`` array of all microbatch inputs,
        replicated over the pp axis.
      num_microbatches: M (static).
      remat: jax.checkpoint the stage fn — recompute stage interiors in
        the backward pass, keeping only boundary activations live (the
        memory behaviour 1F1B buys in the reference).

    Returns ``[M, mb, ...]`` outputs, valid on the LAST stage (other
    stages hold zeros); combine with a ``where(axis_index==S-1, ...)``
    psum or an out_spec that keeps the pp axis.

    Schedule: T = M + S - 1 ticks.  At tick t stage s computes microbatch
    ``t - s`` (when in range) — the classic GPipe wavefront; ppermute
    rotates boundary activations one hop per tick over ICI.
    """
    S = _axis_size(axis_name)
    idx = lax.axis_index(axis_name)
    M = num_microbatches
    mb_shape = microbatches.shape[1:]

    fn = jax.checkpoint(stage_fn) if remat else stage_fn

    # probe output shape: stages must be shape-preserving on the boundary
    out_shape = jax.eval_shape(fn, stage_params,
                               jax.ShapeDtypeStruct(
                                   mb_shape, microbatches.dtype))
    if (out_shape.shape, out_shape.dtype) != (mb_shape, microbatches.dtype):
        raise ValueError(
            "spmd_pipeline requires shape-preserving stages; got "
            f"{mb_shape}->{out_shape.shape}")

    def tick(carry, t):
        recv, outputs = carry
        # stage 0 injects microbatch t (clamped; masked out when t >= M)
        inject = lax.dynamic_index_in_dim(
            microbatches, jnp.clip(t, 0, M - 1), axis=0, keepdims=False)
        x = jnp.where(idx == 0, inject, recv)
        y = fn(stage_params, x)
        # rotate boundary activation to the next stage (ring; the wrap
        # last->first carries garbage that stage 0 ignores via `where`)
        new_recv = lax.ppermute(y, axis_name,
                                [(i, (i + 1) % S) for i in range(S)])
        # last stage records microbatch t-(S-1)
        m = t - (S - 1)
        write = (idx == S - 1) & (m >= 0) & (m < M)
        outputs = lax.dynamic_update_index_in_dim(
            outputs,
            jnp.where(write, y,
                      lax.dynamic_index_in_dim(outputs, jnp.clip(m, 0, M - 1),
                                               axis=0, keepdims=False)),
            jnp.clip(m, 0, M - 1), axis=0)
        return (new_recv, outputs), None

    # the carry becomes device-varying after ppermute; mark the zero init
    # as varying too so shard_map's vma check accepts the scan
    from paddle_tpu.distributed.communication import pvary
    init = (pvary(jnp.zeros(mb_shape, microbatches.dtype), axis_name),
            pvary(jnp.zeros((M,) + mb_shape, microbatches.dtype),
                  axis_name))
    (recv, outputs), _ = lax.scan(tick, init, jnp.arange(M + S - 1))
    return outputs


def stack_stage_params(per_stage_params: List[Any]):
    """[pytree per stage] -> stacked pytree with leading S axis (to be
    sharded P('pp', ...)).  Stages must be homogeneous."""
    return jax.tree.map(lambda *xs: jnp.stack(xs), *per_stage_params)


# -- 1F1B: the explicit fused forward/backward schedule ----------------------

def build_1f1b_schedule(num_stages: int, num_microbatches: int):
    """Static [T, S] op/microbatch tables for the 1F1B schedule (reference
    PipelineParallel.forward_backward_pipeline, pipeline_parallel.py:188).

    Discrete-event simulation on the host (trace-time constant): each stage
    does warmup = S-1-s forwards, then strictly alternates backward/forward
    (the "one forward, one backward" steady state), then drains.  Arrival
    constraints (activation from upstream, cotangent from downstream, one
    hop per tick) are enforced by readiness sets, so the table is valid by
    construction.

    Returns (op[T,S], mb[T,S]) int32 numpy arrays; op: 0 idle, 1 fwd, 2 bwd.
    The max number of in-flight microbatches at stage s is S-s (<= S), which
    bounds the activation buffer — the memory property 1F1B exists for.
    """
    S, M = num_stages, num_microbatches
    fwd_ready = [set() for _ in range(S)]   # microbatches whose input arrived
    bwd_ready = [set() for _ in range(S)]   # cotangent arrived
    fwd_ready[0] = set(range(M))            # stage 0 owns all inputs
    fwd_done = [0] * S
    bwd_done = [0] * S
    ops, mbs = [], []
    guard = 0
    while any(b < M for b in bwd_done):
        guard += 1
        if guard > 4 * (M + S) + 16:
            raise RuntimeError("1f1b schedule did not converge")
        row_op = [0] * S
        row_mb = [0] * S
        events = []  # (stage, kind, m) applied after the tick
        for s in range(S):
            warmup = min(S - 1 - s, M)
            # next microbatch in order for each direction
            fm, bm = fwd_done[s], bwd_done[s]
            can_fwd = fm < M and fm in fwd_ready[s]
            can_bwd = bm < fwd_done[s] and bm in bwd_ready[s]
            prefer_bwd = fwd_done[s] >= warmup
            do_bwd = can_bwd and (prefer_bwd or not can_fwd)
            do_fwd = (not do_bwd) and can_fwd and \
                (fwd_done[s] - bwd_done[s]) <= warmup
            if do_bwd:
                row_op[s], row_mb[s] = 2, bm
                bwd_done[s] += 1
                if s > 0:
                    events.append((s - 1, "bwd", bm))
            elif do_fwd:
                row_op[s], row_mb[s] = 1, fm
                fwd_done[s] += 1
                if s < S - 1:
                    events.append((s + 1, "fwd", fm))
                else:
                    # last stage: its own cotangent is ready immediately
                    events.append((s, "bwd", fm))
        for s, kind, m in events:
            (fwd_ready if kind == "fwd" else bwd_ready)[s].add(m)
        ops.append(row_op)
        mbs.append(row_mb)
    return (np.asarray(ops, np.int32), np.asarray(mbs, np.int32))



def _varying_axes(axis_name, *trees):
    """Union of manual axes any leaf varies over, plus the pipeline axis —
    under a multi-axis mesh (pp x dp x tp) compute mixes them all, so every
    branch output / scan carry is marked varying over the full set."""
    axes = {axis_name}
    for v in jax.tree.leaves(trees):
        vma = _vma_of(v)
        if vma:
            axes |= set(vma)
    return tuple(sorted(axes))


def _pvary_axes(x, axes):
    from paddle_tpu.distributed.communication import pvary
    for ax in axes:
        x = pvary(x, ax)
    return x


def pipeline_1f1b(stage_fn: Callable, first_fn: Callable, last_fn: Callable,
                  stage_params: Any, mb_inputs, mb_labels, *,
                  num_microbatches: int, axis_name: str = "pp",
                  remat: bool = True, first_params: Any = None,
                  last_params: Any = None, stage_grad_reduce=None):
    """Fused forward+backward 1F1B pipeline step INSIDE a shard_map.

    The reference hand-schedules 1F1B across NCCL ranks
    (pipeline_parallel.py:188 warmup/steady/cooldown, p2p_communication.py);
    here the whole schedule is ONE lax.scan over ticks: every tick each
    stage consults the static schedule table and either forwards a
    microbatch, backwards one (recomputing its stage from the saved
    boundary input — the reference's recompute-interval memory trick, so
    only O(S) boundary activations are ever live), or idles.  Boundary
    activations ppermute forward, cotangents ppermute backward, parameter
    gradients accumulate in the carry.

    Args:
      stage_fn:  (params, x[mb, ...]) -> y[mb, ...] — the stage's block
        stack; boundary shape-preserving.
      first_fn:  (first_params-or-stage_params, raw_mb) -> x — input
        embedding, applied only on stage 0 (raw microbatch may be int ids).
      last_fn:   (last_params-or-stage_params, y, labels_mb) -> scalar
        loss — head + loss, applied only on the last stage.
      stage_params: this device's stage param slice (shard_map already
        split the stacked [S, ...] axis).
      first_params / last_params: OPTIONAL separate param trees for the
        embedding / head.  When given, first_fn/last_fn receive them
        instead of stage_params, so stage slices stay structurally
        homogeneous WITHOUT zero-replicated embed/head slots — the
        embed/head arrays live once (replicated or fsdp/tp-sharded by the
        caller), not stacked S-fold.  Their grads come back as separate
        trees, psum'd over the pp axis (stage 0 / stage S-1 own the only
        nonzero contributions).  When None, the old contract holds:
        first_fn/last_fn read from stage_params and their grads fold into
        the stage grads.  (Reference analog: pp_layers.py:92 segmentation
        where stage 0's partition simply owns the embedding layer.)
      mb_inputs: [M, mb, ...] raw microbatch inputs (replicated on pp).
      mb_labels: [M, mb, ...] labels (replicated on pp).

    Returns ``(mean_loss, stage_param_grads)`` without param groups, or
    ``(mean_loss, (stage_grads, first_grads, last_grads))`` when
    first_params/last_params are given (None entries where not given) —
    loss is valid on the last stage (psum'd over pp so every stage sees
    it), stage grads are per-stage.
    """
    S = _axis_size(axis_name)
    idx = lax.axis_index(axis_name)
    M = num_microbatches
    from paddle_tpu.distributed.communication import pvary

    has_first = first_params is not None
    has_last = last_params is not None
    has_groups = has_first or has_last
    fparams = first_params if has_first else stage_params
    lparams = last_params if has_last else stage_params

    op_np, mb_np = build_1f1b_schedule(S, M)
    op_table = jnp.asarray(op_np)    # [T, S]
    mb_table = jnp.asarray(mb_np)
    T = op_np.shape[0]

    fn = jax.checkpoint(stage_fn) if remat else stage_fn

    # probe boundary shape; the embed→block seam may change dtype (e.g.
    # fp32 embedding into a bf16 block stack) — the block output fixes the
    # wire type and the seam casts into it
    x0 = jax.eval_shape(
        first_fn, fparams,
        jax.ShapeDtypeStruct(mb_inputs.shape[1:], mb_inputs.dtype))
    y0 = jax.eval_shape(fn, stage_params, x0)
    if y0.shape != x0.shape:
        raise ValueError(f"stage must preserve boundary shape: {x0} -> {y0}")
    bshape, bdtype = y0.shape, y0.dtype
    if y0.dtype != x0.dtype:
        y1 = jax.eval_shape(fn, stage_params,
                            jax.ShapeDtypeStruct(bshape, bdtype))
        if (y1.shape, y1.dtype) != (bshape, bdtype):
            raise ValueError(
                f"stage must be closed over the wire type {bdtype}: "
                f"{bshape}/{bdtype} -> {y1.shape}/{y1.dtype}")

    zeros_b = lambda: jnp.zeros(bshape, bdtype)
    promote = lambda tree: jax.tree.map(
        lambda a: jnp.zeros(a.shape, jnp.promote_types(a.dtype, jnp.float32)
                            if jnp.issubdtype(a.dtype, jnp.floating)
                            else a.dtype),
        tree)
    # stage_grad_reduce: optional per-tick reduction of the stage-grad
    # contribution (e.g. reduce-scatter over a ZeRO axis).  Applying it
    # INSIDE the tick keeps the grad accumulator at the reduced (sharded)
    # size instead of the full gathered size — at 70B scale the fp32 grad
    # carry would otherwise dominate HBM.  It must be linear (it is summed
    # across ticks) and uniform within every group of devices that share a
    # pp index (it runs inside the op-switch, whose branch choice varies
    # only over pp).
    grad_zero = promote(stage_params)
    if stage_grad_reduce is not None:
        grad_zero = stage_grad_reduce(grad_zero)
    fgrad_zero = promote(fparams) if has_first else None
    lgrad_zero = promote(lparams) if has_last else None

    inv_m = 1.0 / M

    # Sender-side static info lets the receiver decide whether this tick's
    # incoming wire payloads are real: what my upstream (idx-1) / downstream
    # (idx+1) neighbour did LAST tick, from the same static table.
    # up_op[t, s] = op of stage s-1 at tick t-1; down_op likewise.
    up_op = np.zeros_like(op_np)
    up_mb = np.zeros_like(mb_np)
    down_op = np.zeros_like(op_np)
    down_mb = np.zeros_like(mb_np)
    up_op[1:, 1:] = op_np[:-1, :-1]
    up_mb[1:, 1:] = mb_np[:-1, :-1]
    down_op[1:, :-1] = op_np[:-1, 1:]
    down_mb[1:, :-1] = mb_np[:-1, 1:]
    up_op_t = jnp.asarray(up_op)
    up_mb_t = jnp.asarray(up_mb)
    down_op_t = jnp.asarray(down_op)
    down_mb_t = jnp.asarray(down_mb)

    def _store(buf, valid, m, payload):
        """buf[m % S] = payload where valid (else unchanged)."""
        slot = m % S
        cur = lax.dynamic_index_in_dim(buf, slot, 0, keepdims=False)
        return lax.dynamic_update_index_in_dim(
            buf, jnp.where(valid, payload, cur), slot, 0)

    zero_tree = lambda z: jax.tree.map(lambda g: jnp.zeros_like(g), z)

    def tick(carry, t):
        (fwd_wire, bwd_wire, in_buf, cot_buf, grads, fgrads, lgrads,
         loss_acc) = carry
        op = op_table[t, idx]
        m = mb_table[t, idx]

        # 1) bank incoming wire payloads (schedule allows consuming them
        #    ticks later, so they must survive subsequent rotations)
        in_buf = _store(in_buf, up_op_t[t, idx] == 1, up_mb_t[t, idx],
                        fwd_wire)
        cot_buf = _store(cot_buf, down_op_t[t, idx] == 2, down_mb_t[t, idx],
                         bwd_wire)

        raw = lax.dynamic_index_in_dim(mb_inputs, m, 0, keepdims=False)
        lab = lax.dynamic_index_in_dim(mb_labels, m, 0, keepdims=False)
        x_saved = lax.dynamic_index_in_dim(in_buf, m % S, 0, keepdims=False)
        g_recv = lax.dynamic_index_in_dim(cot_buf, m % S, 0, keepdims=False)

        def thread_first(p, pf, x):
            # embed path on stage 0 only; `where` keeps the jaxpr uniform
            # across stages, grads flow to embed params only where idx==0
            x_in = jnp.where(idx == 0, first_fn(pf, raw).astype(bdtype), x)
            return fn(p, x_in)

        # 2) compute — switch so idle ticks cost nothing and fwd ticks
        #    don't pay the vjp.  Every branch output is pvary'd so the
        #    branches agree on varying-manual-axes types.
        def pv(y, dx, gtree, fgtree, lgtree, l):
            pvt = lambda tr: jax.tree.map(lambda a: _pvary_axes(a, vaxes),
                                          tr)
            return (_pvary_axes(y, act_axes), _pvary_axes(dx, act_axes),
                    pvt(gtree), pvt(fgtree), pvt(lgtree),
                    _pvary_axes(l, vaxes))

        def do_idle(_):
            return pv(zeros_b(), zeros_b(), zero_tree(grad_zero),
                      zero_tree(fgrad_zero), zero_tree(lgrad_zero),
                      jnp.zeros(()))

        def do_fwd(_):
            y = thread_first(stage_params, fparams, x_saved)
            return pv(y, zeros_b(), zero_tree(grad_zero),
                      zero_tree(fgrad_zero), zero_tree(lgrad_zero),
                      jnp.zeros(()))

        def do_bwd(_):
            def run(loss_like):
                val, pull = jax.vjp(loss_like, stage_params, fparams,
                                    lparams, x_saved)
                # the seed's varying-axes set must match val's (under a
                # multi-axis mesh the loss also varies over dp/tp axes)
                vma = _vma_of(val)
                seed = _pvary_axes(jnp.ones((), val.dtype),
                                   vma or (axis_name,))
                dp, dfp, dlp, dx = pull(seed)
                return val, dp, dfp, dlp, dx

            def last_branch(_):
                return run(lambda p, pf, pl, x: last_fn(
                    pl, thread_first(p, pf, x), lab) * inv_m)

            def mid_branch(_):
                # lparams is untouched here; jax.vjp returns zero
                # cotangents for unused arguments, keeping the branch
                # pytrees structurally identical
                return run(lambda p, pf, pl, x: jnp.sum(
                    thread_first(p, pf, x).astype(jnp.float32)
                    * g_recv.astype(jnp.float32)))

            val, dp, dfp, dlp, dx = lax.cond(idx == S - 1, last_branch,
                                             mid_branch, None)
            loss_c = jnp.where(idx == S - 1, val, 0.0)
            # fold group grads back into the stage tree when aliased
            if not has_first:
                dp = jax.tree.map(lambda a, b: a + b, dp, dfp)
            if not has_last:
                dp = jax.tree.map(lambda a, b: a + b, dp, dlp)
            cast = lambda dtree, ztree: jax.tree.map(
                lambda d, z: d.astype(z.dtype), dtree, ztree)
            if stage_grad_reduce is not None:
                dp = stage_grad_reduce(jax.tree.map(
                    lambda d: d.astype(jnp.float32)
                    if jnp.issubdtype(d.dtype, jnp.floating) else d, dp))
            return pv(zeros_b(), dx.astype(bdtype), cast(dp, grad_zero),
                      cast(dfp, fgrad_zero) if has_first
                      else zero_tree(fgrad_zero),
                      cast(dlp, lgrad_zero) if has_last
                      else zero_tree(lgrad_zero),
                      loss_c.astype(jnp.float32).reshape(()))

        send_y, send_dx, dp, dfp, dlp, loss_c = lax.switch(
            jnp.clip(op, 0, 2), [do_idle, do_fwd, do_bwd], None)

        add = lambda a, d: jax.tree.map(lambda g, x: g + x, a, d)
        grads = add(grads, dp)
        fgrads = add(fgrads, dfp)
        lgrads = add(lgrads, dlp)
        loss_acc = loss_acc + loss_c

        # 3) rotate: activations forward, cotangents backward (ring; the
        #    wrap edges carry garbage that validity gating ignores)
        new_fwd = lax.ppermute(send_y, axis_name,
                               [(i, (i + 1) % S) for i in range(S)])
        new_bwd = lax.ppermute(send_dx, axis_name,
                               [(i, (i - 1) % S) for i in range(S)])
        return (new_fwd, new_bwd, in_buf, cot_buf, grads, fgrads, lgrads,
                loss_acc), None

    # activations only vary over the pipeline axis and whatever the batch is
    # sharded on (e.g. dp) — marking them varying over tp too would insert a
    # spurious psum in the transpose, double-counting every gradient
    act_axes = _varying_axes(axis_name, mb_inputs, mb_labels)
    vaxes = _varying_axes(axis_name, stage_params, fparams, lparams,
                          mb_inputs, mb_labels)
    # group params arrive pp-replicated (invariant); left that way, the
    # per-tick vjp would AUTO-insert their grad psum over pp INSIDE the
    # lax.cond branch only some pp groups take — a cross-stage collective
    # half the devices never reach (deadlock).  pvary them over the
    # ACTIVATION axes (pp + data axes) so grads come back as per-device
    # partial sums and those reductions happen explicitly, outside
    # divergent control flow.  tp is deliberately left invariant: the wire
    # activations must stay off tp, and any auto tp-reduction is uniform
    # within a tp group (all its members share a pp index and branch).
    if has_first:
        fparams = jax.tree.map(lambda a: _pvary_axes(a, act_axes), fparams)
    if has_last:
        lparams = jax.tree.map(lambda a: _pvary_axes(a, act_axes), lparams)
    pvz = lambda tr: jax.tree.map(lambda z: _pvary_axes(z, vaxes), tr)
    init = (_pvary_axes(zeros_b(), act_axes),
            _pvary_axes(zeros_b(), act_axes),
            _pvary_axes(jnp.zeros((S,) + bshape, bdtype), act_axes),
            _pvary_axes(jnp.zeros((S,) + bshape, bdtype), act_axes),
            pvz(grad_zero), pvz(fgrad_zero), pvz(lgrad_zero),
            _pvary_axes(jnp.zeros((), jnp.float32), vaxes))
    (_, _, _, _, grads, fgrads, lgrads, loss_acc), _ = lax.scan(
        tick, init, jnp.arange(T))

    # every stage reports the (last-stage-only) loss
    loss = lax.psum(loss_acc, axis_name)
    if not has_groups:
        return loss, grads
    # group grads: only stage 0 (first) / stage S-1 (last) hold nonzero
    # contributions; psum over pp makes the true grad visible everywhere
    # (matching the groups' pp-replicated storage)
    psum_tree = lambda tr: jax.tree.map(
        lambda g: lax.psum(g, axis_name), tr) if tr is not None else None
    return loss, (grads, psum_tree(fgrads) if has_first else None,
                  psum_tree(lgrads) if has_last else None)


# -- interleaved virtual stages ----------------------------------------------

def build_interleaved_schedule(num_stages: int, num_chunks: int,
                               num_microbatches: int):
    """Static schedule for interleaved virtual stages (reference
    PipelineParallel._forward_backward_pipeline with virtual_pp_degree,
    pipeline_parallel.py:565,642; PipelineLayerChunk pp_layers.py:214).

    Device s holds chunks c=0..V-1; chunk c on device s is GLOBAL stage
    g = c*S + s (the reference's interleaved layout: consecutive model
    slices round-robin over devices).  Discrete-event simulation: one op
    per device per tick, backward preferred once warmup completes, with
    the same arrival constraints as 1F1B (one hop per tick both ways).

    Returns (op[T,S], chunk[T,S], mb[T,S]); op: 0 idle, 1 fwd, 2 bwd.
    """
    S, V, M = num_stages, num_chunks, num_microbatches
    G = S * V
    dev = lambda g: g % S
    fwd_ready = [set() for _ in range(G)]
    bwd_ready = [set() for _ in range(G)]
    fwd_ready[0] = set(range(M))
    fwd_done = [0] * G
    bwd_done = [0] * G
    ops, chunks, mbs = [], [], []
    guard = 0
    while any(b < M for b in bwd_done):
        guard += 1
        if guard > 8 * (M * V + G) + 16:
            raise RuntimeError("interleaved schedule did not converge")
        row_op = [0] * S
        row_ch = [0] * S
        row_mb = [0] * S
        events = []
        for s in range(S):
            # candidate ops among this device's chunks, deepest global
            # stage first so drains happen promptly
            pick = None
            for c in reversed(range(V)):
                g = c * S + s
                bm = bwd_done[g]
                if bm < fwd_done[g] and bm in bwd_ready[g]:
                    pick = (2, c, bm)
                    break
            if pick is None:
                # forward: lowest chunk whose next microbatch arrived and
                # whose in-flight count stays within the warmup bound
                for c in range(V):
                    g = c * S + s
                    fm = fwd_done[g]
                    warmup = min(G - 1 - g, M)
                    if fm < M and fm in fwd_ready[g] and \
                            (fwd_done[g] - bwd_done[g]) <= warmup:
                        pick = (1, c, fm)
                        break
            if pick is None:
                continue
            kind, c, m = pick
            g = c * S + s
            row_op[s], row_ch[s], row_mb[s] = kind, c, m
            if kind == 1:
                fwd_done[g] += 1
                if g < G - 1:
                    events.append((g + 1, "fwd", m))
                else:
                    events.append((g, "bwd", m))
            else:
                bwd_done[g] += 1
                if g > 0:
                    events.append((g - 1, "bwd", m))
        for g, kind, m in events:
            (fwd_ready if kind == "fwd" else bwd_ready)[g].add(m)
        ops.append(row_op)
        chunks.append(row_ch)
        mbs.append(row_mb)
    return (np.asarray(ops, np.int32), np.asarray(chunks, np.int32),
            np.asarray(mbs, np.int32))


def pipeline_interleaved(stage_fn: Callable, first_fn: Callable,
                         last_fn: Callable, chunk_params: Any,
                         mb_inputs, mb_labels, *, num_microbatches: int,
                         num_chunks: int, axis_name: str = "pp",
                         remat: bool = True):
    """Interleaved-virtual-stage fused fwd+bwd pipeline INSIDE shard_map.

    chunk_params: this device's [V, ...] chunk param stack (the global
    stack is [S, V, ...], shard_map split axis 0; element [s][c] serves
    global stage c*S + s).  Contract otherwise as :func:`pipeline_1f1b`.

    Wire routing differs from plain 1F1B in that the ring wrap is REAL:
    a forward boundary leaving device S-1 (chunk c) lands on device 0
    as the input of chunk c+1, and symmetrically for cotangents — the
    banking tables below encode exactly which (chunk, mb) each tick's
    incoming payload belongs to.
    """
    S = _axis_size(axis_name)
    idx = lax.axis_index(axis_name)
    M = num_microbatches
    V = num_chunks
    G = S * V
    from paddle_tpu.distributed.communication import pvary

    # contract: called inside shard_map with the [S, V, ...] global stack
    # split by in_specs=P('pp'), so every local leaf arrives as [1, V, ...];
    # normalise to [V, ...] here and restore the leading pp axis on the
    # returned grads so out_specs=P('pp') reassembles the global stack
    for a in jax.tree.leaves(chunk_params):
        if a.ndim < 2 or a.shape[0] != 1 or a.shape[1] != V:
            raise ValueError(
                "pipeline_interleaved expects chunk_params leaves shaped "
                f"[1, V={V}, ...] (the shard_map-split [S, V, ...] stack); "
                f"got {a.shape}")
    chunk_params = jax.tree.map(lambda a: a.reshape(a.shape[1:]),
                                chunk_params)

    op_np, ch_np, mb_np = build_interleaved_schedule(S, V, M)
    T = op_np.shape[0]
    op_table = jnp.asarray(op_np)
    ch_table = jnp.asarray(ch_np)
    mb_table = jnp.asarray(mb_np)

    fn = jax.checkpoint(stage_fn) if remat else stage_fn

    # host-side banking tables: validity + (chunk, mb) of each incoming wire
    up_valid = np.zeros((T, S), bool)
    up_ch = np.zeros((T, S), np.int32)
    up_mb = np.zeros((T, S), np.int32)
    dn_valid = np.zeros((T, S), bool)
    dn_ch = np.zeros((T, S), np.int32)
    dn_mb = np.zeros((T, S), np.int32)
    for t in range(1, T):
        for s in range(S):
            u = (s - 1) % S
            if op_np[t - 1, u] == 1:
                c = int(ch_np[t - 1, u])
                tc = c if s > 0 else c + 1
                if tc < V and (c * S + u) < G - 1:
                    up_valid[t, s] = True
                    up_ch[t, s] = tc
                    up_mb[t, s] = mb_np[t - 1, u]
            w = (s + 1) % S
            if op_np[t - 1, w] == 2:
                c = int(ch_np[t - 1, w])
                tc = c if s < S - 1 else c - 1
                if tc >= 0 and (c * S + w) > 0:
                    dn_valid[t, s] = True
                    dn_ch[t, s] = tc
                    dn_mb[t, s] = mb_np[t - 1, w]
    up_valid_t = jnp.asarray(up_valid)
    up_ch_t = jnp.asarray(up_ch)
    up_mb_t = jnp.asarray(up_mb)
    dn_valid_t = jnp.asarray(dn_valid)
    dn_ch_t = jnp.asarray(dn_ch)
    dn_mb_t = jnp.asarray(dn_mb)

    # probe boundary shape
    x0 = jax.eval_shape(
        first_fn, jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape[1:],
                                                              a.dtype),
                               chunk_params),
        jax.ShapeDtypeStruct(mb_inputs.shape[1:], mb_inputs.dtype))
    bshape, bdtype = x0.shape, x0.dtype
    y0 = jax.eval_shape(fn, jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape[1:], a.dtype), chunk_params),
        x0)
    if (y0.shape, y0.dtype) != (bshape, bdtype):
        raise ValueError(f"stage must preserve boundary: {x0} -> {y0}")

    B = min(M, G + 2)  # slots per chunk: in-flight per stage <= G+1
    zeros_b = lambda: jnp.zeros(bshape, bdtype)
    pslice = lambda c: jax.tree.map(
        lambda a: lax.dynamic_index_in_dim(a, c, 0, keepdims=False),
        chunk_params)
    grad_zero = jax.tree.map(
        lambda a: jnp.zeros(a.shape, jnp.promote_types(a.dtype, jnp.float32)
                            if jnp.issubdtype(a.dtype, jnp.floating)
                            else a.dtype),
        chunk_params)
    inv_m = 1.0 / M

    def _store2(buf, valid, c, m, payload):
        """buf[c, m % B] = payload where valid."""
        cur = lax.dynamic_slice(
            buf, (c, m % B) + (0,) * len(bshape), (1, 1) + bshape)
        new = jnp.where(valid, payload.reshape((1, 1) + bshape), cur)
        return lax.dynamic_update_slice(buf, new,
                                        (c, m % B) + (0,) * len(bshape))

    def _load2(buf, c, m):
        return lax.dynamic_slice(
            buf, (c, m % B) + (0,) * len(bshape),
            (1, 1) + bshape).reshape(bshape)

    def tick(carry, t):
        fwd_wire, bwd_wire, in_buf, cot_buf, grads, loss_acc = carry
        op = op_table[t, idx]
        c = ch_table[t, idx]
        m = mb_table[t, idx]

        in_buf = _store2(in_buf, up_valid_t[t, idx], up_ch_t[t, idx],
                         up_mb_t[t, idx], fwd_wire)
        cot_buf = _store2(cot_buf, dn_valid_t[t, idx], dn_ch_t[t, idx],
                          dn_mb_t[t, idx], bwd_wire)

        raw = lax.dynamic_index_in_dim(mb_inputs, m, 0, keepdims=False)
        lab = lax.dynamic_index_in_dim(mb_labels, m, 0, keepdims=False)
        x_saved = _load2(in_buf, c, m)
        g_recv = _load2(cot_buf, c, m)
        params_c = pslice(c)
        is_first = (idx == 0) & (c == 0)
        is_last = (idx == S - 1) & (c == V - 1)

        def thread_first(p, x):
            x_in = jnp.where(is_first, first_fn(p, raw), x)
            return fn(p, x_in)

        def pv(y, dx, gtree, l):
            return (_pvary_axes(y, act_axes), _pvary_axes(dx, act_axes),
                    jax.tree.map(lambda a: _pvary_axes(a, vaxes), gtree),
                    _pvary_axes(l, vaxes))

        def do_idle(_):
            return pv(zeros_b(), zeros_b(), jax.tree.map(
                lambda g: jnp.zeros_like(g), grad_zero), jnp.zeros(()))

        def do_fwd(_):
            y = thread_first(params_c, x_saved)
            return pv(y, zeros_b(), jax.tree.map(
                lambda g: jnp.zeros_like(g), grad_zero), jnp.zeros(()))

        def do_bwd(_):
            def run(loss_like):
                val, pull = jax.vjp(loss_like, params_c, x_saved)
                vma = _vma_of(val)
                seed = _pvary_axes(jnp.ones((), val.dtype),
                                   vma or (axis_name,))
                dp, dx = pull(seed)
                return val, dp, dx

            def last_branch(_):
                return run(lambda p, x: last_fn(p, thread_first(p, x), lab)
                           * inv_m)

            def mid_branch(_):
                return run(lambda p, x: jnp.sum(
                    thread_first(p, x).astype(jnp.float32)
                    * g_recv.astype(jnp.float32)))

            val, dp, dx = lax.cond(is_last, last_branch, mid_branch, None)
            loss_c = jnp.where(is_last, val, 0.0)
            # scatter this chunk's grads into the [V, ...] accumulator
            dpf = jax.tree.map(
                lambda d, z: lax.dynamic_update_index_in_dim(
                    jnp.zeros_like(z), d.astype(z.dtype), c, 0),
                dp, grad_zero)
            return pv(zeros_b(), dx.astype(bdtype), dpf,
                      loss_c.astype(jnp.float32).reshape(()))

        send_y, send_dx, dp, loss_c = lax.switch(
            jnp.clip(op, 0, 2), [do_idle, do_fwd, do_bwd], None)

        grads = jax.tree.map(lambda g, d: g + d, grads, dp)
        loss_acc = loss_acc + loss_c

        new_fwd = lax.ppermute(send_y, axis_name,
                               [(i, (i + 1) % S) for i in range(S)])
        new_bwd = lax.ppermute(send_dx, axis_name,
                               [(i, (i - 1) % S) for i in range(S)])
        return (new_fwd, new_bwd, in_buf, cot_buf, grads, loss_acc), None

    act_axes = _varying_axes(axis_name, mb_inputs, mb_labels)
    vaxes = _varying_axes(axis_name, chunk_params, mb_inputs, mb_labels)
    init = (_pvary_axes(zeros_b(), act_axes),
            _pvary_axes(zeros_b(), act_axes),
            _pvary_axes(jnp.zeros((V, B) + bshape, bdtype), act_axes),
            _pvary_axes(jnp.zeros((V, B) + bshape, bdtype), act_axes),
            jax.tree.map(lambda z: _pvary_axes(z, vaxes), grad_zero),
            _pvary_axes(jnp.zeros((), jnp.float32), vaxes))
    (_, _, _, _, grads, loss_acc), _ = lax.scan(tick, init, jnp.arange(T))
    loss = lax.psum(loss_acc, axis_name)
    grads = jax.tree.map(lambda g: g[None], grads)
    return loss, grads


# -- PP composed with dp/fsdp/tp: the 4-D training step ----------------------

def _spec_axis_pos(spec, axis):
    """Index of the array dim `axis` shards in a PartitionSpec, or None."""
    for i, e in enumerate(spec):
        if e == axis or (isinstance(e, tuple) and axis in e):
            return i
    return None


def _spec_axes(spec):
    out = set()
    for e in spec:
        if isinstance(e, tuple):
            out.update(a for a in e if a is not None)
        elif e is not None:
            out.add(e)
    return out


class PipelineTrainStep(_TrainStepBase):
    """Compiled hybrid-parallel training step: 1F1B pipeline over ``pp``,
    data parallelism over ``dp``, ZeRO-sharded data parallelism over
    ``fsdp``, tensor parallelism over ``tp`` — one mesh, ONE jitted
    program, matching the reference's 4-D hybrid topology
    ``["data", "pipe", "sharding", "model"]`` (fleet/base/topology.py:54).

    Reference role: PipelineParallel inside HybridParallelClipGrad/fleet
    (meta_parallel/pipeline_parallel.py + hybrid_parallel_optimizer.py +
    sharding/group_sharded) where pp/dp/sharding/mp process groups compose.
    Here the composition is a single fully-manual shard_map:

    * pp — the 1F1B tick scan runs over the pp axis.
    * dp + fsdp — each microbatch's SAMPLE axis is split over dp×fsdp;
      every data shard runs all M microbatches on its slice and grads are
      normalized back to the global-batch mean.
    * fsdp (ZeRO): param leaves whose spec names the fsdp axis are STORED
      sharded (so are their optimizer-state leaves — ZeRO-1 memory comes
      free from GSPMD on the update), all_gather'd over fsdp once at step
      entry (ZeRO-3 compute), and their grads reduce-scattered back.
    * tp — ``stage_fn`` is written Megatron-style against LOCAL tp shards
      (explicit lax.psum over the tp axis where its math requires it —
      same contract as mpu layers).

    Args:
      stage_fn/first_fn/last_fn: as :func:`pipeline_1f1b`, operating on
        local tp shards.
      stacked_params: dict name -> global [S, ...] stacked arrays.
      param_specs: dict name -> PartitionSpec with the leading pp axis and
        any fsdp/tp placements, e.g. P('pp', 'fsdp', 'tp').
      first_params/last_params (+ their specs): optional separate
        embed/head param dicts — NOT stacked, NOT pp-sharded (specs name
        only fsdp/tp axes), owned logically by stage 0 / stage S-1 (see
        :func:`pipeline_1f1b`).
      optimizer: a paddle_tpu optimizer (init_state_pytree/apply_gradients
        — grad clip and fp32 master weights ride along exactly as in
        ``jit.TrainStep``; pass ``compute_dtype='bfloat16'`` for AMP-O2).
      batch: step() takes {'inputs': [M, mb, ...], 'labels': [M, mb, ...]};
        the microbatch axis is split over dp×fsdp (× any extra_data_axes).
      extra_data_axes: additional mesh axes the batch is split over — pass
        ``('ep',)`` when the stage runs an all_to_all MoE, so the
        expert-parallel group doubles as a data-parallel group (the
        reference's dp×ep overlap); loss averaging and grad normalization
        account for them automatically.
    """

    def __init__(self, stage_fn, first_fn, last_fn, stacked_params,
                 optimizer, mesh, num_microbatches, param_specs, *,
                 pp_axis: str = "pp", dp_axis: Optional[str] = "dp",
                 fsdp_axis: Optional[str] = "fsdp", remat: bool = True,
                 first_params=None, first_specs=None,
                 last_params=None, last_specs=None, compute_dtype=None,
                 scatter_grads_per_tick: bool = False,
                 extra_data_axes=()):
        from jax.sharding import NamedSharding, PartitionSpec as P

        self.mesh = mesh
        self.num_microbatches = num_microbatches
        self._pp = pp_axis
        self._dp = dp_axis if dp_axis in mesh.axis_names else None
        self._fsdp = fsdp_axis if (fsdp_axis and
                                   fsdp_axis in mesh.axis_names) else None
        data_axes = tuple(a for a in (self._dp, self._fsdp) if a)
        has_first = first_params is not None
        has_last = last_params is not None

        # one flat dict drives placement, donation, clip (global norm spans
        # stage+embed+head), optimizer update, and checkpointing
        flat, specs = {}, {}
        for n, a in stacked_params.items():
            flat[n] = a
            specs[n] = param_specs[n]
        for prefix, tree, tree_specs in (("first/", first_params,
                                          first_specs),
                                         ("last/", last_params,
                                          last_specs)):
            if tree is not None:
                for n, a in tree.items():
                    spec = (tree_specs or {}).get(n, P())
                    if pp_axis in _spec_axes(spec):
                        raise ValueError(
                            f"{prefix}{n}: embed/head params must not be "
                            f"pp-sharded (they are owned by one stage and "
                            f"replicated over pp); got {spec}")
                    flat[prefix + n] = a
                    specs[prefix + n] = spec
        if compute_dtype is not None:
            flat = {n: jnp.asarray(a).astype(compute_dtype)
                    if jnp.issubdtype(jnp.asarray(a).dtype, jnp.floating)
                    else a for n, a in flat.items()}
        self._specs = specs
        param_sh = {n: NamedSharding(mesh, specs[n]) for n in flat}
        self._init_step_state(optimizer, flat, param_sh)

        self._jitted = jax.jit(
            build_pipeline_step_fn(
                stage_fn, first_fn, last_fn, optimizer, mesh,
                num_microbatches, specs, pp_axis=pp_axis, dp_axis=self._dp,
                fsdp_axis=self._fsdp, remat=remat, has_first=has_first,
                has_last=has_last,
                scatter_grads_per_tick=scatter_grads_per_tick,
                extra_data_axes=extra_data_axes),
            donate_argnums=(0, 1, 2))

    def __call__(self, batch):
        mb_inputs = jnp.asarray(batch["inputs"])
        mb_labels = jnp.asarray(batch["labels"])
        return self._run_jitted(mb_inputs, mb_labels)


def build_pipeline_step_fn(stage_fn, first_fn, last_fn, optimizer, mesh,
                           num_microbatches, specs, *, pp_axis="pp",
                           dp_axis=None, fsdp_axis=None, remat=True,
                           has_first=False, has_last=False,
                           scatter_grads_per_tick=False,
                           extra_data_axes=()):
    """The pure 4-D training-step function behind ``PipelineTrainStep``:
    ``step(params, opt_state, step_count, mb_inputs, mb_labels, lr) ->
    (loss, params, opt_state, step_count)``.

    Factored out so callers that never materialize arrays (the capacity
    planner's abstract AOT lowering) compile the exact same program the
    real training step runs.  ``specs`` is the flat dict (stage names
    plus "first/"/"last/" prefixed group names) of PartitionSpecs; dp/fsdp
    axis names must already be filtered against the mesh (None = absent).
    """
    from jax.sharding import PartitionSpec as P

    manual = set(mesh.axis_names)
    fsdp = fsdp_axis
    # extra_data_axes: additional mesh axes the batch is split over (e.g.
    # 'ep' when the stage runs an all_to_all MoE — the expert-parallel
    # group doubles as a data-parallel group for the non-expert params,
    # exactly the reference's dp×ep overlap).  Treated like dp for loss
    # averaging and grad normalization; ep-SHARDED expert leaves come back
    # complete from the a2a transpose and need no extra reduction.
    data_axes = tuple(a for a in (dp_axis, fsdp_axis) if a) + \
        tuple(a for a in extra_data_axes if a in manual)

    def split(params):
        stage, first, last = {}, {}, {}
        for n, v in params.items():
            if n.startswith("first/"):
                first[n[6:]] = v
            elif n.startswith("last/"):
                last[n[5:]] = v
            else:
                stage[n] = v
        return (stage, first if has_first else None,
                last if has_last else None)

    def gather_tree(tree, prefix=""):
        # ZeRO-3: materialize full (per-stage) values of fsdp-sharded
        # leaves; the matching reduce-scatter runs on the grads below
        if tree is None or fsdp is None:
            return tree
        out = {}
        for n, v in tree.items():
            pos = _spec_axis_pos(specs[prefix + n], fsdp)
            out[n] = v if pos is None else lax.all_gather(
                v, fsdp, axis=pos, tiled=True)
        return out

    def scatter_tree(tree, prefix=""):
        if tree is None or fsdp is None:
            return tree
        out = {}
        for n, g in tree.items():
            pos = _spec_axis_pos(specs[prefix + n], fsdp)
            out[n] = g if pos is None else lax.psum_scatter(
                g, fsdp, scatter_dimension=pos, tiled=True)
        return out

    def reduce_leaf(g, spec, exclude=()):
        # vma cleanup: pmean over any axis the grad still varies on
        # but its out_spec omits (values already equal across them)
        present = _spec_axes(spec)
        vma = _vma_of(g)
        for ax in manual - present - set(exclude):
            if ax in vma:
                g = lax.pmean(g, ax)
        return g

    per_tick = scatter_grads_per_tick and fsdp is not None

    def tick_reduce(tree):
        # keep the scan's grad accumulator ZeRO-sharded: reduce-scatter
        # each tick's contribution instead of accumulating full-size
        return scatter_tree(tree)

    def body(params, mb_inputs, mb_labels):
        stage_p, first_p, last_p = split(params)
        out = pipeline_1f1b(
            stage_fn, first_fn, last_fn, gather_tree(stage_p),
            mb_inputs, mb_labels,
            num_microbatches=num_microbatches, axis_name=pp_axis,
            remat=remat,
            first_params=gather_tree(first_p, "first/"),
            last_params=gather_tree(last_p, "last/"),
            stage_grad_reduce=tick_reduce if per_tick else None)
        if has_first or has_last:
            loss, (g_stage, g_first, g_last) = out
        else:
            loss, g_stage = out
            g_first = g_last = None

        # data semantics: each of the D = dp*fsdp data shards computed
        # the mean loss of ITS microbatch slice; the vjp transpose
        # already psum'd grads over axes the params are INVARIANT on
        # (dp always; fsdp for non-fsdp-sharded leaves), and the
        # reduce-scatter below sums the fsdp-sharded ones — so a
        # uniform 1/D turns every leaf into the global-batch mean.
        d_total = 1
        for ax in data_axes:
            d_total *= _axis_size(ax)
        scale = 1.0 / d_total
        norm = lambda tr: None if tr is None else jax.tree.map(
            lambda g: g * scale, tr)
        g_stage, g_first, g_last = norm(g_stage), norm(g_first), \
            norm(g_last)
        for ax in data_axes:
            loss = lax.pmean(loss, ax)
        vma_l = _vma_of(loss)
        for ax in manual - set(data_axes):
            if ax in vma_l:  # e.g. tp: equal across shards, clean vma
                loss = lax.pmean(loss, ax)

        if not per_tick:  # already reduce-scattered inside the ticks
            g_stage = scatter_tree(g_stage)

        def group_reduce(tr, prefix):
            # group grads come back as per-device partial sums over
            # the data axes (their params were pvary'd — see
            # pipeline_1f1b); reduce them explicitly here, OUTSIDE any
            # divergent control flow: sum over dp, sum(+shard) over
            # fsdp.  tp shards hold equal values — reduce_leaf's
            # pmean cleans that vma up below.
            if tr is None:
                return None
            out = {}
            for n, g in tr.items():
                vma = _vma_of(g)
                for ax in data_axes:
                    if ax != fsdp and ax in vma:
                        g = lax.psum(g, ax)
                if fsdp:
                    pos = _spec_axis_pos(specs[prefix + n], fsdp)
                    g = lax.psum(g, fsdp) if pos is None else \
                        lax.psum_scatter(g, fsdp,
                                         scatter_dimension=pos,
                                         tiled=True)
                out[n] = g
            return out

        g_first = group_reduce(g_first, "first/")
        g_last = group_reduce(g_last, "last/")

        merged = {n: reduce_leaf(g, specs[n], exclude=(pp_axis,))
                  for n, g in g_stage.items()}
        for prefix, tr in (("first/", g_first), ("last/", g_last)):
            if tr is not None:
                for n, g in tr.items():
                    merged[prefix + n] = reduce_leaf(g, specs[prefix + n])
        return loss, merged

    from paddle_tpu.distributed.communication import shard_map

    batch_spec = P(None, data_axes) if data_axes else P()
    shmap = shard_map(
        body, mesh=mesh,
        in_specs=(dict(specs), batch_spec, batch_spec),
        out_specs=(P(), dict(specs)))

    def step_impl(params, opt_state, step_count, mb_inputs, mb_labels,
                  lr):
        loss, grads = shmap(params, mb_inputs, mb_labels)
        step_count = step_count + 1
        new_params, new_state = optimizer.apply_gradients(
            params, grads, opt_state, step_count, lr=lr)
        return loss, new_params, new_state, step_count

    return step_impl
