"""Mixture-of-Experts with expert parallelism.

Reference parity: ``MoELayer`` (incubate/distributed/models/moe/moe_layer.py
:261) with gates (moe/gate/: NaiveGate, GShardGate, SwitchGate), dispatch via
``MoEScatter``/``MoEGather`` PyLayers (:97,:147) around the
``global_scatter``/``global_gather`` all-to-all collective ops
(operators/collective/global_scatter_op.cu.cc), capacity + load-balance loss
(moe/utils.py).

TPU-native design (the GShard recipe): token routing is expressed as dense
einsums with a one-hot dispatch mask — no gather/scatter kernels, fully
differentiable, MXU-friendly — and expert weights are stacked ``[E, ...]``
arrays whose PartitionSpec puts E on the ``ep`` mesh axis.  Under jit,
GSPMD turns the dispatch einsum into exactly the all_to_all the reference
implements as ``global_scatter`` (sharding constraints below pin that
layout).  Capacity math and the load-balance auxiliary loss follow GShard
§3.2, matching the reference's utils.
"""

from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp

from paddle_tpu.nn.layer import Layer
from paddle_tpu.nn import functional as F
from paddle_tpu.distributed.mpu import constrain

__all__ = ["top_k_gating", "NaiveGate", "SwitchGate", "GShardGate",
           "MoELayer", "ExpertFFN", "moe_shard_a2a", "moe_forward_a2a",
           "top_k_gating_indices", "moe_forward_index",
           "moe_shard_index_a2a", "moe_forward_ragged",
           "GatedExpertLayer", "gated_experts_forward"]


def top_k_gating(gate_logits, k: int, capacity: int,
                 jitter_key=None, jitter_eps: float = 0.0):
    """GShard top-k gating with capacity.

    Args:
      gate_logits: [tokens, E].
    Returns:
      combine: [tokens, E, C] combine weights (0 for dropped tokens),
      dispatch: same-shape bool mask,
      aux_loss: load-balance loss (mean_prob * mean_assignment * E),
      router z-loss is folded in by callers that want it.
    """
    if jitter_key is not None and jitter_eps > 0:
        noise = jax.random.uniform(jitter_key, gate_logits.shape,
                                   minval=1 - jitter_eps,
                                   maxval=1 + jitter_eps)
        gate_logits = gate_logits * noise
    E = gate_logits.shape[1]
    topi, slot, w, keep, aux_loss = top_k_gating_indices(
        gate_logits, k=k, capacity=capacity)
    # densify the index form into GShard's [T, E, C] one-hot tensors
    onehot = jax.nn.one_hot(topi, E, dtype=w.dtype)       # [T, k, E]
    cap_onehot = jax.nn.one_hot(jnp.clip(slot, 0, capacity - 1), capacity,
                                dtype=w.dtype)            # [T, k, C]
    combine = jnp.einsum("tke,tkc,tk->tec", onehot, cap_onehot, w)
    dispatch = jnp.einsum("tke,tkc->tec",
                          onehot * keep[..., None].astype(w.dtype),
                          cap_onehot) > 0
    return combine, dispatch, aux_loss


def _gshard_aux(probs, topi, E: int, k: int):
    """GShard load-balance loss: E * mean_e(frac_tokens_e * mean_prob_e)
    (single home — shared by the capacity bookkeeping and the ragged
    dropless path so the formula cannot drift)."""
    onehot = jax.nn.one_hot(topi, E, dtype=probs.dtype)   # [T, k, E]
    me = probs.mean(axis=0)
    ce = (onehot.sum(1) > 0).astype(probs.dtype).mean(axis=0) / k
    return (me * ce).sum() * E


def top_k_gating_indices(gate_logits, k: int, capacity: int):
    """Index-form gating — the single implementation of the GShard
    bookkeeping (``top_k_gating`` densifies this form).  Returns
    per-(token, choice) indices, the input to the gather/scatter dispatch
    whose cost is O(T·k·d) instead of the dense contraction's
    O(T·E·C·d) (at bench shapes the dense dispatch einsum costs 3x the
    expert math itself).

    Fully vectorized (no Python loop over k): lax.top_k selects the same
    experts k sequential argmax passes would; queue positions come from
    one cumsum over the k-major flattening (all 1st choices in token
    order, then all 2nd choices, ...).  Standard GShard bookkeeping: an
    over-capacity assignment still occupies its position number, so under
    overflow a later-rank choice may be pushed past capacity where a
    k-pass implementation (recycling dropped slots between passes) would
    have admitted it — slightly more conservative, identical whenever
    capacity is not exceeded (and always under dropless).

    Returns:
      topi:  [T, k] int32 expert ids
      slot:  [T, k] int32 capacity slot within the expert
      w:     [T, k] combine weights, normalized over kept choices
      keep:  [T, k] bool — in-capacity assignments
      aux_loss: scalar GShard load-balance loss
    """
    tokens, E = gate_logits.shape
    probs = jax.nn.softmax(gate_logits, axis=-1)
    k = min(k, E)  # degenerate configs (fewer experts than choices)
    topv, topi = jax.lax.top_k(probs, k)                  # [T, k]
    onehot = jax.nn.one_hot(topi, E, dtype=probs.dtype)   # [T, k, E]
    flat = onehot.transpose(1, 0, 2).reshape(k * tokens, E)
    pos_flat = jnp.cumsum(flat, axis=0) - flat
    pos = pos_flat.reshape(k, tokens, E).transpose(1, 0, 2)
    in_cap = (pos < capacity) & (onehot > 0)
    slot = (pos * onehot).sum(-1).astype(jnp.int32)       # [T, k]
    keep = in_cap.any(-1)                                 # [T, k]
    w = topv * keep.astype(probs.dtype)
    denom = w.sum(axis=1, keepdims=True)
    w = jnp.where(denom > 0, w / jnp.maximum(denom, 1e-9), w)
    return topi, slot, w, keep, _gshard_aux(probs, topi, E, k)


def moe_forward_index(x2d, logits, experts_fn, *, E: int, top_k: int,
                      capacity: int):
    """Gather/scatter expert dispatch (single-program; MaxText-style).

    Builds [E, C] token-index buffers with one masked scatter (dropped
    assignments target an out-of-bounds row, mode='drop'), gathers
    expert inputs directly from the token axis, and combines with a
    [T, k, d] gather — no [T, E, C] tensor exists anywhere.  Gradients
    flow through the gathers (scatter-add transposes).
    """
    T, d = x2d.shape
    topi, slot, w, keep, aux = top_k_gating_indices(logits, k=top_k,
                                                    capacity=capacity)
    safe_e = jnp.where(keep, topi, E)      # OOB row → dropped by scatter
    tok_ids = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32)[:, None],
                               topi.shape)
    tok_for = jnp.zeros((E, capacity), jnp.int32).at[safe_e, slot].set(
        tok_ids, mode="drop")
    # pad slots point at token 0 — harmless garbage: the combine gather
    # reads only (topi, slot) pairs and dropped pairs carry w == 0, so
    # no mask multiply (saves one [E, C, d] HBM pass)
    expert_in = x2d[tok_for]                              # [E, C, d]
    if _grouped_moe_enabled():
        # per-expert kept-assignment counts (the front-packed slot
        # prefix) let the grouped kernel skip empty capacity blocks
        counts = jnp.zeros((E,), jnp.int32).at[safe_e.reshape(-1)].add(
            1, mode="drop")
        expert_out = experts_fn(expert_in, counts)        # [E, C, d]
    else:
        expert_out = experts_fn(expert_in)                # [E, C, d]
    picked = expert_out[topi, jnp.clip(slot, 0, capacity - 1)]  # [T, k, d]
    out = jnp.einsum("tkd,tk->td", picked, w.astype(x2d.dtype))
    dropped = 1.0 - keep.astype(jnp.float32).mean()
    return out, aux, dropped


def moe_forward_ragged(x2d, logits, w1, b1, w2, b2, *, E: int, top_k: int,
                       activation=None):
    """Dropless sort + ``lax.ragged_dot`` expert dispatch (single-program).

    The zero-padding path: the (T, k) assignments are flattened, argsorted
    by expert id, and the expert GEMMs run as ONE grouped matmul over
    exactly T*k rows (``lax.ragged_dot`` with per-expert group sizes) — no
    [E, C] capacity buffers, no padding FLOPs, nothing dropped.  This is
    the TPU-native analog of the reference's pure computation under
    ``global_scatter``/``global_gather`` (global_scatter_op.cu.cc sends
    exactly count rows; here the "send" is an in-chip gather).

    Returns (out [T, d], aux_loss, dropped_frac=0.0).
    """
    act = activation or jax.nn.gelu
    T, d = x2d.shape
    k = min(top_k, E)
    probs = jax.nn.softmax(logits, axis=-1)
    topv, topi = jax.lax.top_k(probs, k)                  # [T, k]
    w = topv / jnp.maximum(topv.sum(-1, keepdims=True), 1e-9)
    flat_e = topi.reshape(-1)                             # [T*k] token-major
    order = jnp.argsort(flat_e)                           # stable
    tok = (order // k).astype(jnp.int32)                  # source token/row
    xs = x2d[tok]                                         # [T*k, d]
    group_sizes = jnp.bincount(flat_e, length=E).astype(jnp.int32)
    es = flat_e[order]                                    # sorted expert ids
    h = jax.lax.ragged_dot(xs, w1, group_sizes) + b1[es]
    ys = jax.lax.ragged_dot(act(h), w2, group_sizes) + b2[es]
    wf = w.reshape(-1)[order].astype(x2d.dtype)
    out = jnp.zeros((T, d), x2d.dtype).at[tok].add(ys * wf[:, None])
    return out, _gshard_aux(probs, topi, E, k), jnp.zeros((), jnp.float32)


ROUTER_RULES = ("softmax_topk", "sigmoid_bias")


def router_gates(logits, top_k: int, rule: str = "softmax_topk",
                 bias=None, scaling: float = 1.0):
    """The router's rule, a stated property of the layer: float32
    ``logits`` [T, E] -> (gates [T, k] float32, chosen ids [T, k]).

    * ``softmax_topk`` (Mixtral, Granite): the top k logits, softmax
      over the chosen.
    * ``sigmoid_bias`` (the DeepSeek-V3 family's auxiliary-loss-free
      balancing): ``s = sigmoid(logits)``; the k experts are the top k of
      ``s + bias`` — ``bias`` [E] steers the choice and is in no weight —
      and ``g_e = scaling * s_e / sum_chosen s``."""
    if rule == "softmax_topk":
        topv, topi = jax.lax.top_k(logits, top_k)
        return jax.nn.softmax(topv, axis=-1), topi
    if rule != "sigmoid_bias":
        raise ValueError(f"router rule {rule!r}: one of {ROUTER_RULES}")
    s = jax.nn.sigmoid(logits)
    choice = s if bias is None else s + bias.astype(jnp.float32)[None]
    _, topi = jax.lax.top_k(choice, top_k)
    chosen = jnp.take_along_axis(s, topi, axis=-1)
    return scaling * chosen / jnp.sum(chosen, -1, keepdims=True), topi


def gated_experts_forward(x2d, router_w, w_in, w_out, *, top_k: int,
                          local_of, row_valid=None, rule="softmax_topk",
                          router_bias=None, scaling: float = 1.0):
    """The served expert layer: gated bias-free experts, the router's
    rule (``router_gates``: top-k then softmax over the chosen logits by
    default), dropless, over the experts held here.

    x2d [T, d]; router_w [d, E]: the router at its published width;
    w_in [H, d, 2f] (``[gate | up]``) and w_out [H, f, d]: the H experts
    this chip holds; ``local_of`` [E] int32 (a constant): an expert's
    index among the held ones, H for one held elsewhere; ``row_valid``
    [T] bool: rows that are tokens (a padded tail and an inactive decode
    row route nowhere, so they read no expert).

    Every token is routed over all E experts; the picks that land on a
    held expert go, grouped by expert, through the gated product
    ``W_out[e] (silu(g) * u)``, ``[g | u] = x W_in[e]``; a pick that
    lands elsewhere contributes nothing here, which is the part of the
    layer's result this chip's experts give.  Which product runs is a
    rule on the static shapes, in one place
    (``ops/pallas/grouped_matmul.py: sorted_ffn_blocks``):

    * from a mean of 32 rows a held expert while the step's float32
      output and its rows fit VMEM (a prefill chunk: 512 tokens x 10
      picks over 36 experts), one Pallas call, ``sorted_gated_ffn``, and
      nothing after it: every group on a tile boundary of a padded row
      buffer kept in VMEM, a 128-row tile to one expert, the hidden tile
      never in HBM, and each tile's rows added under their gates into
      the step's ``[T, d]``, which stays in VMEM over the call -- no
      padded rows, no gather back to the picks and no sum over them in
      HBM;
    * below that (a decode step's 24 rows) and above it, two
      ``lax.ragged_dot`` over the picks sorted by expert: on the TPU
      the compiler's grouped-matmul kernel, a 16-row tile at a decode
      step's rows and a 512-row tile at a chunk's; the rows go back to
      their picks and are summed under the gates by XLA.

    Either way only the groups that have rows are visited, so an expert
    no row chose is not read, and nothing is dropped: there is no
    capacity.  The path taken is counted at trace time
    (``paddle_tpu_grouped_moe_path_total{path=sorted_kernel|ragged_dot}``).
    Returns (out [T, d] float32, counts int32 [3]: held experts with at
    least one row, picks that landed here, picks made)."""
    from paddle_tpu.ops.pallas import grouped_matmul as GM
    T, d = x2d.shape
    H = w_in.shape[0]
    logits = jnp.dot(x2d, router_w, preferred_element_type=jnp.float32)
    gates, topi = router_gates(logits, top_k, rule, router_bias,
                               scaling)                   # [T, k]
    loc = jnp.asarray(local_of, jnp.int32)[topi]          # [T, k] in [0, H]
    if row_valid is not None:
        loc = jnp.where(row_valid[:, None], loc, H)
    flat = loc.reshape(-1)                                # token-major
    # a compare-and-sum, not ``bincount``: that is a scatter-add, one
    # update at a time on the TPU
    sizes = jnp.sum(flat[:, None] == jnp.arange(H)[None], axis=0,
                    dtype=jnp.int32)
    blocks = GM.sorted_ffn_blocks(T, top_k, H, d, w_out.shape[1], x2d.dtype)
    if blocks is not None:
        # a prefill chunk's rows: every group starts on a tile boundary of
        # a padded row buffer, and both products, the gates and the sum
        # over a token's picks are one kernel
        GM.record_path("sorted_kernel")
        block_rows, block_f = blocks
        tile_expert, used, dest, src, row_gate = GM.sorted_tile_plan(
            loc, sizes, block_rows, gates)
        out = GM.sorted_gated_ffn(x2d, dest, src, row_gate, w_in, w_out,
                                  tile_expert, used, block_rows=block_rows,
                                  block_f=block_f)
    else:
        GM.record_path("ragged_dot")
        order = jnp.argsort(flat)                         # stable; H last
        xs = x2d[order // top_k]                          # [T*k, d]
        gu = jax.lax.ragged_dot(xs, w_in, sizes)
        g, u = jnp.split(gu, 2, axis=-1)
        ys = jax.lax.ragged_dot((jax.nn.silu(g) * u).astype(x2d.dtype),
                                w_out, sizes,
                                preferred_element_type=jnp.float32)
        # rows past the held picks are no expert's: ragged_dot leaves
        # them unspecified, so they are zeroed before the gates see them
        ys = jnp.where((flat[order] < H)[:, None], ys, 0.0)
        ys = ys[jnp.argsort(order)].reshape(T, top_k, d)  # un-sort
        out = jnp.einsum("tk,tkd->td", gates, ys)
    picks = T * top_k if row_valid is None \
        else jnp.sum(row_valid.astype(jnp.int32)) * top_k
    counts = jnp.stack([jnp.sum(sizes > 0), jnp.sum(sizes),
                        jnp.asarray(picks, jnp.int32)]).astype(jnp.int32)
    return out, counts


class GatedExpertLayer(Layer):
    """A router over ``num_experts`` and the experts this chip holds
    (``held``: their ids; default all), each ``W_out (silu(g) * u)`` with
    ``[g | u] = W_in h`` and no bias: the form Mixtral, Granite and the
    DeepSeek family serve.  ``rule`` states the router's gating
    (``router_gates``) and ``scaling`` its routed scaling factor.
    Inference only (``gated_experts_forward``):
    dropless, no capacity, no auxiliary loss; a prefill chunk's rows go
    through the repo's own ``sorted_gated_ffn`` kernel, which returns
    the layer's gated sum, and a decode step's through
    ``lax.ragged_dot``, by the rows alone.  ``MoELayer``
    above, with its capacity factor, its five dispatch modes and
    ``ExpertFFN``'s two biased matrices, is the training path and stays
    as it is.

    Told which experts it holds, the layer is what expert parallelism
    asks of a chip: it routes over all experts and computes its own
    experts' part of the result.  The exchange that would bring other
    chips' tokens here is not in this layer, and nothing stands in for
    it."""

    def __init__(self, d_model: int, d_hidden: int, num_experts: int,
                 top_k: int, held=None, dtype="float32",
                 rule: str = "softmax_topk", scaling: float = 1.0):
        super().__init__(dtype=dtype)
        import numpy as np
        from paddle_tpu.nn.common_layers import Linear
        if rule not in ROUTER_RULES:
            raise ValueError(f"router rule {rule!r}: one of {ROUTER_RULES}")
        self.rule, self.scaling = rule, float(scaling)
        self.top_k = int(top_k)
        self.num_experts = int(num_experts)
        self.held = tuple(range(num_experts)) if held is None \
            else tuple(int(e) for e in held)
        if len(set(self.held)) != len(self.held) or not all(
                0 <= e < num_experts for e in self.held):
            raise ValueError(f"held experts {self.held} are not distinct "
                             f"ids below {num_experts}")
        local = np.full((num_experts,), len(self.held), np.int32)
        local[list(self.held)] = np.arange(len(self.held))
        self._local_of = local
        self.router = Linear(d_model, num_experts, bias_attr=False)
        # the choice bias of ``sigmoid_bias``: a leaf of its own, added
        # to the scores in float32 whatever type it is stored in
        self.router_bias = self.create_parameter(
            [num_experts], is_bias=True) \
            if rule == "sigmoid_bias" else None
        self.w_in = self.create_parameter(
            [len(self.held), d_model, 2 * d_hidden])
        self.w_out = self.create_parameter(
            [len(self.held), d_hidden, d_model])

    def forward(self, x, row_valid=None):
        """x [..., d] -> (the held experts' part [..., d] float32,
        counts int32 [3]); ``row_valid`` [...] bool masks rows."""
        from paddle_tpu.core.dispatch import unwrap
        x = unwrap(x)
        x2d = x.reshape(-1, x.shape[-1])
        if row_valid is not None:
            row_valid = unwrap(row_valid).reshape(-1)
        out, counts = gated_experts_forward(
            x2d, unwrap(self.router.weight), unwrap(self.w_in),
            unwrap(self.w_out), top_k=self.top_k,
            local_of=self._local_of, row_valid=row_valid, rule=self.rule,
            router_bias=None if self.router_bias is None
            else unwrap(self.router_bias), scaling=self.scaling)
        return out.reshape(x.shape), counts


class NaiveGate(Layer):
    """Linear router, top-k, no noise (reference moe/gate/naive_gate.py)."""

    def __init__(self, d_model: int, num_experts: int, top_k: int = 2):
        super().__init__()
        self.num_experts = num_experts
        self.top_k = top_k
        self.gate = self.create_parameter([d_model, num_experts])

    def logits(self, x2d):
        from paddle_tpu.core.dispatch import unwrap
        return x2d @ unwrap(self.gate)

    def extra(self) -> dict:
        return {}


class SwitchGate(NaiveGate):
    """top-1 (Switch Transformer; reference moe/gate/switch_gate.py)."""

    def __init__(self, d_model, num_experts, jitter_eps: float = 0.01):
        super().__init__(d_model, num_experts, top_k=1)
        self.jitter_eps = jitter_eps


class GShardGate(NaiveGate):
    """top-2 with capacity (reference moe/gate/gshard_gate.py)."""

    def __init__(self, d_model, num_experts, capacity_factor: float = 1.25):
        super().__init__(d_model, num_experts, top_k=2)
        self.capacity_factor = capacity_factor


class ExpertFFN(Layer):
    """Stacked expert FFNs: [E, d, h] / [E, h, d] weights, E on the ep
    axis.  One einsum per projection keeps every expert's GEMM on the MXU
    and gives GSPMD the expert axis to all_to_all over."""

    def __init__(self, num_experts: int, d_model: int, d_hidden: int,
                 activation: Callable = None, ep_axis: str = "ep"):
        super().__init__()
        from jax.sharding import PartitionSpec as P
        self.num_experts = num_experts
        self.activation = activation or F.gelu
        self.w1 = self.create_parameter([num_experts, d_model, d_hidden])
        self.w2 = self.create_parameter([num_experts, d_hidden, d_model])
        self.b1 = self.create_parameter([num_experts, d_hidden],
                                        is_bias=True)
        self.b2 = self.create_parameter([num_experts, d_model],
                                        is_bias=True)
        self.w1.partition_spec = P(ep_axis, None, None)
        self.w2.partition_spec = P(ep_axis, None, None)
        self.b1.partition_spec = P(ep_axis, None)
        self.b2.partition_spec = P(ep_axis, None)

    def forward(self, expert_inputs, counts=None):
        """expert_inputs: [E, C, d] -> [E, C, d].  ``counts`` (optional
        [E] int32 valid-slot prefix per expert) lets the grouped Pallas
        kernel skip empty capacity blocks when PADDLE_TPU_GROUPED_MOE
        is on; it is ignored by the dense einsum path."""
        from paddle_tpu.core.dispatch import unwrap
        return _expert_ffn(unwrap(expert_inputs), unwrap(self.w1),
                           unwrap(self.b1), unwrap(self.w2), unwrap(self.b2),
                           lambda v: unwrap(self.activation(v)),
                           counts=counts)


def _grouped_moe_enabled() -> bool:
    """Trace-time check of the PADDLE_TPU_GROUPED_MOE knob (lazy import
    keeps distributed/ free of an eager ops.pallas dependency)."""
    from paddle_tpu.ops.pallas.grouped_matmul import grouped_moe_enabled
    return grouped_moe_enabled()


def _router_metrics():
    """Routing-observability instruments (ISSUE 18), lazily created on
    the process-wide registry so an import of distributed/ never pulls
    exporters in."""
    from paddle_tpu.observability import default_registry
    reg = default_registry()
    return {
        "dropped": reg.counter(
            "paddle_tpu_moe_dropped_tokens_total",
            "token-choice assignments dropped by the capacity bound"),
        "overflow": reg.counter(
            "paddle_tpu_moe_capacity_overflow_total",
            "routed forwards in which at least one assignment was "
            "dropped (capacity pressure events)"),
        "aux": reg.gauge(
            "paddle_tpu_moe_aux_loss",
            "GShard load-balance auxiliary loss of the last routed "
            "forward"),
        "load": reg.gauge(
            "paddle_tpu_moe_expert_load",
            "kept token-choice assignments per expert in the last "
            "routed forward", labelnames=("expert",)),
        "imbalance": reg.gauge(
            "paddle_tpu_moe_expert_imbalance",
            "max/mean per-expert load of the last routed forward "
            "(1.0 = perfectly balanced)"),
    }


def _record_router_metrics(aux, dropped_frac, total_assignments,
                           load=None):
    """Update the dropped-token / capacity-overflow counters, the
    aux-loss gauge and the per-expert load/imbalance gauges from one
    routed forward.  Concrete (eager) values only: under jit the stats
    are tracers and the traced program must stay identical to the
    uninstrumented one, so this silently skips (the trace-time
    ``paddle_tpu_grouped_moe_path_total`` counter still attributes the
    implementation path)."""
    try:
        import jax.core as _core
        vals = [aux, dropped_frac]
        if load is not None:
            vals.append(load)
        if any(isinstance(v, _core.Tracer) for v in vals):
            return
        m = _router_metrics()
        m["aux"].set(float(aux))
        df = float(dropped_frac)
        if df > 0:
            m["dropped"].inc(df * total_assignments)
            m["overflow"].inc()
        if load is not None:
            import numpy as _np
            arr = _np.asarray(load, dtype=float)
            for e, val in enumerate(arr):
                m["load"].labels(expert=e).set(float(val))
            mean = arr.mean()
            m["imbalance"].set(
                float(arr.max() / mean) if mean > 0 else 1.0)
    except Exception:  # pragma: no cover - telemetry must never break fwd
        pass


def _expert_ffn(x, w1, b1, w2, b2, act, counts=None):
    """Stacked-expert FFN compute shared by ExpertFFN.forward and the
    all_to_all dispatch path: [E, C, d] -> [E, C, d] (more generally
    [G, C, d] with G a multiple of the expert count — the a2a paths pass
    per-source-shard groups).  With PADDLE_TPU_GROUPED_MOE=1 this routes
    to the grouped Pallas kernel (ops/pallas/grouped_matmul.py), which
    skips capacity blocks past ``counts`` and zeroes their rows — a
    no-op for MoE outputs since those slots carry zero combine weight.
    Knob off, the dense einsum pair below traces byte-identically to
    what it always produced (regression-tested)."""
    from paddle_tpu.ops.pallas import grouped_matmul as _gm
    if _gm.grouped_moe_enabled() and _gm.grouped_ffn_eligible(
            x.shape[0], x.shape[1], x.shape[2], w1.shape[2], w1.shape[0]):
        _gm.record_path("grouped")
        return _gm.grouped_expert_ffn(x, w1, b1, w2, b2, counts=counts,
                                      act=act)
    h = jnp.einsum("ecd,edh->ech", x, w1) + b1[:, None, :]
    return jnp.einsum("ech,ehd->ecd", act(h), w2) + b2[:, None, :]


def _grouped_a2a_ffn(recv, send_counts, w1, b1, w2, b2, act, capacity,
                     ep_axis):
    """Grouped-kernel expert compute for the all_to_all bodies.

    ``recv [E_loc, n*C, d]`` holds n source-shard chunks per local
    expert; each chunk is an independently front-packed capacity buffer,
    so the per-chunk occupancy counts are exchanged alongside the tokens
    (the same all_to_all permutation, tiled over the expert axis) and
    the FFN runs over ``[E_loc*n, C, d]`` groups with ``g // n`` mapping
    groups to local expert weights — empty tail blocks of every chunk
    are skipped, not just the global tail."""
    e_loc, nc, d = recv.shape
    n = nc // capacity
    # [E] -> [n*E_loc] ordered (source shard, local expert); regroup to
    # (local expert, source shard) to match recv's chunk layout
    counts_recv = jax.lax.all_to_all(send_counts, ep_axis, split_axis=0,
                                     concat_axis=0, tiled=True)
    counts_g = counts_recv.reshape(n, e_loc).T.reshape(-1)
    grp = recv.reshape(e_loc * n, capacity, d)
    out = _expert_ffn(grp, w1, b1, w2, b2, act, counts=counts_g)
    return out.reshape(e_loc, nc, d)


def moe_shard_a2a(x2d, gate_w, w1, b1, w2, b2, *, top_k: int,
                  capacity: int, activation=None, ep_axis: str = "ep"):
    """Explicit all_to_all expert dispatch — runs INSIDE shard_map.

    Semantic parity with the reference's global_scatter/global_gather
    collectives (operators/collective/global_scatter_op.cu.cc): each ep
    shard routes its local tokens into per-expert capacity buffers, an
    all_to_all exchanges the expert axis for a source-shard axis, local
    experts run, and the inverse all_to_all returns results.

    Args:
      x2d: [T_loc, d] local tokens.
      gate_w: [d, E] replicated router weight (E = global expert count).
      w1/b1/w2/b2: LOCAL expert slices [E_loc, ...] (ep-sharded).
      capacity: per (source shard, expert) buffer slots.
    Returns:
      out: [T_loc, d]; aux: global mean load-balance loss;
      dropped_frac: fraction of (token, choice) assignments dropped by
      the capacity bound, pmean'd over ep (0.0 when capacity covers every
      local token, i.e. dropless).
    """
    act = activation or jax.nn.gelu
    logits = x2d @ gate_w                                     # [T_loc, E]
    combine, dispatch, aux = top_k_gating(logits, k=top_k, capacity=capacity)
    # honesty accounting: fraction of (token, choice) assignments dropped
    # by the capacity bound, pmean'd over ep (0.0 when dropless=True —
    # capacity == tokens-per-shard can never overflow since one token
    # dispatches to k DISTINCT experts)
    total = x2d.shape[0] * top_k
    dropped_frac = jax.lax.pmean(
        1.0 - dispatch.sum().astype(jnp.float32) / total, ep_axis)

    buf = jnp.einsum("tec,td->ecd", dispatch.astype(x2d.dtype), x2d)
    # [E, C, d] -> split experts to their shards, gather source chunks:
    # [E_loc, n_shards*C, d]
    recv = jax.lax.all_to_all(buf, ep_axis, split_axis=0, concat_axis=1,
                              tiled=True)
    if _grouped_moe_enabled():
        send_counts = dispatch.astype(jnp.int32).sum(axis=(0, 2))  # [E]
        out_loc = _grouped_a2a_ffn(recv, send_counts, w1, b1, w2, b2,
                                   act, capacity, ep_axis)
    else:
        out_loc = _expert_ffn(recv, w1, b1, w2, b2, act)
    # inverse exchange: [E_loc, n*C, d] -> [E, C, d]
    back = jax.lax.all_to_all(out_loc, ep_axis, split_axis=1, concat_axis=0,
                              tiled=True)
    out = jnp.einsum("tec,ecd->td", combine.astype(x2d.dtype), back)
    return out, jax.lax.pmean(aux, ep_axis), dropped_frac


def moe_shard_index_a2a(x2d, gate_w, w1, b1, w2, b2, *, top_k: int,
                        capacity: int, activation=None, ep_axis: str = "ep"):
    """Index-dispatch all_to_all expert exchange — runs INSIDE shard_map.

    The cross-rank ``global_scatter``/``global_gather`` analog (reference
    operators/collective/global_scatter_op.cu.cc) built the TPU way: the
    [E, C, d] send buffer is assembled with an O(T·k·d) scatter/gather
    (cumsum slots front-pack each expert bucket, exactly the send layout
    global_scatter produces) instead of the O(T·E·C·d) one-hot contraction
    of :func:`moe_shard_a2a`; the exchange itself stays the deterministic
    tiled all_to_all so shapes are static for XLA.  A true
    ``lax.ragged_all_to_all`` (variable counts, zero padding on the wire)
    is the natural next step but has no XLA:CPU lowering, which would
    leave the path untestable off-chip — capacity buckets bound the wire
    overhead at (capacity_factor - 1) instead.

    Same contract as :func:`moe_shard_a2a`: local x2d [T_loc, d],
    replicated gate_w [d, E], LOCAL expert slices [E_loc, ...]; returns
    (out [T_loc, d], aux, dropped_frac).
    """
    act = activation or jax.nn.gelu
    logits = x2d @ gate_w                                     # [T_loc, E]
    T = x2d.shape[0]
    E = gate_w.shape[-1]
    topi, slot, w, keep, aux = top_k_gating_indices(logits, k=top_k,
                                                    capacity=capacity)
    dropped_frac = jax.lax.pmean(
        1.0 - keep.astype(jnp.float32).mean(), ep_axis)
    safe_e = jnp.where(keep, topi, E)        # OOB row -> dropped by scatter
    tok_ids = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32)[:, None],
                               topi.shape)
    tok_for = jnp.zeros((E, capacity), jnp.int32).at[safe_e, slot].set(
        tok_ids, mode="drop")
    # pad slots point at token 0 — harmless garbage: the combine gather
    # below reads only (topi, slot) pairs, and dropped pairs carry w == 0,
    # so no `filled` mask multiply (saves one [E, C, d] HBM pass)
    buf = x2d[tok_for]                                        # [E, C, d]
    recv = jax.lax.all_to_all(buf, ep_axis, split_axis=0, concat_axis=1,
                              tiled=True)                     # [E_loc, n*C, d]
    if _grouped_moe_enabled():
        send_counts = jnp.zeros((E,), jnp.int32).at[safe_e.reshape(-1)].add(
            1, mode="drop")
        out_loc = _grouped_a2a_ffn(recv, send_counts, w1, b1, w2, b2,
                                   act, capacity, ep_axis)
    else:
        out_loc = _expert_ffn(recv, w1, b1, w2, b2, act)
    back = jax.lax.all_to_all(out_loc, ep_axis, split_axis=1, concat_axis=0,
                              tiled=True)                     # [E, C, d]
    picked = back[topi, jnp.clip(slot, 0, capacity - 1)]      # [T, k, d]
    out = jnp.einsum("tkd,tk->td", picked, w.astype(x2d.dtype))
    return out, jax.lax.pmean(aux, ep_axis), dropped_frac


def moe_forward_a2a(x, gate_w, w1, b1, w2, b2, *, mesh, top_k: int = 2,
                    capacity_factor: float = 1.25, dropless: bool = False,
                    activation=None, ep_axis: str = "ep",
                    with_stats: bool = False, dispatch: str = "einsum"):
    """Jit-callable wrapper: shard_maps :func:`moe_shard_a2a` over the ep
    axis of ``mesh``.

    x: [B, S, d] — flattened to [B*S, d] and sharded on the token axis
    (constraint: B*S divisible by the ep mesh size); expert weights
    [E, ...] sharded on ep (E divisible by ep size); gate replicated.
    ``with_stats=True`` additionally returns the dropped-assignment
    fraction (always 0.0 under dropless) so capacity pressure is never
    silent.  ``dispatch`` picks the shard body: "einsum" (one-hot
    contraction, :func:`moe_shard_a2a`) or "index" (O(T·k·d)
    scatter/gather build, :func:`moe_shard_index_a2a`)."""
    from jax.sharding import PartitionSpec as P

    from paddle_tpu.distributed.communication import shard_map

    if dispatch not in ("einsum", "index"):
        raise ValueError(f"unknown a2a dispatch {dispatch!r}")
    shape = x.shape
    d = shape[-1]
    x2d = x.reshape(-1, d)  # shard the flat token axis, not the batch axis
    n = mesh.shape[ep_axis]
    E = gate_w.shape[-1]
    T = x2d.shape[0]
    if T % n:
        raise ValueError(f"token count {T} not divisible by ep={n}")
    if E % n:
        raise ValueError(f"expert count {E} not divisible by ep={n}")
    t_loc = T // n
    if dropless:
        capacity = t_loc  # an expert can receive at most every local token
    else:
        capacity = max(1, int(capacity_factor * top_k * t_loc / E))

    body = moe_shard_a2a if dispatch == "einsum" else moe_shard_index_a2a

    def fn(xs, gw, a1, c1, a2, c2):
        return body(xs, gw, a1, c1, a2, c2, top_k=top_k,
                    capacity=capacity, activation=activation,
                    ep_axis=ep_axis)

    mapped = shard_map(
        fn, mesh=mesh,
        in_specs=(P(ep_axis), P(), P(ep_axis), P(ep_axis), P(ep_axis),
                  P(ep_axis)),
        out_specs=(P(ep_axis), P(), P()))
    out, aux, dropped = mapped(x2d, gate_w, w1, b1, w2, b2)
    if with_stats:
        return out.reshape(shape), aux, dropped
    return out.reshape(shape), aux


class MoELayer(Layer):
    """Mixture of experts (reference moe_layer.py:261).

    forward(x: [B, S, d]) -> [B, S, d]; the load-balance aux loss of the
    last call is at ``self.aux_loss`` (callers add it to the objective —
    same contract as the reference's gate.get_loss()).
    """

    def __init__(self, d_model: int, num_experts: int,
                 d_hidden: Optional[int] = None, gate: str = "gshard",
                 top_k: Optional[int] = None,
                 capacity_factor: float = 1.25,
                 experts: Optional[Layer] = None, ep_axis: str = "ep",
                 dispatch_mode: str = "einsum", dropless: bool = False,
                 mesh=None):
        super().__init__()
        if dispatch_mode not in ("einsum", "all_to_all", "index", "ragged",
                                 "all_to_all_index"):
            raise ValueError(f"unknown dispatch_mode {dispatch_mode}")
        if dispatch_mode in ("all_to_all", "all_to_all_index") \
                and mesh is None:
            raise ValueError(f"dispatch_mode={dispatch_mode!r} needs mesh=")
        self.d_model = d_model
        self.num_experts = num_experts
        self.capacity_factor = capacity_factor
        self.ep_axis = ep_axis
        self.dispatch_mode = dispatch_mode
        self.dropless = dropless
        self.mesh = mesh
        if gate == "gshard":
            self.gate = GShardGate(d_model, num_experts, capacity_factor)
        elif gate == "switch":
            self.gate = SwitchGate(d_model, num_experts)
        elif gate == "naive":
            self.gate = NaiveGate(d_model, num_experts,
                                  top_k=top_k or 2)
        else:
            raise ValueError(f"unknown gate {gate}")
        if top_k is not None:
            self.gate.top_k = top_k
        self.experts = experts or ExpertFFN(
            num_experts, d_model, d_hidden or 4 * d_model, ep_axis=ep_axis)
        self.aux_loss = None
        self.router_stats = None  # {"dropped_frac": ...} after forward

    def forward(self, x):
        """NOTE: the gating/dispatch math runs on raw traced values — the
        supported training path is through jit/functional_call (TrainStep),
        where gradients flow through the whole routed computation.  The
        eager tape does not differentiate through this layer."""
        from jax.sharding import PartitionSpec as P
        from paddle_tpu.core.dispatch import unwrap
        data = unwrap(x)
        B, S, d = data.shape
        T = B * S

        if self.dispatch_mode in ("all_to_all", "all_to_all_index"):
            if not isinstance(self.experts, ExpertFFN):
                raise ValueError("all_to_all dispatch requires the stacked "
                                 "ExpertFFN experts")
            out, aux, dropped = moe_forward_a2a(
                data, unwrap(self.gate.gate),
                unwrap(self.experts.w1), unwrap(self.experts.b1),
                unwrap(self.experts.w2), unwrap(self.experts.b2),
                mesh=self.mesh, top_k=self.gate.top_k,
                capacity_factor=self.capacity_factor,
                dropless=self.dropless, ep_axis=self.ep_axis,
                activation=lambda v: unwrap(self.experts.activation(v)),
                with_stats=True,
                dispatch=("index" if self.dispatch_mode == "all_to_all_index"
                          else "einsum"))
            self.aux_loss = aux
            self.router_stats = {"dropped_frac": dropped}
            _record_router_metrics(aux, dropped, T * self.gate.top_k)
            return self._wrap_out(x, out)

        E = self.num_experts
        x2d = data.reshape(T, d)
        # expected assignments are top_k*T/E under balanced routing, so
        # capacity must scale with k (reference GShardGate caps per expert
        # at ceil(cap_rate * tokens), similarly k-aware in effect);
        # dropless pins capacity at T so no token can ever be dropped —
        # exact but O(T^2·E) dispatch memory, toy/test scale only (the
        # all_to_all path bounds capacity at tokens-per-shard instead)
        if self.dropless:
            capacity = T
        else:
            capacity = max(1, int(self.capacity_factor * self.gate.top_k
                                  * T / E))
        logits = unwrap(self.gate.logits(x2d))
        from paddle_tpu.robustness import fault_fires
        if fault_fires("moe.expert_imbalance", experts=E):
            # hot-expert pathology drill: every token prefers expert 0 —
            # the imbalance gauge and aux loss must surface the skew
            logits = logits + jnp.where(jnp.arange(E) == 0, 10.0,
                                        0.0).astype(logits.dtype)
        if self.dispatch_mode == "ragged":
            # dropless sort + grouped-matmul dispatch: no capacity buffers,
            # FLOPs over exactly T*k rows; the single-program fast path
            if not isinstance(self.experts, ExpertFFN):
                raise ValueError("ragged dispatch requires the stacked "
                                 "ExpertFFN experts")
            out, aux, dropped = moe_forward_ragged(
                x2d, logits, unwrap(self.experts.w1),
                unwrap(self.experts.b1), unwrap(self.experts.w2),
                unwrap(self.experts.b2), E=E, top_k=self.gate.top_k,
                activation=lambda v: unwrap(self.experts.activation(v)))
            self.aux_loss = aux
            self.router_stats = {"dropped_frac": dropped}
            _record_router_metrics(aux, dropped, T * self.gate.top_k)
            return self._wrap_out(x, out.reshape(B, S, d))
        if self.dispatch_mode == "index":
            # gather/scatter dispatch: O(T·k·d) — the single-program fast
            # path (under ep sharding keep "einsum": GSPMD lowers that
            # contraction to the all_to_all; a cross-shard gather would
            # all-gather the tokens instead)
            if not isinstance(self.experts, ExpertFFN):
                raise ValueError("index dispatch requires the stacked "
                                 "ExpertFFN experts")

            def experts_fn(buf, counts=None):
                return _expert_ffn(
                    buf, unwrap(self.experts.w1), unwrap(self.experts.b1),
                    unwrap(self.experts.w2), unwrap(self.experts.b2),
                    lambda v: unwrap(self.experts.activation(v)),
                    counts=counts)

            out, aux, dropped = moe_forward_index(
                x2d, logits, experts_fn, E=E, top_k=self.gate.top_k,
                capacity=capacity)
            self.aux_loss = aux
            self.router_stats = {"dropped_frac": dropped}
            _record_router_metrics(aux, dropped, T * self.gate.top_k)
            return self._wrap_out(x, out.reshape(B, S, d))
        combine, dispatch, aux = top_k_gating(
            logits, k=self.gate.top_k, capacity=capacity)
        self.aux_loss = aux
        self.router_stats = {"dropped_frac": 1.0 - dispatch.sum().astype(
            jnp.float32) / (T * self.gate.top_k)}
        _record_router_metrics(aux, self.router_stats["dropped_frac"],
                               T * self.gate.top_k,
                               load=dispatch.sum(axis=(0, 2)))

        # dispatch: [T,E,C] x [T,d] -> [E,C,d]; GSPMD lowers the contraction
        # to the expert all_to_all when E is sharded on ep
        expert_in = jnp.einsum("tec,td->ecd",
                               dispatch.astype(data.dtype), x2d)
        expert_in = constrain(expert_in, P(self.ep_axis, None, None))
        if _grouped_moe_enabled() and isinstance(self.experts, ExpertFFN):
            # cumsum slot assignment front-packs each expert's bucket, so
            # the filled-slot count per expert is a valid-row prefix
            counts = dispatch.astype(jnp.int32).sum(axis=(0, 2))
            expert_out = unwrap(self.experts(expert_in, counts=counts))
        else:
            expert_out = unwrap(self.experts(expert_in))
        # combine: [T,E,C] x [E,C,d] -> [T,d]
        out = jnp.einsum("tec,ecd->td", combine.astype(data.dtype),
                         expert_out)
        return self._wrap_out(x, out.reshape(B, S, d))

    @staticmethod
    def _wrap_out(x, out):
        if hasattr(x, "_data"):
            from paddle_tpu.core.tensor import Tensor
            t = Tensor(out)
            t.stop_gradient = x.stop_gradient
            return t
        return out
