"""Collective communication API.

Reference parity: ``python/paddle/distributed/communication/*.py``
(all_reduce / all_gather / all_to_all / reduce_scatter / broadcast / scatter /
reduce / send / recv / barrier) backed there by NCCL ProcessGroups
(paddle/fluid/distributed/collective/process_group.h:53).

TPU-native design: collectives are **compiler-scheduled XLA ops over ICI**,
not runtime calls on a comm stream.  Each function here therefore has two
behaviours:

* **Traced inside ``shard_map``** (an axis name is in scope): lowers to the
  matching ``jax.lax`` collective (``psum``/``all_gather``/``all_to_all``/
  ``psum_scatter``/``ppermute``).  This is the hot path — SPMD code that the
  reference writes as explicit NCCL calls is written here as shard_map'd
  functions using these same names.
* **Eager, single-controller**: operates on the global view (an all_reduce of
  a fully-replicated array is the identity; with 1 process it is a no-op),
  matching how a single-controller runtime sees already-global arrays.

``wait``/``sync_op``/``use_calc_stream`` knobs from the reference are
accepted and ignored: XLA's dataflow ordering replaces stream/event
synchronisation.
"""

from __future__ import annotations

from typing import Any, List, Optional, Sequence

import jax
import jax.numpy as jnp
from jax import lax, shard_map

__all__ = [
    "ReduceOp", "all_reduce", "all_gather", "all_to_all", "reduce_scatter",
    "broadcast", "reduce", "scatter", "send", "recv", "barrier", "ppermute",
    "new_group", "get_group", "Group", "shift", "shard_map",
    "axis_size", "vma_of",
]

def vma_of(x):
    """The varying-manual-axes set of ``x`` under shard_map tracing."""
    return jax.typeof(x).vma


def pvary(x, axis_name: str):
    """Mark `x` as device-varying over `axis_name` — needed for scan carries
    inside shard_map whose value becomes varying (e.g. after a ppermute)."""
    if axis_name in vma_of(x):
        return x  # already varying over this axis
    return lax.pcast(x, (axis_name,), to="varying")


def pvary_like(x, ref):
    """Vary `x` over every manual axis `ref` is varying over — the right
    seed for a scan accumulator that will be combined with `ref` inside a
    shard_map spanning MULTIPLE mesh axes (e.g. ring attention on an
    (sp, tp) mesh: the kv blocks vary over both axes, so the running
    o/m/l must too, or the scan carry types diverge)."""
    have = vma_of(x)
    missing = tuple(a for a in vma_of(ref) if a not in have)
    if not missing:
        return x
    return lax.pcast(x, missing, to="varying")


class ReduceOp:
    SUM = "sum"
    MAX = "max"
    MIN = "min"
    PROD = "prod"
    AVG = "avg"


def _unwrap(x):
    return x._data if hasattr(x, "_data") else x


def _wrap_like(x, ref):
    if hasattr(ref, "_data"):
        from paddle_tpu.core.tensor import Tensor
        return Tensor(x)
    return x


def axis_size(axis_name) -> int:
    """Static size of a bound mesh axis (raises NameError when the axis
    is unbound)."""
    return lax.axis_size(axis_name)


def _in_trace(axis_name) -> bool:
    """True when `axis_name` is bound by an enclosing shard_map/pmap."""
    if axis_name is None:
        return False
    try:
        axis_size(axis_name)
        return True
    except (NameError, KeyError, ValueError):
        return False


# -- collectives -------------------------------------------------------------

def all_reduce(tensor, op: str = ReduceOp.SUM, group=None,
               sync_op: bool = True, axis_name: Optional[str] = None):
    """SUM/MAX/MIN/PROD across an axis.  Inside shard_map → lax.psum/pmax/…;
    eager single-process → identity (global arrays are already reduced)."""
    axis_name = axis_name or (group.axis_name if group else None)
    x = _unwrap(tensor)
    if _in_trace(axis_name):
        fn = {ReduceOp.SUM: lax.psum, ReduceOp.MAX: lax.pmax,
              ReduceOp.MIN: lax.pmin,
              ReduceOp.AVG: lambda v, a: lax.pmean(v, a)}.get(op)
        if fn is None and op == ReduceOp.PROD:
            fn = lambda v, a: jnp.exp(lax.psum(jnp.log(v), a))
        out = fn(x, axis_name)
        return _wrap_like(out, tensor)
    return tensor


def all_gather(tensor_or_list, tensor=None, group=None, sync_op=True,
               axis_name: Optional[str] = None, axis: int = 0,
               tiled: bool = True):
    """Gather shards along `axis`.  Paddle's list-out signature
    (``all_gather(out_list, tensor)``) and the functional form
    (``y = all_gather(x)``) are both supported."""
    out_list = None
    if tensor is None:
        tensor = tensor_or_list
    else:
        out_list = tensor_or_list
    axis_name = axis_name or (group.axis_name if group else None)
    x = _unwrap(tensor)
    if _in_trace(axis_name):
        out = lax.all_gather(x, axis_name, axis=axis, tiled=tiled)
    else:
        out = x
    if out_list is not None:
        n = axis_size(axis_name) if _in_trace(axis_name) else 1
        for piece in jnp.split(out, n, axis=axis):
            out_list.append(_wrap_like(piece, tensor))
        return None
    return _wrap_like(out, tensor)


def all_to_all(out_or_in, tensor=None, group=None, sync_op=True,
               axis_name: Optional[str] = None,
               split_axis: int = 0, concat_axis: int = 0):
    """MoE-style all-to-all (reference: global_scatter/global_gather ops,
    paddle/fluid/operators/collective/global_scatter_op.cu.cc).  Inside
    shard_map → lax.all_to_all on the expert axis."""
    if tensor is not None:
        out_or_in = tensor  # ignore the out-list form's first arg
    axis_name = axis_name or (group.axis_name if group else None)
    x = _unwrap(out_or_in)
    if _in_trace(axis_name):
        out = lax.all_to_all(x, axis_name, split_axis=split_axis,
                             concat_axis=concat_axis, tiled=True)
        return _wrap_like(out, out_or_in)
    return out_or_in


def reduce_scatter(tensor, op: str = ReduceOp.SUM, group=None, sync_op=True,
                   axis_name: Optional[str] = None, scatter_dimension=0):
    """ZeRO-2 grad primitive (reference: GroupShardedStage2's on-the-fly
    reduce-scatter, fleet/meta_parallel/sharding/group_sharded_stage2.py:46).
    Inside shard_map → lax.psum_scatter."""
    axis_name = axis_name or (group.axis_name if group else None)
    x = _unwrap(tensor)
    if _in_trace(axis_name):
        out = lax.psum_scatter(x, axis_name,
                               scatter_dimension=scatter_dimension,
                               tiled=True)
        return _wrap_like(out, tensor)
    return tensor


def broadcast(tensor, src: int = 0, group=None, sync_op=True,
              axis_name: Optional[str] = None):
    """Select rank `src`'s value on every rank of the axis."""
    axis_name = axis_name or (group.axis_name if group else None)
    x = _unwrap(tensor)
    if _in_trace(axis_name):
        # mask-to-src then psum: the SPMD spelling of a one-to-all
        # (ppermute needs unique sources, so it can't express broadcast)
        idx = lax.axis_index(axis_name)
        masked = jnp.where(idx == src, x, jnp.zeros_like(x))
        out = lax.psum(masked, axis_name)
        return _wrap_like(out, tensor)
    return tensor


def reduce(tensor, dst: int = 0, op: str = ReduceOp.SUM, group=None,
           sync_op=True, axis_name: Optional[str] = None):
    """psum then mask: only `dst` keeps the reduced value (others keep
    their input, matching NCCL reduce semantics loosely)."""
    axis_name = axis_name or (group.axis_name if group else None)
    x = _unwrap(tensor)
    if _in_trace(axis_name):
        summed = _unwrap(all_reduce(x, op=op, axis_name=axis_name))
        idx = lax.axis_index(axis_name)
        out = jnp.where(idx == dst, summed, x)
        return _wrap_like(out, tensor)
    return tensor


def scatter(tensor, tensor_list=None, src: int = 0, group=None, sync_op=True,
            axis_name: Optional[str] = None, axis: int = 0):
    """Each rank takes its slice of src's concatenated input."""
    axis_name = axis_name or (group.axis_name if group else None)
    x = _unwrap(tensor)
    if _in_trace(axis_name):
        n = axis_size(axis_name)
        idx = lax.axis_index(axis_name)
        full = lax.psum(jnp.where(idx == src, x, jnp.zeros_like(x)),
                        axis_name)
        piece = full.shape[axis] // n
        out = lax.dynamic_slice_in_dim(full, idx * piece, piece, axis=axis)
        return _wrap_like(out, tensor)
    return tensor


def ppermute(tensor, perm: Sequence, axis_name: Optional[str] = None,
             group=None):
    """Raw collective-permute — the ICI point-to-point primitive that
    replaces the reference's p2p send/recv
    (fleet/meta_parallel/pp_utils/p2p_communication.py)."""
    axis_name = axis_name or (group.axis_name if group else None)
    x = _unwrap(tensor)
    out = lax.ppermute(x, axis_name, list(perm))
    return _wrap_like(out, tensor)


def shift(tensor, offset: int = 1, axis_name: Optional[str] = None,
          group=None):
    """Rotate values around the axis ring by `offset` (ring-attention /
    pipeline microbatch rotation primitive)."""
    axis_name = axis_name or (group.axis_name if group else None)
    n = axis_size(axis_name)
    perm = [(i, (i + offset) % n) for i in range(n)]
    return ppermute(tensor, perm, axis_name=axis_name)


def send(tensor, dst: int, group=None, sync_op=True,
         axis_name: Optional[str] = None):
    """Point-to-point send: under SPMD this is half of a ppermute; the
    matching recv must use the same (src,dst) pair.  Provided for API parity
    — prefer `ppermute`/`shift` which express both halves at once."""
    raise NotImplementedError(
        "SPMD send/recv must be expressed as a paired ppermute: use "
        "paddle_tpu.distributed.ppermute(x, [(src, dst)], axis_name=...) "
        "which is the XLA collective-permute both ends compile into.")


def recv(tensor, src: int, group=None, sync_op=True,
         axis_name: Optional[str] = None):
    raise NotImplementedError(
        "see paddle_tpu.distributed.send — use ppermute([(src, dst)]).")


def barrier(group=None):
    """Block the host until all queued device work is complete.  XLA's gang
    schedule makes a device-side barrier implicit; the host-side analog is
    draining the dispatch queue."""
    (jnp.zeros(()) + 0).block_until_ready()


# -- groups ------------------------------------------------------------------

class Group:
    """Named communication group = a mesh axis (reference: runtime NCCL
    group, python/paddle/distributed/communication/group.py)."""

    def __init__(self, ranks: List[int], gid: int,
                 axis_name: Optional[str] = None):
        self.ranks = list(ranks)
        self.id = gid
        self.axis_name = axis_name or f"group{gid}"

    @property
    def nranks(self) -> int:
        return len(self.ranks)

    @property
    def world_size(self) -> int:
        return len(self.ranks)

    def get_group_rank(self, rank: int) -> int:
        return self.ranks.index(rank) if rank in self.ranks else -1

    def __repr__(self):
        return f"Group(id={self.id}, axis={self.axis_name}, " \
               f"ranks={self.ranks})"


_GROUPS: dict = {}
_NEXT_GID = [1]


def new_group(ranks: Optional[List[int]] = None, backend: str = "xla",
              axis_name: Optional[str] = None) -> Group:
    """Create a group handle.  Reference parity:
    ``paddle.distributed.new_group`` (distributed/collective.py:175).  On TPU
    a 'group' is a name used in shard_map collectives, not a runtime object;
    creating one is free and requires no rendezvous."""
    import jax
    if ranks is None:
        ranks = list(range(jax.device_count()))
    gid = _NEXT_GID[0]
    _NEXT_GID[0] += 1
    g = Group(ranks, gid, axis_name)
    _GROUPS[gid] = g
    return g


def get_group(gid: int) -> Optional[Group]:
    return _GROUPS.get(gid)
