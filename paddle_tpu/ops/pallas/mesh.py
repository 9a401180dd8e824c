"""How a Pallas kernel meets a mesh — the one place that decides.

XLA cannot partition a Mosaic kernel: a ``pallas_call`` inside a jitted
step whose operands carry a ``NamedSharding`` fails to compile
("Mosaic kernels cannot be automatically partitioned").  A sharded step
(``TrainStep(mesh=...)``) therefore declares its mesh for the duration
of its trace with :func:`step_mesh`, and every kernel gate asks
:func:`current`:

* **flash attention** needs no communication when the batch and the
  heads are what is sharded, and the dense path does not fit at training
  shapes — its call is wrapped in ``shard_map`` over the step's mesh
  (:func:`over_batch_and_heads`);
* **every other kernel** (fused rmsnorm+QKV, fused MLP, fused CE) takes
  weights that the step shards: the gate routes it to the XLA path,
  which GSPMD partitions, and says so in
  ``paddle_tpu_kernel_mesh_route_total``.

Outside a sharded step :func:`current` is None and nothing changes.
"""

from __future__ import annotations

import contextlib
import math

import jax

__all__ = ["step_mesh", "current", "record_route", "shard_map_kernel",
           "over_batch_and_heads"]

_current = None   # (mesh, batch_axes) while a sharded step is traced


@contextlib.contextmanager
def step_mesh(mesh, batch_axes=()):
    """Declare ``mesh`` (batch sharded over ``batch_axes``) as the mesh
    of the step being traced.  A missing or one-device mesh declares
    nothing."""
    global _current
    prev = _current
    if mesh is not None and mesh.size > 1:
        _current = (mesh, tuple(batch_axes))
    try:
        yield
    finally:
        _current = prev


def current():
    """``(mesh, batch_axes)`` of the sharded step being traced, or None."""
    return _current


def record_route(kernel: str, route: str):
    """Trace-time telemetry: how ``kernel`` met the step's mesh
    (``shard_map`` | ``xla``)."""
    from paddle_tpu.observability import default_registry
    default_registry().counter(
        "paddle_tpu_kernel_mesh_route_total",
        "how a Pallas kernel met a sharded step's mesh, at trace time",
        labelnames=("kernel", "route")).labels(
            kernel=kernel, route=route).inc()


def shard_map_kernel(fn, mesh, in_specs, out_specs):
    """``shard_map`` for a body that calls a Pallas kernel.  Compiled for
    the chip the kernel declares its outputs' varying axes and the
    checker stays on; off the chip the kernel runs in the Pallas
    interpreter, which cannot be traced under the checker."""
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs,
                         check_vma=jax.default_backend() == "tpu")


def over_batch_and_heads(attn, q, k, v):
    """Run ``attn(q, k, v)`` (``[batch, seq, heads, head_dim]`` operands,
    no cross-row or cross-head communication) per shard of the step's
    mesh: batch over the step's batch axes, heads over every other axis
    of the mesh — where those axes divide both head counts; otherwise
    the heads stay whole and the call is replicated over them."""
    from jax.sharding import PartitionSpec as P
    mesh, batch_axes = _current
    head_axes = tuple(a for a in mesh.axis_names
                      if a not in batch_axes and mesh.shape[a] > 1)
    n = math.prod(mesh.shape[a] for a in head_axes)
    if q.shape[2] % n or k.shape[2] % n:
        head_axes = ()
    spec = P(batch_axes or None, None, head_axes or None, None)
    record_route("flash", "shard_map")
    return shard_map_kernel(attn, mesh, (spec, spec, spec), spec)(q, k, v)
