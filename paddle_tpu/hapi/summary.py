"""paddle.summary / paddle.flops parity.

Reference: python/paddle/hapi/model_summary.py (summary :?) and
python/paddle/hapi/dynamic_flops.py (flops).  TPU-native twist: FLOPs
come from XLA's own cost analysis of the jitted forward — exact for the
compiled graph rather than per-layer-type lookup tables.
"""

from __future__ import annotations

from typing import Optional

import numpy as np

__all__ = ["summary", "flops"]


def _layer_of(net):
    from paddle_tpu.nn.layer import Layer
    if not isinstance(net, Layer):
        raise TypeError(f"summary/flops expects a Layer, got {type(net)}")
    return net


def summary(net, input_size=None, dtypes=None, input=None):
    """Per-layer parameter table + totals (reference hapi.summary).

    When input_size (or an example input) is given the forward runs once
    and the output shape is reported.  Returns {'total_params': int,
    'trainable_params': int, ['output_shape': tuple]}.
    """
    net = _layer_of(net)
    out_shape = None
    if input is not None or input_size is not None:
        import jax.numpy as jnp
        from paddle_tpu.core.dispatch import unwrap, wrap_like
        if input is None:
            from paddle_tpu.core.dtypes import to_jax
            dt = to_jax(dtypes) if isinstance(dtypes, str) else jnp.float32
            input = wrap_like(jnp.zeros(tuple(input_size), dt))
        probe = net(input)
        first = probe[0] if isinstance(probe, (tuple, list)) else probe
        out_shape = tuple(unwrap(first).shape)
    total = 0
    trainable = 0
    rows = []
    for name, p in net.named_parameters():
        n = int(np.prod(p.shape)) if len(p.shape) else 1
        total += n
        if not p.stop_gradient:
            trainable += n
        rows.append((name, tuple(p.shape), n))

    width = max((len(r[0]) for r in rows), default=20) + 2
    lines = [f"{'Layer (parameter)':{width}s} {'Shape':22s} {'Param #':>12s}",
             "-" * (width + 36)]
    for name, shape, n in rows:
        lines.append(f"{name:{width}s} {str(shape):22s} {n:>12,d}")
    lines.append("-" * (width + 36))
    lines.append(f"Total params: {total:,d}")
    lines.append(f"Trainable params: {trainable:,d}")
    lines.append(f"Non-trainable params: {total - trainable:,d}")
    if out_shape is not None:
        lines.append(f"Output shape: {out_shape}")
    print("\n".join(lines))
    info = {"total_params": total, "trainable_params": trainable}
    if out_shape is not None:
        info["output_shape"] = out_shape
    return info


def flops(net, input_size, custom_ops=None, print_detail: bool = False):
    """Forward-pass FLOPs via XLA cost analysis of the compiled graph
    (reference dynamic_flops.py walks layers with per-type formulas; the
    compiler's own count is exact for the program actually executed).

    input_size: shape of ONE input tensor, e.g. [1, 3, 224, 224].
    """
    import jax
    import jax.numpy as jnp
    from paddle_tpu.core.functional import functional_call, params_of

    net = _layer_of(net)
    params = params_of(net)

    def fwd(params, x):
        out = functional_call(net, params, x)
        return jax.tree.map(
            lambda t: t._data if hasattr(t, "_data") else t, out,
            is_leaf=lambda t: hasattr(t, "_data"))

    dtype = next(iter(params.values())).dtype if params else jnp.float32
    x = jnp.zeros(tuple(input_size), dtype)
    lowered = jax.jit(fwd).lower(params, x)
    cost = lowered.compile().cost_analysis()
    n = int(cost.get("flops", 0.0)) if cost else 0
    if print_detail:
        total_p = sum(int(np.prod(a.shape)) for a in params.values())
        print(f"FLOPs: {n:,d}  (params: {total_p:,d}, "
              f"input: {tuple(input_size)})")
    return n
