"""MoE step decomposition on the chip: where does the time go?

Times, at the moe_train_bench shapes (T=8192 tokens, d=1024, E=16, k=2,
bf16, fwd+bwd), each piece of the MoE sublayer in isolation:
  1. expert FFN GEMMs alone on pre-built [E, C, d] buffers  (MXU floor)
  2. gating bookkeeping alone (logits -> indices/slots/weights)
  3. full routed block, per dispatch mode
  4. the dense shared-expert MLP at the same token count (reference point:
     what a no-routing FFN of the same activated width costs)

Timing discipline: every iteration's input depends on the previous
iteration's scalar output — the chain forces sequential device execution;
one block at the end.
Prints one JSON line.
"""

from __future__ import annotations

import json
import time


def chain_time(step_fn, x0, *rest, iters=20, warmup=2):
    """step_fn(x, *rest) -> scalar; iteration i's input is
    x0 + 1e-20 * scalar_{i-1}, forcing sequential execution."""
    import jax
    import jax.numpy as jnp
    s = jnp.zeros((), jnp.float32)
    for _ in range(warmup):
        s = step_fn(x0 + s.astype(x0.dtype) * 1e-20, *rest)
    jax.block_until_ready(s)
    t0 = time.perf_counter()
    for _ in range(iters):
        s = step_fn(x0 + s.astype(x0.dtype) * 1e-20, *rest)
    jax.block_until_ready(s)
    return (time.perf_counter() - t0) / iters


def main():
    import jax
    import jax.numpy as jnp
    import numpy as np
    from paddle_tpu.distributed.moe import (
        _expert_ffn, moe_forward_index, moe_forward_ragged,
        top_k_gating_indices)

    T, d, h, E, k = 8192, 1024, 1024, 16, 2
    cf = 1.25
    C = int(cf * k * T / E)          # 1280
    dt = jnp.bfloat16
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(T, d)), dt)
    gw = jnp.asarray(rng.normal(size=(d, E)) * 0.02, dt)
    w1 = jnp.asarray(rng.normal(size=(E, d, h)) * 0.02, dt)
    b1 = jnp.zeros((E, h), dt)
    w2 = jnp.asarray(rng.normal(size=(E, h, d)) * 0.02, dt)
    b2 = jnp.zeros((E, d), dt)
    buf = jnp.asarray(rng.normal(size=(E, C, d)), dt)

    def as_step(loss_fn, argnums):
        """fwd+bwd scalar step: loss + tiny*sum(grads) keeps the backward
        pass alive in the dependency chain."""
        vg = jax.value_and_grad(loss_fn, argnums=argnums)

        @jax.jit
        def step(x, *rest):
            v, gs = vg(x, *rest)
            return v + sum(g.astype(jnp.float32).sum() for g in gs) * 1e-12

        return step

    out = {}

    # 1. expert GEMMs alone
    def ffn_loss(buf, w1, b1, w2, b2):
        return _expert_ffn(buf, w1, b1, w2, b2,
                           jax.nn.gelu).astype(jnp.float32).sum()

    t = chain_time(as_step(ffn_loss, (1, 3)), buf, w1, b1, w2, b2)
    out["ffn_only_ms"] = t * 1e3
    ffn_flops = 3 * (2 * E * C * d * h * 2)   # fwd + 2x bwd, two GEMMs
    out["ffn_only_tflops"] = ffn_flops / t / 1e12

    # 2. gating bookkeeping alone
    def gate_loss(x, gw):
        topi, slot, w, keep, aux = top_k_gating_indices(
            (x @ gw).astype(jnp.float32), k=k, capacity=C)
        return w.sum() + aux

    out["gating_ms"] = chain_time(as_step(gate_loss, (1,)), x, gw) * 1e3

    # 3. full routed block per mode
    def block_index(x, gw, w1, b1, w2, b2):
        logits = (x @ gw).astype(jnp.float32)
        o, aux, _ = moe_forward_index(
            x, logits, lambda b: _expert_ffn(b, w1, b1, w2, b2, jax.nn.gelu),
            E=E, top_k=k, capacity=C)
        return o.astype(jnp.float32).sum() + aux

    def block_ragged(x, gw, w1, b1, w2, b2):
        logits = (x @ gw).astype(jnp.float32)
        o, aux, _ = moe_forward_ragged(x, logits, w1, b1, w2, b2,
                                       E=E, top_k=k)
        return o.astype(jnp.float32).sum() + aux

    for name, fn in [("index", block_index), ("ragged", block_ragged)]:
        out[f"block_{name}_ms"] = chain_time(
            as_step(fn, (1, 2, 4)), x, gw, w1, b1, w2, b2) * 1e3

    # 4. dense MLP reference at same activated width (k experts' worth)
    wd1 = jnp.asarray(rng.normal(size=(d, k * h)) * 0.02, dt)
    wd2 = jnp.asarray(rng.normal(size=(k * h, d)) * 0.02, dt)

    def dense_loss(x, wd1, wd2):
        return (jax.nn.gelu(x @ wd1) @ wd2).astype(jnp.float32).sum()

    out["dense_same_width_ms"] = chain_time(
        as_step(dense_loss, (1, 2)), x, wd1, wd2) * 1e3

    out["shapes"] = {"T": T, "d": d, "h": h, "E": E, "k": k, "C": C}
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
