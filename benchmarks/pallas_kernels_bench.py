"""Pallas kernel benchmarks vs the XLA-compiled baselines, on real TPU.

VERDICT r2 item 5 'done' criterion: kernel-level speedup numbers in
benchmarks/.  Measures, at Llama-8B-proxy shapes:

* flash attention fwd+bwd — Pallas kernels (fwd + the dq and dk/dv
  backward kernels) vs XLA's fusion of the dense softmax attention;
* fused residual+RMSNorm — one Pallas pass vs the XLA elementwise chain.

Run ON THE CHIP: python benchmarks/pallas_kernels_bench.py
(prints one JSON line; falls back to interpret off-TPU, which is only a
correctness smoke, not a measurement).
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))


def _timeit(step_scalar, *args, iters=20):
    """step_scalar(carry, *args) -> scalar.  The timing loop runs INSIDE
    one jitted fori_loop (a data-dependent carry defeats hoisting), so a
    single dispatch amortizes per-call host latency; np.asarray forces
    completion."""
    import jax
    from jax import lax

    @jax.jit
    def run(*a):
        def body(i, carry):
            return carry + step_scalar(carry, *a)
        return lax.fori_loop(0, iters, body, 0.0)

    np.asarray(run(*args))                        # compile + warm
    t0 = time.perf_counter()
    out = run(*args)
    np.asarray(out)
    return (time.perf_counter() - t0) / iters


def bench_flash(b=4, s=2048, h=16, hk=8, d=128, dtype="bfloat16"):
    import jax
    import jax.numpy as jnp

    from paddle_tpu.nn.functional.attention import _sdpa_reference
    from paddle_tpu.ops.pallas.flash_attention import flash_attention

    on_tpu = jax.default_backend() == "tpu"
    rng = np.random.default_rng(0)
    dt = jnp.dtype(dtype)
    q = jnp.asarray(rng.standard_normal((b, s, h, d)), dt)
    k = jnp.asarray(rng.standard_normal((b, s, hk, d)), dt)
    v = jnp.asarray(rng.standard_normal((b, s, hk, d)), dt)

    def train(attn):
        def loss(args):
            o = attn(*args)
            return jnp.mean(o.astype(jnp.float32) ** 2)

        def scalar_step(carry, q, k, v):
            # carry-dependent perturbation: keeps each loop iteration live
            q = q * (1 + carry * 1e-12).astype(q.dtype)
            g = jax.grad(loss)((q, k, v))
            return sum(jnp.sum(jnp.abs(x).astype(jnp.float32))
                       for x in g)
        return scalar_step

    # pinned forward blocks: the comparison must not measure whatever
    # the autotuner happens to select
    pallas = train(lambda q, k, v: flash_attention(
        q, k, v, causal=True, interpret=not on_tpu,
        block_q=128, block_k=128))
    xla = train(lambda q, k, v: _sdpa_reference(
        q, jnp.repeat(k, h // hk, 2), jnp.repeat(v, h // hk, 2),
        is_causal=True))

    t_pallas = _timeit(pallas, q, k, v)
    t_xla = _timeit(xla, q, k, v)
    return {"shape": f"b{b} s{s} h{h}/{hk} d{d} {dtype}",
            "pallas_ms": round(t_pallas * 1e3, 3),
            "xla_dense_ms": round(t_xla * 1e3, 3),
            "speedup_vs_xla": round(t_xla / t_pallas, 2)}


def bench_rmsnorm(rows=8192, d=4096, dtype="bfloat16"):
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops.pallas.rmsnorm import fused_rmsnorm

    on_tpu = jax.default_backend() == "tpu"
    rng = np.random.default_rng(0)
    dt = jnp.dtype(dtype)
    x = jnp.asarray(rng.standard_normal((rows, d)), dt)
    r = jnp.asarray(rng.standard_normal((rows, d)), dt)
    w = jnp.asarray(rng.standard_normal((d,)), jnp.float32)

    def fused(carry, x, w, r):
        x = x * (1 + carry * 1e-12).astype(x.dtype)
        y, h = fused_rmsnorm(x, w, residual=r, interpret=not on_tpu)
        return jnp.sum(jnp.abs(y).astype(jnp.float32)) + \
            jnp.sum(jnp.abs(h).astype(jnp.float32))

    def xla(carry, x, w, r):
        x = x * (1 + carry * 1e-12).astype(x.dtype)
        hf = x.astype(jnp.float32) + r.astype(jnp.float32)
        inv = jax.lax.rsqrt(jnp.mean(hf * hf, -1, keepdims=True) + 1e-5)
        y, h = (hf * inv * w).astype(x.dtype), hf.astype(x.dtype)
        return jnp.sum(jnp.abs(y).astype(jnp.float32)) + \
            jnp.sum(jnp.abs(h).astype(jnp.float32))

    t_f = _timeit(fused, x, w, r)
    t_x = _timeit(xla, x, w, r)
    return {"shape": f"{rows}x{d} {dtype}",
            "fused_ms": round(t_f * 1e3, 3),
            "xla_ms": round(t_x * 1e3, 3),
            "speedup": round(t_x / t_f, 2)}


def main():
    import jax

    backend = jax.default_backend()
    out = {"backend": backend,
           "flash": bench_flash(),
           "rmsnorm": bench_rmsnorm()}
    print(json.dumps(out))


if __name__ == "__main__":
    main()
