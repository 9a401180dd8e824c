"""The fused MLP and the fused rmsnorm+QKV alone, by token block, on the chip.

    chiprun -- python benchmarks/fused_block_bench.py

The two per-segment kernels of ``ops/pallas/fused_block.py`` at the rows
the benchmark's cells hand them (mistral-7b's prefill chunk of 256 and
decode step of 32, sarvam's dense layer at a chunk of 512, internlm2's
train step of 16,384), forward only, at the blocks the rule chooses, at
the 64-row blocks chosen before PR 42 and at their neighbours: ms a call,
the passes over the weights, the bytes a second those passes are and the
operations a second (``limit_mib``: the VMEM scope the call asks the
compiler for, null where it asks for nothing).  A program
is ten calls in a row, each fed the one before (a single call of the
smaller shapes is shorter than the host's dispatch); host clock around
five dispatches and one ``block_until_ready``; a line of JSON a variant, all of them again in ``chiprun_out/fused_block.json``, and last
what ``paddle_tpu_fused_block_weight_passes_total`` counted for the
rule's own choices.  Refuses to run without a TPU (``--rehearse`` walks
the same code at a toy width in interpret mode, writes nothing and exits
3).
"""

from __future__ import annotations

import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

from paddle_tpu.ops.pallas import fused_block as FB

BF16 = jnp.bfloat16
ITERS, REPS = 5, 10
# (rows, d, f) and the blocks beside the rule's own
MLP = [((256, 4096, 14336), [(64, 128), (128, 128), (256, 256), (256, 512)]),
       ((512, 4096, 16384), [(64, 128), (256, 128), (512, 256)]),
       ((32, 4096, 14336), []),
       ((16384, 2048, 8192), [(128, 256), (256, 128), (512, 256),
                              (512, 128)])]
# (rows, d, dq, dk, dv)
QKV = [((256, 4096, 4096, 1024, 1024), [(64, 128), (256, 256)]),
       ((512, 4096, 4096, 1024, 1024), [(64, 128), (256, 128)]),
       ((32, 4096, 4096, 1024, 1024), []),
       ((16384, 2048, 2048, 1024, 1024), [(128, 256), (256, 128),
                                          (512, 256)])]


def timed(fn, *args):
    """ms a call of the kernel: ITERS dispatches of REPS calls in a row and
    one wait; median and least of five such rounds, after the program
    compiled and ran three times."""
    for _ in range(3):
        jax.block_until_ready(fn(*args))
    ms = []
    for _ in range(5):
        t0 = time.perf_counter()
        for _ in range(ITERS):
            got = fn(*args)
        jax.block_until_ready(got)
        ms.append((time.perf_counter() - t0) * 1e3 / (ITERS * REPS))
    return statistics.median(ms), min(ms)


def chained(call, blocks):
    """REPS calls, each on the rows of the one before plus a millionth of
    its first output (as wide as the rows at every shape here)."""
    def fn(x, *weights):
        def body(_, rows):
            got = jax.tree.leaves(call(rows, *weights, blocks))[0]
            return rows + (got * 1e-6).astype(rows.dtype)
        return jax.lax.fori_loop(0, REPS, body, x)
    return jax.jit(fn)


def normal(key, *shape):
    return (0.02 * jax.random.normal(key, shape)).astype(BF16)


def main():
    global ITERS, REPS
    dev = jax.devices()[0]
    rehearse = "--rehearse" in sys.argv   # the control flow, at a toy size
    interpret = rehearse
    mlp, qkv = MLP, QKV
    if rehearse:
        ITERS = REPS = 1
        mlp = [((64, 256, 512), [(32, 128)])]
        qkv = [((64, 256, 256, 128, 128), [(32, 128)])]
    elif dev.platform != "tpu":
        print("fused_block_bench: needs a TPU", file=sys.stderr)
        return 2
    key = jax.random.split(jax.random.PRNGKey(42), 8)
    out = []

    def run(kernel, shape, blocks, args, call, flops, weight_bytes,
            ruled=False):
        limit = FB._vmem_limit("mlp" if kernel == "mlp" else "qkv",
                               *blocks, shape[1], 2)
        line = dict(kernel=kernel, shape=shape, blocks=blocks,
                    passes=shape[0] // blocks[0], rule=ruled,
                    limit_mib=limit and limit >> 20,
                    device=dev.device_kind)
        try:
            med, least = timed(chained(call, blocks), *args)
            line.update(ms_median=med, ms_min=least,
                        weight_gb_per_s=line["passes"] * weight_bytes
                        / med / 1e6, tflop_per_s=flops / med / 1e9)
            got = jax.jit(lambda *a: call(*a, blocks))(*args)
        except Exception as e:  # blocks the compiler refuses are a line too
            line["error"], got = str(e)[:300], None
        out.append(line)
        print(json.dumps(line), flush=True)
        return got

    def sweep(kernel, shape, others, args, call, flops, weight_bytes, rule):
        ruled = run(kernel, shape, rule, args, call, flops, weight_bytes,
                    ruled=True)
        for blocks in others:
            got = run(kernel, shape, blocks, args, call, flops,
                      weight_bytes)
            if got is not None and ruled is not None:
                same = all(bool(jnp.array_equal(a, b)) for a, b in
                           zip(jax.tree.leaves(got), jax.tree.leaves(ruled)))
                print(json.dumps(dict(kernel=kernel, shape=shape,
                                      blocks=blocks, same_bits_as_rule=same)),
                      flush=True)

    for (t, d, f), others in mlp:
        args = (normal(key[0], t, d) * 50, normal(key[1], d, f),
                normal(key[2], d, f), normal(key[3], f, d))
        sweep("mlp", (t, d, f), others, args,
              lambda x, wg, wu, wd, b: FB.fused_mlp(
                  x, wg, wu, wd, block_t=b[0], block_f=b[1],
                  interpret=interpret, autotune=False),
              6 * t * d * f, 3 * d * f * 2,
              FB._default_mlp_blocks(t, d, f, "bfloat16"))
    for (t, d, dq, dk, dv), others in qkv:
        args = (normal(key[0], t, d) * 50, 1 + normal(key[4], d),
                normal(key[5], d, dq), normal(key[6], d, dk),
                normal(key[7], d, dv))
        sweep("rmsnorm_qkv", (t, d, dq, dk, dv), others, args,
              lambda x, wn, wq, wk, wv, b: FB.fused_rmsnorm_qkv(
                  x, wn, wq, wk, wv, block_t=b[0], block_o=b[1],
                  interpret=interpret, autotune=False),
              2 * t * d * (dq + dk + dv), d * (dq + dk + dv) * 2,
              FB._default_qkv_blocks(t, d, dq, dk, dv, "bfloat16"))

    # what the counter says of the rule's own choices at these shapes
    passes = FB._passes_counter()
    before = {k: c.value() for k, c in passes.series()}
    S = lambda *s: jax.ShapeDtypeStruct(s, BF16)
    for (t, d, f), _ in mlp:
        jax.eval_shape(lambda *a: FB.fused_mlp(
            *a, interpret=interpret, autotune=False),
            S(t, d), S(d, f), S(d, f), S(f, d))
    for (t, d, dq, dk, dv), _ in qkv:
        jax.eval_shape(lambda *a: FB.fused_rmsnorm_qkv(
            *a, interpret=interpret, autotune=False),
            S(t, d), S(d), S(d, dq), S(d, dk), S(d, dv))
    counted = {"/".join(k): c.value() - before.get(k, 0)
               for k, c in passes.series()}
    print(json.dumps(dict(weight_passes_counted=counted)), flush=True)
    if rehearse:
        return 3
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/fused_block.json", "w") as fh:
        json.dump(out + [dict(weight_passes_counted=counted)], fh, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
