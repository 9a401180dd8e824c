"""The served expert layer alone, at serve-rag's published widths, on the chip.

    chiprun -- python benchmarks/served_experts_bench.py

One layer of granite-4.0-h-small as one chip of two holds it (36 of 72
gated experts, d4096, f768, top 10, bf16): ``gated_experts_forward`` by
each of its two products at a prefill chunk's and a decode step's rows,
the sorted kernel alone on its padded rows at every ``block_f``, and two
``megablox.gmm`` calls at a 128-row tile as the yardstick.  Host clock
around twenty dispatches and one ``block_until_ready``; a line of JSON a
variant, all of them again in ``chiprun_out/served_experts.json``.
Refuses to run without a TPU: a time from the CPU is no device time
(``--rehearse`` walks the same code at a toy width, writes nothing and
exits 3).
"""

from __future__ import annotations

import json
import os
import statistics
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

from paddle_tpu.distributed.moe import gated_experts_forward
from paddle_tpu.ops.pallas import grouped_matmul as GM

E, H, K, D, F = 72, 36, 10, 4096, 768
BF16 = jnp.bfloat16
ITERS = 20


def timed(fn, *args):
    """ms a call: ITERS dispatches in a row and one wait, so the host's
    part overlaps the device's; median and least of five such rounds,
    after the program compiled and ran three times."""
    for _ in range(3):
        jax.block_until_ready(fn(*args))
    ms = []
    for _ in range(5):
        t0 = time.perf_counter()
        for _ in range(ITERS):
            got = fn(*args)
        jax.block_until_ready(got)
        ms.append((time.perf_counter() - t0) * 1e3 / ITERS)
    return statistics.median(ms), min(ms)


def layer(path, forced=None):
    """jit of the layer with the product forced to ``path`` (and the
    kernel's blocks to ``forced``)."""
    local = np.full(E, H, np.int32)
    local[:H] = np.arange(H)

    def fn(x, router, w_in, w_out, valid):
        blocks = GM.sorted_ffn_blocks
        if path == "ragged_dot":
            GM.sorted_ffn_blocks = lambda *a: None
        elif forced:
            GM.sorted_ffn_blocks = lambda *a: forced
        try:
            return gated_experts_forward(x, router, w_in, w_out, top_k=K,
                                         local_of=local, row_valid=valid)
        finally:
            GM.sorted_ffn_blocks = blocks
    return jax.jit(fn)


def routed(x, router, valid, rows):
    """The kernel's own operands for this routing, made once, and the
    sorted rows the other products take."""
    logits = jnp.dot(x, router, preferred_element_type=jnp.float32)
    _, topi = jax.lax.top_k(logits, K)
    loc = jnp.where(topi < H, topi, H)
    loc = jnp.where(valid[:, None], loc, H)
    flat = loc.reshape(-1)
    sizes = jnp.sum(flat[:, None] == jnp.arange(H)[None], axis=0,
                    dtype=jnp.int32)
    te, used, dest = GM.sorted_tile_plan(loc, sizes, rows)
    return x[jnp.argsort(flat) // K], sizes, dest, te, used


def megablox_pair(tiling_in, tiling_out):
    from jax.experimental.pallas.ops.tpu.megablox import gmm

    def fn(xs, sizes, w_in, w_out):
        gu = gmm(xs, w_in, sizes, preferred_element_type=BF16,
                 tiling=tiling_in)
        g, u = jnp.split(gu, 2, axis=-1)
        return gmm((jax.nn.silu(g) * u).astype(BF16), w_out, sizes,
                   preferred_element_type=jnp.float32, tiling=tiling_out)
    return jax.jit(fn)


def ragged_pair():
    def fn(xs, sizes, w_in, w_out):
        gu = jax.lax.ragged_dot(xs, w_in, sizes)
        g, u = jnp.split(gu, 2, axis=-1)
        return jax.lax.ragged_dot((jax.nn.silu(g) * u).astype(BF16), w_out,
                                  sizes, preferred_element_type=jnp.float32)
    return jax.jit(fn)


def main():
    global D, F, ITERS
    dev = jax.devices()[0]
    rehearse = "--rehearse" in sys.argv   # the control flow, at a toy size
    if rehearse:
        D, F, ITERS = 256, 256, 1
    elif dev.platform != "tpu":
        print("served_experts_bench: needs a TPU", file=sys.stderr)
        return 2
    key = jax.random.split(jax.random.PRNGKey(38), 4)
    router = (0.02 * jax.random.normal(key[0], (D, E))).astype(BF16)
    w_in = (0.02 * jax.random.normal(key[1], (H, D, 2 * F))).astype(BF16)
    w_out = (0.02 * jax.random.normal(key[2], (H, F, D))).astype(BF16)
    out = []

    def say(**line):
        line["device"] = dev.device_kind
        out.append(line)
        print(json.dumps(line), flush=True)

    def attempt(what, tokens, fn, *args, **more):
        try:
            med, least = timed(fn, *args)
            say(what=what, tokens=tokens, ms_median=med, ms_min=least,
                **more)
        except Exception as e:   # a candidate the compiler refuses is a line
            say(what=what, tokens=tokens, error=str(e)[:300], **more)

    for tokens, live in ((512, 512), (256, 256), (128, 128), (24, 5)):
        x = jax.random.normal(jax.random.fold_in(key[3], tokens),
                              (tokens, D)).astype(BF16)
        valid = jnp.arange(tokens) < live
        args = (x, router, w_in, w_out, valid)
        ref, counts = layer("ragged_dot")(*args)
        attempt("layer.ragged_dot", tokens, layer("ragged_dot"), *args,
                counts=[int(c) for c in counts])
        rule = GM.sorted_ffn_blocks(tokens, K, H, D, F, BF16)
        if rule is None:           # the rule leaves these rows to ragged_dot
            rule = (16, F // 2)
        xs, sizes, dest, te, used = routed(x, router, valid, rule[0])
        for bf in ((128, 256, 384) if tokens == 512 else (rule[1],)):
            fn = layer("sorted_kernel", (rule[0], bf))
            try:
                got, _ = fn(*args)
                gap = float(jnp.abs(got - ref).max() / jnp.abs(ref).max())
            except Exception as e:
                say(what="layer.sorted_kernel", tokens=tokens, block_f=bf,
                    error=str(e)[:300])
                continue
            attempt("layer.sorted_kernel", tokens, fn, *args, block_f=bf,
                    block_rows=rule[0], gap_to_ragged=gap)
            kern = jax.jit(lambda a, p, wi, wo, t, u, bf=bf, r=rule[0]:
                           GM.sorted_gated_ffn(a, p, wi, wo, t, u,
                                               block_rows=r, block_f=bf))
            attempt("kernel_alone", tokens, kern, x, dest, w_in, w_out, te,
                    used, block_f=bf, tiles_used=int(used[0]),
                    tiles=int(te.shape[0]))
        attempt("products.ragged_dot", tokens, ragged_pair(), xs, sizes,
                w_in, w_out)
        if tokens == 512 and not rehearse:
            for t_in, t_out in (((128, 1024, 768), (128, 768, 2048)),
                                ((128, 2048, 768), (128, 768, 2048)),
                                ((128, 4096, 512), (128, 768, 1024))):
                attempt("products.megablox", tokens,
                        megablox_pair(t_in, t_out), xs, sizes, w_in, w_out,
                        tiling=[t_in, t_out])
    if rehearse:
        return 3
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/served_experts.json", "w") as fh:
        json.dump(out, fh, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
