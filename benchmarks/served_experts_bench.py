"""The served expert layer alone, at the four expert cells' published widths,
on the chip.

    chiprun -- python benchmarks/served_experts_bench.py [cell ...]

One expert layer as one chip of its deployment holds it (bf16, a prefill
chunk of 512 tokens): ``serve-rag`` (granite-4.0-h-small: 36 of 72 gated
experts, d4096, f768, top 10), ``serve-longctx`` (sarvam-105b: 16 of 128,
d4096, f2048, top 8), ``serve-reason`` (kimi-linear: 64 of 256, d2304,
f1024, top 8), ``serve-mixed`` (trinity-large: 16 of 256, d3072, f3072,
top 4).  For each: ``gated_experts_forward`` by each of its two products,
the sorted kernel alone (rows in, the gated ``[T, d]`` out) at the rule's
``block_f`` and its neighbours, and the layer's time by the device's own
operations (a profile of ten calls, ``perf/trace_reduce.py``).  At
``serve-rag``'s widths also fewer rows (256, 128, a decode step's 24) and
two ``megablox.gmm`` calls at a 128-row tile as the yardstick.  Host clock
around twenty dispatches and one ``block_until_ready``; a line of JSON a
variant, all of them again in ``chiprun_out/served_experts.json``.  The
file runs unchanged in a checkout from before the kernel combined (PR 47
and earlier: the kernel alone is then the padded rows' kernel), which is
how the parent's numbers are read beside the change's.
Refuses to run without a TPU: a time from the CPU is no device time
(``--rehearse`` walks the same code at a toy width, writes nothing and
exits 3).
"""

from __future__ import annotations

import json
import os
import re
import statistics
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

from paddle_tpu.distributed.moe import gated_experts_forward
from paddle_tpu.ops.pallas import grouped_matmul as GM

# cell: (routed experts, held, top k, d, f): the configurations' files
# under perf/configs/, an expert layer as one chip holds it
CELLS = {
    "serve-rag": (72, 36, 10, 4096, 768),
    "serve-longctx": (128, 16, 8, 4096, 2048),
    "serve-reason": (256, 64, 8, 2304, 1024),
    "serve-mixed": (256, 16, 4, 3072, 3072),
}
TOY = (8, 4, 2, 256, 256)             # --rehearse
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


def device_ops(fn, *args, calls=10):
    """{operation: ms a call} by the device's own clock: a profile of
    ``calls`` calls, each operation's own time (``.N`` suffixes merged),
    and their sum under ``total``."""
    import shutil
    import tempfile
    from perf import trace_reduce
    jax.block_until_ready(fn(*args))
    tmp = tempfile.mkdtemp(prefix="served_experts_")
    try:
        with jax.profiler.trace(tmp):
            for _ in range(calls):
                got = fn(*args)
            jax.block_until_ready(got)
        path = [os.path.join(r, f) for r, _, fs in os.walk(tmp)
                for f in fs if f.endswith(".xplane.pb")][0]
        ops = {}
        for name, ns in trace_reduce.op_totals(trace_reduce.load(path)).items():
            name = re.sub(r"\.\d+$", "", name)
            ops[name] = ops.get(name, 0.0) + ns / 1e6 / calls
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    ops = {k: round(v, 4) for k, v in sorted(ops.items(), key=lambda kv: -kv[1])
           if v >= 5e-4}
    return dict(total=round(sum(ops.values()), 4), **ops)


def layer(shape, path, forced=None):
    """jit of the layer with the product forced to ``path`` (and the
    kernel's blocks to ``forced``)."""
    E, H, K = shape[:3]
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


def routed(shape, x, router, valid, rows):
    """The kernel's own operands for this routing, made once (the plan's,
    in the order ``sorted_gated_ffn`` takes them around the weights), and
    the sorted rows the other products take."""
    _, H, K = shape[:3]
    logits = jnp.dot(x, router, preferred_element_type=jnp.float32)
    topv, topi = jax.lax.top_k(logits, K)
    loc = jnp.where(topi < H, topi, H)
    loc = jnp.where(valid[:, None], loc, H)
    flat = loc.reshape(-1)
    sizes = jnp.sum(flat[:, None] == jnp.arange(H)[None], axis=0,
                    dtype=jnp.int32)
    try:
        te, used, *rows_of = GM.sorted_tile_plan(
            loc, sizes, rows, jax.nn.softmax(topv, axis=-1))
    except TypeError:      # a checkout whose kernel leaves padded rows
        te, used, *rows_of = GM.sorted_tile_plan(loc, sizes, rows)
    return x[jnp.argsort(flat) // K], sizes, rows_of, te, used


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
    global ITERS
    dev = jax.devices()[0]
    rehearse = "--rehearse" in sys.argv   # the control flow, at a toy size
    names = [a for a in sys.argv[1:] if not a.startswith("--")]
    cells = {n: CELLS[n] for n in names or CELLS}
    if rehearse:
        cells, ITERS = {"toy": TOY}, 1
    elif dev.platform != "tpu":
        print("served_experts_bench: needs a TPU", file=sys.stderr)
        return 2
    out = []

    def say(**line):
        line["device"] = dev.device_kind
        out.append(line)
        print(json.dumps(line), flush=True)

    def attempt(what, cell, tokens, fn, *args, **more):
        try:
            med, least = timed(fn, *args)
            say(what=what, cell=cell, tokens=tokens, ms_median=med,
                ms_min=least, **more)
        except Exception as e:   # a candidate the compiler refuses is a line
            say(what=what, cell=cell, tokens=tokens, error=str(e)[:300],
                **more)

    for cell, shape in cells.items():
        E, H, K, D, F = shape
        key = jax.random.split(jax.random.PRNGKey(38), 4)
        router = (0.02 * jax.random.normal(key[0], (D, E))).astype(BF16)
        w_in = (0.02 * jax.random.normal(key[1], (H, D, 2 * F))).astype(BF16)
        w_out = (0.02 * jax.random.normal(key[2], (H, F, D))).astype(BF16)
        wide = cell in ("serve-rag", "toy")   # fewer rows, the yardstick
        for tokens, live in ((512, 512), (256, 256), (128, 128),
                             (24, 5))[:4 if wide else 1]:
            x = jax.random.normal(jax.random.fold_in(key[3], tokens),
                                  (tokens, D)).astype(BF16)
            valid = jnp.arange(tokens) < live
            args = (x, router, w_in, w_out, valid)
            ref, counts = layer(shape, "ragged_dot")(*args)
            attempt("layer.ragged_dot", cell, tokens,
                    layer(shape, "ragged_dot"), *args,
                    counts=[int(c) for c in counts])
            rule = GM.sorted_ffn_blocks(tokens, K, H, D, F, BF16)
            say(what="rule", cell=cell, tokens=tokens, blocks=rule)
            if rule is None:       # the rule leaves these rows to ragged_dot
                rule = (16, F // 2)
            xs, sizes, rows_of, te, used = routed(shape, x, router, valid,
                                                  rule[0])
            for bf in sorted({rule[1]} | ({b for b in (128, 256, 384, 512)
                                           if F % b == 0}
                                          if tokens == 512 else set())):
                fn = layer(shape, "sorted_kernel", (rule[0], bf))
                try:
                    got, _ = fn(*args)
                    gap = float(jnp.abs(got - ref).max() / jnp.abs(ref).max())
                except Exception as e:
                    say(what="layer.sorted_kernel", cell=cell, tokens=tokens,
                        block_f=bf, error=str(e)[:300])
                    continue
                attempt("layer.sorted_kernel", cell, tokens, fn, *args,
                        block_f=bf, block_rows=rule[0], gap_to_ragged=gap)
                kern = jax.jit(lambda a, wi, wo, t, u, *p, bf=bf, r=rule[0]:
                               GM.sorted_gated_ffn(a, *p, wi, wo, t, u,
                                                   block_rows=r, block_f=bf))
                attempt("kernel_alone", cell, tokens, kern, x, w_in, w_out,
                        te, used, *rows_of, block_f=bf,
                        tiles_used=int(used[0]), tiles=int(te.shape[0]))
                if bf == rule[1] and tokens == 512 and not rehearse:
                    say(what="layer.sorted_kernel.device_ops", cell=cell,
                        tokens=tokens, block_f=bf, ms=device_ops(fn, *args))
            attempt("products.ragged_dot", cell, tokens, ragged_pair(), xs,
                    sizes, w_in, w_out)
            if wide and tokens == 512 and not rehearse:
                for t_in, t_out in (((128, 1024, 768), (128, 768, 2048)),
                                    ((128, 2048, 768), (128, 768, 2048)),
                                    ((128, 4096, 512), (128, 768, 1024))):
                    attempt("products.megablox", cell, tokens,
                            megablox_pair(t_in, t_out), xs, sizes, w_in,
                            w_out, tiling=[t_in, t_out])
        del router, w_in, w_out
    if rehearse:
        return 3
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/served_experts.json", "w") as fh:
        json.dump(out, fh, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
