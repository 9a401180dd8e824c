"""The plain reference's first optimizer steps, at the cell's own size.

Follows the program's first two steps (two for three keeps the check
shorter than the window): float32 weights from the seed, the mean
cross-entropy of the whole batch a row at a time, AdamW.  The walking is
here; the loss is the architecture's (perf/archs/: ``loss``).  It runs
before the program's state exists and frees what it held.  Device memory
at the worst moment: weights, the summed gradient and one row's gradient
(three float32 copies) and one row's activations; the first step's
gradient waits on the host meanwhile (Adam's first moments are functions
of it).  On several chips the leaves are split over them and a row goes
to each chip at once (one group of rows a call, not one row).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from perf import common, weights
from perf.reference.decoder import adamw

STEPS = 2


def _norms(tree):
    return {n: float(v) for n, v in jax.jit(lambda t: {
        n: jnp.sqrt(jnp.sum(jnp.square(a))) for n, a in t.items()})(
            tree).items()}


def follow(cfg, seed, batches, hp, precision="float32", sharding=None):
    """{"loss": [l1, l2], "grad_norm": {leaf: |g1|},
    "delta_norm": {leaf: |p2 - p0|}} for ``batches[0:2]``.
    ``sharding(shape)`` places an array along its first axis (a cell on
    several chips): a leaf, and as many rows of the batch as there are
    chips."""
    assert len(batches) == STEPS
    loss = common.arch_of(cfg).loss
    spec = weights.leaves(cfg)
    out_sh = None if sharding is None else \
        {n: sharding(s) for n, s, _ in spec}
    with jax.default_matmul_precision("highest"):
        w = weights.make_all(cfg, seed, jnp.dtype(cfg["torch_dtype"]),
                             out_sh)
        w = jax.jit(lambda t: jax.tree.map(
            lambda a: a.astype(jnp.float32), t), out_shardings=out_sh)(w)
        rows = batches[0]["input_ids"].shape[0]
        group = 1 if sharding is None else \
            len(sharding((rows,)).device_set)
        if rows % group:
            raise ValueError(f"{rows} rows do not go over {group} chips")
        place = jnp.asarray if sharding is None else \
            (lambda a: jax.device_put(a, sharding(a.shape)))
        calls = rows // group

        def rows_loss(w, ids, labels):
            return loss(w, cfg, ids, labels, precision)

        vg = jax.jit(jax.value_and_grad(rows_loss),
                     # a gradient lies where its leaf lies
                     out_shardings=None if sharding is None
                     else (None, out_sh))
        add = jax.jit(lambda g, gr: jax.tree.map(
            lambda a, b: a + b / calls, g, gr), donate_argnums=0)

        def grad(w, batch):
            total, g = 0.0, None
            for r in range(0, rows, group):
                l, gr = vg(w, place(batch["input_ids"][r:r + group]),
                           place(batch["labels"][r:r + group]))
                total += float(l) / calls
                g = add(g, gr) if g is not None else jax.tree.map(
                    lambda a: a / calls, gr)
            return total, g

        l1, g1 = grad(w, batches[0])
        grad_norm = _norms(g1)
        step1 = jax.jit(lambda p, g: jax.tree.map(
            lambda a, b: adamw(a, b, 0.0, 0.0, 1, **hp)[0], p, g),
            donate_argnums=0)
        w = step1(w, g1)
        g1_host = jax.device_get(g1)
        g1 = None
        l2, g2 = grad(w, batches[1])
        g1 = jax.tree.map(lambda a, ref: jax.device_put(a, ref.sharding),
                          g1_host, g2)
        b1, b2 = hp["beta1"], hp["beta2"]
        def finish(key, p1, ga, gb):
            out = {}
            for i, (n, shape, init) in enumerate(spec):
                p2, _, _ = adamw(
                    p1[n], gb[n], (1 - b1) * ga[n],
                    (1 - b2) * ga[n] * ga[n], 2, **hp)
                p0 = weights._leaf(key, i, shape, init, jnp.dtype(
                    cfg["torch_dtype"])).astype(jnp.float32)
                out[n] = jnp.sqrt(jnp.sum(jnp.square(p2 - p0)))
            return out

        delta = {n: float(v) for n, v in jax.jit(finish)(
            weights.base_key(seed), w, g1, g2).items()}
    return {"loss": [l1, l2], "grad_norm": grad_norm, "delta_norm": delta}


def worst_leaf_gap(got, ref):
    """|got - ref| of each leaf's norm against the reference's norm of
    that leaf or of the median leaf, whichever is larger (some gradients
    are all but zero): (the largest, its leaf, the mean over leaves)."""
    med = float(np.median(list(ref.values())))
    gaps = {n: abs(got[n] - r) / max(r, med) for n, r in ref.items()}
    where = max(gaps, key=gaps.get)
    return gaps[where], where, float(np.mean(list(gaps.values())))
