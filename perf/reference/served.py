"""The plain reference over served requests: for each sampled request one
teacher-forced float32 forward over its prompt and its served tokens, a
layer at a time (a layer's weights are made from the seed, used for every
row, and dropped, so it fits beside the engine), then the head at the
served positions only.  The walking is here; the mathematics is the
architecture's (perf/archs/: ``embed``, ``layer``, ``head``).

Greedy tokens are compared on logits, not by equality: with seeded random
weights the best and the second-best logit are often a rounding apart.
The number compared is how far a served token's logit lies below the
reference's best, as a share of the largest |logit| of that position.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from perf import common, weights


def served_logits(cfg, seed, rows, pad_len, pad_out, precision="float32"):
    """rows: [(prompt, served tokens)].  Returns, for each row, float32
    logits [served tokens, vocab]; entry j predicts served token j.
    Every row is padded to ``pad_len`` positions (causal: the padding
    cannot reach back) and ``pad_out`` served positions, so that every run
    of a cell compiles the same two programs."""
    import time
    t0 = time.perf_counter()
    with jax.default_matmul_precision("highest"):
        make = lambda names: jax.tree.map(
            lambda a: a.astype(jnp.float32),
            weights.make_some(cfg, seed, names,
                              jnp.dtype(cfg["torch_dtype"])))
        arch = common.arch_of(cfg)
        emb = make([n for n, _, _ in arch.embed_leaves(cfg)])
        x, at = [], []
        for prompt, out in rows:
            n = len(prompt) + len(out)
            ids = np.zeros((1, pad_len), np.int32)
            ids[0, :n] = np.concatenate([prompt, out])
            x.append(arch.embed(emb, cfg, jnp.asarray(ids)))
            at.append(len(prompt) - 1 + np.arange(len(out)))
        del emb

        blocks = {}     # layers of one kind are one compiled program

        def block(i):
            kind = arch.layer_kind(cfg, i)
            if kind not in blocks:
                def layer(xr, w):
                    return arch.layer(xr, w, cfg, i,
                                      jnp.arange(xr.shape[1]), precision)
                blocks[kind] = jax.jit(layer)
            return blocks[kind]

        for i in range(cfg["num_hidden_layers"]):
            w = make([n for n, _, _ in arch.layer_leaves(cfg, i)])
            w = {n[len(arch.layer_prefix(i)):]: a for n, a in w.items()}
            x = [block(i)(xr, w) for xr in x]
            del w
        jax.block_until_ready(x)
        t1 = time.perf_counter()
        w = make([n for n, _, _ in arch.head_leaves(cfg)])

        @jax.jit
        def head(h, w):     # weights as arguments: never constants
            return arch.head(h, w, cfg, precision)

        out = []
        for xr, pos in zip(x, at):
            idx = np.zeros(pad_out, np.int32)
            idx[:len(pos)] = pos
            out.append(np.asarray(
                head(xr[0, jnp.asarray(idx)], w))[:len(pos)])
        print(f"[perf] reference[{precision}]: rows "
              f"{[xr.shape[1] for xr in x]}, layers {t1 - t0:.1f} s, head "
              f"{time.perf_counter() - t1:.1f} s", flush=True)
        return out


def gaps(logits, rows, tokens=None):
    """Per served position: (best logit - the token's logit) / max |logit|.
    ``tokens`` replaces the served tokens (the control's choices)."""
    out = []
    for r, (_, served) in enumerate(rows):
        n = len(served)
        lg = logits[r]
        tok = np.asarray(served if tokens is None else tokens[r][:n])
        got = lg[np.arange(n), tok]
        out.append((lg.max(-1) - got) / np.abs(lg).max(-1))
    return np.concatenate(out)
