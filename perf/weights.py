"""Seeded weights, made by the benchmark and given to both sides.

The program's model and the plain reference get the same values from
``--seed``: one jitted call makes every leaf on the device in the type it
is served in (``make_all``), and the reference, which never holds the
program's arrays, makes its own copy a leaf or a layer at a time from the
same keys (``make_leaf``).  Leaf names are the state-dict names the cells'
model class uses; the reference reads the same names.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

MATRIX_STD = 0.02       # GPT-2 / Llama-style initialiser scale
NORM_JITTER = 0.1       # norm gains 1 + 0.1 n: an all-ones gain hides it


def layer_leaves(cfg, i):
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    hd = cfg.get("head_dim") or d // cfg["num_attention_heads"]
    dq = cfg["num_attention_heads"] * hd
    dkv = cfg["num_key_value_heads"] * hd
    p = f"model.layers_{i}."
    return [(p + "input_layernorm.weight", (d,)),
            (p + "self_attn.q_proj.weight", (d, dq)),
            (p + "self_attn.k_proj.weight", (d, dkv)),
            (p + "self_attn.v_proj.weight", (d, dkv)),
            (p + "self_attn.o_proj.weight", (dq, d)),
            (p + "post_attention_layernorm.weight", (d,)),
            (p + "mlp.gate_proj.weight", (d, f)),
            (p + "mlp.up_proj.weight", (d, f)),
            (p + "mlp.down_proj.weight", (f, d))]


def leaves(cfg):
    """[(name, shape)] in a fixed order; a leaf's index is its key."""
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    out = [("model.embed_tokens.weight", (v, d))]
    for i in range(cfg["num_hidden_layers"]):
        out += layer_leaves(cfg, i)
    out += [("model.norm.weight", (d,)), ("lm_head.weight", (d, v))]
    return out


def base_key(seed: int):
    # --seed may pass 2**31: split it so no part overflows an int32
    return jax.random.fold_in(jax.random.PRNGKey(seed % 65536),
                              seed // 65536)


def _leaf(key, index, shape, dtype):
    n = jax.random.normal(jax.random.fold_in(key, index), shape,
                          jnp.float32)
    if len(shape) == 1:
        return (1.0 + NORM_JITTER * n).astype(dtype)
    return (MATRIX_STD * n).astype(dtype)


def _make(key, indices, shapes, dtype):
    return [_leaf(key, indices[j], shape, dtype)
            for j, shape in enumerate(shapes)]


def make_some(cfg, seed, names, dtype, out_shardings=None):
    """The named leaves in one jitted call, on the device, in ``dtype``
    (the reference asks for a layer at a time).  A leaf's index is an
    argument, not a constant: every layer is the same program."""
    index = {n: (i, s) for i, (n, s) in enumerate(leaves(cfg))}
    fn = jax.jit(functools.partial(
        _make, shapes=tuple(index[n][1] for n in names),
        dtype=jnp.dtype(dtype)),
        out_shardings=None if out_shardings is None
        else [out_shardings[n] for n in names])
    made = fn(base_key(seed),
              jnp.asarray([index[n][0] for n in names], jnp.int32))
    return dict(zip(names, made))


def make_all(cfg, seed, dtype, out_shardings=None):
    """Every leaf in one jitted call."""
    return make_some(cfg, seed, [n for n, _ in leaves(cfg)], dtype,
                     out_shardings)
