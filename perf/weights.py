"""Seeded weights, made by the benchmark and given to both sides.

The program's model and the plain reference get the same values from
``--seed``: one jitted call makes every leaf on the device in the type it
is served in (``make_all``), and the reference, which never holds the
program's arrays, makes its own copy a leaf or a layer at a time from the
same keys (``make_some``).  Which leaves there are, in which order and
with which initialiser, is the architecture's to say (perf/archs/): the
names are the state-dict names of the program's model class, and the
reference reads the same names.  An initialiser is one of the three kinds
here or one of the architecture's own (its ``INITS``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from perf import common

MATRIX_STD = 0.02       # GPT-2 / Llama-style initialiser scale
NORM_JITTER = 0.1       # norm gains 1 + 0.1 n: an all-ones gain hides it
KINDS = ("gain", "matrix", "vector")    # _leaf's own; any other name is
# looked for in the architecture's INITS


def leaves(cfg):
    """[(name, shape, init)] in a fixed order; a leaf's index is its key.
    ``init`` is a kind of ``_leaf``'s or, for a kind of the architecture's
    own (``INITS = {kind: fn(key, shape) -> float32 array}`` in its
    file), that function."""
    arch = common.arch_of(cfg)
    own = getattr(arch, "INITS", {})
    return [(n, s, k if k in KINDS else own.get(k, k))
            for n, s, k in arch.leaves(cfg)]


def base_key(seed: int):
    # --seed may pass 2**31: split it so no part overflows an int32
    return jax.random.fold_in(jax.random.PRNGKey(seed % 65536),
                              seed // 65536)


def _leaf(key, index, shape, init, dtype):
    """``init``: ``gain`` (a norm's, 1 + 0.1 n), ``matrix`` (0.02 n),
    ``vector`` (a bias or a sink logit: 0.02 n, whatever its shape) or an
    architecture's own function of the leaf's key and shape."""
    key = jax.random.fold_in(key, index)
    if callable(init):
        return init(key, shape).astype(dtype)
    n = jax.random.normal(key, shape, jnp.float32)
    if init == "gain":
        return (1.0 + NORM_JITTER * n).astype(dtype)
    if init not in KINDS:
        raise ValueError(f"unknown initialiser {init!r}")
    return (MATRIX_STD * n).astype(dtype)


def _make(key, indices, shapes, inits, dtype):
    return [_leaf(key, indices[j], shape, inits[j], dtype)
            for j, shape in enumerate(shapes)]


def make_some(cfg, seed, names, dtype, out_shardings=None):
    """The named leaves in one jitted call, on the device, in ``dtype``
    (the reference asks for a layer at a time).  A leaf's index is an
    argument, not a constant: every layer is the same program."""
    index = {n: (i, s, k) for i, (n, s, k) in enumerate(leaves(cfg))}
    fn = jax.jit(functools.partial(
        _make, shapes=tuple(index[n][1] for n in names),
        inits=tuple(index[n][2] for n in names), dtype=jnp.dtype(dtype)),
        out_shardings=None if out_shardings is None
        else [out_shardings[n] for n in names])
    made = fn(base_key(seed),
              jnp.asarray([index[n][0] for n in names], jnp.int32))
    return dict(zip(names, made))


def make_all(cfg, seed, dtype, out_shardings=None):
    """Every leaf in one jitted call."""
    return make_some(cfg, seed, [n for n, _, _ in leaves(cfg)], dtype,
                     out_shardings)


def give(model, cfg, seed):
    """The seed's leaves set in place of the program's initialiser's, by
    state-dict name, in the type the configuration states."""
    given = make_all(cfg, seed, jnp.dtype(cfg["torch_dtype"]))
    for name, t in model.state_dict(keep_vars=True).items():
        t._set_data(given.pop(name))
    if given:
        raise KeyError(f"the model has no parameter {sorted(given)}")
