"""Mamba-2 (state-space duality) in jax.numpy: the causal depthwise
convolution with a carried tail, the chunked scan for a span of tokens
and the one-token state update.

One head's recurrence, with ``a_t = exp(dt_t * A)`` (``A < 0``):

    h_t = a_t * h_{t-1} + dt_t * x_t (outer) B_t        h: [p, n]
    y_t = h_t C_t                                       (the caller adds D x_t)

``ssd_scan`` computes it a chunk of ``Q`` positions at a time (Dao & Gu
2024, section 6): inside a chunk the outputs are a masked
``(C B^T * L) X`` product, each chunk's contribution to the state is one
product, and the states cross chunks by a scan over ``l / Q`` steps, so
the state is carried in and out and a span can follow another.  Every
array here is float32 and every product ``HIGHEST``: the state is a long
sum, and the products are a few GFLOP beside the projections' hundred.

A position with ``dt == 0`` leaves the state exactly as it was
(``exp(0) * h + 0``), which is how a padded tail and an inactive row are
masked; ``causal_conv`` masks its tail the same way through ``valid``.
All three are XLA programs: the serve cell's trace reads their time
under the model's ``ssm`` scope.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["causal_conv", "ssd_scan", "ssm_step"]

_HI = jax.lax.Precision.HIGHEST


def causal_conv(x, tail, w, b, valid):
    """Depthwise causal convolution over a span that follows ``tail``.

    x [B, S, C]: the span's inputs; tail [B, K-1, C]: the K-1 inputs
    before it (zeros at a sequence's start); w [K, C], row K-1 on the
    current position; b [C] or None; valid [B] int32: how many of the S
    positions are real.  Returns (conv + b [B, S, C] float32, the tail
    after the last real position [B, K-1, C] in ``tail``'s type): with
    ``valid == 0`` the tail comes back bit for bit."""
    K, S = w.shape[0], x.shape[1]
    padded = jnp.concatenate([tail.astype(x.dtype), x], axis=1)
    w32 = w.astype(jnp.float32)
    out = sum(padded[:, k:k + S].astype(jnp.float32) * w32[k]
              for k in range(K))
    if b is not None:
        out = out + b.astype(jnp.float32)
    if S == 1:
        # a decode step: the tail moves by one position or stays.  The
        # batched slice below is a gather, which the TPU compiler can run
        # as a loop over the rows (~10 device operations a slot a layer).
        new_tail = jnp.where((valid > 0)[:, None, None],
                             padded[:, 1:], padded[:, :-1])
    else:
        new_tail = jax.vmap(lambda p, v: jax.lax.dynamic_slice_in_dim(
            p, v, K - 1, 0))(padded, valid.astype(jnp.int32))
    return out, new_tail.astype(tail.dtype)


def ssd_scan(x, dt, A, B, C, h0, chunk):
    """The recurrence over a span, chunk by chunk.

    x [b, l, h, p], dt [b, l, h] (after softplus; 0 masks a position),
    A [h] (negative), B, C [b, l, n] (one group), h0 [b, h, p, n]; all
    float32; ``l`` a multiple of ``chunk``.  Returns (y [b, l, h, p]
    without the D term, the state after position l - 1)."""
    b, l, h, p = x.shape
    n = B.shape[-1]
    Q = chunk
    c = l // Q
    x = x.reshape(b, c, Q, h, p)
    dt = dt.reshape(b, c, Q, h)
    B = B.reshape(b, c, Q, n)
    C = C.reshape(b, c, Q, n)
    cs = jnp.cumsum(dt * A, axis=2)                 # [b, c, Q, h], <= 0
    xd = x * dt[..., None]
    # inside a chunk: y_q = sum_{s <= q} (C_q . B_s) exp(cs_q - cs_s) xd_s
    cst = jnp.moveaxis(cs, 3, 2)                    # [b, c, h, Q]
    seg = cst[..., :, None] - cst[..., None, :]     # [b, c, h, q, s]
    tri = jnp.tril(jnp.ones((Q, Q), bool))
    L = jnp.exp(jnp.where(tri, seg, -jnp.inf))
    G = jnp.einsum("bcqn,bcsn->bcqs", C, B, precision=_HI)
    y = jnp.einsum("bchqs,bcshp->bcqhp", G[:, :, None] * L, xd,
                   precision=_HI)
    # each chunk's own contribution to the state at its end
    to_end = jnp.exp(cs[:, :, -1:, :] - cs)         # [b, c, Q, h]
    states = jnp.einsum("bcshp,bcsn->bchpn", xd * to_end[..., None], B,
                        precision=_HI)
    decay = jnp.exp(cs[:, :, -1, :])                # [b, c, h]

    def cross(hprev, inp):
        s_c, d_c = inp
        return hprev * d_c[..., None, None] + s_c, hprev

    h_last, h_in = jax.lax.scan(
        cross, h0, (jnp.moveaxis(states, 1, 0), jnp.moveaxis(decay, 1, 0)))
    h_in = jnp.moveaxis(h_in, 0, 1)                 # [b, c, h, p, n]
    # what the state a chunk starts from adds to its outputs
    y = y + jnp.einsum("bcqn,bchpn->bcqhp", C, h_in, precision=_HI) \
        * jnp.exp(cs)[..., None]
    return y.reshape(b, l, h, p), h_last


def ssm_step(x, dt, A, B, C, h):
    """One position: x [b, h, p], dt [b, h], A [h], B, C [b, n],
    h [b, h, p, n]; float32.  Returns (y [b, h, p], the new state); a
    row with ``dt == 0`` keeps its state bit for bit."""
    a = jnp.exp(dt * A)
    h = h * a[..., None, None] + \
        (dt[..., None] * x)[..., None] * B[:, None, None, :]
    return jnp.sum(h * C[:, None, None, :], axis=-1), h
