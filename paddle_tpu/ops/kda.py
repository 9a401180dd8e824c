"""Kimi Delta Attention (a gated delta rule whose decay is per channel)
in jax.numpy: the chunked scan for a span of tokens and the one-token
state update.

One head's recurrence, with ``alpha_t = exp(a_t)`` (``a_t <= 0`` a
channel of the keys) and ``beta_t`` in (0, 1):

    S' = Diag(alpha_t) S_{t-1}                          S: [d_k, d_v]
    S_t = S' + beta_t k_t (v_t - S'^T k_t)^T            (the delta rule)
    o_t = S_t^T q_t

``kda_scan`` computes it a chunk of ``Q`` positions at a time.  With
``G_t = sum_{i <= t} a_i`` inside a chunk and ``u_t = v_t - S'^T k_t``
(what the delta rule writes), unrolling gives

    S_t = Diag(e^{G_t}) S_0 + sum_{s <= t} Diag(e^{G_t - G_s}) beta_s k_s u_s^T
    (I + A Diag(beta)) U = V - (K * e^G) S_0,
        A_ts = sum_c k_tc k_sc e^{G_tc - G_sc}   (s < t)
    o_t = (q_t * e^{G_t}) S_0 + sum_{s <= t} B_ts beta_s u_s,
        B_ts = sum_c q_tc k_sc e^{G_tc - G_sc}   (s <= t)

so a chunk is one unit-triangular inverse (the UT transform: written as
``log2 Q`` products, and applied to ``[V | K * e^G]`` for every chunk at
once, which leaves the scan over chunks three small products a step) and the states cross chunks by a scan
over ``l / Q`` steps: the state is carried in and out and a span can
follow another.

Decays enter only as ``exp`` of differences ``G_t - G_s`` with ``s <= t``,
which are never positive: ``A`` and ``B`` are summed over the channels
with the decay inside the sum.  The textbook factorisation ``(q * e^G)
(k * e^{-G})^T`` is not used: with the published initialisers a channel's
log-decay over a chunk reaches hundreds and ``e^{-G}`` overflows float32.
Every array here is float32 and every product ``HIGHEST``: the state is a
long sum.

A position with ``a == 0`` and ``beta == 0`` leaves the state exactly as
it was (``exp(0) * S + 0``), which is how a padded tail and an inactive
row are masked.  Both are XLA programs: the serve cell's trace reads their
time under the model's ``kda`` scope.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["kda_scan", "kda_step"]

_HI = jax.lax.Precision.HIGHEST


def kda_scan(q, k, v, a, beta, S0, chunk):
    """The recurrence over a span, chunk by chunk.

    q, k [b, l, h, dk] (normalised and scaled by the caller), v
    [b, l, h, dv], a [b, l, h, dk] (log-decay, <= 0), beta [b, l, h],
    S0 [b, h, dk, dv]; all float32.  ``l`` need not be a multiple of
    ``chunk``: the last chunk is padded with masked positions.  Returns
    (o [b, l, h, dv], the state after position l - 1)."""
    b, l, h, dk = q.shape
    dv = v.shape[-1]
    Q = chunk
    pad = (-l) % Q
    c = (l + pad) // Q

    def chunks(x):      # [b, l, h, ...] -> [b, c, h, Q, ...]
        x = jnp.pad(x, [(0, 0), (0, pad)] + [(0, 0)] * (x.ndim - 2))
        return jnp.moveaxis(x.reshape((b, c, Q) + x.shape[2:]), 2, 3)

    q, k, v, a, beta = map(chunks, (q, k, v, a, beta))
    G = jnp.cumsum(a, axis=3)                       # [b, c, h, Q, dk], <= 0
    # the decay from s to t, a channel: only where s <= t, so never > 1
    seen = jnp.tril(jnp.ones((Q, Q), bool))
    D = jnp.exp(jnp.where(seen[..., None],
                          G[..., :, None, :] - G[..., None, :, :],
                          -jnp.inf))                # [b, c, h, t, s, dk]
    # two sums over the channels, each with the decay inside it: written
    # so, the TPU compiler keeps [t, s, channel] out of HBM (one stacked
    # sum shares the exponentials and writes 0.5 GB of them a layer)
    kD = k[..., None, :, :] * D
    A = jnp.tril(jnp.sum(k[..., :, None, :] * kD, -1), -1)     # s < t
    Bm = jnp.sum(q[..., :, None, :] * kD, -1) * beta[..., None, :]
    eG = jnp.exp(G)
    # U = Wv - Wk S0: the solve does not wait for the state.  M = A
    # Diag(beta) is strictly lower triangular, so (I + M)^-1 = (I - M)
    # (I + M^2)(I + M^4)... ends after log2(Q) factors: plain products,
    # a fifth faster on the chip at the published widths than the
    # compiler's triangular solve (PERF.md section 6, PR 43)
    mm = lambda x, y: jnp.einsum("...ts,...su->...tu", x, y, precision=_HI)
    M = A * beta[..., None, :]
    T = jnp.eye(Q, dtype=M.dtype) - M
    for _ in range(max(Q - 1, 1).bit_length() - 1):
        M = mm(M, M)
        T = T + mm(T, M)
    W = mm(T, jnp.concatenate([v, k * eG], -1))
    Wv, Wk = W[..., :dv], W[..., dv:]
    qG = q * eG
    to_end = k * (beta[..., None] * jnp.exp(G[..., -1:, :] - G))
    decay = eG[..., -1, :]                          # [b, c, h, dk]

    def cross(S, inp):
        Wv_c, Wk_c, qG_c, B_c, end_c, d_c = inp
        U = Wv_c - jnp.einsum("bhqk,bhkv->bhqv", Wk_c, S, precision=_HI)
        o = jnp.einsum("bhqk,bhkv->bhqv", qG_c, S, precision=_HI) + \
            jnp.einsum("bhqs,bhsv->bhqv", B_c, U, precision=_HI)
        S = S * d_c[..., None] + \
            jnp.einsum("bhsk,bhsv->bhkv", end_c, U, precision=_HI)
        return S, o

    S, o = jax.lax.scan(cross, S0, [jnp.moveaxis(x, 1, 0) for x in
                                    (Wv, Wk, qG, Bm, to_end, decay)])
    o = jnp.moveaxis(o, 0, 1)                       # [b, c, h, Q, dv]
    return jnp.moveaxis(o, 2, 3).reshape(b, c * Q, h, dv)[:, :l], S


def kda_step(q, k, v, a, beta, S):
    """One position: q, k, a [b, h, dk], v [b, h, dv], beta [b, h],
    S [b, h, dk, dv]; float32.  Returns (o [b, h, dv], the new state); a
    row with ``a == 0`` and ``beta == 0`` keeps its state bit for bit.

    The state is read twice and written once: with ``S' = Diag(alpha) S``,
    ``u = v - S^T (alpha k)`` and ``o = S^T (alpha q) + beta (q . k) u``
    come from one pass over ``S``, the new state from a second."""
    alpha = jnp.exp(a)
    u = v - jnp.sum(S * (alpha * k)[..., None], axis=-2)
    o = jnp.sum(S * (alpha * q)[..., None], axis=-2) + \
        (beta * jnp.sum(q * k, -1))[..., None] * u
    S = S * alpha[..., None] + (beta[..., None] * k)[..., None] \
        * u[..., None, :]
    return o, S
