"""Loss functionals (parity: python/paddle/nn/functional/loss.py).

cross_entropy keeps logits in fp32 for the softmax (TPU numerics), computes
log-softmax fused — this is the op the reference implements as
c_softmax_with_cross_entropy for TP; the sharded variant lives in
paddle_tpu/distributed/tp.py."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from paddle_tpu.core.dispatch import eager_op


def _reduce(loss, reduction):
    if reduction == "mean":
        return jnp.mean(loss)
    if reduction == "sum":
        return jnp.sum(loss)
    return loss


def _ce_route_counter():
    from paddle_tpu.observability import default_registry
    return default_registry().counter(
        "paddle_tpu_fused_ce_calls_total",
        "cross_entropy routing decisions by path (counted at trace time)",
        labelnames=("path",))


@eager_op
def cross_entropy(input, label, weight=None, ignore_index=-100,
                  reduction="mean", soft_label=False, axis=-1,
                  use_softmax=True, label_smoothing=0.0):
    # Fused Pallas fast path (hard labels, no class weights): the vocab
    # axis streams through VMEM blockwise, so neither the fp32
    # log-softmax nor the one-hot backward ever materializes at
    # [batch, seq, vocab].  MUST route before the fp32 cast below — the
    # cast is itself the [B, S, V] fp32 intermediate being avoided.
    if (use_softmax and not soft_label and weight is None
            and label_smoothing == 0.0 and input.ndim >= 2
            and axis in (-1, input.ndim - 1)):
        lbl = label
        if lbl.ndim == input.ndim and lbl.shape[-1] == 1:
            lbl = jnp.squeeze(lbl, axis=-1)
        v = input.shape[-1]
        if lbl.ndim == input.ndim - 1 and \
                jnp.issubdtype(lbl.dtype, jnp.integer):
            from paddle_tpu.ops.pallas.cross_entropy import (
                fused_ce_eligible, fused_ce_enabled,
                fused_softmax_cross_entropy)
            t = int(lbl.size)
            if fused_ce_enabled() and fused_ce_eligible(t, v):
                _ce_route_counter().labels(path="fused").inc()
                valid = lbl != ignore_index
                safe = jnp.where(valid, lbl, 0)
                per = fused_softmax_cross_entropy(
                    input.reshape(-1, v), safe.reshape(-1))
                loss = jnp.where(valid, per.reshape(lbl.shape), 0.0)
                if reduction == "mean":
                    denom = jnp.maximum(
                        jnp.sum(valid.astype(jnp.float32)), 1.0)
                    return jnp.sum(loss) / denom
                return _reduce(loss, reduction)
            _ce_route_counter().labels(path="fallback").inc()
    x = input.astype(jnp.float32)
    if use_softmax:
        logp = jax.nn.log_softmax(x, axis=axis)
    else:
        logp = jnp.log(jnp.maximum(x, 1e-30))
    n_classes = x.shape[axis]

    if soft_label:
        tgt = label.astype(jnp.float32)
        if label_smoothing > 0:
            tgt = (1 - label_smoothing) * tgt + label_smoothing / n_classes
        loss = -jnp.sum(tgt * logp, axis=axis)
        if weight is not None:
            w = jnp.sum(tgt * weight, axis=axis)
            loss = loss * w
            # weighted mean divides by the sum of weights (matching the
            # hard-label branch below), not the element count
            if reduction == "mean":
                return jnp.sum(loss) / jnp.maximum(jnp.sum(w), 1e-12)
        return _reduce(loss, reduction)

    lbl = label
    if lbl.ndim == x.ndim and lbl.shape[axis] == 1:
        lbl = jnp.squeeze(lbl, axis=axis)
    valid = lbl != ignore_index
    safe_lbl = jnp.where(valid, lbl, 0)
    picked = jnp.take_along_axis(
        logp, jnp.expand_dims(safe_lbl, axis), axis=axis)
    picked = jnp.squeeze(picked, axis=axis)
    if label_smoothing > 0:
        smooth = jnp.mean(logp, axis=axis)
        picked = (1 - label_smoothing) * picked + label_smoothing * smooth
    loss = -picked
    if weight is not None:
        w = jnp.take(weight, safe_lbl)
        loss = loss * w
        loss = jnp.where(valid, loss, 0.0)
        if reduction == "mean":
            return jnp.sum(loss) / jnp.maximum(
                jnp.sum(jnp.where(valid, w, 0.0)), 1e-12)
    loss = jnp.where(valid, loss, 0.0)
    if reduction == "mean":
        denom = jnp.maximum(jnp.sum(valid.astype(jnp.float32)), 1.0)
        return jnp.sum(loss) / denom
    return _reduce(loss, reduction)


@eager_op
def softmax_with_cross_entropy(logits, label, soft_label=False,
                               ignore_index=-100, return_softmax=False,
                               axis=-1):
    x = logits.astype(jnp.float32)
    logp = jax.nn.log_softmax(x, axis=axis)
    if soft_label:
        loss = -jnp.sum(label.astype(jnp.float32) * logp, axis=axis,
                        keepdims=True)
    else:
        lbl = label
        squeeze = lbl.ndim == x.ndim and lbl.shape[axis] == 1
        if squeeze:
            lbl = jnp.squeeze(lbl, axis=axis)
        valid = lbl != ignore_index
        safe = jnp.where(valid, lbl, 0)
        picked = jnp.squeeze(jnp.take_along_axis(
            logp, jnp.expand_dims(safe, axis), axis=axis), axis=axis)
        loss = jnp.where(valid, -picked, 0.0)[..., None]
    if return_softmax:
        return loss, jax.nn.softmax(x, axis=axis)
    return loss


@eager_op
def mse_loss(input, label, reduction="mean"):
    return _reduce(jnp.square(input - label), reduction)


@eager_op
def l1_loss(input, label, reduction="mean"):
    return _reduce(jnp.abs(input - label), reduction)


@eager_op
def smooth_l1_loss(input, label, reduction="mean", delta=1.0):
    d = input - label
    ad = jnp.abs(d)
    loss = jnp.where(ad < delta, 0.5 * d * d / delta, ad - 0.5 * delta)
    return _reduce(loss, reduction)


@eager_op
def nll_loss(input, label, weight=None, ignore_index=-100, reduction="mean"):
    valid = label != ignore_index
    safe = jnp.where(valid, label, 0)
    picked = jnp.take_along_axis(input, safe[..., None] if input.ndim == 2
                                 else jnp.expand_dims(safe, 1), axis=1)
    picked = jnp.squeeze(picked, axis=1)
    loss = -picked
    if weight is not None:
        w = jnp.take(weight, safe)
        loss = loss * w
        loss = jnp.where(valid, loss, 0.0)
        if reduction == "mean":
            return jnp.sum(loss) / jnp.sum(jnp.where(valid, w, 0.0))
    loss = jnp.where(valid, loss, 0.0)
    if reduction == "mean":
        return jnp.sum(loss) / jnp.maximum(
            jnp.sum(valid.astype(jnp.float32)), 1.0)
    return _reduce(loss, reduction)


@eager_op
def binary_cross_entropy(input, label, weight=None, reduction="mean"):
    x = jnp.clip(input.astype(jnp.float32), 1e-12, 1 - 1e-12)
    loss = -(label * jnp.log(x) + (1 - label) * jnp.log1p(-x))
    if weight is not None:
        loss = loss * weight
    return _reduce(loss, reduction)


@eager_op
def binary_cross_entropy_with_logits(logit, label, weight=None,
                                     reduction="mean", pos_weight=None):
    x = logit.astype(jnp.float32)
    neg_abs = -jnp.abs(x)
    if pos_weight is not None:
        log_w = (pos_weight - 1) * label + 1
        loss = (1 - label) * x + log_w * (jnp.log1p(jnp.exp(neg_abs)) +
                                          jnp.maximum(-x, 0))
    else:
        loss = jnp.maximum(x, 0) - x * label + jnp.log1p(jnp.exp(neg_abs))
    if weight is not None:
        loss = loss * weight
    return _reduce(loss, reduction)


@eager_op
def kl_div(input, label, reduction="mean", log_target=False):
    if log_target:
        loss = jnp.exp(label) * (label - input)
    else:
        safe = jnp.maximum(label, 1e-12)
        loss = label * (jnp.log(safe) - input)
    if reduction == "batchmean":
        return jnp.sum(loss) / input.shape[0]
    return _reduce(loss, reduction)


@eager_op
def margin_ranking_loss(input, other, label, margin=0.0, reduction="mean"):
    loss = jnp.maximum(-label * (input - other) + margin, 0.0)
    return _reduce(loss, reduction)


@eager_op
def hinge_embedding_loss(input, label, margin=1.0, reduction="mean"):
    loss = jnp.where(label == 1, input, jnp.maximum(margin - input, 0.0))
    return _reduce(loss, reduction)


@eager_op
def cosine_embedding_loss(input1, input2, label, margin=0.0, reduction="mean"):
    cos = jnp.sum(input1 * input2, axis=-1) / jnp.maximum(
        jnp.linalg.norm(input1, axis=-1) * jnp.linalg.norm(input2, axis=-1),
        1e-12)
    loss = jnp.where(label == 1, 1 - cos, jnp.maximum(cos - margin, 0.0))
    return _reduce(loss, reduction)


@eager_op
def triplet_margin_loss(input, positive, negative, margin=1.0, p=2.0,
                        epsilon=1e-6, swap=False, reduction="mean"):
    def dist(a, b):
        return jnp.sum(jnp.abs(a - b) ** p + epsilon, axis=-1) ** (1.0 / p)
    dp = dist(input, positive)
    dn = dist(input, negative)
    if swap:
        dn2 = dist(positive, negative)
        dn = jnp.minimum(dn, dn2)
    loss = jnp.maximum(dp - dn + margin, 0.0)
    return _reduce(loss, reduction)


@eager_op
def ctc_loss(log_probs, labels, input_lengths, label_lengths, blank=0,
             reduction="mean", norm_by_times=False):
    # log_probs: [T, B, C] (paddle layout) — use a scan over time with the
    # standard alpha recursion in log space; static shapes for XLA.
    T, B, C = log_probs.shape
    L = labels.shape[1]
    S = 2 * L + 1
    lp = log_probs.astype(jnp.float32)

    # extended label sequence with blanks: [B, S]
    ext = jnp.full((B, S), blank, dtype=labels.dtype)
    ext = ext.at[:, 1::2].set(labels)
    neg_inf = jnp.asarray(-1e30, jnp.float32)

    # transition allowed from s-2 when ext[s] != blank and ext[s] != ext[s-2]
    ext_prev2 = jnp.pad(ext[:, :-2], ((0, 0), (2, 0)), constant_values=-1)
    allow_skip = (ext != blank) & (ext != ext_prev2)

    def emit(t_lp, s_idx):
        return jnp.take_along_axis(t_lp, s_idx, axis=1)

    alpha0 = jnp.full((B, S), neg_inf)
    alpha0 = alpha0.at[:, 0].set(emit(lp[0], ext[:, 0:1])[:, 0])
    alpha0 = alpha0.at[:, 1].set(
        jnp.where(L > 0, emit(lp[0], ext[:, 1:2])[:, 0], neg_inf))

    def step(alpha, t_lp):
        a_prev = alpha
        a_shift1 = jnp.pad(alpha[:, :-1], ((0, 0), (1, 0)),
                           constant_values=-1e30)
        a_shift2 = jnp.pad(alpha[:, :-2], ((0, 0), (2, 0)),
                           constant_values=-1e30)
        a_shift2 = jnp.where(allow_skip, a_shift2, neg_inf)
        m = jnp.maximum(jnp.maximum(a_prev, a_shift1), a_shift2)
        m_safe = jnp.maximum(m, -1e29)
        # states with NO live incoming path have sum_exp == 0; log(0)
        # is -inf and its 1/0 cotangent turns the whole backward pass
        # NaN, so floor the sum and re-mask the result to the finite
        # sentinel (the floor keeps the log's gradient finite even for
        # the branch jnp.where does not select)
        sum_exp = (jnp.exp(a_prev - m_safe) + jnp.exp(a_shift1 - m_safe)
                   + jnp.exp(a_shift2 - m_safe))
        tot = jnp.where(
            m <= -1e29, neg_inf,
            m_safe + jnp.log(jnp.maximum(sum_exp, 1e-30)))
        new_alpha = tot + emit(t_lp, ext)
        return new_alpha, new_alpha

    _, alphas = jax.lax.scan(step, alpha0, lp[1:])
    alphas = jnp.concatenate([alpha0[None], alphas], axis=0)  # [T, B, S]

    # gather alpha at t = input_length-1, s = 2*label_length and 2*label_length-1
    t_idx = jnp.clip(input_lengths - 1, 0, T - 1)
    per_b = jnp.take_along_axis(
        alphas, t_idx[None, :, None], axis=0)[0]  # [B, S]
    s1 = jnp.clip(2 * label_lengths, 0, S - 1)
    s2 = jnp.clip(2 * label_lengths - 1, 0, S - 1)
    a1 = jnp.take_along_axis(per_b, s1[:, None], axis=1)[:, 0]
    a2 = jnp.take_along_axis(per_b, s2[:, None], axis=1)[:, 0]
    m = jnp.maximum(a1, a2)
    m_safe = jnp.maximum(m, -1e29)
    sum_exp = jnp.exp(a1 - m_safe) + jnp.exp(a2 - m_safe)
    ll = jnp.where(m <= -1e29, neg_inf,
                   m_safe + jnp.log(jnp.maximum(sum_exp, 1e-30)))
    loss = -ll
    if reduction == "mean":
        return jnp.mean(loss / jnp.maximum(label_lengths, 1))
    return _reduce(loss, reduction)


@eager_op
def sigmoid_focal_loss(logit, label, normalizer=None, alpha=0.25, gamma=2.0,
                       reduction="sum"):
    p = jax.nn.sigmoid(logit.astype(jnp.float32))
    ce = jnp.maximum(logit, 0) - logit * label + jnp.log1p(
        jnp.exp(-jnp.abs(logit)))
    p_t = p * label + (1 - p) * (1 - label)
    loss = ce * ((1 - p_t) ** gamma)
    if alpha >= 0:
        a_t = alpha * label + (1 - alpha) * (1 - label)
        loss = a_t * loss
    if normalizer is not None:
        loss = loss / normalizer
    return _reduce(loss, reduction)


@eager_op
def square_error_cost(input, label):
    return jnp.square(input - label)


# (the public __all__ is computed once at the end of the module)


@eager_op
def fused_linear_cross_entropy(hidden, weight, labels, chunk_size=None,
                               reduction="mean", ignore_index=-100):
    """Fused lm-head + softmax cross-entropy over token chunks.

    Reference role: the fused softmax-with-cross-entropy kernels
    (phi/kernels/fusion, fused c_softmax_with_cross_entropy) — the lm-head
    logits [T, V] are never materialized: a ``lax.scan`` walks chunks of
    rows, and a chunk against the whole head gives those rows' complete
    logits, hence their logsumexp, their probabilities, their rows of dh
    and their share of dW in one visit.  With ``reduction`` "mean" / "sum"
    the per-token weight is known in the forward pass and the upstream
    cotangent is one scalar, so under differentiation the forward walk
    makes dh and dW itself (three head products a chunk, no logits
    recomputed) and the backward only scales them; the undifferentiated
    call runs the loss half alone (one product).  ``reduction="none"``
    learns its per-token cotangents only in the backward, so the same walk
    runs again there.

    hidden: [T, d] (flatten batch x seq first); weight: [d, V];
    labels: [T] int (ignore_index entries contribute no loss/grad).
    chunk_size: ROWS a chunk (it counted vocab columns when the walk was
    over the vocabulary).  None, as every model passes, is the largest
    power of two whose float32 logits chunk stays within 1 GiB, never more
    than T: 2048 rows at V 92544, 8192 at V 32768.
    The products take the operands in their own dtype with float32
    accumulation; statistics, probabilities and the dW accumulator are
    float32.  Differentiable wrt hidden and weight.
    """
    lbl = jnp.asarray(labels).astype(jnp.int32)
    mask = lbl != ignore_index
    safe = jnp.where(mask, lbl, 0)
    rows = _ce_chunk_rows(hidden.shape[0], weight.shape[1], chunk_size)
    if reduction == "none":
        # zeroing outside the custom_vjp also zeroes the pad cotangents, so
        # ignored tokens contribute neither loss nor dh/dW
        return jnp.where(
            mask, _fused_ce_per_token(hidden, weight, safe, rows), 0.0)
    omega = mask.astype(jnp.float32)
    if reduction == "mean":
        omega = omega / jnp.maximum(mask.sum(), 1)
    return _fused_ce_reduced(hidden, weight, safe, omega, rows)


from functools import partial as _partial  # noqa: E402

_CE_LOGITS_BYTES = 1 << 30


def _ce_chunk_rows(t, v, chunk_size=None):
    """Rows of one chunk of the token walk."""
    if chunk_size is None:
        chunk_size = 1 << ((_CE_LOGITS_BYTES // (4 * v)).bit_length() - 1)
    return max(1, min(int(chunk_size), t))


def _ce_chunk(h_c, w, lbl_c, om_c, want_grads):
    """One chunk of rows against the whole head: the rows' weighted losses
    and, when wanted, their dh [r, d] and their share of dW [d, V] under
    the per-token weights om_c (all float32)."""
    logits = jnp.dot(h_c, w, preferred_element_type=jnp.float32)   # [r, V]
    m = logits.max(axis=1)
    lse = m + jnp.log(jnp.exp(logits - m[:, None]).sum(axis=1))
    hit = lbl_c[:, None] == jnp.arange(w.shape[1])[None, :]
    gold = jnp.where(hit, logits, 0.0).sum(axis=1)
    # a weightless row (ignored, padded) reads 0 whatever its logits hold
    loss = jnp.where(om_c != 0, (lse - gold) * om_c, 0.0)
    if not want_grads:
        return loss
    delta = ((jnp.exp(logits - lse[:, None]) - hit)
             * om_c[:, None]).astype(w.dtype)
    dh_c = jax.lax.dot_general(delta, w, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)
    dw_c = jax.lax.dot_general(h_c, delta, (((0,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)
    return loss, dh_c, dw_c


def _ce_walk(h, w, lbl, omega, rows, want_grads):
    """Scan the chunk body over the rows: the weighted per-token losses
    [T] and, when wanted, float32 dh [T, d] and dW [d, V].

    Chunk i holds rows i, i + n, i + 2n, ...: any partition of the rows is
    valid (the loss is a sum over tokens), and this one leaves a batch
    sharding of the row axis on the rows of every chunk, where contiguous
    chunks would each live on one shard.  Rows padded up to a chunk
    multiple carry weight 0."""
    dt = jnp.result_type(h.dtype, w.dtype)
    h, w = h.astype(dt), w.astype(dt)
    t = h.shape[0]
    n = -(-t // rows)

    def chunks(x):
        x = jnp.pad(x, ((0, n * rows - t),) + ((0, 0),) * (x.ndim - 1))
        return jnp.swapaxes(x.reshape((rows, n) + x.shape[1:]), 0, 1)

    def unchunk(x):
        x = jnp.swapaxes(x, 0, 1)
        return x.reshape((n * rows,) + x.shape[2:])[:t]

    xs = (chunks(h), chunks(lbl), chunks(omega))
    if not want_grads:
        _, loss = jax.lax.scan(
            lambda c, x: (c, _ce_chunk(x[0], w, x[1], x[2], False)),
            None, xs)
        return unchunk(loss)

    def step(dw, x):
        loss, dh_c, dw_c = _ce_chunk(x[0], w, x[1], x[2], True)
        return dw + dw_c, (loss, dh_c)

    dw, (loss, dh) = jax.lax.scan(
        step, jnp.zeros(w.shape, jnp.float32), xs)
    return unchunk(loss), unchunk(dh), dw


@_partial(jax.custom_vjp, nondiff_argnums=(4,))
def _fused_ce_reduced(h, w, lbl, omega, rows):
    """sum_t omega_t * loss_t, omega being the mask ("sum") or mask / count
    ("mean"): known in the forward pass, so the forward can make dh, dW."""
    return _ce_walk(h, w, lbl, omega, rows, False).sum()


def _fused_ce_reduced_fwd(h, w, lbl, omega, rows):
    # the gradients for a unit cotangent, from the visit that made the loss
    loss, dh, dw = _ce_walk(h, w, lbl, omega, rows, True)
    return loss.sum(), (dh.astype(h.dtype), dw.astype(w.dtype))


def _fused_ce_reduced_bwd(rows, res, g):
    # one scalar scales both (the constant 1 under value_and_grad, which
    # XLA folds)
    dh, dw = ((g * x.astype(jnp.float32)).astype(x.dtype) for x in res)
    return dh, dw, None, None


_fused_ce_reduced.defvjp(_fused_ce_reduced_fwd, _fused_ce_reduced_bwd)


@_partial(jax.custom_vjp, nondiff_argnums=(3,))
def _fused_ce_per_token(h, w, lbl, rows):
    return _ce_walk(h, w, lbl, jnp.ones(lbl.shape, jnp.float32), rows,
                    False)


def _fused_ce_per_token_fwd(h, w, lbl, rows):
    return _fused_ce_per_token(h, w, lbl, rows), (h, w, lbl)


def _fused_ce_per_token_bwd(rows, res, g):
    # per-token cotangents are the weights of the walk: a recompute
    h, w, lbl = res
    _, dh, dw = _ce_walk(h, w, lbl, g, rows, True)
    return dh.astype(h.dtype), dw.astype(w.dtype), None


_fused_ce_per_token.defvjp(_fused_ce_per_token_fwd, _fused_ce_per_token_bwd)


# recompute the public surface to include the fused loss above



# -- round-4 loss additions (reference python/paddle/nn/functional/loss.py) --

@eager_op
def huber_loss(input, label, delta=1.0, reduction="mean"):
    """Reference huber_loss: quadratic inside |d|<=delta, linear outside
    (smooth_l1 without the 1/delta normalization)."""
    d = input - label
    ad = jnp.abs(d)
    loss = jnp.where(ad <= delta, 0.5 * d * d,
                     delta * (ad - 0.5 * delta))
    return _reduce(loss, reduction)


@eager_op
def poisson_nll_loss(input, label, log_input=True, full=False,
                     epsilon=1e-8, reduction="mean"):
    """Poisson negative log likelihood (reference poisson_nll_loss)."""
    if log_input:
        loss = jnp.exp(input) - label * input
    else:
        loss = input - label * jnp.log(input + epsilon)
    if full:
        stirling = label * jnp.log(label) - label + \
            0.5 * jnp.log(2.0 * jnp.pi * label)
        loss = loss + jnp.where(label > 1, stirling, 0.0)
    return _reduce(loss, reduction)


@eager_op
def gaussian_nll_loss(input, label, variance, full=False, epsilon=1e-6,
                      reduction="mean"):
    """Gaussian negative log likelihood with predicted variance
    (reference gaussian_nll_loss)."""
    var = jnp.maximum(variance, epsilon)
    loss = 0.5 * (jnp.log(var) + jnp.square(input - label) / var)
    if full:
        loss = loss + 0.5 * jnp.log(2.0 * jnp.pi)
    return _reduce(loss, reduction)


@eager_op
def multi_margin_loss(input, label, p=1, margin=1.0, weight=None,
                      reduction="mean"):
    """Multi-class margin loss (reference multi_margin_loss):
    mean_j!=y max(0, margin - x_y + x_j)^p."""
    n, c = input.shape
    x_y = jnp.take_along_axis(input, label[:, None], axis=1)   # [N, 1]
    viol = jnp.maximum(0.0, margin - x_y + input) ** p         # [N, C]
    if weight is not None:
        viol = viol * jnp.take(weight, label)[:, None]
    mask = jnp.arange(c)[None, :] != label[:, None]
    loss = jnp.sum(jnp.where(mask, viol, 0.0), axis=1) / c
    return _reduce(loss, reduction)


@eager_op
def log_loss(input, label, epsilon=1e-4):
    """Binary log loss on probabilities (reference log_loss)."""
    return -label * jnp.log(input + epsilon) \
        - (1.0 - label) * jnp.log(1.0 - input + epsilon)


@eager_op
def dice_loss(input, label, epsilon=1e-5):
    """Dice loss over softmax probabilities (reference dice_loss:
    input [N, ..., C] probs, label [N, ..., 1] int)."""
    lbl = jnp.squeeze(label, axis=-1)
    onehot = jax.nn.one_hot(lbl, input.shape[-1], dtype=input.dtype)
    reduce_axes = tuple(range(1, input.ndim))
    inter = jnp.sum(input * onehot, axis=reduce_axes)
    union = jnp.sum(input, axis=reduce_axes) + \
        jnp.sum(onehot, axis=reduce_axes)
    return jnp.mean(1.0 - (2.0 * inter + epsilon) / (union + epsilon))


@eager_op
def npair_loss(anchor, positive, labels, l2_reg=0.002):
    """N-pair loss (reference npair_loss): CE over anchor@positive.T
    similarities + L2 on the embeddings."""
    sim = anchor @ positive.T                              # [N, N]
    n = sim.shape[0]
    logp = jax.nn.log_softmax(sim, axis=1)
    same = labels[:, None] == labels[None, :]
    w = same.astype(sim.dtype)
    w = w / jnp.sum(w, axis=1, keepdims=True)
    ce = -jnp.mean(jnp.sum(w * logp, axis=1))
    # reference coefficient: Beta = 0.25 (npair_loss l2loss term)
    reg = l2_reg * 0.25 * (jnp.mean(jnp.sum(jnp.square(anchor), axis=1))
                           + jnp.mean(jnp.sum(jnp.square(positive), axis=1)))
    return ce + reg


@eager_op
def pairwise_distance(x, y, p=2.0, epsilon=1e-6, keepdim=False):
    """p-norm of x - y along the last axis (reference
    nn/functional/distance.py)."""
    import math
    # epsilon is added to the SIGNED difference before |.| (reference adds
    # it to sub = x - y + eps), so negative components match bit-for-bit
    d = jnp.abs((x - y) + epsilon)
    if isinstance(p, (int, float)) and math.isinf(p):
        out = jnp.max(d, axis=-1) if p > 0 else jnp.min(d, axis=-1)
    else:
        out = jnp.sum(d ** p, axis=-1) ** (1.0 / p)
    return out[..., None] if keepdim else out


@eager_op
def margin_cross_entropy(logits, label, margin1=1.0, margin2=0.5,
                         margin3=0.0, scale=64.0, return_softmax=False,
                         reduction="mean"):
    """ArcFace-family margin softmax (reference margin_cross_entropy:
    target cos(theta) -> cos(margin1*theta + margin2) - margin3, scaled).
    `logits` are cosine similarities in [-1, 1]."""
    onehot = jax.nn.one_hot(label, logits.shape[-1], dtype=logits.dtype)
    cos = jnp.clip(logits, -1.0, 1.0)
    theta = jnp.arccos(cos)
    target = jnp.cos(margin1 * theta + margin2) - margin3
    adjusted = jnp.where(onehot > 0, target, cos) * scale
    logp = jax.nn.log_softmax(adjusted, axis=-1)
    loss = -jnp.sum(onehot * logp, axis=-1)
    loss = _reduce(loss, reduction)
    if return_softmax:
        return loss, jax.nn.softmax(adjusted, axis=-1)
    return loss


__all__ = [_n for _n, _v in list(globals().items())
           if not _n.startswith("_") and callable(_v)
           and (hasattr(_v, "__wrapped_pure__")
                or getattr(_v, "__module__", None) == __name__)]
