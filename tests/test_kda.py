"""``ops/kda.py`` — the gated delta rule whose decay is per channel —
against the recurrence written a token at a time, at a test's size on the
CPU: the chunked scan at chunk sizes that do and do not divide the span,
with a state carried in, with decays that take a channel to nothing in
one token and betas near 0 and 1, its masking, and the one-token update
after a scan.

Tolerances.  Everything is float32; the chunked form and the recurrence
are two orderings of the same sums (a triangular solve and three products
a chunk against a loop over positions), and a chunk's decays are
differences of running sums of the log-decay, which lose about one part
in 1e7 of the running sum: over the spans here outputs and states agree
to 3e-7 of their largest value, and the limit is TOL = 5e-6.  A state
rounded to bfloat16 between two spans moves the outputs by ~1e-3 (8
mantissa bits), and a recurrence without its delta correction or without
its decay by a tenth or more: the last tests show that each fails.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops import kda

TOL = 5e-6


def _inputs(length, seed=4, b=2, h=3, dk=16, dv=8, top=30.0):
    """q and k normalised as the layer hands them over; a channel's
    log-decay between -1e-3 and -``top`` a token (e^-30: nothing is
    left after one), times a factor that varies by position; beta
    between 0.02 and 0.98; a state to start from."""
    rng = np.random.default_rng(seed)
    f = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    q, k, v = unit(f(b, length, h, dk)) * dk ** -0.5, \
        unit(f(b, length, h, dk)), f(b, length, h, dv)
    rate = jnp.exp(jnp.asarray(rng.uniform(
        np.log(1e-3), np.log(top), (b, 1, h, dk)), jnp.float32))
    a = -rate * jax.nn.softplus(f(b, length, h, dk))
    beta = jax.nn.sigmoid(4.0 * f(b, length, h))
    return q, k, v, a, beta, f(b, h, dk, dv)


def _recurrence(q, k, v, a, beta, S, delta=True, decay=True):
    """The module's docstring, a position at a time; ``delta`` and
    ``decay`` leave a term out."""
    os = []
    for t in range(q.shape[1]):
        if decay:
            S = S * jnp.exp(a[:, t])[..., None]
        u = v[:, t]
        if delta:
            u = u - jnp.einsum("bhkv,bhk->bhv", S, k[:, t])
        S = S + (beta[:, t, :, None] * k[:, t])[..., None] * u[..., None, :]
        os.append(jnp.einsum("bhkv,bhk->bhv", S, q[:, t]))
    return jnp.stack(os, 1), S


def _gap(got, want):
    return float(jnp.abs(got - want).max()) / float(jnp.abs(want).max())


@pytest.mark.parametrize("length,chunk", [
    (24, 8),        # whole chunks
    (37, 8),        # a padded last chunk
    (64, 16),
    (40, 64),       # one chunk, longer than the span
])
def test_chunked_scan_is_the_recurrence(length, chunk):
    q, k, v, a, beta, S0 = _inputs(length)
    assert float(jnp.exp(a).min()) < 1e-9       # alpha near 0 somewhere
    assert float(beta.min()) < 0.05 and float(beta.max()) > 0.95
    o, S = kda.kda_scan(q, k, v, a, beta, S0, chunk)
    o_ref, S_ref = _recurrence(q, k, v, a, beta, S0)
    assert o.shape == o_ref.shape and _gap(o, o_ref) <= TOL
    assert _gap(S, S_ref) <= TOL


def test_the_step_is_the_recurrence():
    q, k, v, a, beta, S0 = _inputs(1)
    o, S = kda.kda_step(q[:, 0], k[:, 0], v[:, 0], a[:, 0], beta[:, 0], S0)
    o_ref, S_ref = _recurrence(q, k, v, a, beta, S0)
    assert _gap(o, o_ref[:, 0]) <= TOL and _gap(S, S_ref) <= TOL


def test_decays_of_hundreds_a_chunk_stay_finite():
    """A log-decay of -8 a token is -512 over a 64-token chunk: the
    textbook ``k * exp(-G)`` overflows float32 there, and the chunk form
    (``exp`` of non-positive differences only) is still the
    recurrence."""
    q, k, v, a, beta, S0 = _inputs(64, top=8.0)
    a = a.at[:, :, :, 0].set(-8.0)
    G = np.cumsum(np.asarray(a), axis=1)
    with np.errstate(over="ignore"):
        assert np.isinf(np.exp(-G.astype(np.float32))).any()
    o, S = kda.kda_scan(q, k, v, a, beta, S0, 64)
    assert bool(jnp.isfinite(o).all()) and bool(jnp.isfinite(S).all())
    o_ref, S_ref = _recurrence(q, k, v, a, beta, S0)
    assert _gap(o, o_ref) <= TOL and _gap(S, S_ref) <= TOL


def test_a_masked_position_leaves_the_state_bit_for_bit():
    """``a == 0`` and ``beta == 0``: a span of them hands the state back
    as it came, a tail of them after 12 positions leaves what the 12
    leave (whatever q, k and v hold there), and a row of a step that has
    them keeps its state while its neighbours move."""
    q, k, v, a, beta, S0 = _inputs(16)
    zero = lambda x, lo: x.at[:, lo:].set(0.0)
    _, S = kda.kda_scan(q, k, v, zero(a, 0), zero(beta, 0), S0, 8)
    assert np.array_equal(np.asarray(S), np.asarray(S0))
    o_pad, S_pad = kda.kda_scan(q, k, v, zero(a, 12), zero(beta, 12), S0, 8)
    o_cut, S_cut = kda.kda_scan(q[:, :12], k[:, :12], v[:, :12], a[:, :12],
                                beta[:, :12], S0, 8)
    assert np.array_equal(np.asarray(S_pad), np.asarray(S_cut))
    assert np.array_equal(np.asarray(o_pad[:, :12]), np.asarray(o_cut))
    _, S_other = kda.kda_scan(q, k, v.at[:, 12:].set(7.0), zero(a, 12),
                              zero(beta, 12), S0, 8)
    assert np.array_equal(np.asarray(S_pad), np.asarray(S_other))
    row = jnp.asarray([1.0, 0.0])[:, None]
    _, S1 = kda.kda_step(q[:, 0], k[:, 0], v[:, 0], a[:, 0] * row[..., None],
                         beta[:, 0] * row, S0)
    assert np.array_equal(np.asarray(S1[1]), np.asarray(S0[1]))
    assert not np.array_equal(np.asarray(S1[0]), np.asarray(S0[0]))


def test_a_step_after_a_scan_is_one_longer_scan():
    q, k, v, a, beta, S0 = _inputs(25)
    o_all, S_all = kda.kda_scan(q, k, v, a, beta, S0, 8)
    head = lambda x: x[:, :24]
    _, S = kda.kda_scan(*map(head, (q, k, v, a, beta)), S0, 8)
    o, S = kda.kda_step(q[:, 24], k[:, 24], v[:, 24], a[:, 24], beta[:, 24],
                        S)
    assert _gap(o, o_all[:, 24]) <= TOL and _gap(S, S_all) <= TOL


def test_a_bfloat16_state_between_two_spans_fails_the_tolerance():
    """The same scan in two spans of 16: carried in float32 it is the
    recurrence to TOL; with the state rounded to bfloat16 at the boundary
    the second span's outputs are off by far more."""
    q, k, v, a, beta, S0 = _inputs(32, top=1.0)
    o_ref, _ = _recurrence(q, k, v, a, beta, S0)
    first = [x[:, :16] for x in (q, k, v, a, beta)]
    second = [x[:, 16:] for x in (q, k, v, a, beta)]
    _, S = kda.kda_scan(*first, S0, 8)
    scale = float(jnp.abs(o_ref).max())
    o2, _ = kda.kda_scan(*second, S, 8)
    assert float(jnp.abs(o2 - o_ref[:, 16:]).max()) <= TOL * scale
    o2, _ = kda.kda_scan(*second, S.astype(jnp.bfloat16).astype(jnp.float32),
                         8)
    assert float(jnp.abs(o2 - o_ref[:, 16:]).max()) > 50 * TOL * scale


@pytest.mark.parametrize("left_out", ["delta", "decay"])
def test_a_term_left_out_fails_the_tolerance(left_out):
    """The recurrence without the delta correction (plain gated linear
    attention) or without the decay (the plain delta rule) is another
    function: the scan is a tenth or more of its size away from it."""
    q, k, v, a, beta, S0 = _inputs(24)
    o, _ = kda.kda_scan(q, k, v, a, beta, S0, 8)
    other, _ = _recurrence(q, k, v, a, beta, S0,
                           **{left_out: False})
    assert _gap(o, other) > 0.1


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_a_one_position_convolution_moves_the_tail_as_a_span_does(dtype):
    """``mamba2.causal_conv`` takes a decode step's new tail by a select
    (the batched slice is a loop over the rows on the TPU): the same bits
    as a two-position span cut to one or to no real position, and a row
    with ``valid == 0`` keeps its tail."""
    from paddle_tpu.ops import mamba2
    rng = np.random.default_rng(11)
    f = lambda *s: jnp.asarray(rng.normal(size=s), dtype)
    x, tail, w = f(5, 1, 12), f(5, 3, 12), f(4, 12)
    valid = jnp.asarray([1, 0, 1, 1, 0], jnp.int32)
    out, new = mamba2.causal_conv(x, tail, w, None, valid)
    wide = jnp.concatenate([x, f(5, 1, 12)], axis=1)
    out2, new2 = mamba2.causal_conv(wide, tail, w, None, valid)
    assert new.dtype == tail.dtype
    np.testing.assert_array_equal(np.asarray(new, np.float32),
                                  np.asarray(new2, np.float32))
    np.testing.assert_array_equal(np.asarray(out[:, 0]),
                                  np.asarray(out2[:, 0]))
    still = np.asarray(valid) == 0
    np.testing.assert_array_equal(np.asarray(new, np.float32)[still],
                                  np.asarray(tail, np.float32)[still])
    np.testing.assert_array_equal(np.asarray(new, np.float32)[~still, -1],
                                  np.asarray(x, np.float32)[~still, 0])
