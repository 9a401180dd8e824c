"""The flash-attention backward: two Pallas kernels, the only backward.

Interpret-mode dq / dk / dv against float32 naive attention over the
query-group sizes, maskings, tile shapes and dtypes the kernels see; the
walk's causal bounds; the tile rule's choices at the shapes the cells
run, frozen; and a jaxpr walk that finds no score tensor outside the
kernels.  What Mosaic makes of them is `test_flash_attention_tpu.py`'s.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops.pallas import flash_attention as FA


def _naive(q, k, v, causal):
    """[b, h, s, d] float32 attention, kv heads repeated to the group."""
    rep = q.shape[1] // k.shape[1]
    k, v = (jnp.repeat(x, rep, axis=1) for x in (k, v))
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / q.shape[-1] ** 0.5
    if causal:
        n = q.shape[2]
        s = jnp.where(jnp.tril(jnp.ones((n, n), bool)), s, -1e30)
    return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), v)


def _case(seed, b, s, hq, hk, d, dtype):
    rng = np.random.default_rng(seed)
    mk = lambda h: jnp.asarray(rng.standard_normal((b, h, s, d)), dtype)
    return mk(hq), mk(hk), mk(hk), mk(hq)


def _bwd(q, k, v, g, causal, **tiles):
    s, d = q.shape[2], q.shape[3]
    out, lse = FA._fwd_pallas(q, k, v, scale=d ** -0.5, causal=causal,
                              block_q=min(128, s), block_k=min(128, s),
                              interpret=True)
    return FA._bwd_pallas(q, k, v, out, lse, g, scale=d ** -0.5,
                          causal=causal, interpret=True, **tiles)


# (s, tile_q, tile_k, q_span, k_span): tile_q != tile_k both ways with
# several spans; one tile the whole sequence; the rule's own choice
TILINGS = {
    "tq64_tk128": (256, 64, 128, 128, 256),
    "tq128_tk64": (256, 128, 64, 256, 128),
    "one_tile": (128, 128, 128, 128, 128),
    "rule": (256, None, None, None, None),
}


# float32 everywhere; bf16 on the grouped, hand-tiled cases
CASES = [(rep, causal, tiling, dtype)
         for dtype in ("float32", "bfloat16")
         for rep in (1, 2, 4) for causal in (True, False)
         for tiling in sorted(TILINGS)
         if dtype == "float32" or (rep > 1 and tiling != "rule")]


@pytest.mark.parametrize(
    "rep,causal,tiling,dtype", CASES,
    ids=[f"rep{r}-{'causal' if c else 'full'}-{t}-{d}"
         for r, c, t, d in CASES])
def test_backward_matches_naive_attention(rep, causal, tiling, dtype):
    s, tq, tk, qs, ks = TILINGS[tiling]
    dt = jnp.dtype(dtype)
    q, k, v, g = _case(rep, 1, s, 4, 4 // rep, 64, dt)
    f32 = lambda x: x.astype(jnp.float32)
    want = jax.grad(lambda q, k, v: jnp.sum(_naive(q, k, v, causal)
                                            * f32(g)), (0, 1, 2))(
        f32(q), f32(k), f32(v))
    got = _bwd(q, k, v, g, causal, tile_q=tq, tile_k=tk, q_span=qs,
               k_span=ks)
    tol = 2e-5 if dtype == "float32" else 2e-2
    for name, a, w in zip(("dq", "dk", "dv"), got, want):
        assert a.dtype == dt and a.shape == w.shape
        err = float(jnp.max(jnp.abs(f32(a) - w)) / jnp.max(jnp.abs(w)))
        assert err < tol, (name, err)


@pytest.mark.parametrize("tile_q,tile_k,span", [
    (512, 512, 2048), (256, 512, 1024), (512, 256, 512), (128, 128, 128)])
def test_causal_walk_copies_nothing_above_the_diagonal(tile_q, tile_k, span):
    """Clamped index maps: the dk/dv grid's steps before a k tile's
    diagonal name the span that holds it, the dq grid's steps after a q
    tile's diagonal name the span that holds that — consecutive equal
    block indices, so the pipeline copies nothing for them."""
    s = 4096
    n_span = s // span
    m = np.arange(n_span)
    walked, _, stat = FA._dkv_maps(tile_k, span, True, xp=np)
    for j in range(s // tile_k):
        first = (j * tile_k) // span
        assert list(walked(0, 0, j, m)[2]) == [max(first, x) for x in m]
        assert list(stat(0, 0, j, m)[2]) == [max(first, x) for x in m]
        # the first live span holds a row at or past the k tile's first
        assert (first + 1) * span > j * tile_k >= first * span
    _, walked, _ = FA._dq_maps(2, tile_q, span, True, xp=np)
    for i in range(s // tile_q):
        last = ((i + 1) * tile_q - 1) // span
        assert list(walked(0, 3, i, m)[2]) == [min(last, x) for x in m]
        assert walked(0, 3, i, m)[1] == 1          # q head 3 -> kv head 1
    # not causal: every span is its own
    walked, _, _ = FA._dkv_maps(tile_k, span, False, xp=np)
    assert list(walked(0, 0, 0, m)[2]) == list(m)


# the rule's choices at the shapes that reach it, frozen:
# (s, d, rep, itemsize) -> (tile, q_span, k_span)
RULE = {
    "train_1chip_s4096_rep2_bf16": ((4096, 128, 2, 2), (512, 4096, 4096)),
    "mistral_s4096_rep4_bf16": ((4096, 128, 4, 2), (512, 2048, 4096)),
    "mha_s2048_rep1_bf16": ((2048, 128, 1, 2), (512, 2048, 2048)),
    "s8192_rep4_bf16": ((8192, 128, 4, 2), (512, 2048, 8192)),
    "s32768_rep2_bf16": ((32768, 128, 2, 2), (512, 4096, 8192)),
    "s4096_rep2_f32": ((4096, 128, 2, 4), (512, 2048, 4096)),
    "d256_s8192_rep2_bf16": ((8192, 256, 2, 2), (512, 2048, 4096)),
    "s1024_padded_d128_rep1": ((1024, 128, 1, 2), (512, 1024, 1024)),
    "s768_is_256s": ((768, 128, 2, 2), (256, 768, 768)),
    "s384_is_128s": ((384, 128, 2, 2), (128, 384, 384)),
    "s64_is_one_tile": ((64, 64, 2, 4), (64, 64, 64)),
}


@pytest.mark.parametrize("shape", sorted(RULE))
def test_tile_rule_frozen(shape):
    args, want = RULE[shape]
    tile, q_span, k_span = got = FA.bwd_tiles(*args)
    assert got == want
    s = args[0]
    assert s % tile == 0 and q_span % tile == 0 and k_span % tile == 0
    assert s % q_span == 0 and s % k_span == 0


def _outvars_outside_kernels(jaxpr):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            continue
        yield from eqn.outvars
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _outvars_outside_kernels(sub)


@pytest.mark.parametrize("causal", [True, False], ids=["causal", "full"])
def test_no_score_tensor_outside_the_kernels(causal):
    """No equation of the differentiated call, outside the pallas_calls,
    puts out a [..., s, tile] score tensor: the recompute is the
    kernels'.  (d = 64 here, so no [.., s, d] operand can pass for one.)"""
    b, s, h, hk, d = 2, 512, 4, 2, 64
    S = lambda heads: jax.ShapeDtypeStruct((b, s, heads, d), jnp.bfloat16)
    loss = lambda q, k, v: jnp.sum(FA.flash_attention(
        q, k, v, causal=causal, interpret=True).astype(jnp.float32))
    jaxpr = jax.make_jaxpr(jax.grad(loss, (0, 1, 2)))(S(h), S(hk), S(hk))
    text = str(jaxpr)
    assert "flash_bwd_dq" in text and "flash_bwd_dkv" in text
    seen = 0
    for var in _outvars_outside_kernels(jaxpr.jaxpr):
        shape = getattr(var.aval, "shape", ())
        seen += 1
        assert not (len(shape) >= 2 and shape[-2] == s
                    and shape[-1] >= 128), (var, shape)
    assert seen > 10


def test_one_backward_in_the_tree():
    """ROADMAP D1: the loser went with its knob."""
    import inspect

    from paddle_tpu.ops.pallas import autotune
    assert not hasattr(FA, "_bwd_blockwise")
    assert not hasattr(FA, "flash_bwd_env")
    assert "pallas_bwd" not in inspect.signature(FA.flash_attention).parameters
    assert "pallas_bwd" not in inspect.signature(
        autotune.flash_block_sizes).parameters
    assert autotune.flash_key(4, 4096, 16, 8, 128, "bfloat16", True,
                              backend="tpu") == \
        "b4s4096h16k8d128bfloat16c1@tpu"
