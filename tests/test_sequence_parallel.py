"""Ring attention / Ulysses / Pallas flash attention tests.

Parity oracle: the dense XLA attention on the full (unsharded) sequence."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

import paddle_tpu.distributed as dist
from paddle_tpu.nn.functional.attention import _sdpa_reference


def make_qkv(b=2, s=64, h=4, d=16, kv_heads=None, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (b, s, h, d), jnp.float32) * 0.5
    k = jax.random.normal(ks[1], (b, s, kv_heads or h, d), jnp.float32) * 0.5
    v = jax.random.normal(ks[2], (b, s, kv_heads or h, d), jnp.float32) * 0.5
    return q, k, v


def sp_mesh(n=8):
    return Mesh(np.array(jax.devices()[:n]), ("sp",))


class TestRingAttention:
    @pytest.mark.parametrize("causal", [False, True])
    def test_matches_dense(self, causal):
        q, k, v = make_qkv()
        mesh = sp_mesh()
        fn = dist.make_ring_attention(mesh, causal=causal)
        got = jax.jit(fn)(q, k, v)
        want = _sdpa_reference(q, k, v, is_causal=causal)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)

    def test_gqa_broadcast(self):
        q, k, v = make_qkv(h=8, kv_heads=2)
        mesh = sp_mesh()
        got = jax.jit(dist.make_ring_attention(mesh, causal=True))(q, k, v)
        want = _sdpa_reference(q, jnp.repeat(k, 4, 2), jnp.repeat(v, 4, 2),
                               is_causal=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)

    @pytest.mark.slow  # ring bwd trace; CI SPMD + MoE gates run it
    def test_grads_match_dense(self):
        q, k, v = make_qkv(s=32)
        mesh = sp_mesh(4)
        ring = dist.make_ring_attention(mesh, causal=True)

        g1 = jax.jit(jax.grad(lambda q, k, v: (ring(q, k, v) ** 2).sum(),
                              argnums=(0, 1, 2)))(q, k, v)
        g2 = jax.grad(lambda q, k, v: (
            _sdpa_reference(q, k, v, is_causal=True) ** 2).sum(),
            argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g1, g2):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=5e-4, atol=1e-5)


class TestUlysses:
    @pytest.mark.parametrize("causal", [False, True])
    def test_matches_dense(self, causal):
        q, k, v = make_qkv(h=8)
        mesh = sp_mesh()
        fn = dist.make_ulysses_attention(mesh, causal=causal)
        got = jax.jit(fn)(q, k, v)
        want = _sdpa_reference(q, k, v, is_causal=causal)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)

    def test_heads_not_divisible_raises(self):
        q, k, v = make_qkv(h=4)  # 4 heads, sp=8
        mesh = sp_mesh()
        fn = dist.make_ulysses_attention(mesh)
        with pytest.raises(ValueError, match="not divisible"):
            jax.jit(fn)(q, k, v)


class TestPallasFlashAttention:
    @pytest.mark.parametrize("causal", [False, True])
    def test_matches_dense(self, causal):
        from paddle_tpu.ops.pallas.flash_attention import flash_attention
        q, k, v = make_qkv(s=256, d=64)
        got = flash_attention(q, k, v, causal=causal, interpret=True)
        want = _sdpa_reference(q, k, v, is_causal=causal)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)

    def test_gqa(self):
        from paddle_tpu.ops.pallas.flash_attention import flash_attention
        q, k, v = make_qkv(s=128, h=8, kv_heads=2, d=64)
        got = flash_attention(q, k, v, causal=True, interpret=True)
        want = _sdpa_reference(q, jnp.repeat(k, 4, 2), jnp.repeat(v, 4, 2),
                               is_causal=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)

    @pytest.mark.slow
    def test_backward_blockwise(self):
        from paddle_tpu.ops.pallas.flash_attention import flash_attention
        q, k, v = make_qkv(s=128, d=64)
        g1 = jax.grad(lambda q, k, v: (flash_attention(
            q, k, v, causal=True, interpret=True) ** 2).sum(),
            argnums=(0, 1, 2))(q, k, v)
        g2 = jax.grad(lambda q, k, v: (_sdpa_reference(
            q, k, v, is_causal=True) ** 2).sum(), argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g1, g2):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=1e-5)

    def test_indivisible_seq_raises(self):
        from paddle_tpu.ops.pallas.flash_attention import flash_attention
        q, k, v = make_qkv(s=100, d=64)
        with pytest.raises(ValueError, match="divisible"):
            flash_attention(q, k, v, block_q=64, block_k=64, interpret=True)

    @pytest.mark.slow
    def test_backward_pallas_gqa_matches_dense(self):
        # grouped-GQA through the Pallas dkv kernel (query-group inner axis)
        from paddle_tpu.ops.pallas.flash_attention import flash_attention
        q, k, v = make_qkv(s=256, h=8, kv_heads=2, d=64)
        g1 = jax.grad(lambda q, k, v: (flash_attention(
            q, k, v, causal=True, interpret=True, block_q=64,
            block_k=128) ** 2).sum(), argnums=(0, 1, 2))(q, k, v)
        g2 = jax.grad(lambda q, k, v: (_sdpa_reference(
            q, jnp.repeat(k, 4, 2), jnp.repeat(v, 4, 2),
            is_causal=True) ** 2).sum(), argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g1, g2):  # repeat is inside the oracle lambda, so
            # autodiff already sums kv grads over the query group
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=1e-5)


class TestFusedRMSNorm:
    def _ref(self, x, w, res, eps=1e-5):
        h = x.astype(jnp.float32)
        if res is not None:
            h = h + res.astype(jnp.float32)
        inv = jax.lax.rsqrt(jnp.mean(h * h, -1, keepdims=True) + eps)
        return (h * inv * w).astype(x.dtype), h.astype(x.dtype)

    @pytest.mark.parametrize("with_res", [False, True])
    def test_forward_matches(self, with_res):
        from paddle_tpu.ops.pallas.rmsnorm import fused_rmsnorm
        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.standard_normal((4, 16, 128)), jnp.float32)
        w = jnp.asarray(rng.standard_normal((128,)), jnp.float32)
        res = jnp.asarray(rng.standard_normal((4, 16, 128)),
                          jnp.float32) if with_res else None
        y, h = fused_rmsnorm(x, w, residual=res, interpret=True)
        wy, wh = self._ref(x, w, res)
        np.testing.assert_allclose(np.asarray(y), np.asarray(wy),
                                   rtol=2e-6, atol=2e-6)
        np.testing.assert_allclose(np.asarray(h), np.asarray(wh),
                                   rtol=2e-6, atol=2e-6)

    @pytest.mark.parametrize("with_res", [False, True])
    def test_grads_match(self, with_res):
        from paddle_tpu.ops.pallas.rmsnorm import fused_rmsnorm
        rng = np.random.default_rng(1)
        x = jnp.asarray(rng.standard_normal((2, 8, 128)), jnp.float32)
        w = jnp.asarray(rng.standard_normal((128,)), jnp.float32)
        res = jnp.asarray(rng.standard_normal((2, 8, 128)),
                          jnp.float32) if with_res else None

        def lf(fused):
            def f(x, w, *r):
                rr = r[0] if with_res else None
                if fused:
                    y, h = fused_rmsnorm(x, w, residual=rr, interpret=True)
                else:
                    y, h = self._ref(x, w, rr)
                return jnp.sum(y ** 2) + jnp.sum(jnp.tanh(h))
            return f

        args = (x, w, res) if with_res else (x, w)
        an = (0, 1, 2) if with_res else (0, 1)
        gf = jax.grad(lf(True), argnums=an)(*args)
        gr = jax.grad(lf(False), argnums=an)(*args)
        for a, b in zip(gf, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=1e-5)

    def test_fallback_on_untileable_shapes(self):
        from paddle_tpu.ops.pallas.rmsnorm import fused_rmsnorm
        x = jnp.ones((3, 5, 100), jnp.float32)   # d % 128 != 0
        w = jnp.ones((100,), jnp.float32)
        y, h = fused_rmsnorm(x, w)
        wy, wh = self._ref(x, w, None)
        np.testing.assert_allclose(np.asarray(y), np.asarray(wy),
                                   rtol=1e-6)


class TestAutotuneCache:
    def test_measures_once_and_persists(self, tmp_path, monkeypatch):
        from paddle_tpu.ops.pallas import autotune as at
        monkeypatch.setenv("PADDLE_TPU_AUTOTUNE_CACHE",
                           str(tmp_path / "cache.json"))
        at.clear_cache()
        calls = []

        def bench(c):
            calls.append(c)
            return {16: 2.0, 32: 1.0, 64: 3.0}[c[0]]

        got = at.autotune("op", "k1", [(16,), (32,), (64,)], bench, (16,))
        assert tuple(got) == (32,)
        assert len(calls) == 3
        # second call: cached, no measurement
        got2 = at.autotune("op", "k1", [(16,), (32,), (64,)], bench, (16,))
        assert tuple(got2) == (32,) and len(calls) == 3
        # new process simulation: reload from disk
        at._mem_cache.clear()
        at._loaded = False
        got3 = at.autotune("op", "k1", [(16,), (32,), (64,)], bench, (16,))
        assert tuple(got3) == (32,) and len(calls) == 3

    def test_sweep_inside_a_jit_trace_measures_and_failures_are_counted(
            self, tmp_path, monkeypatch, capsys):
        """First use of a shape is inside a jit trace: the sweep still
        runs on concrete values.  A failing candidate is counted and
        named; a sweep where every candidate fails raises."""
        from paddle_tpu.observability import default_registry
        from paddle_tpu.ops.pallas import autotune as at
        monkeypatch.setenv("PADDLE_TPU_AUTOTUNE_CACHE",
                           str(tmp_path / "c.json"))
        at.clear_cache()
        from paddle_tpu.ops.pallas.flash_attention import flash_attention
        inner = jax.jit(lambda q: jnp.sum(flash_attention(
            q, q, q, interpret=True, autotune=False)))

        def bench(c):   # a jitted Pallas kernel on concrete arrays
            if c == (1,):
                raise ValueError("mosaic says no")
            q = jnp.ones((1, 128, 1, 128), jnp.float32)
            return abs(float(np.asarray(inner(q)))) * c[0]

        def traced(x):
            assert tuple(at.autotune("op", "kt", [(1,), (2,), (3,)],
                                     bench, (9,))) == (2,)
            return x + 1

        failed = lambda: dict(
            ("/".join(k), c.value()) for k, c in default_registry().get(
                "paddle_tpu_autotune_cache_total").series()
        ).get("op/candidate_failed", 0)
        before = failed()
        jax.jit(traced)(1.0)
        assert failed() == before + 1
        assert "candidate (1,) failed: ValueError: mosaic says no" in \
            capsys.readouterr().err
        with pytest.raises(RuntimeError, match="all 2 candidates failed"):
            at.autotune("op", "kf", [(1,), (2,)], lambda c: 1 / 0, (9,))

    def test_disabled_uses_default(self, tmp_path, monkeypatch):
        from paddle_tpu.ops.pallas import autotune as at
        monkeypatch.setenv("PADDLE_TPU_AUTOTUNE_CACHE",
                           str(tmp_path / "c.json"))
        monkeypatch.setenv("PADDLE_TPU_AUTOTUNE", "0")
        at.clear_cache()
        got = at.autotune("op", "k2", [(1,), (2,)],
                          lambda c: 1 / 0, (9,))
        assert got == (9,)

    def test_flash_candidates_respect_vmem(self):
        from paddle_tpu.ops.pallas.autotune import _flash_candidates
        cands = _flash_candidates(8192, 128, "bfloat16")
        assert (128, 128) in cands and (512, 512) in cands
        assert all(bq * bk * 4 < 10 * (1 << 20) for bq, bk in cands)


# ---------------------------------------------------------------------------
# flash-backed ring attention (ISSUE 18 tentpole, layer 2)
# ---------------------------------------------------------------------------


def _stripe(x, sp):
    """Natural order -> striped shards in rank order: global token
    j*sp + r lands at shard r, local slot j."""
    return jnp.concatenate([x[:, r::sp] for r in range(sp)], axis=1)


def _unstripe(y, sp):
    b, s = y.shape[:2]
    return jnp.swapaxes(y.reshape((b, sp, s // sp) + y.shape[2:]), 1, 2) \
        .reshape(y.shape)


class TestRingFlash:
    """``impl="flash"`` / PADDLE_TPU_RING_FLASH=1: per-hop flash kernel +
    lse merge.  Oracle: dense attention on the full sequence."""

    @pytest.mark.parametrize("sp", [2, 4])
    @pytest.mark.parametrize("causal", [False, True])
    def test_matches_dense_fp32(self, sp, causal):
        q, k, v = make_qkv(s=128)
        mesh = sp_mesh(sp)
        fn = dist.make_ring_attention(mesh, causal=causal, impl="flash")
        got = jax.jit(fn)(q, k, v)
        want = _sdpa_reference(q, k, v, is_causal=causal)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)

    @pytest.mark.parametrize("sp", [2, 4])
    def test_matches_dense_bf16(self, sp):
        q, k, v = make_qkv(s=128)
        q, k, v = (x.astype(jnp.bfloat16) for x in (q, k, v))
        mesh = sp_mesh(sp)
        fn = dist.make_ring_attention(mesh, causal=True, impl="flash")
        got = np.asarray(jax.jit(fn)(q, k, v), np.float32)
        want = np.asarray(_sdpa_reference(
            q.astype(jnp.float32), k.astype(jnp.float32),
            v.astype(jnp.float32), is_causal=True), np.float32)
        assert np.isfinite(got).all()
        np.testing.assert_allclose(got, want, rtol=3e-2, atol=3e-2)

    def test_gqa(self):
        q, k, v = make_qkv(s=128, h=8, kv_heads=2)
        mesh = sp_mesh(4)
        fn = dist.make_ring_attention(mesh, causal=True, impl="flash")
        got = jax.jit(fn)(q, k, v)
        want = _sdpa_reference(q, jnp.repeat(k, 4, 2), jnp.repeat(v, 4, 2),
                               is_causal=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)

    def _ring_jaxpr(self, monkeypatch, knob):
        monkeypatch.setenv("PADDLE_TPU_RING_FLASH", knob)
        mesh = sp_mesh(4)
        fn = dist.make_ring_attention(mesh, causal=True)

        def f(q, k, v):    # fresh closure: make_jaxpr caches by identity
            return fn(q, k, v)

        q, k, v = make_qkv(s=32)
        return str(jax.make_jaxpr(f)(q, k, v))

    def test_knob_routes_and_zero_restores_dense_path(self, monkeypatch):
        """Acceptance: knob off keeps the exact dense-fold program (no
        pallas_call, byte-identical before/after a knob-on trace); =1
        swaps the per-hop fold to the flash kernel."""
        j_base = self._ring_jaxpr(monkeypatch, "0")
        j_on = self._ring_jaxpr(monkeypatch, "1")
        j_off = self._ring_jaxpr(monkeypatch, "0")
        assert "pallas_call" not in j_base
        assert "pallas_call" in j_on
        assert j_base == j_off

    def test_overlap_knob_composes(self, monkeypatch):
        """PR 15's ppermute-before-fold overlap stays correct under the
        flash fold."""
        monkeypatch.setenv("PADDLE_TPU_COLLECTIVE_OVERLAP", "1")
        q, k, v = make_qkv(s=128)
        mesh = sp_mesh(4)
        fn = dist.make_ring_attention(mesh, causal=True, impl="flash")
        got = jax.jit(fn)(q, k, v)
        want = _sdpa_reference(q, k, v, is_causal=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)

    @pytest.mark.slow  # full bwd trace through the scan of switches
    def test_grads_match_dense(self):
        q, k, v = make_qkv(s=64)
        mesh = sp_mesh(4)
        ring = dist.make_ring_attention(mesh, causal=True, impl="flash")
        g1 = jax.jit(jax.grad(lambda q, k, v: (ring(q, k, v) ** 2).sum(),
                              argnums=(0, 1, 2)))(q, k, v)
        g2 = jax.grad(lambda q, k, v: (
            _sdpa_reference(q, k, v, is_causal=True) ** 2).sum(),
            argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g1, g2):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=5e-4, atol=1e-5)

    @pytest.mark.slow  # seq >> 2048: the long-context acceptance run
    def test_long_context_seq_4096(self):
        q, k, v = make_qkv(b=1, s=4096, h=2, d=64)
        mesh = sp_mesh(8)
        fn = dist.make_ring_attention(mesh, causal=True, impl="flash")
        got = jax.jit(fn)(q, k, v)
        want = _sdpa_reference(q, k, v, is_causal=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)


class TestStripedRing:
    """Striped layout (local slot j == global j*sp + rank): causal load
    balance.  Inputs/outputs travel striped; the oracle stripes the
    dense result."""

    @pytest.mark.parametrize("sp", [2, 4])
    def test_matches_dense_fp32(self, sp):
        q, k, v = make_qkv(s=64)
        mesh = sp_mesh(sp)
        fn = dist.make_striped_ring_attention(mesh)
        got = jax.jit(fn)(_stripe(q, sp), _stripe(k, sp), _stripe(v, sp))
        want = _stripe(_sdpa_reference(q, k, v, is_causal=True), sp)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)

    def test_unstripe_roundtrip(self):
        x = jnp.arange(2 * 16 * 4 * 8, dtype=jnp.float32) \
            .reshape(2, 16, 4, 8)
        assert np.array_equal(np.asarray(_unstripe(_stripe(x, 4), 4)),
                              np.asarray(x))

    def test_bf16_causal_finite_and_matches(self):
        """Regression (ISSUE 18 satellite): striped hops with src > rank
        fully mask their first rows — before the finfo mask + alive
        guard, bf16 causal folded exp(mask - mask) == 1 garbage into
        those rows (NaN/garbage outputs)."""
        sp = 4
        q, k, v = make_qkv(s=64, seed=9)
        qb, kb, vb = (x.astype(jnp.bfloat16) for x in (q, k, v))
        mesh = sp_mesh(sp)
        fn = dist.make_striped_ring_attention(mesh)
        got = np.asarray(jax.jit(fn)(
            _stripe(qb, sp), _stripe(kb, sp), _stripe(vb, sp)), np.float32)
        assert np.isfinite(got).all()
        want = np.asarray(_stripe(
            _sdpa_reference(q, k, v, is_causal=True), sp), np.float32)
        np.testing.assert_allclose(got, want, rtol=3e-2, atol=3e-2)


class TestMaskValue:
    def test_finite_and_summable_per_dtype(self):
        from paddle_tpu.distributed.sequence_parallel import mask_value
        for dt in (jnp.float32, jnp.bfloat16, jnp.float16):
            m = mask_value(dt)
            assert np.isfinite(m)
            # two masked scores (or mask + any finite score) must not
            # overflow the dtype — the -1e30 literal broke this for fp16
            assert np.isfinite(np.asarray(m + m, jnp.dtype(dt)))

    def test_padded_tail_rows_stay_finite(self):
        """A causal ring over a padded tail (queries whose keys are all
        masked in some hop) must produce finite outputs — the alive
        guard zeroes dead rows instead of folding exp(0)."""
        from paddle_tpu.distributed import shard_map
        from paddle_tpu.distributed.sequence_parallel import (
            striped_ring_attention)
        from jax.sharding import PartitionSpec as P
        sp = 4
        q, k, v = make_qkv(s=32, seed=11)
        qb, kb, vb = (_stripe(x, sp).astype(jnp.bfloat16)
                      for x in (q, k, v))
        mesh = sp_mesh(sp)
        spec = P(None, "sp", None, None)
        fn = shard_map(striped_ring_attention, mesh=mesh,
                       in_specs=(spec, spec, spec), out_specs=spec)
        out = np.asarray(fn(qb, kb, vb), np.float32)
        assert np.isfinite(out).all()
