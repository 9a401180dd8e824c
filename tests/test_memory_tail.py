"""Memory-bound-tail package tests (ISSUE 3).

Covers: the Pallas vocab-blockwise fused cross-entropy (forward + grad
parity vs the reference path, ignore_index, the no-[B,S,V]-fp32
jaxpr/cost-model assertion), the flash-attention backward vs jax.grad of
naive attention, TrainStep microbatch gradient accumulation equivalence,
the device-prefetch iterator, DataLoader prefetch lifecycle, and the
soft-label + weight mean-reduction fix.

Everything runs interpret-mode on CPU (conftest pins JAX_PLATFORMS).
"""

import time

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from paddle_tpu.core.dispatch import unwrap  # noqa: E402


# ---------------------------------------------------------------------------
# fused cross-entropy
# ---------------------------------------------------------------------------

class TestFusedCrossEntropyKernel:
    def test_fwd_matches_logsumexp(self):
        from paddle_tpu.ops.pallas.cross_entropy import \
            fused_softmax_cross_entropy
        rng = np.random.default_rng(0)
        for t, v in [(64, 256), (100, 384), (8, 128)]:
            x = jnp.asarray(rng.standard_normal((t, v)) * 3, jnp.float32)
            lbl = jnp.asarray(rng.integers(0, v, (t,)), jnp.int32)
            got = fused_softmax_cross_entropy(x, lbl)
            ref = jax.nn.logsumexp(x, axis=-1) - \
                jnp.take_along_axis(x, lbl[:, None], 1)[:, 0]
            assert float(jnp.abs(got - ref).max()) < 1e-5

    def test_grad_matches_softmax_minus_onehot(self):
        from paddle_tpu.ops.pallas.cross_entropy import \
            fused_softmax_cross_entropy
        rng = np.random.default_rng(1)
        t, v = 48, 256
        x = jnp.asarray(rng.standard_normal((t, v)), jnp.float32)
        lbl = jnp.asarray(rng.integers(0, v, (t,)), jnp.int32)
        # weighted sum exercises per-token cotangents
        w = jnp.asarray(rng.standard_normal((t,)), jnp.float32)
        g = jax.grad(lambda a: jnp.sum(
            fused_softmax_cross_entropy(a, lbl) * w))(x)
        p = jax.nn.softmax(x, axis=-1)
        onehot = jax.nn.one_hot(lbl, v)
        ref = (p - onehot) * w[:, None]
        assert float(jnp.abs(g - ref).max()) < 1e-5

    def test_vocab_not_multiple_of_128_rejected(self):
        from paddle_tpu.ops.pallas.cross_entropy import (
            fused_ce_eligible, fused_softmax_cross_entropy)
        assert not fused_ce_eligible(8, 200)
        with pytest.raises(ValueError):
            fused_softmax_cross_entropy(jnp.zeros((8, 200)),
                                        jnp.zeros((8,), jnp.int32))


class TestFusedCrossEntropyRouting:
    @pytest.fixture(autouse=True)
    def _force_fused(self, monkeypatch):
        monkeypatch.setenv("PADDLE_TPU_FUSED_CE", "1")

    def _ref(self, monkeypatch, *args, **kw):
        import paddle_tpu.nn.functional as F
        monkeypatch.setenv("PADDLE_TPU_FUSED_CE", "0")
        try:
            return unwrap(F.cross_entropy(*args, **kw))
        finally:
            monkeypatch.setenv("PADDLE_TPU_FUSED_CE", "1")

    @pytest.mark.parametrize("reduction", ["mean", "sum", "none"])
    def test_parity_with_ignore_index(self, monkeypatch, reduction):
        import paddle_tpu.nn.functional as F
        rng = np.random.default_rng(2)
        B, S, V = 2, 24, 256
        x = jnp.asarray(rng.standard_normal((B, S, V)), jnp.float32)
        lbl = jnp.asarray(rng.integers(0, V, (B, S)), jnp.int32)
        lbl = lbl.at[0, :7].set(-100)
        got = unwrap(F.cross_entropy(x, lbl, reduction=reduction))
        ref = self._ref(monkeypatch, x, lbl, reduction=reduction)
        err = float(jnp.abs(jnp.asarray(got) - jnp.asarray(ref)).max())
        assert err < 1e-5, err

    def test_grad_parity_bf16(self, monkeypatch):
        import paddle_tpu.nn.functional as F
        rng = np.random.default_rng(3)
        B, S, V = 2, 16, 256
        x = jnp.asarray(rng.standard_normal((B, S, V)), jnp.bfloat16)
        lbl = jnp.asarray(rng.integers(0, V, (B, S)), jnp.int32)
        lbl = lbl.at[1, -3:].set(-100)

        def loss(a):
            return unwrap(F.cross_entropy(a, lbl))

        g1 = jax.grad(loss)(x)
        monkeypatch.setenv("PADDLE_TPU_FUSED_CE", "0")
        g0 = jax.grad(loss)(x)
        err = float(jnp.abs((g1 - g0).astype(jnp.float32)).max())
        assert err < 1e-4, err
        # ignored rows contribute no gradient
        assert float(jnp.abs(g1.astype(jnp.float32)[1, -3:]).max()) == 0.0

    def test_no_fp32_vocab_intermediate_in_grad_jaxpr(self):
        """Acceptance: with bf16 logits the fused path's fwd+bwd jaxpr
        holds NO fp32 [B*S, V]-sized value outside the Pallas kernels —
        the fp32 log-softmax (and the one-hot) never materialize."""
        import paddle_tpu.nn.functional as F
        from paddle_tpu.analysis.tracing import walk_eqns
        B, S, V = 2, 64, 512
        x = jnp.zeros((B, S, V), jnp.bfloat16)
        lbl = jnp.zeros((B, S), jnp.int32)

        jaxpr = jax.make_jaxpr(
            jax.grad(lambda a: unwrap(F.cross_entropy(a, lbl))))(x)
        big_fp32 = []
        for eqn, path, _w in walk_eqns(jaxpr):
            if "pallas_call[" in path:
                continue  # kernel-internal avals are block-shaped anyway
            for ovar in eqn.outvars:
                av = getattr(ovar, "aval", None)
                if av is not None and av.dtype == jnp.float32 and \
                        int(np.prod(av.shape)) >= B * S * V:
                    big_fp32.append((eqn.primitive.name, av.shape))
        assert not big_fp32, big_fp32

    def test_cost_model_charges_fused_traffic(self, monkeypatch):
        """The analysis cost model accounts a pallas_call at CALL level:
        the fused CE moves strictly fewer (unfused-model) bytes than the
        reference lowering of the same loss+grad."""
        import paddle_tpu.nn.functional as F
        from paddle_tpu.analysis import check
        B, S, V = 2, 64, 512
        x = jnp.zeros((B, S, V), jnp.bfloat16)
        lbl = jnp.zeros((B, S), jnp.int32)

        def loss(a, b):
            return unwrap(F.cross_entropy(a, b))

        def cost():
            rep = check(jax.grad(loss), x, lbl, passes=["cost-model"])
            return rep.extras["cost"]

        fused = cost()
        monkeypatch.setenv("PADDLE_TPU_FUSED_CE", "0")
        fallback = cost()
        assert fused.total_bytes < 0.5 * fallback.total_bytes, \
            (fused.total_bytes, fallback.total_bytes)

    def test_route_counter_increments(self):
        import paddle_tpu.nn.functional as F
        from paddle_tpu.observability import default_registry
        x = jnp.zeros((4, 256), jnp.float32)
        lbl = jnp.zeros((4,), jnp.int32)
        unwrap(F.cross_entropy(x, lbl))
        m = default_registry().get("paddle_tpu_fused_ce_calls_total")
        got = {"/".join(k): c.value() for k, c in m.series()}
        assert got.get("fused", 0) >= 1


# ---------------------------------------------------------------------------
# flash-attention backward
# ---------------------------------------------------------------------------

class TestFlashBackwardVsNaive:
    @pytest.mark.parametrize("causal", [True, False])
    def test_grads_match_naive_attention(self, causal):
        from paddle_tpu.ops.pallas.flash_attention import flash_attention
        from paddle_tpu.nn.functional.attention import _sdpa_reference
        rng = np.random.default_rng(4)
        b, s, h, hk, d = 1, 256, 4, 2, 128
        q = jnp.asarray(rng.standard_normal((b, s, h, d)), jnp.float32)
        k = jnp.asarray(rng.standard_normal((b, s, hk, d)), jnp.float32)
        v = jnp.asarray(rng.standard_normal((b, s, hk, d)), jnp.float32)

        def loss_flash(*a):
            return (flash_attention(*a, causal=causal)
                    .astype(jnp.float32) ** 2).mean()

        def loss_ref(*a):
            return (unwrap(_sdpa_reference(*a, is_causal=causal))
                    .astype(jnp.float32) ** 2).mean()

        gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b_ in zip(gf, gr):
            assert float(jnp.abs(a - b_).max()) < 1e-4


# ---------------------------------------------------------------------------
# microbatch gradient accumulation
# ---------------------------------------------------------------------------

class TestGradAccum:
    def _train(self, accum, steps=3, lr=1e-3):
        import paddle_tpu as pp
        from paddle_tpu.jit import TrainStep
        from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
        pp.seed(0)
        cfg = LlamaConfig.tiny()
        rng = np.random.default_rng(0)
        ids = rng.integers(0, cfg.vocab_size, (4, 17)).astype(np.int32)
        batch = {"input_ids": ids[:, :-1], "labels": ids[:, 1:]}
        model = LlamaForCausalLM(cfg)
        opt = pp.optimizer.AdamW(learning_rate=lr,
                                 parameters=model.parameters())
        step = TrainStep(model, opt, accum_steps=accum)
        losses = [float(step(batch)) for _ in range(steps)]
        return losses, step.params

    @pytest.mark.slow
    def test_accum4_matches_full_batch(self):
        l1, p1 = self._train(1)
        l4, p4 = self._train(4)
        for a, b in zip(l1, l4):
            assert abs(a - b) < 1e-4, (l1, l4)
        for n in p1:
            d = float(jnp.abs(p1[n].astype(jnp.float32)
                              - p4[n].astype(jnp.float32)).max())
            assert d < 1e-4, (n, d)

    def test_indivisible_batch_raises(self):
        with pytest.raises(ValueError, match="not divisible"):
            self._train(3, steps=1)

    def test_accum_histogram_observed(self):
        from paddle_tpu.observability import default_registry
        self._train(2, steps=1)
        m = default_registry().get("paddle_tpu_train_accum_microbatches")
        assert m is not None and m.series()

    def test_invalid_accum_steps(self):
        import paddle_tpu as pp
        from paddle_tpu.jit import TrainStep
        from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
        model = LlamaForCausalLM(LlamaConfig.tiny())
        opt = pp.optimizer.SGD(learning_rate=1e-2,
                               parameters=model.parameters())
        with pytest.raises(ValueError, match="accum_steps"):
            TrainStep(model, opt, accum_steps=0)


# ---------------------------------------------------------------------------
# device prefetch
# ---------------------------------------------------------------------------

class TestDevicePrefetch:
    def test_order_values_and_device_residency(self):
        from paddle_tpu.io import device_prefetch

        def gen():
            for i in range(10):
                yield {"x": np.full((2, 2), i, np.float32)}

        with device_prefetch(gen(), depth=2) as it:
            got = list(it)
        assert len(got) == 10
        assert all(isinstance(b["x"], jax.Array) for b in got)
        assert [float(b["x"][0, 0]) for b in got] == list(range(10))

    def test_early_close_stops_thread(self):
        from paddle_tpu.io import device_prefetch

        def gen():
            for i in range(1000):
                yield np.zeros((4,), np.float32)

        it = device_prefetch(gen(), depth=2)
        next(it)
        it.close()
        deadline = time.time() + 5
        while it._thread.is_alive() and time.time() < deadline:
            time.sleep(0.01)
        assert not it._thread.is_alive(), "prefetch thread leaked"

    def test_exception_propagates(self):
        from paddle_tpu.io import device_prefetch

        def bad():
            yield np.zeros((2,), np.float32)
            raise RuntimeError("boom")

        it = device_prefetch(bad())
        next(it)
        with pytest.raises(RuntimeError, match="boom"):
            while True:
                next(it)

    def test_sharded_placement_with_mesh(self):
        from jax.sharding import PartitionSpec as P
        from paddle_tpu.io import device_prefetch
        devs = jax.devices()
        if len(devs) < 2:
            pytest.skip("needs the virtual multi-device CPU mesh")
        mesh = jax.sharding.Mesh(np.array(devs[:2]), ("dp",))

        def gen():
            yield np.arange(8, dtype=np.float32).reshape(2, 4)

        with device_prefetch(gen(), mesh=mesh, spec=P("dp")) as it:
            out = next(it)
        assert len(out.sharding.device_set) == 2
        np.testing.assert_array_equal(
            np.asarray(out), np.arange(8, dtype=np.float32).reshape(2, 4))

    def test_prefetch_metrics_exist(self):
        from paddle_tpu.io import device_prefetch
        from paddle_tpu.observability import default_registry
        with device_prefetch(iter([np.zeros(2)]), depth=1) as it:
            list(it)
        assert default_registry().get(
            "paddle_tpu_prefetch_batches_total").value() >= 1


# ---------------------------------------------------------------------------
# DataLoader prefetch lifecycle (satellite fix)
# ---------------------------------------------------------------------------

class TestDataLoaderAbandonment:
    def test_early_break_then_close_leaves_no_thread(self):
        from paddle_tpu.io import DataLoader, TensorDataset
        ds = TensorDataset([np.arange(400, dtype=np.float32)
                            .reshape(100, 4)])
        dl = DataLoader(ds, batch_size=5, use_buffer_reader=True,
                        prefetch_factor=2)
        it = iter(dl)
        next(it)  # consume one batch, abandon the rest
        it.close()
        deadline = time.time() + 5
        while it._thread.is_alive() and time.time() < deadline:
            time.sleep(0.01)
        assert not it._thread.is_alive(), "dataloader prefetch thread leaked"

    def test_context_manager_and_reuse(self):
        from paddle_tpu.io import DataLoader, TensorDataset
        ds = TensorDataset([np.arange(40, dtype=np.float32).reshape(10, 4)])
        dl = DataLoader(ds, batch_size=2, use_buffer_reader=True)
        with iter(dl) as it:
            next(it)
        # a fresh epoch works after closing the previous iterator
        assert sum(1 for _ in dl) == 5

    def test_close_idempotent(self):
        from paddle_tpu.io import DataLoader, TensorDataset
        ds = TensorDataset([np.zeros((4, 2), np.float32)])
        it = iter(DataLoader(ds, batch_size=2, use_buffer_reader=True))
        list(it)
        it.close()
        it.close()


# ---------------------------------------------------------------------------
# soft-label + weight mean reduction (satellite fix)
# ---------------------------------------------------------------------------

class TestSoftLabelWeightMean:
    def test_divides_by_weight_sum(self):
        import paddle_tpu.nn.functional as F
        rng = np.random.default_rng(5)
        n, c = 6, 5
        x = jnp.asarray(rng.standard_normal((n, c)), jnp.float32)
        lbl = jnp.asarray(rng.integers(0, c, (n,)), jnp.int32)
        soft = jax.nn.one_hot(lbl, c)
        w = jnp.asarray(rng.uniform(0.5, 2.0, (c,)), jnp.float32)
        got = float(unwrap(F.cross_entropy(x, soft, weight=w,
                                           soft_label=True)))
        # reference math: weighted per-row CE, normalized by sum of
        # per-row weights — identical to the hard-label weighted branch
        logp = jax.nn.log_softmax(x, axis=-1)
        per = -jnp.take_along_axis(logp, lbl[:, None], 1)[:, 0]
        wr = jnp.take(w, lbl)
        want = float(jnp.sum(per * wr) / jnp.sum(wr))
        assert abs(got - want) < 1e-5
        # and matches the hard-label branch exactly
        hard = float(unwrap(F.cross_entropy(x, lbl, weight=w)))
        assert abs(got - hard) < 1e-5

    def test_unweighted_soft_label_unchanged(self):
        import paddle_tpu.nn.functional as F
        rng = np.random.default_rng(6)
        x = jnp.asarray(rng.standard_normal((4, 3)), jnp.float32)
        soft = jax.nn.softmax(jnp.asarray(
            rng.standard_normal((4, 3)), jnp.float32))
        got = float(unwrap(F.cross_entropy(x, soft, soft_label=True)))
        logp = jax.nn.log_softmax(x, axis=-1)
        want = float(jnp.mean(-jnp.sum(soft * logp, axis=-1)))
        assert abs(got - want) < 1e-5
