"""API-surface sweep: incubate fused layers, sparse tensors, vision ops,
varlen attention, device memory stats, quant observers.

Reference test strategy per area noted inline (SURVEY §4 style: numeric
parity against a composed-from-primitives oracle).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as pp


class TestDeviceMemoryStats:
    def test_api_shape(self):
        # reference: paddle.device.cuda.memory_allocated surface; values may
        # be 0 where the backend exposes no stats (the CPU)
        assert isinstance(pp.device.memory_allocated(), int)
        assert isinstance(pp.device.max_memory_allocated(), int)
        assert isinstance(pp.device.memory_stats(), dict)
        assert pp.device.cuda.memory_allocated() >= 0
        assert pp.device.cuda.device_count() >= 1
        pp.device.cuda.empty_cache()


class TestVarlenAttention:
    def test_matches_per_sequence_dense(self):
        from paddle_tpu.nn.functional.attention import (_sdpa_reference,
                                                        flash_attn_unpadded)
        rng = np.random.default_rng(0)
        cu = np.array([0, 3, 8], np.int32)
        h, d = 2, 4
        q, k, v = (rng.normal(size=(8, h, d)).astype(np.float32)
                   for _ in range(3))
        for causal in (True, False):
            out, _ = flash_attn_unpadded(
                jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                jnp.asarray(cu), jnp.asarray(cu), 5, 5, causal=causal)
            out = np.asarray(out)
            for s, e in zip(cu[:-1], cu[1:]):
                ref = _sdpa_reference(jnp.asarray(q[s:e])[None],
                                      jnp.asarray(k[s:e])[None],
                                      jnp.asarray(v[s:e])[None],
                                      None, 0.0, causal)
                np.testing.assert_allclose(out[s:e], np.asarray(ref)[0],
                                           rtol=1e-5, atol=1e-5)

    def test_causal_bottom_right_aligned_decode(self):
        """seqlen_q=1 vs seqlen_k=10 (decode with KV cache): flash-attn
        >= 2.1 varlen semantics let the single query see ALL keys."""
        from paddle_tpu.nn.functional.attention import flash_attn_unpadded
        rng = np.random.default_rng(3)
        h, d = 1, 4
        k = rng.normal(size=(10, h, d)).astype(np.float32)
        v = rng.normal(size=(10, h, d)).astype(np.float32)
        q = rng.normal(size=(1, h, d)).astype(np.float32)
        out, _ = flash_attn_unpadded(
            jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
            jnp.asarray(np.array([0, 1], np.int32)),
            jnp.asarray(np.array([0, 10], np.int32)), 1, 10, causal=True)
        # oracle: plain softmax over all 10 keys
        s = (q[:, 0] @ k[:, 0].T) / np.sqrt(d)
        p = np.exp(s - s.max())
        p /= p.sum()
        want = p @ v[:, 0]
        np.testing.assert_allclose(np.asarray(out)[0, 0], want[0],
                                   rtol=1e-5, atol=1e-5)

    def test_no_cross_sequence_leak(self):
        from paddle_tpu.nn.functional.attention import flash_attn_unpadded
        cu = np.array([0, 2, 4], np.int32)
        q = np.zeros((4, 1, 2), np.float32)
        k = np.zeros((4, 1, 2), np.float32)
        v = np.zeros((4, 1, 2), np.float32)
        v[2:] = 100.0  # second sequence's values
        out, _ = flash_attn_unpadded(jnp.asarray(q), jnp.asarray(k),
                                     jnp.asarray(v), jnp.asarray(cu),
                                     jnp.asarray(cu), 2, 2)
        out = np.asarray(out)
        assert np.abs(out[:2]).max() == 0.0  # seq 1 never sees seq 2


class TestIncubateFused:
    def test_fused_linear_matches_linear(self):
        pp.seed(0)
        from paddle_tpu.incubate.nn import FusedLinear
        fl = FusedLinear(8, 4)
        lin = pp.nn.Linear(8, 4)
        lin.weight.set_value(fl.weight.numpy())
        lin.bias.set_value(fl.bias.numpy())
        x = pp.randn([3, 8])
        np.testing.assert_allclose(fl(x).numpy(), lin(x).numpy(), rtol=1e-5)

    def test_fused_mha_matches_composed(self):
        """post-LN fused attention == manual qkv/sdpa/linear/LN chain."""
        pp.seed(1)
        from paddle_tpu.incubate.nn import FusedMultiHeadAttention
        from paddle_tpu.nn import functional as F
        e, h = 8, 2
        attn = FusedMultiHeadAttention(e, h, dropout_rate=0.0,
                                       attn_dropout_rate=0.0)
        x = pp.randn([2, 5, e])
        out = attn(x).numpy()

        qkv_w = attn.qkv_weight.numpy()   # [3, h, hd, e]
        qkv_b = attn.qkv_bias.numpy()
        xr = x.numpy()
        qkv = np.einsum("bse,thde->bsthd", xr, qkv_w) + qkv_b[None, None]
        q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
        a = F.scaled_dot_product_attention(
            jnp.asarray(q), jnp.asarray(k), jnp.asarray(v))
        proj = np.einsum("bshd,hde->bse", np.asarray(a),
                         attn.linear_weight.numpy().reshape(h, e // h, e))
        proj = proj + attn.linear_bias.numpy()
        want = F.layer_norm(jnp.asarray(xr + proj), [e],
                            jnp.asarray(attn.ln_scale.numpy()),
                            jnp.asarray(attn.ln_bias.numpy()))
        np.testing.assert_allclose(out, np.asarray(want), rtol=1e-4,
                                   atol=1e-5)

    def test_encoder_layer_trains(self):
        pp.seed(2)
        from paddle_tpu.incubate.nn import FusedTransformerEncoderLayer
        enc = FusedTransformerEncoderLayer(8, 2, 16, dropout_rate=0.0)
        opt = pp.optimizer.SGD(learning_rate=0.1,
                               parameters=enc.parameters())
        x = pp.randn([2, 4, 8])
        losses = []
        for _ in range(3):
            loss = (enc(x) ** 2).mean()
            loss.backward()
            opt.step()
            opt.clear_grad()
            losses.append(float(loss.numpy()))
        assert losses[-1] < losses[0]

    def test_fused_dropout_add_eval_is_plain_add(self):
        from paddle_tpu.incubate.nn import FusedDropoutAdd
        fda = FusedDropoutAdd(p=0.9)
        fda.eval()
        x, y = pp.randn([4]), pp.randn([4])
        np.testing.assert_allclose(fda(x, y).numpy(),
                                   x.numpy() + y.numpy(), rtol=1e-6)


class TestSparse:
    def _coo(self):
        i = np.array([[0, 1, 2], [1, 2, 0]])
        v = np.array([1.0, 2.0, 3.0], np.float32)
        return pp.sparse.sparse_coo_tensor(i, v, [3, 3])

    def test_coo_roundtrip(self):
        s = self._coo()
        dense = np.asarray(s.to_dense()._data)
        want = np.zeros((3, 3), np.float32)
        want[0, 1], want[1, 2], want[2, 0] = 1, 2, 3
        np.testing.assert_allclose(dense, want)
        assert s.nnz() == 3
        assert s.shape == [3, 3]

    def test_csr_conversion(self):
        s = self._coo()
        csr = s.to_sparse_csr()
        np.testing.assert_array_equal(np.asarray(csr.crows()._data),
                                      [0, 1, 2, 3])
        back = np.asarray(csr.to_dense()._data)
        np.testing.assert_allclose(back, np.asarray(s.to_dense()._data))

    def test_csr_from_arrays(self):
        csr = pp.sparse.sparse_csr_tensor(
            [0, 1, 2, 3], [1, 2, 0], np.array([1., 2., 3.], np.float32),
            [3, 3])
        np.testing.assert_allclose(np.asarray(csr.to_dense()._data),
                                   np.asarray(self._coo().to_dense()._data))

    def test_ops(self):
        s = self._coo()
        d = np.eye(3, dtype=np.float32)
        out = np.asarray(pp.sparse.matmul(s, d)._data)
        np.testing.assert_allclose(out, np.asarray(s.to_dense()._data))
        dbl = pp.sparse.add(s, s)
        np.testing.assert_allclose(np.asarray(dbl.to_dense()._data),
                                   2 * np.asarray(s.to_dense()._data))
        neg = pp.sparse.neg(s)
        relu = pp.sparse.relu(neg)
        assert float(np.asarray(relu.to_dense()._data).sum()) == 0.0
        t = pp.sparse.transpose(s, [1, 0])
        np.testing.assert_allclose(np.asarray(t.to_dense()._data),
                                   np.asarray(s.to_dense()._data).T)

    def test_masked_matmul(self):
        s = self._coo()
        x = np.arange(6, dtype=np.float32).reshape(3, 2)
        y = np.arange(6, dtype=np.float32).reshape(2, 3)
        out = pp.sparse.masked_matmul(x, y, s)
        full = x @ y
        dense = np.asarray(out.to_dense()._data)
        mask = np.asarray(s.to_dense()._data) != 0
        np.testing.assert_allclose(dense[mask], full[mask], rtol=1e-6)
        assert (dense[~mask] == 0).all()


class TestVisionOps:
    def test_nms(self):
        from paddle_tpu.vision.ops import nms
        boxes = np.array([[0, 0, 10, 10], [1, 1, 11, 11], [20, 20, 30, 30],
                          [21, 21, 29, 29], [50, 50, 60, 60]], np.float32)
        scores = np.array([0.9, 0.8, 0.7, 0.95, 0.5], np.float32)
        kept = np.asarray(nms(jnp.asarray(boxes), 0.5, jnp.asarray(scores)))
        assert kept.tolist() == [3, 0, 4]

    def test_nms_categories(self):
        from paddle_tpu.vision.ops import nms
        boxes = np.array([[0, 0, 10, 10], [1, 1, 11, 11]], np.float32)
        scores = np.array([0.9, 0.8], np.float32)
        cats = np.array([0, 1])
        kept = np.asarray(nms(jnp.asarray(boxes), 0.5, jnp.asarray(scores),
                              category_idxs=jnp.asarray(cats),
                              categories=[0, 1]))
        assert set(kept.tolist()) == {0, 1}  # different class: both survive

    def test_roi_align_constant_and_shape(self):
        from paddle_tpu.vision.ops import roi_align
        x = np.full((2, 3, 16, 16), 7.0, np.float32)
        rois = np.array([[2, 2, 10, 10], [0, 0, 8, 8], [4, 4, 12, 12]],
                        np.float32)
        out = np.asarray(roi_align(jnp.asarray(x), jnp.asarray(rois),
                                   jnp.asarray([2, 1]), 4))
        assert out.shape == (3, 3, 4, 4)
        np.testing.assert_allclose(out, 7.0, rtol=1e-6)

    def test_roi_align_ramp_interpolation(self):
        from paddle_tpu.vision.ops import roi_align
        ramp = np.broadcast_to(
            np.arange(16, dtype=np.float32)[None, None, None, :],
            (1, 1, 16, 16)).copy()
        out = np.asarray(roi_align(
            jnp.asarray(ramp),
            jnp.asarray(np.array([[2, 2, 10, 10]], np.float32)),
            jnp.asarray([1]), 2))
        # interior RoI (no edge clamping): bins centred at x = 3.5 and 7.5
        np.testing.assert_allclose(out[0, 0, 0], [3.5, 7.5], rtol=1e-5)


class TestQuantObservers:
    def test_histogram_kl_robust_to_outliers(self):
        from paddle_tpu.quantization import (AbsMaxObserver,
                                             HistogramObserver, KLObserver)
        rng = np.random.default_rng(0)
        data = rng.normal(0, 1, (10, 4096)).astype(np.float32)
        data[0, 0] = 50.0
        scales = {}
        for cls in (AbsMaxObserver, HistogramObserver, KLObserver):
            o = cls()
            for row in data:
                o.observe(row)
            scales[cls.__name__] = o.scale() * 127
        assert scales["AbsMaxObserver"] > 40     # destroyed by the outlier
        assert 2 < scales["HistogramObserver"] < 8
        assert 2 < scales["KLObserver"] < 8

    def test_kl_quantizes_bulk_finer_than_absmax(self):
        """KL clips outliers, spending the int8 range on the bulk — its
        quantization error over the non-outlier mass must beat absmax's
        (which wastes the range covering the outliers)."""
        from paddle_tpu.quantization import AbsMaxObserver, KLObserver
        rng = np.random.default_rng(1)
        data = rng.normal(0, 1, 8192).astype(np.float32)
        data[:4] = 60.0
        bulk = data[4:]

        def bulk_mse(scale):
            q = np.clip(np.round(bulk / scale), -128, 127) * scale
            return float(np.mean((q - bulk) ** 2))

        a, k = AbsMaxObserver(), KLObserver()
        a.observe(data)
        k.observe(data)
        assert bulk_mse(k.scale()) < bulk_mse(a.scale()) / 10


class TestIncubateAutograd:
    def test_functional_transforms(self):
        f = lambda x: (x ** 3).sum()
        x = pp.to_tensor(np.array([1.0, 2.0], np.float32))
        H = pp.incubate.autograd.hessian(f, x)
        np.testing.assert_allclose(np.asarray(H._data),
                                   np.diag([6.0, 12.0]), rtol=1e-5)
        out, (g,) = pp.incubate.autograd.vjp(f, x)
        np.testing.assert_allclose(np.asarray(g._data), [3.0, 12.0],
                                   rtol=1e-5)
        out, jv = pp.incubate.autograd.jvp(f, x,
                                           pp.to_tensor(
                                               np.array([1., 0.],
                                                        np.float32)))
        np.testing.assert_allclose(float(jv._data), 3.0, rtol=1e-5)


class TestLongTailOps:
    def test_structural_ops(self):
        x = pp.to_tensor(np.arange(6, dtype=np.float32).reshape(2, 3))
        assert [tuple(a.shape) for a in pp.hsplit(x, 3)] == [(2, 1)] * 3
        assert [tuple(a.shape) for a in pp.vsplit(x, 2)] == [(1, 3)] * 2
        assert tuple(pp.vstack([x, x]).shape) == (4, 3)
        assert tuple(pp.hstack([x, x]).shape) == (2, 6)
        assert tuple(pp.dstack([x, x]).shape) == (2, 3, 2)
        assert tuple(pp.column_stack([x, x]).shape) == (2, 6)
        parts = pp.tensor_split(x, 2, axis=1)
        assert tuple(parts[0].shape) == (2, 2)
        assert tuple(pp.atleast_2d(pp.to_tensor(
            np.float32(3.0))).shape) == (1, 1)
        bd = pp.block_diag([np.eye(1, dtype=np.float32),
                            2 * np.eye(2, dtype=np.float32)])
        np.testing.assert_allclose(
            np.asarray(bd), np.diag([1.0, 2.0, 2.0]).astype(np.float32))

    def test_diag_fill_take(self):
        np.testing.assert_allclose(
            pp.diag_embed(pp.to_tensor(
                np.array([1.0, 2.0], np.float32))).numpy(),
            np.diag([1.0, 2.0]))
        x = pp.to_tensor(np.arange(6, dtype=np.float32).reshape(2, 3))
        fd = pp.fill_diagonal(x, value=9.0).numpy()
        assert fd[0, 0] == 9.0 and fd[1, 1] == 9.0 and fd[0, 1] == 1.0
        np.testing.assert_allclose(
            pp.take(x, pp.to_tensor(np.array([0, 5]))).numpy(), [0.0, 5.0])

    def test_scatter_variants(self):
        x = pp.to_tensor(np.zeros((4, 3), np.float32))
        out = pp.select_scatter(x, pp.to_tensor(np.ones(3, np.float32)),
                                axis=0, index=2)
        np.testing.assert_allclose(out.numpy()[2], 1.0)
        out2 = pp.slice_scatter(x, pp.to_tensor(np.full((2, 3), 5.0,
                                                        np.float32)),
                                axes=[0], starts=[1], ends=[3])
        np.testing.assert_allclose(out2.numpy()[1:3], 5.0)

    def test_cdist_matches_scipy_style(self):
        rng = np.random.default_rng(0)
        a = rng.normal(size=(3, 4)).astype(np.float32)
        b = rng.normal(size=(5, 4)).astype(np.float32)
        got = np.asarray(pp.cdist(pp.to_tensor(a), pp.to_tensor(b))._data)
        want = np.sqrt(((a[:, None] - b[None]) ** 2).sum(-1))
        np.testing.assert_allclose(got, want, rtol=1e-5)
        got1 = np.asarray(pp.cdist(pp.to_tensor(a), pp.to_tensor(b),
                                   p=1.0)._data)
        np.testing.assert_allclose(
            got1, np.abs(a[:, None] - b[None]).sum(-1), rtol=1e-5)

    def test_vander_trapezoid_sinc(self):
        v = pp.vander(pp.to_tensor(np.array([1.0, 2.0, 3.0], np.float32)),
                      n=3)
        np.testing.assert_allclose(v.numpy(), np.vander([1, 2, 3], 3))
        y = pp.to_tensor(np.array([1.0, 2.0, 3.0], np.float32))
        np.testing.assert_allclose(float(pp.trapezoid(y)._data), 4.0)
        np.testing.assert_allclose(
            float(pp.sinc(pp.to_tensor(np.float32(0.0)))._data), 1.0)


class TestFusedLinearCrossEntropy:
    def test_matches_reference_ce(self):
        from paddle_tpu.nn.functional.loss import (cross_entropy,
                                                   fused_linear_cross_entropy)
        rng = np.random.default_rng(0)
        T, d, V = 12, 16, 1000
        h = rng.normal(size=(T, d)).astype(np.float32)
        w = (rng.normal(size=(d, V)) * 0.1).astype(np.float32)
        lbl = rng.integers(0, V, T)
        ref = cross_entropy(jnp.asarray(h) @ jnp.asarray(w),
                            jnp.asarray(lbl))
        got = fused_linear_cross_entropy(jnp.asarray(h), jnp.asarray(w),
                                         lbl, chunk_size=128)
        np.testing.assert_allclose(float(got), float(ref), rtol=1e-5)

    def test_grads_match_reference(self):
        from paddle_tpu.nn.functional.loss import (cross_entropy,
                                                   fused_linear_cross_entropy)
        rng = np.random.default_rng(1)
        T, d, V = 8, 12, 300
        h = jnp.asarray(rng.normal(size=(T, d)).astype(np.float32))
        w = jnp.asarray((rng.normal(size=(d, V)) * 0.1).astype(np.float32))
        lbl = rng.integers(0, V, T)
        gh_r, gw_r = jax.grad(
            lambda a, b: cross_entropy(a @ b, jnp.asarray(lbl))._data
            if hasattr(cross_entropy(a @ b, jnp.asarray(lbl)), "_data")
            else cross_entropy(a @ b, jnp.asarray(lbl)),
            argnums=(0, 1))(h, w)
        gh_f, gw_f = jax.grad(
            lambda a, b: fused_linear_cross_entropy(a, b, lbl,
                                                    chunk_size=64),
            argnums=(0, 1))(h, w)
        np.testing.assert_allclose(np.asarray(gh_f), np.asarray(gh_r),
                                   rtol=1e-4, atol=1e-6)
        np.testing.assert_allclose(np.asarray(gw_f), np.asarray(gw_r),
                                   rtol=1e-4, atol=1e-6)

    def test_eager_tape_flows(self):
        from paddle_tpu.nn.functional.loss import fused_linear_cross_entropy
        rng = np.random.default_rng(2)
        h = pp.to_tensor(rng.normal(size=(4, 8)).astype(np.float32),
                         stop_gradient=False)
        w = pp.to_tensor((rng.normal(size=(8, 50)) * 0.1)
                         .astype(np.float32), stop_gradient=False)
        loss = fused_linear_cross_entropy(h, w, rng.integers(0, 50, 4),
                                          chunk_size=16)
        assert not loss.stop_gradient
        loss.backward()
        assert h.grad is not None and w.grad is not None

    def test_unreduced_and_sum(self):
        from paddle_tpu.nn.functional.loss import fused_linear_cross_entropy
        rng = np.random.default_rng(3)
        h = jnp.asarray(rng.normal(size=(5, 8)).astype(np.float32))
        w = jnp.asarray(rng.normal(size=(8, 40)).astype(np.float32))
        lbl = rng.integers(0, 40, 5)
        none_r = fused_linear_cross_entropy(h, w, lbl, chunk_size=16,
                                            reduction="none")
        assert none_r.shape == (5,)
        s = fused_linear_cross_entropy(h, w, lbl, chunk_size=16,
                                       reduction="sum")
        np.testing.assert_allclose(float(s), float(none_r.sum()),
                                   rtol=1e-6)

    def test_ignore_index_masks_loss_and_grads(self):
        from paddle_tpu.nn.functional.loss import (cross_entropy,
                                                   fused_linear_cross_entropy)
        rng = np.random.default_rng(4)
        T, d, V = 6, 8, 60
        h = jnp.asarray(rng.normal(size=(T, d)).astype(np.float32))
        w = jnp.asarray((rng.normal(size=(d, V)) * 0.1).astype(np.float32))
        lbl = rng.integers(0, V, T)
        lbl[2] = -100
        lbl[5] = -100
        ref = cross_entropy(h @ w, jnp.asarray(lbl), ignore_index=-100)
        got = fused_linear_cross_entropy(h, w, lbl, chunk_size=16)
        np.testing.assert_allclose(float(got), float(ref), rtol=1e-5)
        # pad tokens produce zero hidden-state gradient rows
        gh = jax.grad(lambda a: fused_linear_cross_entropy(
            a, w, lbl, chunk_size=16))(h)
        assert float(jnp.abs(gh[2]).sum()) == 0.0
        assert float(jnp.abs(gh[5]).sum()) == 0.0
        assert float(jnp.abs(gh[0]).sum()) > 0.0

    # -- the token walk: T 13 is no multiple of the 4-row chunk, V 200 no
    # multiple of 128

    @staticmethod
    def _case(seed, dtype=np.float32, ignore=False, transposed=False,
              T=13, d=16, V=200):
        rng = np.random.default_rng(seed)
        h = jnp.asarray(rng.normal(size=(T, d)), dtype)
        w = jnp.asarray(rng.normal(size=(V, d) if transposed else (d, V))
                        * 0.2, dtype)
        lbl = rng.integers(0, V, T)
        if ignore:
            lbl[[1, 6, T - 1]] = -100
        cot = jnp.asarray(rng.uniform(0.5, 1.5, T), jnp.float32)
        return h, w, jnp.asarray(lbl), cot

    @pytest.mark.parametrize("transposed", [False, True],
                             ids=["w", "embedding_t"])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                             ids=["float32", "bfloat16"])
    @pytest.mark.parametrize("ignore", [False, True],
                             ids=["all_labels", "ignore_index"])
    @pytest.mark.parametrize("reduction", ["mean", "sum", "none"])
    def test_token_walk_matches_materialised_logits(
            self, reduction, ignore, dtype, transposed):
        from paddle_tpu.nn.functional.loss import (cross_entropy,
                                                   fused_linear_cross_entropy)
        h, w, lbl, cot = self._case(10, dtype, ignore, transposed)
        head = (lambda b: b.T) if transposed else (lambda b: b)

        def scalar(per_token_or_loss):
            # "none" gets a cotangent that differs by token
            return (per_token_or_loss * cot).sum() \
                if reduction == "none" else per_token_or_loss

        def ref(a, b):
            logits = a.astype(jnp.float32) @ head(b).astype(jnp.float32)
            return scalar(cross_entropy(logits, lbl, reduction=reduction))

        def fused(a, b):
            return scalar(fused_linear_cross_entropy(
                a, head(b), lbl, chunk_size=4, reduction=reduction))

        (l_r, (gh_r, gw_r)) = jax.value_and_grad(ref, argnums=(0, 1))(h, w)
        (l_f, (gh_f, gw_f)) = jax.value_and_grad(fused, argnums=(0, 1))(h, w)
        assert gh_f.dtype == h.dtype and gw_f.dtype == w.dtype
        assert gw_f.shape == w.shape
        # bf16: the reference's gradients are float32 products rounded
        # once, the walk's are products of a bf16 delta
        tol = 1e-5 if dtype == jnp.float32 else 2e-2
        np.testing.assert_allclose(float(l_f), float(l_r), rtol=tol)
        for got, want in ((gh_f, gh_r), (gw_f, gw_r)):
            want = np.asarray(want, np.float32)
            np.testing.assert_allclose(
                np.asarray(got, np.float32), want, rtol=tol,
                atol=tol * np.abs(want).max())

    @pytest.mark.parametrize("reduction", ["mean", "sum"])
    def test_upstream_scale_scales_both_gradients(self, reduction):
        from paddle_tpu.nn.functional.loss import fused_linear_cross_entropy
        h, w, lbl, _ = self._case(11, ignore=True)
        f = lambda s: jax.grad(
            lambda a, b: s * fused_linear_cross_entropy(
                a, b, lbl, chunk_size=4, reduction=reduction),
            argnums=(0, 1))(h, w)
        for one, three in zip(f(1.0), f(3.0)):
            assert float(jnp.abs(one).max()) > 0
            np.testing.assert_allclose(np.asarray(three),
                                       3.0 * np.asarray(one), rtol=1e-6)

    @pytest.mark.parametrize("reduction", ["mean", "sum", "none"])
    def test_every_label_ignored(self, reduction):
        from paddle_tpu.nn.functional.loss import fused_linear_cross_entropy
        h, w, _, _ = self._case(12)
        lbl = jnp.full((h.shape[0],), -100)
        loss, grads = jax.value_and_grad(
            lambda a, b: fused_linear_cross_entropy(
                a, b, lbl, chunk_size=4, reduction=reduction).sum(),
            argnums=(0, 1))(h, w)
        assert float(loss) == 0.0
        assert all(float(jnp.abs(g).max()) == 0.0 for g in grads)

    @pytest.mark.parametrize("t,v,rows", [
        (16384, 92544, 2048),       # train-1chip's head: 723 MiB of logits
        (16384, 32768, 8192),       # 1 GiB exactly
        (1000, 92544, 1000),        # fewer rows than one chunk: one chunk
    ])
    def test_default_rows_by_rule(self, t, v, rows):
        from paddle_tpu.nn.functional.loss import _ce_chunk_rows
        assert _ce_chunk_rows(t, v) == rows
        assert _ce_chunk_rows(t, v, 64) == 64       # a given value is rows

    # -- the mechanism, held by the program's structure (it always engages,
    # so a counter would be a constant)

    @staticmethod
    def _walk(jaxpr, scan_len=None):
        """(equation, length of the innermost enclosing scan) for every
        equation, sub-jaxprs included."""
        for eqn in jaxpr.eqns:
            yield eqn, scan_len
            inner = eqn.params.get("length") \
                if eqn.primitive.name == "scan" else scan_len
            for p in eqn.params.values():
                for sub in (p if isinstance(p, (list, tuple)) else [p]):
                    sub = getattr(sub, "jaxpr", sub)
                    if hasattr(sub, "eqns"):
                        yield from \
                            TestFusedLinearCrossEntropy._walk(sub, inner)

    @pytest.mark.parametrize("what,dots", [
        ("value_and_grad_mean", 3), ("value_and_grad_sum", 3),
        ("primal_mean", 1), ("value_and_grad_none", 4)])
    def test_head_products_and_no_full_logits(self, what, dots):
        from paddle_tpu.nn.functional.loss import fused_linear_cross_entropy
        T, d, V, rows = 24, 16, 200, 8
        h, w, lbl, _ = self._case(13, T=T, d=d, V=V)
        reduction = what.rsplit("_", 1)[1]
        f = lambda a, b: fused_linear_cross_entropy(
            a, b, lbl, chunk_size=rows, reduction=reduction).sum()
        if what.startswith("value_and_grad"):
            f = jax.value_and_grad(f, argnums=(0, 1))
        eqns = list(self._walk(jax.make_jaxpr(f)(h, w).jaxpr))
        # every head product sits in a scan over the T / rows chunks: a
        # reduced loss makes logits, dh and dW in ONE walk and its backward
        # multiplies nothing; "none" walks again in its backward
        found = [n for e, n in eqns if e.primitive.name == "dot_general"]
        assert found == [T // rows] * dots, found
        scans = [e for e, _ in eqns if e.primitive.name == "scan"]
        assert len(scans) == (2 if what == "value_and_grad_none" else 1)
        biggest = max((int(np.prod(v.aval.shape)) for e, _ in eqns
                       for v in e.outvars if hasattr(v.aval, "shape")),
                      default=0)
        assert biggest < T * V, biggest         # no [T, V] value anywhere

    def test_vocab_sharded_head_on_four_devices(self):
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
        from paddle_tpu.nn.functional.loss import fused_linear_cross_entropy
        if len(jax.devices()) < 4:
            pytest.skip("needs four devices")
        T, d, V = 32, 16, 256
        h, w, lbl, _ = self._case(14, ignore=True, T=T, d=d, V=V)
        f = jax.value_and_grad(
            lambda a, b, c: fused_linear_cross_entropy(a, b, c,
                                                       chunk_size=8),
            argnums=(0, 1))
        want = jax.jit(f)(h, w, lbl)
        mesh = Mesh(np.array(jax.devices()[:4]).reshape(2, 2),
                    ("fsdp", "tp"))
        put = lambda x, *spec: jax.device_put(
            x, NamedSharding(mesh, P(*spec)))
        # rows follow the batch's sharding, the head is vocab-sharded
        got = jax.jit(f)(put(h, "fsdp", None), put(w, "fsdp", "tp"),
                         put(lbl, "fsdp"))
        for g, r in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
            np.testing.assert_allclose(np.asarray(g), np.asarray(r),
                                       rtol=2e-5, atol=1e-7)


class TestHub:
    """paddle.hub parity (reference hapi/hub.py), local source scope."""

    @pytest.fixture
    def repo(self, tmp_path):
        (tmp_path / "hubconf.py").write_text(
            'dependencies = ["numpy"]\n\n'
            "def tiny_mlp(hidden=8):\n"
            '    """A tiny MLP. Args: hidden (int)."""\n'
            "    import paddle_tpu as pp\n"
            "    return pp.nn.Sequential(pp.nn.Linear(4, hidden),\n"
            "                            pp.nn.ReLU(),\n"
            "                            pp.nn.Linear(hidden, 2))\n\n"
            "def _private():\n"
            "    pass\n")
        return str(tmp_path)

    def test_list_help_load(self, repo):
        import paddle_tpu as pp
        assert pp.hub.list(repo) == ["tiny_mlp"]
        assert "tiny MLP" in pp.hub.help(repo, "tiny_mlp")
        net = pp.hub.load(repo, "tiny_mlp", hidden=16)
        out = net(pp.randn([2, 4]))
        assert tuple(out.shape) == (2, 2)

    def test_unknown_entrypoint_and_source(self, repo):
        import paddle_tpu as pp
        with pytest.raises(ValueError, match="available"):
            pp.hub.load(repo, "nope")
        with pytest.raises(NotImplementedError, match="local"):
            pp.hub.list(repo, source="github")

    def test_missing_dependency_reported(self, tmp_path):
        import paddle_tpu as pp
        (tmp_path / "hubconf.py").write_text(
            'dependencies = ["definitely_not_installed_xyz"]\n'
            "def m():\n    pass\n")
        with pytest.raises(RuntimeError, match="dependencies"):
            pp.hub.list(str(tmp_path))
