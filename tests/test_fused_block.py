"""Transformer-block megakernels + persistent autotune cache (ISSUE 8).

Covers: fused rmsnorm+QKV and fused (SwiGLU) MLP Pallas kernels —
interpret-mode fwd/bwd numerics vs the unfused reference at fp32 and
bf16 tolerances, the jaxpr cost-model assertions that each fused kernel
accesses strictly fewer HBM bytes than the unfused lowering on llama
block shapes, the PADDLE_TPU_FUSED_BLOCK routing (knob off restores the
previous path exactly; ineligible shapes fall back), the autoshard
checker round-trip of the fused model on the 8-device harness, and the
autotune cache v2 (versioned schema, corrupt-file tolerance, backend
key separation, hit/miss counters, offline dry-run sweep persistence).

Everything runs interpret-mode on CPU (conftest pins JAX_PLATFORMS).
"""

import json

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from paddle_tpu.core.dispatch import unwrap  # noqa: E402
from paddle_tpu.ops.pallas import autotune as at  # noqa: E402
from paddle_tpu.ops.pallas import fused_block as FB  # noqa: E402

EPS = 1e-5


def _qkv_ref(x, wn, wq, wk, wv):
    xf = x.astype(jnp.float32)
    inv = jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + EPS)
    xn = ((xf * inv) * wn.astype(jnp.float32)).astype(x.dtype)
    return xn @ wq, xn @ wk, xn @ wv


def _mlp_ref(x, wg, wu, wd):
    xf = x.astype(jnp.float32)
    h = (jax.nn.silu(xf @ wg.astype(jnp.float32)) *
         (xf @ wu.astype(jnp.float32))).astype(x.dtype)
    return (h.astype(jnp.float32) @ wd.astype(jnp.float32)).astype(x.dtype)


def _qkv_weights(rng, d, dq, dkv, dtype=jnp.float32):
    return (jnp.asarray(rng.standard_normal((d,)), dtype),
            jnp.asarray(rng.standard_normal((d, dq)) * 0.05, dtype),
            jnp.asarray(rng.standard_normal((d, dkv)) * 0.05, dtype),
            jnp.asarray(rng.standard_normal((d, dkv)) * 0.05, dtype))


# ---------------------------------------------------------------------------
# fused rmsnorm + QKV kernel
# ---------------------------------------------------------------------------

class TestFusedRmsnormQKV:
    def test_fwd_matches_reference(self):
        rng = np.random.default_rng(0)
        for t, d, dq, dkv in [(64, 128, 256, 128), (24, 128, 128, 128),
                              (128, 256, 256, 256)]:
            x = jnp.asarray(rng.standard_normal((t, d)), jnp.float32)
            wn, wq, wk, wv = _qkv_weights(rng, d, dq, dkv)
            q, k, v = FB.fused_rmsnorm_qkv(x, wn, wq, wk, wv, epsilon=EPS)
            qr, kr, vr = _qkv_ref(x, wn, wq, wk, wv)
            for a, b in zip((q, k, v), (qr, kr, vr)):
                assert float(jnp.abs(a - b).max()) < 1e-5

    def test_leading_dims_preserved(self):
        rng = np.random.default_rng(1)
        x = jnp.asarray(rng.standard_normal((2, 16, 128)), jnp.float32)
        wn, wq, wk, wv = _qkv_weights(rng, 128, 256, 128)
        q, k, v = FB.fused_rmsnorm_qkv(x, wn, wq, wk, wv)
        assert q.shape == (2, 16, 256)
        assert k.shape == v.shape == (2, 16, 128)

    @pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5),
                                           (jnp.bfloat16, 3e-2)])
    def test_grads_match_reference(self, dtype, tol):
        rng = np.random.default_rng(2)
        t, d, dq, dkv = 64, 128, 256, 128
        x = jnp.asarray(rng.standard_normal((t, d)), dtype)
        wn, wq, wk, wv = _qkv_weights(rng, d, dq, dkv, dtype)
        cq = jnp.asarray(rng.standard_normal((t, dq)), jnp.float32)
        ck = jnp.asarray(rng.standard_normal((t, dkv)), jnp.float32)

        def loss_fused(x, wn, wq, wk, wv):
            q, k, v = FB.fused_rmsnorm_qkv(x, wn, wq, wk, wv, epsilon=EPS)
            return (jnp.sum(q.astype(jnp.float32) * cq)
                    + jnp.sum(k.astype(jnp.float32) * ck)
                    + jnp.sum(v.astype(jnp.float32) ** 2))

        def loss_ref(x, wn, wq, wk, wv):
            q, k, v = _qkv_ref(x, wn, wq, wk, wv)
            return (jnp.sum(q.astype(jnp.float32) * cq)
                    + jnp.sum(k.astype(jnp.float32) * ck)
                    + jnp.sum(v.astype(jnp.float32) ** 2))

        gf = jax.grad(loss_fused, argnums=(0, 1, 2, 3, 4))(x, wn, wq, wk, wv)
        gr = jax.grad(loss_ref, argnums=(0, 1, 2, 3, 4))(x, wn, wq, wk, wv)
        for a, b in zip(gf, gr):
            scale = max(float(jnp.abs(b.astype(jnp.float32)).max()), 1e-6)
            err = float(jnp.abs(a.astype(jnp.float32)
                                - b.astype(jnp.float32)).max()) / scale
            assert err < tol, (a.shape, err)

    def test_ineligible_shape_falls_back_correctly(self):
        rng = np.random.default_rng(3)
        # d = 96 is not lane-tileable: reference math, same API
        x = jnp.asarray(rng.standard_normal((10, 96)), jnp.float32)
        wn = jnp.ones((96,), jnp.float32)
        w = jnp.asarray(rng.standard_normal((96, 96)), jnp.float32)
        q, k, v = FB.fused_rmsnorm_qkv(x, wn, w, w, w)
        jaxpr = str(jax.make_jaxpr(
            lambda a: FB.fused_rmsnorm_qkv(a, wn, w, w, w))(x))
        assert "pallas_call" not in jaxpr
        qr, _, _ = _qkv_ref(x, wn, w, w, w)
        assert float(jnp.abs(q - qr).max()) < 1e-5

    def test_bad_explicit_blocks_raise(self):
        x = jnp.zeros((64, 128), jnp.float32)
        wn = jnp.ones((128,), jnp.float32)
        w = jnp.zeros((128, 128), jnp.float32)
        with pytest.raises(ValueError, match="not divisible"):
            FB.fused_rmsnorm_qkv(x, wn, w, w, w, block_t=48, block_o=128)


# ---------------------------------------------------------------------------
# fused MLP / FFN kernels
# ---------------------------------------------------------------------------

class TestFusedMLP:
    def test_fwd_matches_reference(self):
        rng = np.random.default_rng(4)
        for t, d, f in [(64, 128, 512), (32, 128, 128), (128, 256, 384)]:
            x = jnp.asarray(rng.standard_normal((t, d)), jnp.float32)
            wg = jnp.asarray(rng.standard_normal((d, f)) * 0.05, jnp.float32)
            wu = jnp.asarray(rng.standard_normal((d, f)) * 0.05, jnp.float32)
            wd = jnp.asarray(rng.standard_normal((f, d)) * 0.05, jnp.float32)
            y = FB.fused_mlp(x, wg, wu, wd)
            yr = _mlp_ref(x, wg, wu, wd)
            scale = max(float(jnp.abs(yr).max()), 1e-6)
            assert float(jnp.abs(y - yr).max()) / scale < 1e-5

    @pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5),
                                           (jnp.bfloat16, 3e-2)])
    def test_grads_match_reference(self, dtype, tol):
        rng = np.random.default_rng(5)
        t, d, f = 64, 128, 384
        x = jnp.asarray(rng.standard_normal((t, d)), dtype)
        wg = jnp.asarray(rng.standard_normal((d, f)) * 0.05, dtype)
        wu = jnp.asarray(rng.standard_normal((d, f)) * 0.05, dtype)
        wd = jnp.asarray(rng.standard_normal((f, d)) * 0.05, dtype)

        def lf(*a):
            return jnp.sum(FB.fused_mlp(*a).astype(jnp.float32) ** 2)

        def lr(*a):
            return jnp.sum(_mlp_ref(*a).astype(jnp.float32) ** 2)

        gf = jax.grad(lf, argnums=(0, 1, 2, 3))(x, wg, wu, wd)
        gr = jax.grad(lr, argnums=(0, 1, 2, 3))(x, wg, wu, wd)
        for a, b in zip(gf, gr):
            scale = max(float(jnp.abs(b.astype(jnp.float32)).max()), 1e-6)
            err = float(jnp.abs(a.astype(jnp.float32)
                                - b.astype(jnp.float32)).max()) / scale
            assert err < tol, (a.shape, err)

    @pytest.mark.parametrize("act", ["relu", "gelu", "silu"])
    @pytest.mark.parametrize("bias", [True, False])
    def test_ffn_acts_and_bias(self, act, bias):
        import paddle_tpu.nn.functional as F
        rng = np.random.default_rng(6)
        t, d, f = 32, 128, 256
        x = jnp.asarray(rng.standard_normal((t, d)), jnp.float32)
        w1 = jnp.asarray(rng.standard_normal((d, f)) * 0.1, jnp.float32)
        w2 = jnp.asarray(rng.standard_normal((f, d)) * 0.1, jnp.float32)
        b1 = jnp.asarray(rng.standard_normal((f,)), jnp.float32) \
            if bias else None
        b2 = jnp.asarray(rng.standard_normal((d,)), jnp.float32) \
            if bias else None
        act_fn = {"relu": jax.nn.relu, "silu": jax.nn.silu,
                  "gelu": lambda a: jax.nn.gelu(a, approximate=False)}[act]

        def ref(x, w1, w2):
            u = x @ w1 + (b1 if bias else 0.0)
            return act_fn(u) @ w2 + (b2 if bias else 0.0)

        y = FB.fused_ffn(x, w1, w2, b1, b2, activation=act)
        yr = ref(x, w1, w2)
        scale = max(float(jnp.abs(yr).max()), 1e-6)
        assert float(jnp.abs(y - yr).max()) / scale < 1e-5

        gf = jax.grad(lambda *a: jnp.sum(
            FB.fused_ffn(*a, b1, b2, activation=act) ** 2),
            argnums=(0, 1, 2))(x, w1, w2)
        gr = jax.grad(lambda *a: jnp.sum(ref(*a) ** 2),
                      argnums=(0, 1, 2))(x, w1, w2)
        for a, b in zip(gf, gr):
            scale = max(float(jnp.abs(b).max()), 1e-6)
            assert float(jnp.abs(a - b).max()) / scale < 2e-5

    def test_unsupported_activation_raises(self):
        x = jnp.zeros((8, 128), jnp.float32)
        w = jnp.zeros((128, 128), jnp.float32)
        with pytest.raises(ValueError, match="activation"):
            FB.fused_mlp(x, w, w, w, activation="tanh")


# ---------------------------------------------------------------------------
# cost model: the fused kernels' HBM bytes beat the unfused jaxpr
# ---------------------------------------------------------------------------

class TestCostModelBytes:
    """Acceptance: on llama block shapes, each fused kernel accesses
    strictly fewer (cost-model, unfused-equivalent) HBM bytes than the
    reference lowering — forward alone AND through the gradient."""

    def _cost(self, fn, *args):
        from paddle_tpu.analysis import check
        rep = check(fn, *args, passes=["cost-model"])
        return rep.extras["cost"]

    def test_qkv_fused_fewer_bytes(self):
        # llama-block proportions: d model, dq = d, GQA kv at d/2
        t, d, dq, dkv = 512, 128, 128, 128
        x = jnp.zeros((t, d), jnp.bfloat16)
        wn = jnp.ones((d,), jnp.bfloat16)
        wq = jnp.zeros((d, dq), jnp.bfloat16)
        wk = jnp.zeros((d, dkv), jnp.bfloat16)
        wv = jnp.zeros((d, dkv), jnp.bfloat16)

        def fused(x, wn, wq, wk, wv):
            return FB.fused_rmsnorm_qkv(x, wn, wq, wk, wv, epsilon=EPS)

        fwd_fused = self._cost(fused, x, wn, wq, wk, wv)
        fwd_ref = self._cost(_qkv_ref, x, wn, wq, wk, wv)
        assert fwd_fused.total_bytes < 0.7 * fwd_ref.total_bytes, \
            (fwd_fused.total_bytes, fwd_ref.total_bytes)

        def g(fn):
            return jax.grad(lambda *a: sum(
                jnp.sum(o.astype(jnp.float32) ** 2) for o in fn(*a)))

        grad_fused = self._cost(g(fused), x, wn, wq, wk, wv)
        grad_ref = self._cost(g(_qkv_ref), x, wn, wq, wk, wv)
        assert grad_fused.total_bytes < grad_ref.total_bytes, \
            (grad_fused.total_bytes, grad_ref.total_bytes)

    def test_mlp_fused_fewer_bytes(self):
        # f/d = 4 and t >> d: the llama bench regime where the [T, f]
        # hidden intermediate dominates the traffic
        t, d, f = 1024, 128, 512
        x = jnp.zeros((t, d), jnp.bfloat16)
        wg = jnp.zeros((d, f), jnp.bfloat16)
        wu = jnp.zeros((d, f), jnp.bfloat16)
        wd = jnp.zeros((f, d), jnp.bfloat16)

        fwd_fused = self._cost(FB.fused_mlp, x, wg, wu, wd)
        fwd_ref = self._cost(_mlp_ref, x, wg, wu, wd)
        assert fwd_fused.total_bytes < 0.7 * fwd_ref.total_bytes, \
            (fwd_fused.total_bytes, fwd_ref.total_bytes)

        def g(fn):
            return jax.grad(lambda *a: jnp.sum(
                fn(*a).astype(jnp.float32) ** 2))

        grad_fused = self._cost(g(FB.fused_mlp), x, wg, wu, wd)
        grad_ref = self._cost(g(_mlp_ref), x, wg, wu, wd)
        assert grad_fused.total_bytes < grad_ref.total_bytes, \
            (grad_fused.total_bytes, grad_ref.total_bytes)


# ---------------------------------------------------------------------------
# in-model routing (llama decoder block + nn.Transformer FFN)
# ---------------------------------------------------------------------------

def _eligible_cfg():
    from paddle_tpu.models import LlamaConfig
    return LlamaConfig.tiny(hidden_size=128, intermediate_size=256,
                            num_attention_heads=2, num_key_value_heads=2,
                            vocab_size=256)


class TestRouting:
    def _layer_jaxpr(self, monkeypatch, knob):
        import paddle_tpu as pp
        from paddle_tpu.core.functional import functional_call, params_of
        from paddle_tpu.models import LlamaForCausalLM
        monkeypatch.setenv("PADDLE_TPU_FUSED_BLOCK", knob)
        pp.seed(0)
        model = LlamaForCausalLM(_eligible_cfg())
        layer = model.model.layers[0]
        p = params_of(layer)
        x = jnp.zeros((2, 16, 128), jnp.float32)
        cos = unwrap(model.model.rope_cos)
        sin = unwrap(model.model.rope_sin)

        def f(p, x):    # fresh closure: make_jaxpr caches by identity
            return unwrap(functional_call(layer, p, x, cos, sin))

        return str(jax.make_jaxpr(f)(p, x))

    def test_knob_routes_and_zero_restores_previous_path(self, monkeypatch):
        """Acceptance: PADDLE_TPU_FUSED_BLOCK=0 restores the exact
        previous (pre-megakernel) lowering — no Pallas call anywhere in
        the decoder block jaxpr; =1 fuses both segments."""
        j1 = self._layer_jaxpr(monkeypatch, "1")
        j0 = self._layer_jaxpr(monkeypatch, "0")
        assert j1.count("pallas_call") >= 2      # rmsnorm+QKV and MLP
        assert "pallas_call" not in j0
        assert "dot_general" in j0               # the unfused matmul chain

    def test_logits_parity_knob_on_off(self, monkeypatch):
        import paddle_tpu as pp
        from paddle_tpu.models import LlamaForCausalLM
        rng = np.random.default_rng(7)
        ids = rng.integers(0, 256, (2, 16)).astype(np.int32)
        pp.seed(0)
        model = LlamaForCausalLM(_eligible_cfg())
        monkeypatch.setenv("PADDLE_TPU_FUSED_BLOCK", "1")
        l1 = np.asarray(model(pp.to_tensor(ids)).numpy(), np.float32)
        monkeypatch.setenv("PADDLE_TPU_FUSED_BLOCK", "0")
        l0 = np.asarray(model(pp.to_tensor(ids)).numpy(), np.float32)
        assert np.abs(l1 - l0).max() < 2e-4, np.abs(l1 - l0).max()

    @pytest.mark.slow
    def test_trainstep_losses_match_reference_path(self, monkeypatch):
        import paddle_tpu as pp
        from paddle_tpu.jit import TrainStep
        from paddle_tpu.models import LlamaForCausalLM
        rng = np.random.default_rng(8)
        ids = rng.integers(0, 256, (2, 17)).astype(np.int32)
        batch = {"input_ids": ids[:, :-1], "labels": ids[:, 1:]}

        def run(knob):
            monkeypatch.setenv("PADDLE_TPU_FUSED_BLOCK", knob)
            pp.seed(0)
            model = LlamaForCausalLM(_eligible_cfg())
            opt = pp.optimizer.AdamW(learning_rate=1e-3,
                                     parameters=model.parameters())
            step = TrainStep(model, opt)
            return [float(step(batch)) for _ in range(3)]

        l1, l0 = run("1"), run("0")
        assert all(abs(a - b) < 5e-4 for a, b in zip(l1, l0)), (l1, l0)
        assert l1[-1] < l1[0]

    def test_ineligible_config_takes_reference_path(self, monkeypatch):
        """The stock tiny config (d=64) cannot tile the VPU lanes: the
        knob stays on but every block routes reference, counted."""
        import paddle_tpu as pp
        from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
        from paddle_tpu.observability import default_registry
        monkeypatch.setenv("PADDLE_TPU_FUSED_BLOCK", "1")
        pp.seed(0)
        model = LlamaForCausalLM(LlamaConfig.tiny())
        m = default_registry().counter(
            "paddle_tpu_fused_block_path_total",
            labelnames=("kernel", "path"))
        before = {"/".join(k): c.value() for k, c in m.series()}
        ids = np.zeros((2, 16), np.int32)
        jaxpr = str(jax.make_jaxpr(
            lambda a: unwrap(model(a)))(jnp.asarray(ids)))
        assert "pallas_call" not in jaxpr
        after = {"/".join(k): c.value() for k, c in m.series()}
        assert after.get("rmsnorm_qkv/reference", 0) > \
            before.get("rmsnorm_qkv/reference", 0)
        assert after.get("mlp/reference", 0) > before.get("mlp/reference", 0)

    def test_decode_path_with_knob_on(self, monkeypatch):
        """Single-token decode rows (batch < 8) fall back cleanly —
        generation works with the knob forced on."""
        import paddle_tpu as pp
        from paddle_tpu.models import LlamaForCausalLM
        monkeypatch.setenv("PADDLE_TPU_FUSED_BLOCK", "1")
        pp.seed(0)
        model = LlamaForCausalLM(_eligible_cfg())
        ids = np.random.default_rng(9).integers(0, 256, (2, 8)) \
            .astype(np.int32)
        out = model.generate(pp.to_tensor(ids), max_new_tokens=3)
        arr = out[0] if isinstance(out, (tuple, list)) else out
        assert np.asarray(arr.numpy() if hasattr(arr, "numpy")
                          else arr).shape[1] == 11

    def test_encoder_ffn_routes_and_matches(self, monkeypatch):
        import paddle_tpu as pp
        import paddle_tpu.nn as nn
        rng = np.random.default_rng(10)
        src = pp.to_tensor(rng.standard_normal((2, 8, 128))
                           .astype(np.float32))
        monkeypatch.setenv("PADDLE_TPU_FUSED_BLOCK", "1")
        enc = nn.TransformerEncoderLayer(128, 2, 256, dropout=0.0,
                                         activation="gelu")
        enc.eval()
        y1 = enc(src).numpy()
        monkeypatch.setenv("PADDLE_TPU_FUSED_BLOCK", "0")
        y0 = enc(src).numpy()
        assert np.abs(np.asarray(y1, np.float32)
                      - np.asarray(y0, np.float32)).max() < 2e-5

    def test_encoder_ffn_dropout_training_falls_back(self, monkeypatch):
        import paddle_tpu as pp
        import paddle_tpu.nn as nn
        from paddle_tpu.observability import default_registry
        monkeypatch.setenv("PADDLE_TPU_FUSED_BLOCK", "1")
        enc = nn.TransformerEncoderLayer(128, 2, 256, dropout=0.1,
                                         activation="relu")
        enc.train()
        m = default_registry().counter(
            "paddle_tpu_fused_block_path_total",
            labelnames=("kernel", "path"))
        before = {"/".join(k): c.value() for k, c in m.series()}
        src = pp.to_tensor(np.zeros((2, 8, 128), np.float32))
        enc(src)
        after = {"/".join(k): c.value() for k, c in m.series()}
        assert after.get("ffn/reference", 0) > before.get("ffn/reference", 0)
        assert after.get("ffn/fused", 0) == before.get("ffn/fused", 0)


# ---------------------------------------------------------------------------
# autoshard checker round-trip on the 8-device harness (acceptance)
# ---------------------------------------------------------------------------

class TestAutoshardRoundTrip:
    def test_fused_model_roundtrips_checker_clean(self, monkeypatch):
        import paddle_tpu as pp
        from paddle_tpu.analysis import autoshard
        from paddle_tpu.jit import TrainStep
        from paddle_tpu.models import LlamaForCausalLM
        if len(jax.devices()) < 8:
            pytest.skip("needs the virtual 8-device CPU mesh")
        monkeypatch.setenv("PADDLE_TPU_FUSED_BLOCK", "1")
        pp.seed(0)
        model = LlamaForCausalLM(_eligible_cfg())
        opt = pp.optimizer.AdamW(learning_rate=1e-3,
                                 parameters=model.parameters())
        step = TrainStep(model, opt)
        batch = {"input_ids": jax.ShapeDtypeStruct((8, 16), jnp.int32),
                 "labels": jax.ShapeDtypeStruct((8, 16), jnp.int32)}
        res = autoshard.plan(step, batch, n_devices=8, topk=2)
        assert res.plans
        for p in res.plans:
            rep = p.verify(step, batch)
            assert not rep.errors() and not rep.warnings(), (
                p.candidate.label + "\n" + rep.format())


# ---------------------------------------------------------------------------
# autotune cache v2
# ---------------------------------------------------------------------------

@pytest.fixture()
def tuned(tmp_path, monkeypatch):
    """Isolated cache file + disabled seed layer, restored afterwards."""
    path = tmp_path / "autotune.json"
    monkeypatch.setenv("PADDLE_TPU_AUTOTUNE_CACHE", str(path))
    monkeypatch.setenv("PADDLE_TPU_AUTOTUNE_SEED", "0")
    at.reload()
    yield path
    at.reload()


class TestAutotuneCache:
    def test_miss_measures_persists_then_hits(self, tuned):
        calls = []

        def bench(c):
            calls.append(c)
            return {(64, 128): 0.5, (128, 128): 0.1}[c]

        got = at.autotune("fused_qkv", "k1@cpu-interpret",
                          [(64, 128), (128, 128)], bench, (8, 128))
        assert got == (128, 128) and len(calls) == 2
        # fresh process simulation: reload from disk, bench must not run
        at.reload()
        got2 = at.autotune("fused_qkv", "k1@cpu-interpret",
                           [(64, 128), (128, 128)],
                           lambda c: pytest.fail("re-timed"), (8, 128))
        assert tuple(got2) == (128, 128)
        raw = json.loads(tuned.read_text())
        assert raw["version"] == at.CACHE_VERSION
        assert raw["entries"]["fused_qkv|k1@cpu-interpret"] == [128, 128]

    def test_version_mismatch_silently_invalidated(self, tuned):
        # v1-era flat schema: must be ignored, not raised on
        tuned.write_text(json.dumps({"fused_qkv|old": [999, 999]}))
        at.reload()
        assert at.cached_entries() == {}
        got = at.autotune("fused_qkv", "old", [(64, 128)],
                          lambda c: 0.1, (8, 128))
        assert got == (64, 128)                  # measured, not the stale 999

    def test_corrupt_cache_tolerated(self, tuned):
        tuned.write_text('{"version": 2, "entries": {"fused_')  # truncated
        at.reload()
        assert at.cached_entries() == {}
        # and the next save round-trips cleanly over the corpse
        at.autotune("fused_mlp", "k@cpu-interpret", [(64, 128)],
                    lambda c: 0.1, (8, 128))
        at.reload()
        assert at.cached_entries() == {"fused_mlp|k@cpu-interpret": [64, 128]}

    def test_backend_component_separates_namespaces(self, tuned):
        key_cpu = at.qkv_key(512, 128, 128, 128, 128, "float32",
                             interpret=True)
        key_tpu = at.qkv_key(512, 128, 128, 128, 128, "float32",
                             backend="tpu:TPU_v5_lite")
        assert key_cpu != key_tpu
        assert key_cpu.endswith("@cpu-interpret")
        at.autotune("fused_qkv", key_cpu, [(64, 128)], lambda c: 0.1,
                    (8, 128))
        benched = []
        at.autotune("fused_qkv", key_tpu, [(256, 256)],
                    lambda c: benched.append(c) or 0.1, (8, 128))
        assert benched, "TPU key was served from the CPU entry"

    def test_the_fused_block_keys_carry_the_vmem_scope(self, monkeypatch):
        monkeypatch.delenv("PADDLE_TPU_AUTOTUNE_SEED", raising=False)
        # a winner taller than the compiler's own scope holds compiles
        # only under code that asks for VMEM: a cache a checkout of
        # before PR 42 also reads and writes must not exchange entries
        for key in (at.mlp_key(16384, 2048, 8192, "bfloat16",
                               backend="tpu:TPU_v5_lite"),
                    at.qkv_key(16384, 2048, 2048, 1024, 1024, "bfloat16",
                               backend="tpu:TPU_v5_lite")):
            assert f"+vmem{FB._VMEM_LIMIT >> 20}@tpu:" in key
        seed = at._parse(at.seed_path())
        assert all("+vmem64@" in k for k in seed
                   if k.startswith(("fused_mlp|", "fused_qkv|")))

    def test_dtype_in_keys(self, tuned):
        a = at.mlp_key(512, 128, 512, "bfloat16", interpret=True)
        b = at.mlp_key(512, 128, 512, "float32", interpret=True)
        assert a != b

    def test_hit_miss_counters(self, tuned):
        from paddle_tpu.observability import default_registry
        m = default_registry().counter(
            "paddle_tpu_autotune_cache_total", labelnames=("op", "result"))
        before = {"/".join(k): c.value() for k, c in m.series()}
        at.autotune("fused_mlp", "c@cpu-interpret", [(64, 128)],
                    lambda c: 0.1, (8, 128))
        at.autotune("fused_mlp", "c@cpu-interpret", [(64, 128)],
                    lambda c: 0.1, (8, 128))
        after = {"/".join(k): c.value() for k, c in m.series()}
        assert after.get("fused_mlp/miss", 0) == \
            before.get("fused_mlp/miss", 0) + 1
        assert after.get("fused_mlp/hit", 0) == \
            before.get("fused_mlp/hit", 0) + 1

    def test_seed_layer_loads_and_user_overrides(self, tmp_path,
                                                 monkeypatch):
        seed = tmp_path / "seed.json"
        user = tmp_path / "user.json"
        seed.write_text(json.dumps({
            "version": at.CACHE_VERSION,
            "entries": {"fused_mlp|s@tpu:v5": [128, 256],
                        "flash|f@tpu:v5": [256, 256, True]}}))
        user.write_text(json.dumps({
            "version": at.CACHE_VERSION,
            "entries": {"fused_mlp|s@tpu:v5": [256, 512]}}))
        monkeypatch.setenv("PADDLE_TPU_AUTOTUNE_SEED", str(seed))
        monkeypatch.setenv("PADDLE_TPU_AUTOTUNE_CACHE", str(user))
        at.reload()
        entries = at.cached_entries()
        assert entries["flash|f@tpu:v5"] == [256, 256, True]   # from seed
        assert entries["fused_mlp|s@tpu:v5"] == [256, 512]     # user wins
        at.reload()

    def test_sweep_dry_run_cli_roundtrip(self, tuned):
        rc = at.main(["--sweep", "--dry-run", "--cache", str(tuned)])
        assert rc == 0
        at.reload()
        entries = at.cached_entries()
        ops = {k.split("|", 1)[0] for k in entries}
        assert {"flash", "fused_ce", "fused_qkv", "fused_mlp"} <= ops
        # every entry hits without benching (fresh-process semantics)
        for key, val in entries.items():
            op, k = key.split("|", 1)
            got = at.autotune(op, k, [tuple(val)],
                              lambda c: pytest.fail("re-timed"), None)
            assert tuple(got) == tuple(val)

    def test_sweep_target_tag(self, tuned):
        rc = at.main(["--sweep", "--dry-run", "--cache", str(tuned),
                      "--target", "tpu:TPU_v5_lite", "--ops", "fused_mlp"])
        assert rc == 0
        at.reload()
        assert all(k.endswith("@tpu:TPU_v5_lite")
                   for k in at.cached_entries())

    def test_default_blocks_divide_shapes(self):
        from paddle_tpu.ops.pallas.fused_block import (_default_mlp_blocks,
                                                       _default_qkv_blocks)
        for t, d, dq, dkv in [(8192, 2048, 2048, 1024),
                              (8192, 4096, 4096, 1024), (64, 128, 128, 128)]:
            bt, bo = _default_qkv_blocks(t, d, dq, dkv, dkv, "bfloat16")
            assert t % bt == 0 and dq % bo == 0 and dkv % bo == 0
        for t, d, f in [(8192, 2048, 7168), (8192, 4096, 14336),
                        (64, 128, 512)]:
            bt, bf = _default_mlp_blocks(t, d, f, "bfloat16")
            assert t % bt == 0 and f % bf == 0


# ---------------------------------------------------------------------------
# the block rule (PR 42): weight passes by the shape's arithmetic
# ---------------------------------------------------------------------------

def _rule_as_it_was(kernel, t, widths, d, dtype):
    """The rule of PR 8 - PR 41: the first pair, widest column block
    first, then tallest token block, under 10 MB."""
    item = 2 if "16" in dtype else 4
    bts = (512, 256, 128, 64, 32, 16) if item == 2 else \
        (512, 256, 128, 64, 32, 16, 8)
    for bc in (512, 256, 128):
        if any(w % bc for w in widths):
            continue
        for bt in bts:
            if t % bt == 0 and FB.block_vmem_bytes(kernel, bt, bc, d,
                                                   item) < 10 * (1 << 20):
                return bt, bc
    return bts[-1], 128


def _rule(kernel, shape, dtype="bfloat16"):
    if kernel == "mlp":
        t, d, f = shape
        return (FB._default_mlp_blocks(t, d, f, dtype),
                at._mlp_candidates(t, d, f, dtype))
    t, d, dq, dk, dv = shape
    return (FB._default_qkv_blocks(t, d, dq, dk, dv, dtype),
            at._qkv_candidates(t, d, dq, dk, dv, dtype))


# the shapes the benchmark's cells hand the two kernels: what the rule
# answered until PR 41, what it answers, and the passes over the weights
CELL_SHAPES = [
    # mistral-7b, serve-chat: a prefill chunk, a decode step
    ("mlp", (256, 4096, 14336), (64, 128), (256, 128), 1),
    ("mlp", (32, 4096, 14336), (32, 128), (32, 128), 1),
    ("qkv", (256, 4096, 4096, 1024, 1024), (64, 128), (256, 128), 1),
    ("qkv", (32, 4096, 4096, 1024, 1024), (32, 128), (32, 128), 1),
    # sarvam's dense layer, serve-longctx: a prefill chunk
    ("mlp", (512, 4096, 16384), (64, 128), (512, 128), 1),
    # granite's one attention layer, serve-rag: a prefill chunk
    ("qkv", (512, 4096, 4096, 1024, 1024), (64, 128), (512, 128), 1),
    # internlm2, train-1chip: swept
    ("mlp", (16384, 2048, 8192), (128, 256), (256, 256), 64),
    ("qkv", (16384, 2048, 2048, 1024, 1024), (128, 256), (256, 256), 64),
]
SWEPT_UNTIL_PR41 = [(64, 128), (128, 128), (256, 128), (64, 256), (128, 256)]


class TestBlockRule:
    @pytest.mark.parametrize("kernel,shape,was,now,passes", CELL_SHAPES)
    def test_the_rule_at_the_cells_shapes(self, kernel, shape, was, now,
                                          passes):
        t, d = shape[:2]
        assert _rule_as_it_was(kernel, t, shape[2:], d, "bfloat16") == was
        blocks, cands = _rule(kernel, shape)
        assert blocks == now and t // blocks[0] == passes
        assert FB.block_vmem_bytes(kernel, *blocks, d, 2) <= FB._VMEM_BUDGET
        assert blocks in cands
        if passes == 1:
            # one pass is the arithmetic's answer: nothing to sweep, and a
            # shape that read its weights once keeps its blocks
            assert cands == [blocks]
            assert blocks == was or t // was[0] > 1
        else:
            # the train shape: what the sweep held stays, in its order,
            # the blocks it ran by default among them; taller ones join
            assert cands[:5] == SWEPT_UNTIL_PR41 and was in cands
            assert blocks[0] >= FB._ridge_rows(2) > was[0]
            assert all(c[0] >= blocks[0] for c in cands[5:])

    def test_one_pass_or_at_the_ridge_comes_out_as_it_went_in(self):
        moved = kept = 0
        for dtype in ("bfloat16", "float32"):
            ridge = FB._ridge_rows(2 if dtype == "bfloat16" else 4)
            for d in (128, 512, 1024, 2048, 4096):
                for t in (16, 32, 64, 256, 512, 4096, 16384):
                    for kernel, widths in (("mlp", (4 * d,)),
                                           ("qkv", (d, d // 4, d // 4))):
                        was = _rule_as_it_was(kernel, t, widths, d, dtype)
                        now = FB._choose_blocks(kernel, t, widths, d, dtype)
                        if was[0] == t or was[0] >= ridge:
                            assert now == was, (kernel, t, d, dtype)
                            kept += 1
                        else:
                            # only the token block grows: the columns are
                            # walked in the order they were
                            assert now[1] == was[1] and now[0] >= was[0]
                            assert now[0] == t or now[0] >= ridge or \
                                now == was, (kernel, t, d, dtype, now)
                            moved += now != was
        assert kept > 50 and moved > 20

    def test_the_working_set_is_the_issues(self):
        mib = 1 << 20
        assert FB.block_vmem_bytes("mlp", 64, 128, 4096, 2) < 10 * mib
        assert FB.block_vmem_bytes("mlp", 128, 128, 4096, 2) > 10 * mib
        assert FB.block_vmem_bytes("mlp", 256, 128, 4096, 2) == 18 * mib
        assert FB.block_vmem_bytes("mlp", 512, 128, 4096, 2) == 30 * mib
        assert FB._ridge_rows(2) == 240
        # the ask: nothing inside the compiler's own scope, else half
        # again the working set, and what the budget holds stays under
        # the limit
        assert FB._vmem_limit("mlp", 32, 128, 4096, 2) is None
        assert FB._vmem_limit("mlp", 256, 128, 4096, 2) == 27 * mib
        assert FB._vmem_limit("mlp", 512, 128, 4096, 2) == 45 * mib
        assert FB._vmem_limit("qkv", 512, 128, 4096, 2) == 35 * mib
        assert FB._vmem_limit("qkv", 512, 128, 4096, 2, True) == 47 * mib
        assert FB._vmem_limit("mlp", 2048, 128, 4096, 2) == FB._VMEM_LIMIT
        assert 3 * FB._VMEM_BUDGET // 2 <= FB._VMEM_LIMIT

    @pytest.mark.parametrize("kernel,shape,was,now,passes", CELL_SHAPES)
    def test_the_verifier_counts_no_more_than_the_rule(self, kernel, shape,
                                                       was, now, passes):
        from paddle_tpu.analysis import kernel_verify as kv
        t, d = shape[:2]
        spec = (FB._mlp_verify_spec(*shape, *now, "bfloat16")
                if kernel == "mlp" else
                FB._qkv_verify_spec(*shape, *now, "bfloat16",
                                    residuals=False))
        # the spec carries the scope the call asks for: the compiler's
        # own where that holds the blocks (a decode step: the call as it
        # was), the declared limit where they need it
        asks = FB._vmem_limit(kernel, *now, d, 2)
        assert (asks is None) == (now == was)
        assert asks is None or FB.block_vmem_bytes(kernel, *now, d, 2) \
            < asks <= FB._VMEM_LIMIT
        assert spec.vmem_limit == (asks or kv.VMEM_LIMIT_BYTES)
        assert kv.footprint_bytes(spec) <= \
            FB.block_vmem_bytes(kernel, *now, d, 2)

    @pytest.mark.parametrize("rows,asks", [(256, (27, 22)),
                                           (32, (None, None))])
    def test_both_calls_ask_the_compiler_for_what_the_blocks_need(
            self, rows, asks):
        # mistral-7b's prefill chunk asks for 27 and 22 MiB; its decode
        # step asks for nothing, as before PR 42
        S = lambda *s: jax.ShapeDtypeStruct(s, jnp.bfloat16)
        d, f = 4096, 14336
        mlp = jax.make_jaxpr(lambda *a: FB.fused_mlp(
            *a, interpret=False, autotune=False))(
            S(rows, d), S(d, f), S(d, f), S(f, d))
        qkv = jax.make_jaxpr(lambda *a: FB.fused_rmsnorm_qkv(
            *a, interpret=False, autotune=False))(
            S(rows, d), S(d), S(d, d), S(d, 1024), S(d, 1024))
        for jaxpr, mib in zip((mlp, qkv), asks):
            want = mib and mib << 20
            assert f"vmem_limit_bytes={want}," in str(jaxpr)

    @pytest.mark.parametrize("t", [256, 512])
    def test_mlp_of_one_token_block_matches_reference_and_64_rows(self, t):
        rng = np.random.default_rng(42)
        d, f = 256, 512
        x = jnp.asarray(rng.standard_normal((t, d)), jnp.bfloat16)
        wg, wu, wd = (jnp.asarray(rng.standard_normal(s) * 0.05,
                                  jnp.bfloat16)
                      for s in ((d, f), (d, f), (f, d)))
        y = FB.fused_mlp(x, wg, wu, wd, block_t=t, block_f=128)
        yr = _mlp_ref(x, wg, wu, wd).astype(jnp.float32)
        scale = float(jnp.abs(yr).max())
        assert float(jnp.abs(y.astype(jnp.float32) - yr).max()) / scale \
            < 2e-2
        # a row's sum is formed in the order it was: the same bits
        y64 = FB.fused_mlp(x, wg, wu, wd, block_t=64, block_f=128)
        assert bool(jnp.array_equal(y, y64))

    @pytest.mark.parametrize("t", [256, 512])
    def test_qkv_of_one_token_block_matches_reference_and_64_rows(self, t):
        rng = np.random.default_rng(43)
        d, dq, dkv = 256, 256, 128
        x = jnp.asarray(rng.standard_normal((t, d)), jnp.bfloat16)
        w = _qkv_weights(rng, d, dq, dkv, jnp.bfloat16)
        got = FB.fused_rmsnorm_qkv(x, *w, epsilon=EPS, block_t=t,
                                   block_o=128)
        for a, b in zip(got, _qkv_ref(x, *w)):
            b = b.astype(jnp.float32)
            assert float(jnp.abs(a.astype(jnp.float32) - b).max()) \
                / float(jnp.abs(b).max()) < 2e-2
        old = FB.fused_rmsnorm_qkv(x, *w, epsilon=EPS, block_t=64,
                                   block_o=128)
        assert all(bool(jnp.array_equal(a, b)) for a, b in zip(got, old))

    @pytest.mark.parametrize("kernel,shape,was,now,passes", CELL_SHAPES)
    def test_the_weight_passes_counter(self, kernel, shape, was, now,
                                       passes):
        label = "mlp" if kernel == "mlp" else "rmsnorm_qkv"
        read = lambda n: FB._passes_counter().labels(
            kernel=label, passes=str(n)).value()
        before = {n: read(n) for n in (passes, shape[0] // was[0])}
        S = lambda *s: jax.ShapeDtypeStruct(s, jnp.bfloat16)
        t, d = shape[:2]
        if kernel == "mlp":
            f = shape[2]
            jax.eval_shape(lambda *a: FB.fused_mlp(*a, autotune=False),
                           S(t, d), S(d, f), S(d, f), S(f, d))
        else:
            dq, dk, dv = shape[2:]
            jax.eval_shape(
                lambda *a: FB.fused_rmsnorm_qkv(*a, autotune=False),
                S(t, d), S(d), S(d, dq), S(d, dk), S(d, dv))
        assert read(passes) == before[passes] + 1
        if shape[0] // was[0] != passes:
            assert read(shape[0] // was[0]) == before[shape[0] // was[0]]
