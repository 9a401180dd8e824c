"""Whole-decoder-block megakernel + compute/collective overlap (ISSUE 15).

Covers: the single-pass decoder-block Pallas kernel — interpret-mode
fwd/bwd parity vs the unfused reference at train and decode shapes (fp32
and bf16 tolerances), the PADDLE_TPU_FUSED_BLOCK=decoder tier routing
(one pallas_call per layer; every other knob value reproduces its
previous jaxpr exactly; ineligible shapes fall back), the cost-model
acceptance ratio (fused block < 0.5x the unfused chain's HBM bytes at
bench-llama widths), the VMEM-budget eligibility gate, the autotune-v2
decoder entries, and the collective-overlap knob — TrainStep FSDP
prefetch semantics (knob-off jaxpr identical, knob-on loss-equivalent,
trace counters), the async ring exchange, and the overlap-aware
autoshard cost model (discounted vs raw charge, PlanResult.table).

Everything runs interpret-mode on the 8-device virtual CPU platform
(conftest pins JAX_PLATFORMS).
"""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from paddle_tpu.core.dispatch import unwrap  # noqa: E402
from paddle_tpu.ops.pallas import fused_block as FB  # noqa: E402

EPS = 1e-5


def _weights(rng, d, dq, dkv, f, dtype=jnp.float32):
    mk = lambda *shape: jnp.asarray(rng.standard_normal(shape) * 0.05,
                                    dtype)
    return dict(
        wn1=jnp.asarray(rng.standard_normal((d,)), dtype),
        wn2=jnp.asarray(rng.standard_normal((d,)), dtype),
        wq=mk(d, dq), wk=mk(d, dkv), wv=mk(d, dkv), wo=mk(dq, d),
        wg=mk(d, f), wu=mk(d, f), wd=mk(f, d))


def _call(x, w, nh, nkvh, cos, sin, use_pallas):
    return FB.fused_decoder_block(
        x, w["wn1"], w["wq"], w["wk"], w["wv"], cos, sin, w["wo"],
        w["wn2"], w["wg"], w["wu"], w["wd"], num_heads=nh,
        num_kv_heads=nkvh, epsilon=EPS, use_pallas=use_pallas)


def _tables(hd, n=256):
    from paddle_tpu.nn.functional.attention import rotary_freqs
    return rotary_freqs(hd, n)


# ---------------------------------------------------------------------------
# kernel numerics
# ---------------------------------------------------------------------------

class TestFusedDecoderKernel:
    @pytest.mark.parametrize("shape", [
        (2, 64, 256, 2, 1, 512),     # train shape, GQA rep=2
        (4, 16, 256, 2, 2, 512),     # short prefill, MHA
        (8, 8, 128, 1, 1, 256),      # decode-sized row batch
    ])
    def test_fwd_matches_reference(self, shape):
        b, s, d, nh, nkvh, f = shape
        hd = d // nh if d // nh >= 128 else 128
        dq, dkv = nh * hd, nkvh * hd
        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.standard_normal((b, s, d)), jnp.float32)
        w = _weights(rng, d, dq, dkv, f)
        cos, sin = _tables(hd)
        assert FB.fused_decoder_eligible(b, s, d, dq, dkv, hd, f,
                                         "float32")
        y = _call(x, w, nh, nkvh, cos, sin, use_pallas=True)
        yr = _call(x, w, nh, nkvh, cos, sin, use_pallas=False)
        scale = max(float(jnp.abs(yr).max()), 1e-6)
        assert float(jnp.abs(y - yr).max()) / scale < 2e-5

    def test_fwd_matches_reference_bf16(self):
        b, s, d, nh, nkvh, f = 2, 64, 256, 2, 1, 512
        hd, dq, dkv = 128, 256, 128
        rng = np.random.default_rng(1)
        x = jnp.asarray(rng.standard_normal((b, s, d)), jnp.bfloat16)
        w = _weights(rng, d, dq, dkv, f, jnp.bfloat16)
        cos, sin = _tables(hd)
        y = _call(x, w, nh, nkvh, cos, sin, True).astype(jnp.float32)
        yr = _call(x, w, nh, nkvh, cos, sin, False).astype(jnp.float32)
        scale = max(float(jnp.abs(yr).max()), 1e-6)
        assert float(jnp.abs(y - yr).max()) / scale < 3e-2

    @pytest.mark.slow
    def test_grads_match_reference(self):
        b, s, d, nh, nkvh, f = 2, 64, 256, 2, 1, 512
        hd = 128
        rng = np.random.default_rng(2)
        x = jnp.asarray(rng.standard_normal((b, s, d)), jnp.float32)
        w = _weights(rng, d, nh * hd, nkvh * hd, f)
        cos, sin = _tables(hd)

        def loss(flag):
            def L(x_, wq, wg, wn1):
                w2 = dict(w, wq=wq, wg=wg, wn1=wn1)
                y = _call(x_, w2, nh, nkvh, cos, sin, flag)
                return jnp.sum(y.astype(jnp.float32) ** 2)
            return jax.grad(L, argnums=(0, 1, 2, 3))(
                x, w["wq"], w["wg"], w["wn1"])

        for a, b_ in zip(loss(True), loss(False)):
            scale = max(float(jnp.abs(b_).max()), 1e-6)
            err = float(jnp.abs(a - b_).max()) / scale
            assert err < 2e-5, (a.shape, err)

    def test_ineligible_shape_falls_back_correctly(self):
        # d = 96 cannot tile the lanes; s = 12 cannot tile the sublanes
        # — the API stays total: the reference composition serves them
        rng = np.random.default_rng(3)
        hd = 128
        cos, sin = _tables(hd)
        for b, s, d in [(2, 16, 96), (2, 12, 256)]:
            assert not FB.fused_decoder_eligible(b, s, d, hd, hd, hd,
                                                 256, "float32")
            w = _weights(rng, d, hd, hd, 256)
            x = jnp.asarray(rng.standard_normal((b, s, d)), jnp.float32)
            y = _call(x, w, 1, 1, cos, sin, None)     # auto -> fallback
            yu = _unfused_chain(x, w["wn1"], w["wq"], w["wk"], w["wv"],
                                cos[:s], sin[:s], w["wo"], w["wn2"],
                                w["wg"], w["wu"], w["wd"], 1, 1)
            scale = max(float(jnp.abs(yu).max()), 1e-6)
            assert float(jnp.abs(y - yu).max()) / scale < 1e-4

    def test_vmem_budget_gates_eligibility(self):
        # bench-llama train widths (s=2048, dkv=1024): the sequence-wide
        # K/V scratch alone exceeds the budget -> ineligible, while the
        # same widths at s=512/dkv=512 fit
        assert not FB.fused_decoder_eligible(
            4, 2048, 2048, 2048, 1024, 128, 7168, "bfloat16")
        assert FB.fused_decoder_eligible(
            4, 512, 1024, 1024, 512, 128, 3584, "bfloat16")
        # the budget fn is monotone in s (the K/V term)
        lo = FB.decoder_vmem_bytes(128, 1024, 1024, 512, 128, 3584,
                                   16, 128, 128, "bfloat16")
        hi = FB.decoder_vmem_bytes(4096, 1024, 1024, 512, 128, 3584,
                                   16, 128, 128, "bfloat16")
        assert hi > lo

    def test_bad_explicit_blocks_raise(self):
        rng = np.random.default_rng(4)
        w = _weights(rng, 256, 256, 128, 512)
        cos, sin = _tables(128)
        x = jnp.zeros((2, 64, 256), jnp.float32)
        with pytest.raises(ValueError, match="not divisible"):
            FB.fused_decoder_block(
                x, w["wn1"], w["wq"], w["wk"], w["wv"], cos, sin,
                w["wo"], w["wn2"], w["wg"], w["wu"], w["wd"],
                num_heads=2, num_kv_heads=1, use_pallas=True,
                block_t=48, block_o=128, block_f=128)


# ---------------------------------------------------------------------------
# in-model routing: the decoder tier and its knob-off equality
# ---------------------------------------------------------------------------

def _decoder_cfg():
    from paddle_tpu.models import LlamaConfig
    return LlamaConfig.tiny(hidden_size=256, intermediate_size=512,
                            num_attention_heads=2, num_key_value_heads=1,
                            vocab_size=256)


def _segment_cfg():
    # per-segment-eligible but decoder-INELIGIBLE (head_dim = 64): the
    # decoder tier must fall back to exactly the tier-"1" lowering
    from paddle_tpu.models import LlamaConfig
    return LlamaConfig.tiny(hidden_size=128, intermediate_size=256,
                            num_attention_heads=2, num_key_value_heads=2,
                            vocab_size=256)


class TestDecoderRouting:
    def _layer_jaxpr(self, monkeypatch, knob, cfg, s=16):
        import paddle_tpu as pp
        from paddle_tpu.core.functional import functional_call, params_of
        from paddle_tpu.models import LlamaForCausalLM
        monkeypatch.setenv("PADDLE_TPU_FUSED_BLOCK", knob)
        pp.seed(0)
        model = LlamaForCausalLM(cfg)
        layer = model.model.layers[0]
        p = params_of(layer)
        x = jnp.zeros((2, s, cfg.hidden_size), jnp.float32)
        cos = unwrap(model.model.rope_cos)
        sin = unwrap(model.model.rope_sin)

        def f(p, x):    # fresh closure: make_jaxpr caches by identity
            return unwrap(functional_call(layer, p, x, cos, sin))

        return str(jax.make_jaxpr(f)(p, x))

    def test_decoder_tier_is_one_pallas_call(self, monkeypatch):
        j = self._layer_jaxpr(monkeypatch, "decoder", _decoder_cfg(), s=64)
        assert j.count("pallas_call") == 1

    def test_other_knob_values_reproduce_previous_jaxpr(self, monkeypatch):
        """Acceptance: both new knobs off reproduce the exact previous
        jaxpr.  Tier "1" must not change with the decoder code present,
        and the decoder tier's fallback on a decoder-ineligible config
        must be string-identical to tier "1"."""
        import re
        norm = lambda j: re.sub(r"0x[0-9a-f]+", "0xX", j)
        cfg = _segment_cfg()
        j1 = norm(self._layer_jaxpr(monkeypatch, "1", cfg))
        jdec = norm(self._layer_jaxpr(monkeypatch, "decoder", cfg))
        j0 = norm(self._layer_jaxpr(monkeypatch, "0", cfg))
        assert jdec == j1                    # fallback == per-segment tier
        assert j1.count("pallas_call") >= 2  # rmsnorm+QKV and MLP
        assert "pallas_call" not in j0       # the pre-PR-8 lowering

    def test_logits_parity_decoder_vs_off(self, monkeypatch):
        import paddle_tpu as pp
        from paddle_tpu.models import LlamaForCausalLM
        rng = np.random.default_rng(7)
        ids = rng.integers(0, 256, (2, 64)).astype(np.int32)

        def logits(knob):
            monkeypatch.setenv("PADDLE_TPU_FUSED_BLOCK", knob)
            pp.seed(0)
            model = LlamaForCausalLM(_decoder_cfg())
            return np.asarray(model(pp.to_tensor(ids)).numpy(),
                              np.float32)

        ld, l0 = logits("decoder"), logits("0")
        assert np.abs(ld - l0).max() < 2e-4, np.abs(ld - l0).max()

    @pytest.mark.slow
    def test_trainstep_losses_match_reference_path(self, monkeypatch):
        import paddle_tpu as pp
        from paddle_tpu.jit import TrainStep
        from paddle_tpu.models import LlamaForCausalLM
        rng = np.random.default_rng(8)
        ids = rng.integers(0, 256, (2, 65)).astype(np.int32)
        batch = {"input_ids": ids[:, :-1], "labels": ids[:, 1:]}

        def run(knob):
            monkeypatch.setenv("PADDLE_TPU_FUSED_BLOCK", knob)
            pp.seed(0)
            model = LlamaForCausalLM(_decoder_cfg())
            opt = pp.optimizer.AdamW(learning_rate=1e-3,
                                     parameters=model.parameters())
            step = TrainStep(model, opt)
            return [float(step(batch)) for _ in range(3)]

        ld, l0 = run("decoder"), run("0")
        assert all(abs(a - b) < 5e-4 for a, b in zip(ld, l0)), (ld, l0)
        assert ld[-1] < ld[0]

    def test_decode_generate_works_with_decoder_tier(self, monkeypatch):
        """Cached decode carries a cache -> the decoder tier must stand
        aside (trace-time) and generation still works."""
        import paddle_tpu as pp
        from paddle_tpu.models import LlamaForCausalLM
        monkeypatch.setenv("PADDLE_TPU_FUSED_BLOCK", "decoder")
        pp.seed(0)
        model = LlamaForCausalLM(_decoder_cfg())
        ids = np.random.default_rng(9).integers(0, 256, (2, 8)) \
            .astype(np.int32)
        out = model.generate(pp.to_tensor(ids), max_new_tokens=3)
        arr = out[0] if isinstance(out, (tuple, list)) else out
        assert np.asarray(arr.numpy() if hasattr(arr, "numpy")
                          else arr).shape[1] == 11

    def test_path_counter_records_decoder_tier(self, monkeypatch):
        import paddle_tpu as pp
        from paddle_tpu.core.functional import functional_call, params_of
        from paddle_tpu.models import LlamaForCausalLM
        from paddle_tpu.observability import default_registry
        monkeypatch.setenv("PADDLE_TPU_FUSED_BLOCK", "decoder")
        pp.seed(0)
        model = LlamaForCausalLM(_decoder_cfg())
        layer = model.model.layers[0]
        m = default_registry().counter(
            "paddle_tpu_fused_block_path_total",
            labelnames=("kernel", "path"))
        before = {"/".join(k): c.value() for k, c in m.series()}
        p = params_of(layer)
        x = jnp.zeros((2, 64, 256), jnp.float32)
        cos = unwrap(model.model.rope_cos)
        sin = unwrap(model.model.rope_sin)
        jax.make_jaxpr(lambda p, x: unwrap(
            functional_call(layer, p, x, cos, sin)))(p, x)
        after = {"/".join(k): c.value() for k, c in m.series()}
        assert after.get("decoder_block/fused", 0) > \
            before.get("decoder_block/fused", 0)


# ---------------------------------------------------------------------------
# cost model: the whole-block kernel's HBM bytes vs the unfused chain
# ---------------------------------------------------------------------------

def _unfused_chain(x, wn1, wq, wk, wv, cos, sin, wo, wn2, wg, wu, wd,
                   nh, nkvh):
    """The fully-unfused decoder block in plain jax (no Pallas anywhere)
    — the pre-megakernel lowering the acceptance ratio is measured
    against."""
    b, s, d = x.shape
    dq = wq.shape[1]
    hd = dq // nh
    xf = x.astype(jnp.float32)
    inv = jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + EPS)
    xn = ((xf * inv) * wn1.astype(jnp.float32)).astype(x.dtype)
    q = (xn.reshape(-1, d) @ wq).reshape(b, s, nh, hd)
    k = (xn.reshape(-1, d) @ wk).reshape(b, s, nkvh, hd)
    v = (xn.reshape(-1, d) @ wv).reshape(b, s, nkvh, hd)
    q = FB._rope_ref(q, cos, sin)
    k = FB._rope_ref(k, cos, sin)
    rep = nh // nkvh
    kq = jnp.repeat(k, rep, axis=2)
    vq = jnp.repeat(v, rep, axis=2)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                        kq.astype(jnp.float32)) / (hd ** 0.5)
    mask = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(mask[None, None], scores, -1e30)
    p = jax.nn.softmax(scores, axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", p, vq.astype(jnp.float32)) \
        .astype(x.dtype)
    h = (o.reshape(-1, dq) @ wo).astype(x.dtype).reshape(b, s, d)
    x2 = x + h
    x2f = x2.astype(jnp.float32)
    inv2 = jax.lax.rsqrt(jnp.mean(x2f * x2f, -1, keepdims=True) + EPS)
    xn2 = ((x2f * inv2) * wn2.astype(jnp.float32)).astype(x.dtype)
    g = xn2.reshape(-1, d) @ wg
    u = xn2.reshape(-1, d) @ wu
    hh = (jax.nn.silu(g) * u).astype(x.dtype)
    return x2 + (hh @ wd).astype(x.dtype).reshape(b, s, d)


class TestDecoderCostModel:
    def test_bytes_ratio_under_half_at_bench_llama_shapes(self):
        """Acceptance: fused decoder block < 0.5x the unfused chain's
        cost-model HBM bytes at bench-llama per-layer widths."""
        from paddle_tpu.analysis import check
        b, s, d, nh, nkvh, hd, f = 1, 2048, 2048, 16, 8, 128, 7168
        dq, dkv = nh * hd, nkvh * hd
        dt = jnp.bfloat16
        x = jnp.zeros((b, s, d), dt)
        w = {k: jnp.zeros(shape, dt) for k, shape in dict(
            wn1=(d,), wn2=(d,), wq=(d, dq), wk=(d, dkv), wv=(d, dkv),
            wo=(dq, d), wg=(d, f), wu=(d, f), wd=(f, d)).items()}
        cos, sin = _tables(hd, 4096)
        args = (x, w["wn1"], w["wq"], w["wk"], w["wv"], cos[:s], sin[:s],
                w["wo"], w["wn2"], w["wg"], w["wu"], w["wd"])

        def fused(*a):
            # use_pallas + explicit blocks forced: the trace is abstract
            # (no VMEM runs), measuring the kernel's call-level byte
            # accounting at widths the VMEM gate rejects for execution
            return FB.fused_decoder_block(
                a[0], *a[1:], num_heads=nh, num_kv_heads=nkvh,
                epsilon=EPS, use_pallas=True, autotune=False,
                block_t=128, block_o=128, block_f=512)

        def unfused(*a):
            return _unfused_chain(*a, nh=nh, nkvh=nkvh)

        cf = check(fused, *args, passes=["cost-model"]).extras["cost"]
        cu = check(unfused, *args, passes=["cost-model"]).extras["cost"]
        ratio = cf.total_bytes / cu.total_bytes
        assert ratio < 0.5, (cf.total_bytes, cu.total_bytes, ratio)

    def test_fused_beats_segment_chain_at_eligible_shape(self):
        """At an eligible shape, one whole-block pass also accesses
        fewer bytes than the PR-8 per-segment chain (fused QKV + flash +
        fused MLP with HBM round-trips at every boundary)."""
        from paddle_tpu.analysis import check
        import os
        b, s, d, nh, nkvh, hd, f = 4, 512, 1024, 8, 4, 128, 3584
        dq, dkv = nh * hd, nkvh * hd
        dt = jnp.bfloat16
        rng = np.random.default_rng(0)
        x = jnp.zeros((b, s, d), dt)
        w = {k: jnp.zeros(shape, dt) for k, shape in dict(
            wn1=(d,), wn2=(d,), wq=(d, dq), wk=(d, dkv), wv=(d, dkv),
            wo=(dq, d), wg=(d, f), wu=(d, f), wd=(f, d)).items()}
        cos, sin = _tables(hd, 1024)

        def fused(xx):
            return FB.fused_decoder_block(
                xx, w["wn1"], w["wq"], w["wk"], w["wv"], cos, sin,
                w["wo"], w["wn2"], w["wg"], w["wu"], w["wd"],
                num_heads=nh, num_kv_heads=nkvh, epsilon=EPS,
                use_pallas=True, autotune=False)

        def segments(xx):
            # the PR-8 lowering: per-segment kernels, boundary HBM trips
            q, k, v = FB.fused_rmsnorm_qkv(
                xx, w["wn1"], w["wq"], w["wk"], w["wv"], epsilon=EPS,
                use_pallas=True, autotune=False)
            q = FB._rope_ref(q.reshape(b, s, nh, hd), cos[:s], sin[:s])
            k = FB._rope_ref(k.reshape(b, s, nkvh, hd), cos[:s], sin[:s])
            from paddle_tpu.ops.pallas.flash_attention import \
                flash_attention
            o = flash_attention(q, k, v.reshape(b, s, nkvh, hd),
                                causal=True, block_q=128, block_k=128,
                                autotune=False)
            h = (o.reshape(-1, dq) @ w["wo"]).astype(dt).reshape(b, s, d)
            x2 = xx + h
            x2f = x2.astype(jnp.float32)
            inv2 = jax.lax.rsqrt(
                jnp.mean(x2f * x2f, -1, keepdims=True) + EPS)
            xn2 = ((x2f * inv2)
                   * w["wn2"].astype(jnp.float32)).astype(dt)
            y = FB.fused_mlp(xn2, w["wg"], w["wu"], w["wd"],
                             use_pallas=True, autotune=False)
            return x2 + y

        cf = check(fused, x, passes=["cost-model"]).extras["cost"]
        cs = check(segments, x, passes=["cost-model"]).extras["cost"]
        assert cf.total_bytes < cs.total_bytes, (cf.total_bytes,
                                                 cs.total_bytes)


# ---------------------------------------------------------------------------
# autotune-v2: decoder entries
# ---------------------------------------------------------------------------

class TestAutotuneDecoder:
    def test_candidates_divide_shapes_and_fit_budget(self):
        from paddle_tpu.ops.pallas import autotune as at
        cands = at._decoder_candidates(512, 1024, 1024, 512, 128, 3584,
                                       "bfloat16")
        assert cands
        for bt, bo, bf in cands:
            assert 512 % bt == 0 and 1024 % bo == 0 and 3584 % bf == 0
            assert bo % 128 == 0
            assert FB.decoder_vmem_bytes(
                512, 1024, 1024, 512, 128, 3584, bt, bo, bf,
                "bfloat16") < FB._DECODER_VMEM_BUDGET

    def test_dry_run_sweep_persists_decoder_entries(self, tmp_path,
                                                    monkeypatch):
        from paddle_tpu.ops.pallas import autotune as at
        path = tmp_path / "autotune.json"
        monkeypatch.setenv("PADDLE_TPU_AUTOTUNE_CACHE", str(path))
        monkeypatch.setenv("PADDLE_TPU_AUTOTUNE_SEED", "0")
        at.reload()
        try:
            rc = at.main(["--sweep", "--dry-run", "--cache", str(path),
                          "--ops", "fused_decoder"])
            assert rc == 0
            at.reload()
            entries = at.cached_entries()
            assert entries and all(k.startswith("fused_decoder|")
                                   for k in entries)
            for key, val in entries.items():
                op, k = key.split("|", 1)
                got = at.autotune(op, k, [tuple(val)],
                                  lambda c: pytest.fail("re-timed"),
                                  None)
                assert tuple(got) == tuple(val)
        finally:
            at.reload()


# ---------------------------------------------------------------------------
# device profiler: the decoder-block fusion-boundary segment
# ---------------------------------------------------------------------------

class TestProfilerSegment:
    def test_llama_segments_gain_decoder_fused_boundary(self, monkeypatch):
        import paddle_tpu as pp
        from paddle_tpu.models import LlamaForCausalLM
        from paddle_tpu.observability.device_profiler import \
            llama_step_segments
        monkeypatch.setenv("PADDLE_TPU_FUSED_BLOCK", "decoder")
        pp.seed(0)
        model = LlamaForCausalLM(_decoder_cfg())
        ids = np.zeros((2, 64), np.int32)
        segs = llama_step_segments(model, {"input_ids": ids,
                                           "labels": ids})
        by_name = {s.name: s for s in segs}
        seg = by_name["decoder_block_fused"]
        assert seg.group == "fused_boundary"
        # the segment routes like the layer: decoder tier -> ONE kernel;
        # knob off -> a different lowering (the unfused layer, whose
        # sdpa may still route flash — its own independent knob)
        import re
        norm = lambda j: re.sub(r"0x[0-9a-f]+", "0xX", j)

        def trace():    # fresh closure: make_jaxpr caches by identity
            return norm(str(jax.make_jaxpr(
                lambda p, h: seg.fn(p, h))(*seg.args)))

        jaxpr = trace()
        assert jaxpr.count("pallas_call") == 1
        monkeypatch.setenv("PADDLE_TPU_FUSED_BLOCK", "0")
        jaxpr0 = trace()
        assert jaxpr0 != jaxpr


# ---------------------------------------------------------------------------
# collective overlap: TrainStep FSDP prefetch + ring exchange + knobs
# ---------------------------------------------------------------------------

def _mesh(shape, names):
    from jax.sharding import Mesh
    n = int(np.prod(shape))
    if len(jax.devices()) < n:
        pytest.skip("needs the virtual 8-device CPU mesh")
    return Mesh(np.asarray(jax.devices()[:n]).reshape(shape), names)


def _fsdp_step(overlap, cfg=None, collective_overlap=None):
    import paddle_tpu as pp
    from jax.sharding import PartitionSpec as P
    from paddle_tpu.distributed.sharding import shard_plan
    from paddle_tpu.jit import TrainStep
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
    mesh = _mesh((2, 4), ("dp", "fsdp"))
    pp.seed(0)
    model = LlamaForCausalLM(cfg or LlamaConfig.tiny())
    plan = shard_plan(model, level="p_g_os", axis="fsdp")
    opt = pp.optimizer.AdamW(learning_rate=1e-3,
                             parameters=model.parameters())
    return TrainStep(model, opt, mesh=mesh,
                     param_specs=plan.param_specs, batch_spec=P("dp"),
                     collective_overlap=overlap
                     if collective_overlap is None else collective_overlap)


class TestCollectiveOverlap:
    def test_knob_off_jaxpr_identical(self):
        """Acceptance: the overlap knob off reproduces the exact
        previous step jaxpr (env unset == explicit False), and on
        changes it."""
        a = _fsdp_step(None)          # env unset -> off
        b = _fsdp_step(False)
        c = _fsdp_step(True)
        assert not a._collective_overlap and c._collective_overlap

        def jx(st):
            lr = jnp.zeros((), jnp.float32)
            batch = {"input_ids": jax.ShapeDtypeStruct((8, 16), jnp.int32),
                     "labels": jax.ShapeDtypeStruct((8, 16), jnp.int32)}
            return str(jax.make_jaxpr(st._step_impl)(
                st.params, st.opt_state, st.step_count, batch, st._key,
                lr))

        ja, jb, jc = jx(a), jx(b), jx(c)
        assert ja == jb
        assert jc != ja
        assert "optimization_barrier" in jc

    @pytest.mark.slow
    def test_loss_equivalent_and_counter_fires(self):
        rng = np.random.default_rng(0)
        ids = rng.integers(0, 256, (8, 17)).astype(np.int32)
        batch = {"input_ids": ids[:, :-1], "labels": ids[:, 1:]}
        from paddle_tpu.observability import default_registry
        m = default_registry().counter(
            "paddle_tpu_collective_overlap_total", labelnames=("path",))
        before = {"/".join(k): c.value() for k, c in m.series()}
        off = _fsdp_step(False)
        on = _fsdp_step(True)
        l_off = [float(off(batch)) for _ in range(3)]
        l_on = [float(on(batch)) for _ in range(3)]
        assert all(abs(a - b) < 1e-5 for a, b in zip(l_off, l_on)), \
            (l_off, l_on)
        after = {"/".join(k): c.value() for k, c in m.series()}
        assert after.get("fsdp_prefetch", 0) > \
            before.get("fsdp_prefetch", 0)

    def test_inactive_without_fsdp_axis(self):
        import paddle_tpu as pp
        from paddle_tpu.jit import TrainStep
        from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
        pp.seed(0)
        model = LlamaForCausalLM(LlamaConfig.tiny())
        opt = pp.optimizer.AdamW(learning_rate=1e-3,
                                 parameters=model.parameters())
        step = TrainStep(model, opt, collective_overlap=True)
        assert not step._collective_overlap    # no mesh -> inert

    def test_cache_key_discriminates_overlap(self):
        off = _fsdp_step(False)
        on = _fsdp_step(True)
        assert "ovl=0" in off._cache_extra()
        assert "ovl=1" in on._cache_extra()

    def test_prefetch_groups_schedule(self):
        from paddle_tpu.distributed.sharding import prefetch_groups
        names = ["model.layers_1.mlp.up_proj.weight",
                 "model.layers_0.self_attn.q_proj.weight",
                 "model.embed_tokens.weight",
                 "model.layers_0.mlp.gate_proj.weight",
                 "lm_head.weight"]
        groups = prefetch_groups(names)
        assert groups[0] == ["model.embed_tokens.weight",
                             "lm_head.weight"]
        assert sorted(groups[1]) == [
            "model.layers_0.mlp.gate_proj.weight",
            "model.layers_0.self_attn.q_proj.weight"]
        assert groups[2] == ["model.layers_1.mlp.up_proj.weight"]

    def test_gathered_spec_drops_axis(self):
        from jax.sharding import PartitionSpec as P
        from paddle_tpu.distributed.sharding import gathered_spec
        assert gathered_spec(P("fsdp", "tp"), "fsdp") == P(None, "tp")
        assert gathered_spec(P(("dp", "fsdp")), "fsdp") == P("dp")
        assert gathered_spec(P("tp"), "fsdp") == P("tp")

    def test_ring_exchange_overlap_parity(self, monkeypatch):
        from paddle_tpu.distributed.sequence_parallel import \
            make_ring_attention
        mesh = _mesh((4,), ("sp",))
        rng = np.random.default_rng(5)
        q = jnp.asarray(rng.standard_normal((2, 64, 4, 32)), jnp.float32)
        k = jnp.asarray(rng.standard_normal((2, 64, 4, 32)), jnp.float32)
        v = jnp.asarray(rng.standard_normal((2, 64, 4, 32)), jnp.float32)
        monkeypatch.delenv("PADDLE_TPU_COLLECTIVE_OVERLAP",
                           raising=False)
        base = make_ring_attention(mesh, "sp", causal=True)(q, k, v)
        monkeypatch.setenv("PADDLE_TPU_COLLECTIVE_OVERLAP", "1")
        over = make_ring_attention(mesh, "sp", causal=True)(q, k, v)
        np.testing.assert_allclose(np.asarray(base), np.asarray(over),
                                   rtol=0, atol=0)


# ---------------------------------------------------------------------------
# overlap-aware autoshard cost model
# ---------------------------------------------------------------------------

class TestOverlapCostModel:
    def test_collective_seconds_discount(self):
        from paddle_tpu.analysis.passes.cost_model import \
            collective_seconds
        raw = collective_seconds("all_gather", 1 << 20, 4)
        assert collective_seconds("all_gather", 1 << 20, 4,
                                  overlap_fraction=1.0) == 0.0
        half = collective_seconds("all_gather", 1 << 20, 4,
                                  overlap_fraction=0.5)
        assert abs(half - raw * 0.5) < 1e-12
        # all_reduce only half-hides: of=1.0 leaves half the charge
        ar = collective_seconds("all_reduce", 1 << 20, 4)
        assert abs(collective_seconds("all_reduce", 1 << 20, 4,
                                      overlap_fraction=1.0)
                   - ar * 0.5) < 1e-12

    def test_default_fraction_follows_env_knob(self, monkeypatch):
        from paddle_tpu.analysis.passes import cost_model as cm
        monkeypatch.delenv("PADDLE_TPU_COLLECTIVE_OVERLAP",
                           raising=False)
        assert cm.default_overlap_fraction() == 0.0
        monkeypatch.setenv("PADDLE_TPU_COLLECTIVE_OVERLAP", "1")
        assert cm.default_overlap_fraction() == \
            cm.DEFAULT_OVERLAP_FRACTION

    def _plan(self, options=None):
        import paddle_tpu as pp
        from paddle_tpu.analysis import autoshard
        from paddle_tpu.jit import TrainStep
        from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
        if len(jax.devices()) < 8:
            pytest.skip("needs the virtual 8-device CPU mesh")
        pp.seed(0)
        model = LlamaForCausalLM(LlamaConfig.tiny(hidden_size=128))
        opt = pp.optimizer.AdamW(learning_rate=1e-3,
                                 parameters=model.parameters())
        step = TrainStep(model, opt)
        batch = {"input_ids": jax.ShapeDtypeStruct((8, 16), jnp.int32),
                 "labels": jax.ShapeDtypeStruct((8, 16), jnp.int32)}
        return autoshard.plan(step, batch, n_devices=8, topk=3,
                              options=options)

    def test_planner_discounts_and_table_prints_both(self):
        res0 = self._plan()
        res1 = self._plan(options={"overlap_fraction": 0.75})
        by_label = {s.candidate.label: s for s in res1.scored
                    if s.pruned is None}
        found = False
        for s0 in res0.scored:
            if s0.pruned is not None or s0.collective_raw_s <= 0:
                continue
            s1 = by_label.get(s0.candidate.label)
            if s1 is None:
                continue
            found = True
            assert abs(s1.collective_raw_s - s0.collective_s) < 1e-12
            assert s1.collective_s < s1.collective_raw_s
        assert found, "no communicating candidate to compare"
        table = res1.table()
        assert "raw ms" in table and "overlap_fraction=0.75" in table
        # knob-off table keeps both columns, no overlap footer
        t0 = res0.table()
        assert "raw ms" in t0 and "overlap_fraction=" not in t0
