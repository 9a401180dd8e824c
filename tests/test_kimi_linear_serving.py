"""KDA layers with per-slot state beside latent attention without rotary
positions over a latent block pool, a dense layer before expert layers —
the ``kimi_linear`` configuration of the layer-pattern model — against
the plain reference of perf/archs/kimi_linear.py, at a test's size on the
CPU with seeded weights: the full forward, the serving engine's chunked
prefill + decode with both kinds of state at once, slot reuse, the
engine's gauges over both pools, the scopes in its two programs, latent
attention with nothing turned, and the chip's share of an expert layer.

Tolerances.  Everything here is float32 under ``highest``; the program
and the reference are two orderings of the same sums (the chunked delta
rule against the recurrence, a walk over tiles of cached latents against
a masked softmax, a grouped product against a gather a held expert at a
time), so they differ by float32 rounding over a few hundred terms:
logits agree to ~1e-6 of the largest |logit| and the limit is TOL = 5e-6.
A term left out moves the logits by a hundred times that or more: the
shared expert, the output gate, the decay and beta are each shown to
(tests/test_kda.py shows the delta correction and a bfloat16 state).
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perf import common, weights

TOL = 5e-6

# K K K M K: a dense layer first, a whole period, and a KDA layer after
# the latent one
CFG = dict(arch="kimi_linear", hidden_size=64, num_hidden_layers=5,
           first_k_dense_replace=1, intermediate_size=96,
           moe_intermediate_size=32, num_shared_experts=1,
           num_attention_heads=4, num_key_value_heads=4, head_dim=16,
           kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
           v_head_dim=16, mla_use_nope=True,
           linear_attn_config={"full_attn_layers": [4, 8],
                               "kda_layers": [1, 2, 3, 5, 6, 7],
                               "head_dim": 16, "num_heads": 4,
                               "short_conv_kernel_size": 4},
           num_experts=4, published={"num_experts": 16},
           num_experts_per_token=4, routed_scaling_factor=2.446,
           model_max_length=512, rms_norm_eps=1e-5, vocab_size=128,
           tie_word_embeddings=False, torch_dtype="float32")
ENGINE = dict(slots=3, max_len=96, kv_block_size=4, prefill_chunk=16,
              prefill_buckets=(16,))
SEED = 5


@pytest.fixture(scope="module")
def arch():
    return common.arch_of(CFG)


@pytest.fixture(scope="module")
def model(arch):
    return arch.build(CFG, SEED, jax.devices()[0])


@pytest.fixture(scope="module")
def leaves():
    return weights.make_all(CFG, SEED, jnp.float32)


def _ref_logits(arch, leaves, ids, cfg=CFG):
    with jax.default_matmul_precision("highest"):
        return np.asarray(arch.logits(leaves, cfg, jnp.asarray(ids)))


def _prompts(lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, CFG["vocab_size"], n, dtype=np.int32)
            for n in lengths]


def _engine(model, **over):
    from paddle_tpu.inference.serving import ContinuousBatchingEngine
    return ContinuousBatchingEngine(model, **dict(ENGINE, **over))


def _served_gap(arch, leaves, prompt, toks):
    """Widest |program's choice - reference's best| / max |logit| over the
    served positions, the reference teacher-forced in one full forward."""
    toks = np.asarray(toks)
    lg = _ref_logits(arch, leaves, np.concatenate([prompt, toks])[None])[0]
    at = lg[len(prompt) - 1:len(prompt) - 1 + len(toks)]
    return float(((at.max(-1) - at[np.arange(len(toks)), toks])
                  / np.abs(at).max(-1)).max())


# -- the model ----------------------------------------------------------------

def test_full_forward_is_the_references(arch, model, leaves):
    ids = np.stack(_prompts([37, 37]))
    ref = _ref_logits(arch, leaves, ids)
    with jax.default_matmul_precision("highest"):
        got = np.asarray(model(jnp.asarray(ids)))
    assert np.abs(got - ref).max() <= TOL * np.abs(ref).max()
    assert (ref.argmax(-1) == ids).mean() < 0.2     # not the input echoed


def test_leaves_are_the_models_state_dict_and_total_params_their_sum(
        arch, model):
    names = [n for n, _, _ in arch.leaves(CFG)]
    assert len(names) == len(set(names))
    state = model.state_dict(keep_vars=True)
    assert set(names) == set(state)
    for n, shape, _ in arch.leaves(CFG):
        assert tuple(state[n].shape) == tuple(shape), n
    assert arch.total_params(CFG) == sum(
        int(np.prod(s)) for _, s, _ in arch.leaves(CFG))
    assert [arch.layer_kind(CFG, i) for i in range(5)] == [
        "kda+dense", "kda+experts", "kda+experts", "mla+experts",
        "kda+experts"]
    assert model.config.layer_types == ("kda", "kda", "kda",
                                        "latent_attention", "kda")
    # the state a slot keeps: a tail over the q | k | v channels and a
    # [heads, d, d] matrix, a KDA layer
    assert model.slot_state_shapes() == [((3, 192), (4, 16, 16))] * 4
    assert model.config.latent_row == 40


@pytest.mark.parametrize("what", ["shared expert", "output gate", "decay",
                                  "beta"])
def test_a_term_left_out_fails_the_tolerance(arch, leaves, what):
    """The program with one term of a layer made trivial — the shared
    expert's output zero, the output gate a constant half, the decay
    none (``exp(A_log)`` ~ 0), beta a constant half — is a hundred times
    TOL or more away from the reference."""
    model = arch.build(CFG, SEED, jax.devices()[0])
    state = model.state_dict(keep_vars=True)
    if what == "shared expert":
        hit = [n for n in state if n.endswith("shared_mlp.output_linear"
                                              ".weight")]
    elif what == "output gate":
        hit = [n for n in state if n.endswith("kda.g_proj.weight")]
    elif what == "decay":
        hit = [n for n in state if n.endswith("kda.A_log")]
    else:
        hit = [n for n in state if n.endswith("kda.in_proj.weight")]
    assert hit
    for n in hit:
        t = state[n]
        if what == "decay":
            t._set_data(jnp.full(t.shape, -100.0, jnp.float32))
        elif what == "beta":        # the last 4 columns are beta's
            t._set_data(t._data.at[:, -4:].set(0.0))
        else:
            t._set_data(jnp.zeros(t.shape, jnp.float32))
    ids = np.stack(_prompts([37]))
    ref = _ref_logits(arch, leaves, ids)
    with jax.default_matmul_precision("highest"):
        got = np.asarray(model(jnp.asarray(ids)))
    assert np.abs(got - ref).max() > 100 * TOL * np.abs(ref).max()


# -- the engine: a latent pool and slot state at once -------------------------

@pytest.mark.parametrize("lengths", [
    (16, 32),       # whole prefill chunks (16)
    (21, 5, 43),    # a padded tail in the last chunk; three lengths
    (45,),          # three chunks: the state and the block table both grow
])
def test_prefill_in_chunks_then_decode_is_the_references_forward(
        arch, model, leaves, lengths):
    eng = _engine(model)
    assert eng._prefix is None              # sharing is off by itself
    assert len(eng._pool.kpools) == 1 and not eng._pool.vpools
    assert eng._pool.kpools[0].shape[-1] == 128     # 40 padded to a lane
    assert len(eng._state.layers) == 4
    prompts = _prompts(lengths, seed=len(lengths))
    with jax.default_matmul_precision("highest"):
        rids = [eng.add_request(p, max_new_tokens=7) for p in prompts]
        res = eng.run()
    for rid, p in zip(rids, prompts):
        assert len(res[rid][1]) == 7
        assert _served_gap(arch, leaves, p, res[rid][1]) <= TOL
    assert eng._allocator.used_blocks == 0  # latent blocks freed at the end


def test_a_slot_another_request_just_left_starts_from_zero_state(
        arch, model, leaves):
    """Five requests over two slots: the later ones are admitted into
    slots whose state, and whose block-table row, an earlier request left
    behind; the gauges read both pools."""
    eng = _engine(model, slots=2)
    prompts = _prompts((19, 30, 11, 26, 17), seed=9)
    from paddle_tpu.observability import default_registry
    reg = default_registry()
    with jax.default_matmul_precision("highest"):
        rids = [eng.add_request(p, max_new_tokens=5) for p in prompts]
        eng.step()
        eng.step()
        assert reg.get("paddle_tpu_serving_state_slots_used").value() == 2
        assert eng._allocator.used_blocks >= -(-(19 + 5) // 4)
        res = eng.run()
    for rid, p in zip(rids, prompts):
        assert _served_gap(arch, leaves, p, res[rid][1]) <= TOL
    assert reg.get("paddle_tpu_serving_state_bytes").value() == \
        eng._state.nbytes == 2 * 4 * (3 * 192 + 4 * 16 * 16) * 4
    assert reg.get("paddle_tpu_serving_state_slots_used").value() == 0
    assert common.total("paddle_tpu_serving_kv_pool_bytes") >= \
        eng._pool.nbytes == eng._num_blocks * 4 * 128 * 4
    assert eng._allocator.used_blocks == 0
    touched = dict((k[0], c.value()) for k, c in
                   reg.get("paddle_tpu_moe_experts_touched").series())
    assert 0 < touched["sum"] <= touched["layer_steps"] * CFG["num_experts"]


def test_aot_warmup_compiles_both_programs_over_both_kinds_of_state(
        arch, model, leaves):
    """``aot_warmup`` over a latent pool and slot state together, then the
    compiled programs serve the reference's tokens; each program carries
    the model's scopes, the recurrence's nested in ``ssm``."""
    from perf import program_spans
    eng = _engine(model)
    stats = eng.aot_warmup()
    assert {"serving.decode", "serving.prefill_chunk[16]"} <= set(stats)
    for compiled in (eng._decode_compiled, eng._prefill_chunk_compiled):
        text = compiled.as_text()
        found = set(program_spans.scope_by_instruction(
            text, arch.SCOPES).values())
        assert {"ssm", "attn", "moe", "mlp", "lm_head_ce"} <= found
        assert "/ssm/kda/" in text
        assert set(program_spans.scope_by_instruction(
            text, (arch.RECURRENCE,)).values()) == {"kda"}
    prompt = _prompts([27], seed=2)[0]
    with jax.default_matmul_precision("highest"):
        rid = eng.add_request(prompt, max_new_tokens=6)
        toks = eng.run()[rid][1]
    assert _served_gap(arch, leaves, prompt, toks) <= TOL


def test_recover_rebuilds_both_pools(model):
    eng = _engine(model)
    eng.add_request(_prompts([20])[0], max_new_tokens=3)
    for _ in range(3):      # admit, then the prompt's two chunks
        eng.step()
    assert any(float(jnp.abs(st.ssm).max()) > 0 for st in eng._state.layers)
    assert float(jnp.abs(eng._pool.kpools[0]).max()) > 0
    eng._recover(RuntimeError("injected"))
    assert all(float(jnp.abs(a).max()) == 0
               for st in eng._state.layers for a in st)
    assert eng._allocator.used_blocks == 0


def test_what_stays_refused_with_slot_state(model):
    from paddle_tpu.models import HybridConfig
    eng = _engine(model)
    rid = eng.add_request(_prompts([9])[0], max_new_tokens=2)
    for what in (lambda: eng.park(rid), lambda: eng.export_handoff(rid)):
        with pytest.raises(ValueError, match="recurrent state"):
            what()
    eng.run()
    with pytest.raises(ValueError, match="spec_decode"):
        _engine(model, spec_decode=2)
    with pytest.raises(ValueError, match="kda_n_heads"):
        HybridConfig.tiny(layer_types=("kda", "attention", "kda"))
    with pytest.raises(NotImplementedError, match="one kind of block pool"):
        HybridConfig.tiny(layer_types=("kda", "attention",
                                       "latent_attention"), kda_n_heads=4,
                          kda_head_dim=16, kv_lora_rank=32,
                          qk_nope_head_dim=16, qk_rope_head_dim=8,
                          v_head_dim=16)


# -- the masking of the layer's state -----------------------------------------

def _mixer_and_state(rows=3):
    from paddle_tpu.inference.kv_cache import SlotState
    from paddle_tpu.models import HybridConfig, KDAMixer
    mixer = KDAMixer(HybridConfig.tiny(kda_n_heads=4, kda_head_dim=16))
    rng = np.random.default_rng(3)
    mixer.A_log._set_data(jnp.log(jnp.asarray(
        rng.uniform(1, 16, 4), jnp.float32)))
    mixer.dt_bias._set_data(jnp.asarray(rng.normal(-2, 1, 64), jnp.float32))
    conv, ssm = mixer.state_shapes()
    state = SlotState(
        jnp.asarray(rng.normal(size=(rows,) + conv), jnp.float32),
        jnp.asarray(rng.normal(size=(rows,) + ssm), jnp.float32))
    return mixer, state, rng


def test_an_inactive_decode_row_keeps_its_state_bit_for_bit():
    from paddle_tpu.inference.kv_cache import StepInfo
    mixer, state, rng = _mixer_and_state()
    u = jnp.asarray(rng.normal(size=(3, 1, 64)), jnp.float32)
    _, new = mixer(u, state, StepInfo(jnp.asarray([1, 0, 1], jnp.int32)))
    for old, got in zip(state, new):
        assert np.array_equal(np.asarray(old[1]), np.asarray(got[1]))
        assert not np.array_equal(np.asarray(old[0]), np.asarray(got[0]))
        assert not np.array_equal(np.asarray(old[2]), np.asarray(got[2]))


def test_a_padded_tail_leaves_no_trace_in_the_state():
    """A 16-wide chunk holding 11 tokens leaves slot 1 the state the 11
    tokens alone leave, whatever the 5 padded positions hold; the other
    slots are untouched, bit for bit."""
    from paddle_tpu.inference.kv_cache import StepInfo
    mixer, state, rng = _mixer_and_state()
    u = jnp.asarray(rng.normal(size=(1, 16, 64)), jnp.float32)
    slot = jnp.asarray(1, jnp.int32)
    with jax.default_matmul_precision("highest"):
        y_pad, pad = mixer(u, state, StepInfo(jnp.asarray([11]), slot))
        other = u.at[:, 11:].set(7.0)
        _, pad2 = mixer(other, state, StepInfo(jnp.asarray([11]), slot))
        y_cut, cut = mixer(u[:, :11], state,
                           StepInfo(jnp.asarray([11]), slot))
    for a, b, c, old in zip(pad, pad2, cut, state):
        assert np.array_equal(np.asarray(a), np.asarray(b))
        np.testing.assert_allclose(np.asarray(a[1]), np.asarray(c[1]),
                                   rtol=0, atol=TOL * float(
                                       jnp.abs(c[1]).max()))
        for other_slot in (0, 2):
            assert np.array_equal(np.asarray(a[other_slot]),
                                  np.asarray(old[other_slot]))
    np.testing.assert_allclose(np.asarray(y_pad[:, :11]),
                               np.asarray(y_cut), rtol=0, atol=TOL)


# -- latent attention with nothing turned -------------------------------------

def test_latent_attention_without_rotary_is_the_expanded_form():
    """``position_embedding_type`` ``nope``: the layer's scores are ``(q_n
    k_n + q_r k_r) scale`` with q_r and k_r as projected — without a
    cache, and from a latent pool in two dispatches (the cached row is
    ``[c | k_r]`` unturned) — and the same layer under ``rope`` gives
    something else."""
    import paddle_tpu as pp
    from paddle_tpu.inference.kv_cache import PagedCache, PagedKVPool
    from paddle_tpu.models import HybridConfig
    from paddle_tpu.models.latent_attention import LatentAttention
    pp.seed(7)
    dims = dict(layer_types=("latent_attention",) * 3, kv_lora_rank=32,
                qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
                head_dim=24)
    att = LatentAttention(HybridConfig.tiny(**dims))
    assert att.rotary is False
    x = jnp.asarray(np.random.default_rng(1).normal(size=(1, 21, 64)),
                    jnp.float32)
    data = lambda layer: np.asarray(layer.weight._data)
    with jax.default_matmul_precision("highest"):
        got = np.asarray(att(x))
        pool = PagedKVPool(1, 12, 4, 1, 40, jnp.float32, latent=True)
        bt = jnp.arange(1, 11, dtype=jnp.int32)[None]
        o1, cache = att(x[:, :16], PagedCache(pool.kpools[0], None, bt),
                        jnp.asarray([0], jnp.int32))
        o2, _ = att(x[:, 16:], cache, jnp.asarray([16], jnp.int32))
    xs = np.asarray(x)[0]
    q = (xs @ data(att.q_proj)).reshape(21, 4, 24)
    ckr = xs @ data(att.kv_a_proj_with_mqa)
    c = ckr[:, :32]
    c = c / np.sqrt((c * c).mean(-1, keepdims=True) + att.eps) \
        * data(att.kv_a_layernorm)
    kv = (c @ data(att.kv_b_proj)).reshape(21, 4, 32)
    k = np.concatenate([kv[..., :16],
                        np.broadcast_to(ckr[:, None, 32:], (21, 4, 8))], -1)
    sc = np.einsum("qhd,khd->hqk", q, k) * 24 ** -0.5
    sc = np.where(np.tril(np.ones((21, 21), bool)), sc, -np.inf)
    p = np.exp(sc - sc.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    want = np.einsum("hqk,khd->qhd", p, kv[..., 16:]).reshape(21, 64) \
        @ data(att.o_proj)
    scale = np.abs(want).max()
    assert np.abs(got[0] - want).max() <= TOL * scale
    cached = np.concatenate([np.asarray(o1), np.asarray(o2)], 1)[0]
    assert np.abs(cached - want).max() <= TOL * scale
    turned = LatentAttention(HybridConfig.tiny(
        position_embedding_type="rope", **dims))
    assert turned.rotary is True
    for name in ("q_proj", "kv_a_proj_with_mqa", "kv_b_proj", "o_proj",
                 "kv_a_layernorm"):
        getattr(turned, name).weight._set_data(
            getattr(att, name).weight._data)
    with jax.default_matmul_precision("highest"):
        other = np.asarray(turned(x))
    assert np.abs(other[0] - want).max() > 1e-3 * scale


# -- the chip's share of an expert layer --------------------------------------

def test_the_four_shares_and_the_shared_expert_once_are_the_whole_layer(
        arch):
    """Guide section 4's test at this model's cut (four chips a layer):
    the four chips' routed parts plus what every chip computes alike (the
    shared expert), counted once, are what the uncut reference gives for
    the whole layer — from the program's expert layer told its ids, and
    from the reference's own cut."""
    from paddle_tpu.distributed.moe import gated_experts_forward
    from perf.reference.decoder import matmul
    from perf.archs import sarvam_mla
    mm = functools.partial(matmul, precision="float32")
    whole = dict(CFG, num_experts=16, published={"num_experts": 16})
    w = {n[len("model.layers_1."):]: a for n, a in weights.make_some(
        whole, SEED, [n for n, _, _ in arch.layer_leaves(whole, 1)],
        jnp.float32).items()}
    y = jnp.asarray(np.random.default_rng(6).normal(size=(1, 29, 64)),
                    jnp.float32)
    view = arch._as_sarvam(whole)
    with jax.default_matmul_precision("highest"):
        full = sarvam_mla._experts(y, w, view, mm)
        parts, ours = [], []
        for first in (0, 4, 8, 12):
            cut = dict(w)
            for n in ("block_sparse_moe.w_in", "block_sparse_moe.w_out"):
                cut[n] = w[n][first:first + 4]
            parts.append(sarvam_mla._experts(y, cut, view, mm,
                                             held=range(first, first + 4)))
            local = np.full(16, 4, np.int32)
            local[first:first + 4] = np.arange(4)
            out, counts = gated_experts_forward(
                y[0], w["block_sparse_moe.router.weight"],
                cut["block_sparse_moe.w_in"],
                cut["block_sparse_moe.w_out"], top_k=4, local_of=local,
                rule="sigmoid_bias",
                router_bias=w["block_sparse_moe.router_bias"],
                scaling=2.446)
            ours.append(out[None])
            assert int(counts[2]) == 29 * 4
        shared = sarvam_mla._gated(
            y, w["shared_mlp.input_linear.weight"],
            w["shared_mlp.output_linear.weight"], mm)
        scale = float(jnp.abs(full + shared).max())
        for four in (parts, ours):
            assert float(jnp.abs(sum(four) + shared
                                 - (full + shared)).max()) <= TOL * scale
        assert float(jnp.abs(parts[0] - full).max()) > 0.05 * scale
    # the whole layer through the reference: the cut's routed part is
    # the first share
    x = jnp.asarray(np.random.default_rng(8).normal(size=(1, 29, 64)),
                    jnp.float32)
    cut = dict(w)
    for n in ("block_sparse_moe.w_in", "block_sparse_moe.w_out"):
        cut[n] = w[n][:4]
    with jax.default_matmul_precision("highest"):
        a = arch.layer(x, w, whole, 1, None)
        b = arch.layer(x, cut, CFG, 1, None)
    assert float(jnp.abs(a - b).max()) > 1e-3 * float(jnp.abs(a).max())
