"""The hybrid decoder (Mamba-2 + attention, gated experts told which they
hold) against the plain reference of perf/archs/granite_moe_hybrid.py, at
a test's size on the CPU with seeded weights: the full forward, the
serving engine's chunked prefill + decode with per-slot state, the state's
masking, the chunked scan, the chip's share of an expert layer, dropless
routing, and every refusal of what carries KV blocks only.

Tolerances.  Everything here is float32 under ``highest``; the program
and the reference are two orderings of the same sums (a chunked scan
against a recurrence, a grouped product against a dense masked sum), so
they differ by float32 rounding accumulated over at most a few hundred
terms: logits of spread 1e-3..1e-2 agree to 2e-7 absolutely, and the
limit used is TOL = 2e-6 of the largest |logit|.  Carrying the SSM
state in bfloat16 across a chunk boundary moves an output by ~1e-3 of
its size (8 mantissa bits), three orders above TOL: the last test of the
scan shows that it fails.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perf import common, weights

TOL = 2e-6

CFG = dict(arch="granite_moe_hybrid", hidden_size=64, num_hidden_layers=4,
           layer_types=["mamba", "attention", "mamba", "mamba"],
           num_attention_heads=4, num_key_value_heads=2,
           intermediate_size=32, shared_intermediate_size=48,
           num_local_experts=4, published={"num_local_experts": 8},
           num_experts_per_tok=2,
           mamba_n_heads=8, mamba_d_head=16, mamba_d_state=16,
           mamba_d_conv=4, mamba_n_groups=1, mamba_chunk_size=8,
           mamba_conv_bias=True, embedding_multiplier=1.0,
           attention_multiplier=1 / 16, residual_multiplier=0.22,
           logits_scaling=16, position_embedding_type="nope",
           max_position_embeddings=512, rms_norm_eps=1e-5, vocab_size=128,
           torch_dtype="float32")
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


def _ref_logits(arch, leaves, ids):
    with jax.default_matmul_precision("highest"):
        return np.asarray(arch.logits(leaves, CFG, jnp.asarray(ids)))


def _prompts(lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, CFG["vocab_size"], n, dtype=np.int32)
            for n in lengths]


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
    # the tokens are not the inputs echoed by the tied head: the layers
    # decide them (see the note on embedding_multiplier in tests/perf)
    assert (ref.argmax(-1) == ids).mean() < 0.2


def test_leaves_are_the_models_state_dict(arch, model):
    names = [n for n, _, _ in arch.leaves(CFG)]
    assert len(names) == len(set(names))
    state = model.state_dict(keep_vars=True)
    assert set(names) == set(state)
    for n, shape, _ in arch.leaves(CFG):
        assert tuple(state[n].shape) == tuple(shape), n
    total = sum(int(np.prod(s)) for _, s, _ in arch.leaves(CFG))
    assert arch.total_params(CFG) == total


def test_lazy_guard_builds_no_array_until_given():
    from paddle_tpu.models import HybridConfig, HybridForCausalLM
    from paddle_tpu.nn import LazyGuard
    with LazyGuard():
        m = HybridForCausalLM(HybridConfig.tiny(dtype="bfloat16"))
    params = m.state_dict(keep_vars=True)
    assert all(isinstance(t._data, jax.ShapeDtypeStruct)
               for t in params.values())
    assert all(t._data.dtype == jnp.bfloat16 for t in params.values())
    name, t = next(iter(params.items()))
    t._set_data(jnp.ones(t._data.shape, jnp.bfloat16))
    assert isinstance(t._data, jax.Array)
    from paddle_tpu.nn import Linear        # outside the guard: as before
    assert isinstance(Linear(4, 4).weight._data, jax.Array)


def test_attention_without_rotary_and_with_a_stated_scale():
    """``LlamaAttention`` under a config that says ``nope`` and an
    ``attention_multiplier`` is softmax(multiplier * q k^T) v with no
    rotation; a ``LlamaConfig`` says neither and is left as it was."""
    from paddle_tpu.models import HybridConfig, LlamaConfig
    from paddle_tpu.models.llama import LlamaAttention
    c = HybridConfig.tiny(attention_multiplier=0.05)
    att = LlamaAttention(c)
    assert att.rotary is False and att.q_scale == pytest.approx(
        0.05 * c.head_dim ** 0.5)
    plain = LlamaAttention(LlamaConfig.tiny())
    assert plain.rotary is True and plain.q_scale is None
    x = jnp.asarray(np.random.default_rng(1).normal(size=(1, 9, 64)),
                    jnp.float32)
    with jax.default_matmul_precision("highest"):
        got = np.asarray(att(x, None, None)._data)
        q, k, v = (np.asarray(p(x)._data) for p in
                   (att.q_proj, att.k_proj, att.v_proj))
    h, kv, hd = c.num_attention_heads, c.num_key_value_heads, c.head_dim
    q = q.reshape(9, h, hd)
    k = np.repeat(k.reshape(9, kv, hd), h // kv, 1)
    v = np.repeat(v.reshape(9, kv, hd), h // kv, 1)
    sc = np.einsum("qhd,khd->hqk", q, k) * 0.05
    sc = np.where(np.tril(np.ones((9, 9), bool)), sc, -np.inf)
    p = np.exp(sc - sc.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    want = np.einsum("hqk,khd->qhd", p, v).reshape(9, h * hd) \
        @ np.asarray(att.o_proj.weight._data)
    assert np.abs(got[0] - want).max() < 1e-5 * np.abs(want).max()


# -- the engine: chunked prefill, decode, slot reuse --------------------------

@pytest.mark.parametrize("lengths", [
    (16, 32),       # whole prefill chunks (16) and whole scan chunks (8)
    (21, 5, 43),    # neither: a padded tail in the last chunk
    (24, 8),        # whole scan chunks, not whole prefill chunks
])
def test_prefill_in_chunks_then_decode_is_the_references_forward(
        arch, model, leaves, lengths):
    from paddle_tpu.inference.serving import ContinuousBatchingEngine
    eng = ContinuousBatchingEngine(model, **ENGINE)
    assert eng._prefix is None              # sharing is off by itself
    assert len(eng._pool.kpools) == 1       # pools only where a layer attends
    assert len(eng._state.layers) == 3
    prompts = _prompts(lengths, seed=len(lengths))
    with jax.default_matmul_precision("highest"):
        rids = [eng.add_request(p, max_new_tokens=7) for p in prompts]
        res = eng.run()
    for rid, p in zip(rids, prompts):
        assert len(res[rid][1]) == 7
        assert _served_gap(arch, leaves, p, res[rid][1]) <= TOL


def test_a_reused_slot_starts_from_zero_state(arch, model, leaves):
    """Five requests over two slots: the later ones are admitted into
    slots whose state an earlier request left behind."""
    from paddle_tpu.inference.serving import ContinuousBatchingEngine
    eng = ContinuousBatchingEngine(model, **dict(ENGINE, slots=2))
    prompts = _prompts((19, 30, 11, 26, 17), seed=9)
    with jax.default_matmul_precision("highest"):
        rids = [eng.add_request(p, max_new_tokens=5) for p in prompts]
        res = eng.run()
    for rid, p in zip(rids, prompts):
        assert _served_gap(arch, leaves, p, res[rid][1]) <= TOL
    from paddle_tpu.observability import default_registry
    reg = default_registry()
    assert reg.get("paddle_tpu_serving_state_bytes").value() == \
        eng._state.nbytes > 0
    assert reg.get("paddle_tpu_serving_state_slots_used").value() == 0
    picks = reg.get("paddle_tpu_moe_picks_total").value()
    local = reg.get("paddle_tpu_moe_local_picks_total").value()
    assert 0 < local < picks and picks % CFG["num_experts_per_tok"] == 0
    touched = dict((k[0], c.value()) for k, c in
                   reg.get("paddle_tpu_moe_experts_touched").series())
    assert 0 < touched["sum"] <= touched["layer_steps"] \
        * CFG["num_local_experts"]
    assert touched["layer_steps"] % len(CFG["layer_types"]) == 0


def _moe_totals():
    from paddle_tpu.observability import default_registry
    reg = default_registry()
    touched = reg.get("paddle_tpu_moe_experts_touched")
    steps = dict((k[0], c.value()) for k, c in touched.series()) \
        if touched is not None else {}
    picks = reg.get("paddle_tpu_moe_picks_total")
    return steps.get("layer_steps", 0), picks.value() if picks else 0


def test_experts_without_slot_state_are_counted_by_their_layers(arch):
    """What a model says of its state and of its experts are two
    statements: one whose every layer attends keeps no slot state (the
    prefix cache stays on, nothing is refused) and still has its three
    expert layers counted, a layer a decode step."""
    from paddle_tpu.inference.serving import ContinuousBatchingEngine
    cfg = dict(CFG, num_hidden_layers=3, layer_types=["attention"] * 3)
    eng = ContinuousBatchingEngine(
        arch.build(cfg, SEED, jax.devices()[0]), **ENGINE)
    assert eng._state is None and eng._prefix is not None
    assert len(eng._pool.kpools) == 3
    w = weights.make_all(cfg, SEED, jnp.float32)
    prompt = _prompts([21], seed=3)[0]
    before = _moe_totals()
    with jax.default_matmul_precision("highest"):
        rid = eng.add_request(prompt, max_new_tokens=6)
        toks = np.asarray(eng.run()[rid][1])
        lg = np.asarray(arch.logits(w, cfg, jnp.asarray(
            np.concatenate([prompt, toks])[None])))[0]
    at = lg[len(prompt) - 1:len(prompt) - 1 + len(toks)]
    assert ((at.max(-1) - at[np.arange(len(toks)), toks])
            / np.abs(at).max(-1)).max() <= TOL
    steps, picks = (a - b for a, b in zip(_moe_totals(), before))
    # the first token is the prefill's; five decode steps of three layers
    assert steps == 5 * 3 and picks == 5 * 3 * CFG["num_experts_per_tok"]


def test_slot_state_without_counted_experts_counts_none(
        arch, model, leaves, monkeypatch):
    """A model with recurrent layers that states no routed expert layer
    is served from its slot state, and nothing is counted for it."""
    from paddle_tpu.inference.serving import ContinuousBatchingEngine
    monkeypatch.setattr(type(model), "routed_expert_layers", lambda self: 0)
    eng = ContinuousBatchingEngine(model, **ENGINE)
    assert eng._state is not None and not hasattr(eng, "_moe_counters")
    prompt = _prompts([21], seed=3)[0]
    before = _moe_totals()
    with jax.default_matmul_precision("highest"):
        rid = eng.add_request(prompt, max_new_tokens=6)
        toks = eng.run()[rid][1]
    assert _served_gap(arch, leaves, prompt, toks) <= TOL
    assert _moe_totals() == before


def test_recover_rebuilds_the_slot_state(model):
    from paddle_tpu.inference.serving import ContinuousBatchingEngine
    eng = ContinuousBatchingEngine(model, **ENGINE)
    eng.add_request(_prompts([20])[0], max_new_tokens=3)
    for _ in range(3):      # admit, then the prompt's two chunks
        eng.step()
    assert any(float(jnp.abs(st.ssm).max()) > 0 for st in eng._state.layers)
    eng._recover(RuntimeError("injected"))
    assert all(float(jnp.abs(a).max()) == 0
               for st in eng._state.layers for a in st)


# -- the state's masking ------------------------------------------------------

def _mixer_and_state(rows=3):
    from paddle_tpu.inference.kv_cache import SlotState
    from paddle_tpu.models import HybridConfig, Mamba2Mixer
    mixer = Mamba2Mixer(HybridConfig.tiny())
    rng = np.random.default_rng(3)
    mixer.A_log._set_data(jnp.log(jnp.asarray(
        rng.uniform(1, 16, 8), jnp.float32)))
    mixer.dt_bias._set_data(jnp.asarray(rng.normal(-2, 1, 8), jnp.float32))
    mixer.D._set_data(jnp.ones(8, jnp.float32))
    mixer.conv1d.bias._set_data(jnp.asarray(rng.normal(0, 0.1, 160),
                                            jnp.float32))
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
        # the same 11 tokens as one chunk of their own (8 + 3 in the scan)
        y_cut, cut = mixer(u[:, :11], state,
                           StepInfo(jnp.asarray([11]), slot))
    for a, b, c, old in zip(pad, pad2, cut, state):
        assert np.array_equal(np.asarray(a), np.asarray(b))
        np.testing.assert_allclose(np.asarray(a[1]), np.asarray(c[1]),
                                   rtol=0, atol=2e-6 * float(
                                       jnp.abs(c[1]).max()))
        for other_slot in (0, 2):
            assert np.array_equal(np.asarray(a[other_slot]),
                                  np.asarray(old[other_slot]))
    np.testing.assert_allclose(np.asarray(y_pad[:, :11]),
                               np.asarray(y_cut), rtol=0, atol=2e-6)


# -- the chunked scan ---------------------------------------------------------

def _scan_inputs(length, seed=4):
    rng = np.random.default_rng(seed)
    f = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)
    x, B, C = f(2, length, 8, 16), f(2, length, 16), f(2, length, 16)
    dt = jax.nn.softplus(f(2, length, 8) - 2.0)
    A = -jnp.asarray(rng.uniform(1, 16, 8), jnp.float32)
    return x, dt, A, B, C, f(2, 8, 16, 16)


def _recurrence(x, dt, A, B, C, h):
    from paddle_tpu.ops import mamba2
    ys = []
    for t in range(x.shape[1]):
        y, h = mamba2.ssm_step(x[:, t], dt[:, t], A, B[:, t], C[:, t], h)
        ys.append(y)
    return jnp.stack(ys, 1), h


@pytest.mark.parametrize("length,chunk", [(24, 8), (32, 32), (40, 8)])
def test_chunked_scan_is_the_recurrence(length, chunk):
    from paddle_tpu.ops import mamba2
    x, dt, A, B, C, h0 = _scan_inputs(length)
    y, h = mamba2.ssd_scan(x, dt, A, B, C, h0, chunk)
    y_ref, h_ref = _recurrence(x, dt, A, B, C, h0)
    assert float(jnp.abs(y - y_ref).max()) <= 5e-6 * float(
        jnp.abs(y_ref).max())
    assert float(jnp.abs(h - h_ref).max()) <= 5e-6 * float(
        jnp.abs(h_ref).max())


def test_a_bfloat16_state_between_two_spans_fails_the_tolerance():
    """The same scan in two spans of 16: carried in float32 it is the
    recurrence to 5e-6; with the state rounded to bfloat16 at the
    boundary the second span's outputs are off by far more."""
    from paddle_tpu.ops import mamba2
    x, dt, A, B, C, h0 = _scan_inputs(32)
    y_ref, _ = _recurrence(x, dt, A, B, C, h0)
    half = lambda a: (a[:, :16], a[:, 16:])
    (x1, x2), (d1, d2), (B1, B2), (C1, C2) = map(half, (x, dt, B, C))
    _, h = mamba2.ssd_scan(x1, d1, A, B1, C1, h0, 8)
    scale = float(jnp.abs(y_ref).max())
    y2, _ = mamba2.ssd_scan(x2, d2, A, B2, C2, h, 8)
    assert float(jnp.abs(y2 - y_ref[:, 16:]).max()) <= 5e-6 * scale
    y2, _ = mamba2.ssd_scan(x2, d2, A, B2, C2,
                            h.astype(jnp.bfloat16).astype(jnp.float32), 8)
    assert float(jnp.abs(y2 - y_ref[:, 16:]).max()) > 50 * 5e-6 * scale


# -- the chip's share of an expert layer --------------------------------------

def _uncut_layer(arch, i=0):
    """Layer ``i``'s leaves at the router's whole width, and the
    configurations of the whole layer and of a chip's half."""
    whole = dict(CFG, num_local_experts=8, published={})
    w = weights.make_some(
        whole, SEED, [n for n, _, _ in arch.layer_leaves(whole, i)],
        jnp.float32)
    p = arch.layer_prefix(i)
    w = {n[len(p):]: a for n, a in w.items()}
    half = dict(CFG, num_local_experts=4,
                published={"num_local_experts": 8})
    return whole, half, w


def _half_leaves(w, first):
    """The chip that holds experts ``first`` ... ``first + 3``: their
    weights, and the router's columns turned so that they are ids 0-3
    (an arch file's chip holds the first ids; top-k does not care which
    column an expert sits in)."""
    cut = dict(w)
    for n in ("block_sparse_moe.w_in", "block_sparse_moe.w_out"):
        cut[n] = w[n][first:first + 4]
    cut["block_sparse_moe.router.weight"] = jnp.roll(
        w["block_sparse_moe.router.weight"], -first, axis=1)
    return cut


def test_the_two_shares_and_the_shared_expert_once_are_the_whole_layer(
        arch):
    """Guide section 4's test: both chips' routed parts plus what every
    chip computes alike (the shared expert), counted once, are what the
    uncut reference gives for the whole layer — from the program's
    expert layer, and from the reference's own cut."""
    import functools
    from paddle_tpu.distributed.moe import gated_experts_forward
    from perf.reference.decoder import matmul
    whole, half, w = _uncut_layer(arch)
    mm = functools.partial(matmul, precision="float32")
    y = jnp.asarray(np.random.default_rng(6).normal(size=(1, 29, 64)),
                    jnp.float32)
    with jax.default_matmul_precision("highest"):
        full = arch._experts(y, w, whole, mm)
        parts, ours = [], []
        for first in (0, 4):
            cut = _half_leaves(w, first)
            parts.append(arch._experts(y, cut, half, mm))
            local = np.full(8, 4, np.int32)     # the program: told its ids
            local[first:first + 4] = np.arange(4)
            out, counts = gated_experts_forward(
                y[0], w["block_sparse_moe.router.weight"],
                cut["block_sparse_moe.w_in"],
                cut["block_sparse_moe.w_out"], top_k=2, local_of=local)
            ours.append(out[None])
            assert int(counts[2]) == 29 * 2
        shared = arch._gated(y, w["shared_mlp.input_linear.weight"],
                             w["shared_mlp.output_linear.weight"], mm)
        scale = float(jnp.abs(full + shared).max())
        for pair in (parts, ours):
            assert float(jnp.abs(pair[0] + pair[1] + shared
                                 - (full + shared)).max()) <= 5e-6 * scale
        # the share is a share: neither half alone is the layer
        assert float(jnp.abs(parts[0] - full).max()) > 0.05 * scale


def test_routing_is_dropless_under_a_skewed_router(arch):
    """Every token picks the same two experts (the router's columns 1 and
    2 dwarf the rest): a capacity would drop most of them; here every
    token gets both experts' outputs, as the dense reference does."""
    import functools
    from paddle_tpu.distributed.moe import gated_experts_forward
    from perf.reference.decoder import matmul
    whole, _, w = _uncut_layer(arch)
    x = jnp.abs(jnp.asarray(np.random.default_rng(7).normal(
        size=(1, 64, 64)), jnp.float32))
    router = w["block_sparse_moe.router.weight"].at[:, 1:3].set(5.0)
    w = dict(w, **{"block_sparse_moe.router.weight": router})
    with jax.default_matmul_precision("highest"):
        out, counts = gated_experts_forward(
            x[0], router, w["block_sparse_moe.w_in"],
            w["block_sparse_moe.w_out"], top_k=2,
            local_of=np.arange(8, dtype=np.int32))
        ref = arch._experts(x, w, whole, functools.partial(
            matmul, precision="float32"))
    assert [int(c) for c in counts] == [2, 128, 128]   # 2 experts, no drop
    assert float(jnp.abs(out - ref[0]).max()) <= 5e-6 * float(
        jnp.abs(ref).max())
    assert float(jnp.abs(out).min(axis=-1).max()) > 0   # no zeroed token


def test_masked_rows_route_nowhere(arch):
    from paddle_tpu.distributed.moe import gated_experts_forward
    _, _, w = _uncut_layer(arch)
    x = jnp.asarray(np.random.default_rng(8).normal(size=(6, 64)),
                    jnp.float32)
    out, counts = gated_experts_forward(
        x, w["block_sparse_moe.router.weight"],
        w["block_sparse_moe.w_in"], w["block_sparse_moe.w_out"], top_k=2,
        local_of=np.arange(8, dtype=np.int32),
        row_valid=jnp.asarray([True, False, True, False, False, False]))
    assert int(counts[1]) == int(counts[2]) == 4 and int(counts[0]) <= 4
    assert float(jnp.abs(out[jnp.asarray([1, 3, 4, 5])]).max()) == 0.0


# -- what carries KV blocks only is refused -----------------------------------

def test_spec_decode_kv_tier_and_roles_are_refused_at_construction(model):
    from paddle_tpu.inference.kv_tier import KVTierManager
    from paddle_tpu.inference.serving import ContinuousBatchingEngine
    for kwargs, word in ((dict(spec_decode=2), "spec_decode"),
                         (dict(kv_tier=KVTierManager()), "kv_tier"),
                         (dict(role="prefill"), "role")):
        with pytest.raises(ValueError, match=word):
            ContinuousBatchingEngine(model, **ENGINE, **kwargs)


def test_prefix_cache_is_off_and_repeated_prompts_serve_right(
        arch, model, leaves):
    """``prefix_cache=True`` (the default) over a model with slot state:
    the engine serves with sharing off — the same prompt twice prefills
    twice and both answers are the reference's."""
    from paddle_tpu.inference.serving import ContinuousBatchingEngine
    from paddle_tpu.observability import default_registry
    eng = ContinuousBatchingEngine(model, prefix_cache=True, **ENGINE)
    prompt = _prompts([40])[0]
    with jax.default_matmul_precision("highest"):
        rids = [eng.add_request(prompt, max_new_tokens=4) for _ in (0, 1)]
        res = eng.run()
    assert list(res[rids[0]][1]) == list(res[rids[1]][1])
    assert _served_gap(arch, leaves, prompt, res[rids[0]][1]) <= TOL
    assert default_registry().get(
        "paddle_tpu_serving_prefix_cache_blocks").value() == 0


def test_park_resume_and_handoff_are_refused(model):
    from paddle_tpu.inference.serving import ContinuousBatchingEngine
    eng = ContinuousBatchingEngine(model, **ENGINE)
    rid = eng.add_request(_prompts([12])[0], max_new_tokens=4)
    eng.step()
    for call, word in ((lambda: eng.park(rid), "park"),
                       (lambda: eng.resume(rid), "resume"),
                       (lambda: eng.export_handoff(rid), "export_handoff"),
                       (lambda: eng.add_request(
                           _prompts([12])[0], prefill_only=True), "handoff"),
                       (lambda: eng.add_request(
                           _prompts([12])[0], handoff={"block_size": 4}),
                        "handoff")):
        with pytest.raises(ValueError, match=word):
            call()
    eng.run()       # and the engine is still serving
    assert eng.request_status(rid) == "ok"


# -- a decode step dispatched before the one before it is read ----------------

@pytest.mark.parametrize("k", [1, 4])
def test_dispatch_then_collect_serves_the_references_tokens(
        arch, model, leaves, k):
    """Mixed prompt lengths over three slots, one offered late, so chunks
    run between decode steps whose tokens the host has yet to read: the
    slot state and the attention layer's blocks go on from the device's
    own tokens, and every request gets the reference's."""
    from paddle_tpu.inference.serving import ContinuousBatchingEngine
    eng = ContinuousBatchingEngine(model, **dict(ENGINE, steps_per_sync=k))
    prompts = _prompts((21, 5, 43, 18), seed=20 + k)
    budgets = (9, 12, 6, 7)
    with jax.default_matmul_precision("highest"):
        rids = [eng.add_request(p, max_new_tokens=b)
                for p, b in zip(prompts[:3], budgets)]
        for _ in range(6):
            eng.step()
        rids.append(eng.add_request(prompts[3], max_new_tokens=budgets[3]))
        res = eng.run()
    for rid, p, b in zip(rids, prompts, budgets):
        assert len(res[rid][1]) == b
        assert _served_gap(arch, leaves, p, res[rid][1]) <= TOL
    assert eng._inflight is None and not eng.pending


def test_expert_counts_are_annotated_once_a_dispatch_in_dispatch_order(
        model, monkeypatch):
    """``serving.moe_counts`` is written when a dispatch's counts reach
    the host: one a dispatch, in the order they were issued, never more
    than the one unread dispatch and the one just issued behind."""
    import contextlib
    from paddle_tpu.inference.serving import ContinuousBatchingEngine
    from paddle_tpu.observability import tracing
    events, issued = [], []

    @contextlib.contextmanager
    def annotation(name, **stats):
        if name == "serving.moe_counts":
            events.append(("counts", stats["touched"]))
        yield
    monkeypatch.setattr(tracing, "host_annotation", annotation)
    eng = ContinuousBatchingEngine(model, **ENGINE)
    dispatch = eng._dispatch_batched

    def dispatch_spy(*a):
        d, rest = dispatch(*a)
        issued.append(d.out[1])
        events.append(("dispatch", None))
        return d, rest
    eng._dispatch_batched = dispatch_spy
    for p in _prompts((19, 7, 25), seed=31):
        eng.add_request(p, max_new_tokens=8)
    eng.run()
    behind = 0
    for kind, _ in events:
        behind += 1 if kind == "dispatch" else -1
        assert 0 <= behind <= 2
    assert behind == 0 and len(issued) > 8
    assert [t for kind, t in events if kind == "counts"] == \
        [int(np.asarray(c)[0]) for c in issued]
