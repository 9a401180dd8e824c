"""Latent attention (MLA) served from a latent block pool, a dense layer
before expert layers, a sigmoid router with a choice bias — the
``sarvam_mla`` configuration of the layer-pattern model — against the
plain reference of perf/archs/sarvam_mla.py, at a test's size on the CPU
with seeded weights.

Tolerances.  Everything here is float32 under ``highest``; the program
and the reference are two orderings of the same sums (a walk over tiles
of cached latents with an online softmax against a masked softmax a head
at a time; the absorbed form against the expanded; a grouped product
against a gather a held expert at a time), so they differ by float32
rounding over a few hundred terms: TOL = 5e-6 of the largest |value|.
A latent cached in bfloat16 moves an output by ~4e-3 of its size (8
mantissa bits), three orders above TOL: a test shows that it fails.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perf import common, weights

TOL = 5e-6

CFG = dict(arch="sarvam_mla", hidden_size=64, num_hidden_layers=3,
           first_k_dense_replace=1, intermediate_size=96,
           moe_intermediate_size=32, num_shared_experts=1,
           num_attention_heads=4, head_dim=48, q_head_dim=24,
           kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
           v_head_dim=16, use_qk_norm=True, num_experts=4,
           published={"num_experts": 16}, num_experts_per_tok=4,
           routed_scaling_factor=2.5, moe_router_enable_expert_bias=True,
           rope_theta=10000,
           rope_scaling={"type": "deepseek_yarn", "factor": 40,
                         "original_max_position_embeddings": 64,
                         "beta_fast": 32, "beta_slow": 1, "mscale": 1,
                         "mscale_all_dim": 1},
           max_position_embeddings=512, rms_norm_eps=1e-6, vocab_size=128,
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


def _ref_logits(arch, leaves, ids):
    with jax.default_matmul_precision("highest"):
        return np.asarray(arch.logits(leaves, CFG, jnp.asarray(ids)))


def _prompts(lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, CFG["vocab_size"], n, dtype=np.int32)
            for n in lengths]


def _engine(model, **over):
    from paddle_tpu.inference.serving import ContinuousBatchingEngine
    return ContinuousBatchingEngine(model, **dict(ENGINE, **over))


def _served_gap(arch, leaves, prompt, toks):
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
    assert (ref.argmax(-1) == ids).mean() < 0.2     # no echo of the input


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


def test_the_published_cut_holds_what_the_configuration_says():
    """``total_params`` of perf/configs/sarvam-105b.L5.json: the dense
    layer, four expert layers of 16 held experts, an eighth of the
    vocabulary twice (untied): 2.66 B; 5 x 640 stored values a token."""
    import os
    cfg = common.load_json(os.path.join(
        common.ROOT, "perf", "configs", "sarvam-105b.L5.json"))
    a = common.arch_of(cfg)
    assert 2.65e9 < a.total_params(cfg) < 2.67e9
    assert a.total_params(cfg) == sum(
        int(np.prod(s)) for _, s, _ in a.leaves(cfg))
    assert a.kv_bytes_per_token(cfg) == 5 * 640 * 2
    assert a.latent_prefill_cost(cfg, 0, 1) == 5 * 2 * 64 * 320
    ops, moved = a.latent_decode_cost(cfg, 1000.0)
    assert ops == 5 * 1000 * 2 * 64 * (576 + 512) and moved == 6.4e6


@pytest.mark.parametrize("lengths", [(45,), (37, 20, 9)])
def test_prefill_in_chunks_then_decode_is_the_references_forward(
        arch, model, leaves, lengths):
    """Chunked prefill (16 a chunk, so three chunks and a padded tail),
    then decode through the latent cache, several slots at once: every
    served token is the reference's own best of one teacher-forced
    forward, to rounding."""
    eng = _engine(model)
    prompts = _prompts(lengths, seed=3)
    rids = [eng.add_request(p, max_new_tokens=8) for p in prompts]
    with jax.default_matmul_precision("highest"):
        res = eng.run()
    assert eng._pool.latent and not eng._pool.vpools
    for rid, p in zip(rids, prompts):
        assert _served_gap(arch, leaves, p, res[rid][1]) <= TOL


def test_served_logits_are_the_references(arch, model, leaves):
    """The engine's own forward over a latent cache, logits against
    logits: a prompt in two dispatches (a 24-token chunk, then 13 more at
    their positions), which is what the engine's programs trace."""
    from paddle_tpu.inference.kv_cache import PagedCache, PagedKVPool
    ids = _prompts([37], seed=4)[0]
    pool = PagedKVPool(3, 12, 4, 1, 40, jnp.float32, latent=True)
    bt = jnp.arange(1, 11, dtype=jnp.int32)[None]
    caches = [PagedCache(k, None, bt) for k in pool.kpools]
    with jax.default_matmul_precision("highest"):
        lg1, caches = model(jnp.asarray(ids[None, :24]), None, caches,
                            jnp.asarray([0], jnp.int32))
        lg2, _ = model(jnp.asarray(ids[None, 24:]), None, list(caches),
                       jnp.asarray([24], jnp.int32))
    got = np.concatenate([np.asarray(lg1), np.asarray(lg2)], 1)
    ref = _ref_logits(arch, leaves, ids[None])
    assert np.abs(got - ref).max() <= TOL * np.abs(ref).max()


# -- the two attention forms, the kernel, the cache ---------------------------

def _latent_case(rng, B=3, S=8, h=4, rank=128, rope=16, nope=32, v=16,
                 bs=16, mb=8):
    width = rank + rope
    stored = -(-width // 128) * 128
    nb = 1 + B * mb
    pool = np.zeros((nb, bs, stored), np.float32)
    pool[..., :width] = rng.normal(size=(nb, bs, width))
    bt = 1 + np.arange(B * mb, dtype=np.int32).reshape(B, mb)
    w_kvb = rng.normal(size=(rank, h * (nope + v))).astype(np.float32) * .1
    q = rng.normal(size=(B, S, h, nope + rope)).astype(np.float32)
    return jnp.asarray(pool), jnp.asarray(bt), jnp.asarray(w_kvb), \
        jnp.asarray(q), dict(rank=rank, nope=nope)


def _expanded_reference(q, pool, bt, qpos, w_kvb, rank, nope, scale):
    """Masked softmax over the whole table, keys and values rebuilt."""
    B, S, h, qk = q.shape
    rows = pool[bt].reshape(B, -1, pool.shape[-1])[..., :rank + qk - nope]
    kv = jnp.einsum("btc,chn->bthn", rows[..., :rank],
                    w_kvb.reshape(rank, h, -1))
    k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(
        rows[:, :, None, rank:], kv.shape[:3] + (qk - nope,))], -1)
    s = jnp.einsum("bshn,bthn->bhst", q, k) * scale
    seen = jnp.arange(rows.shape[1])[None, None, None] <= \
        qpos[:, None, :, None]
    p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), -1)
    return jnp.einsum("bhst,bthv->bshv", p, kv[..., nope:])


def test_the_chunk_walk_is_the_masked_softmax():
    """The expanded walk, tile by tile with an online softmax, against
    the masked softmax over the whole table; the absorbed form's witness
    is the decode kernel, below."""
    from paddle_tpu.ops.pallas import latent_attention as LA
    pool, bt, w_kvb, q, dims = _latent_case(np.random.default_rng(0))
    qpos = jnp.asarray([[0], [30], [120]]) + jnp.arange(8)[None]
    with jax.default_matmul_precision("highest"):
        ref = _expanded_reference(q, pool, bt, qpos, w_kvb, scale=0.2,
                                  **dims)
        got = LA.latent_chunk_attention(q, pool, bt, qpos, w_kvb,
                                        scale=0.2, **dims)
    assert float(jnp.abs(got - ref).max()) <= TOL * float(jnp.abs(ref).max())


def test_the_decode_kernel_is_the_absorbed_form_of_the_same_scores():
    """The Pallas kernel (interpreted here) over absorbed queries, then
    ``W_uv``: the expanded reference's output for one query a row."""
    from paddle_tpu.ops.pallas import latent_attention as LA
    rng = np.random.default_rng(1)
    pool, bt, w_kvb, q, dims = _latent_case(rng, S=1)
    rank, nope = dims["rank"], dims["nope"]
    lengths = jnp.asarray([5, 37, 128], jnp.int32)
    wb = w_kvb.reshape(rank, 4, -1)
    with jax.default_matmul_precision("highest"):
        qa = jnp.concatenate(
            [jnp.einsum("bhn,chn->bhc", q[:, 0, :, :nope], wb[..., :nope]),
             q[:, 0, :, nope:],
             jnp.zeros((3, 4, pool.shape[-1] - rank - 16))], -1) * 0.2
        ol = LA.latent_decode_attention(qa, pool, bt, lengths, rank)
        got = jnp.einsum("bhc,chv->bhv", ol, wb[..., nope:])
        ref = _expanded_reference(q, pool, bt, lengths[:, None] - 1, w_kvb,
                                  scale=0.2, **dims)[:, 0]
    assert float(jnp.abs(got - ref).max()) <= TOL * float(jnp.abs(ref).max())


def test_a_chunk_reads_the_context_it_can_see_and_no_further():
    """Tiles past the furthest query are never gathered: NaN there
    leaves the result finite and equal (the walk's trip count is the
    visible context's, not the table's)."""
    from paddle_tpu.ops.pallas import latent_attention as LA
    pool, bt, w_kvb, q, dims = _latent_case(np.random.default_rng(2),
                                            B=1, bs=16, mb=96)
    qpos = 200 + jnp.arange(8)[None]                  # the first tile only
    clean = LA.latent_chunk_attention(q, pool, bt, qpos, w_kvb, scale=0.2,
                                      **dims)
    dirty = pool.at[1 + 512 // 16:].set(jnp.nan)      # tiles 1 and 2
    got = LA.latent_chunk_attention(q, dirty, bt, qpos, w_kvb, scale=0.2,
                                    **dims)
    assert bool(jnp.all(jnp.isfinite(got)))
    assert float(jnp.abs(got - clean).max()) == 0.0


def test_a_bfloat16_latent_in_a_float32_run_fails_the_tolerance():
    from paddle_tpu.ops.pallas import latent_attention as LA
    pool, bt, w_kvb, q, dims = _latent_case(np.random.default_rng(3))
    qpos = jnp.asarray([[0], [30], [120]]) + jnp.arange(8)[None]
    with jax.default_matmul_precision("highest"):
        ref = _expanded_reference(q, pool, bt, qpos, w_kvb, scale=0.2,
                                  **dims)
        got = LA.latent_chunk_attention(
            q, pool.astype(jnp.bfloat16).astype(jnp.float32), bt, qpos,
            w_kvb, scale=0.2, **dims)
    assert float(jnp.abs(got - ref).max()) > \
        100 * TOL * float(jnp.abs(ref).max())


def test_the_path_taken_is_counted_at_trace_time():
    from paddle_tpu.ops.pallas import latent_attention as LA
    pool, bt, w_kvb, q, dims = _latent_case(np.random.default_rng(4))
    before = common.series("paddle_tpu_latent_attention_path_total")
    qpos = jnp.arange(8)[None] + jnp.zeros((3, 1), jnp.int32)
    LA.latent_chunk_attention(q, pool, bt, qpos, w_kvb, scale=0.2, **dims)
    after = common.series("paddle_tpu_latent_attention_path_total")
    assert after.get("chunk_expanded", 0) == \
        before.get("chunk_expanded", 0) + 1
    assert not LA.latent_decode_eligible(512, 16, jnp.bfloat16)  # no TPU


def test_the_references_blocks_are_the_same_sums(arch, leaves, monkeypatch):
    """Queries in blocks of 8 and a head at a time against one block and
    all four heads at once: the same logits to float32 rounding."""
    ids = _prompts([40], seed=9)[0][None]
    monkeypatch.setattr(arch, "Q_BLOCK", 8)
    monkeypatch.setattr(arch, "HEAD_GROUP", 1)
    small = _ref_logits(arch, leaves, ids)
    monkeypatch.setattr(arch, "Q_BLOCK", 1024)
    monkeypatch.setattr(arch, "HEAD_GROUP", 4)
    whole = _ref_logits(arch, leaves, ids)
    assert np.abs(small - whole).max() <= TOL * np.abs(whole).max()


# -- rotary positions ---------------------------------------------------------

def test_yarn_frequencies_and_the_score_scale_are_the_closed_form(arch):
    """The published numbers: 64 rotary dims, theta 1e4, factor 40 over
    4096: the fast dims keep theta^(-2j/64), the slow ones are divided
    by 40, a ramp between; scale = 192^-1/2 (0.1 ln 40 + 1)^2."""
    import math
    import os
    from paddle_tpu.models.latent_attention import (latent_score_scale,
                                                    yarn_inv_freq)
    cfg = common.load_json(os.path.join(
        common.ROOT, "perf", "configs", "sarvam-105b.L5.json"))
    got = np.asarray(yarn_inv_freq(64, 10000.0, cfg["rope_scaling"]))
    ref = np.asarray(arch.yarn_inv_freq(cfg))
    np.testing.assert_allclose(got, ref, rtol=1e-6)
    j = np.arange(32)
    plain = 10000.0 ** (-2.0 * j / 64)
    corr = lambda t: 64 * math.log(4096 / (2 * math.pi * t)) \
        / (2 * math.log(10000.0))
    lo, hi = math.floor(corr(32)), math.ceil(corr(1))
    assert (lo, hi) == (10, 23)
    np.testing.assert_allclose(got[:lo + 1], plain[:lo + 1], rtol=1e-6)
    np.testing.assert_allclose(got[hi:], plain[hi:] / 40, rtol=1e-6)
    assert np.all(np.diff(got) < 0)
    scale = latent_score_scale(192, cfg["rope_scaling"])
    assert scale == pytest.approx(192 ** -0.5 * (0.1 * math.log(40) + 1) ** 2)
    assert scale == pytest.approx(0.0722 * 1.874, rel=2e-3)
    assert arch.score_scale(cfg) == pytest.approx(scale)
    # no scaling: the plain frequencies and the plain scale
    np.testing.assert_allclose(yarn_inv_freq(64, 10000.0, None), plain,
                               rtol=1e-6)
    assert latent_score_scale(192, None) == pytest.approx(192 ** -0.5)


# -- the router's rule --------------------------------------------------------

def _layer_leaves(arch, leaves, i):
    p = arch.layer_prefix(i)
    return {n[len(p):]: a for n, a in leaves.items() if n.startswith(p)}


def test_the_routers_rule_with_and_without_the_bias(arch, leaves):
    """``sigmoid_bias``: the program's gates are the reference's weights,
    they sum to routed_scaling_factor, and the seeded bias changes
    choices (counted); without a bias the rule is top-k of the scores;
    ``softmax_topk`` stays what it was."""
    from paddle_tpu.distributed.moe import router_gates
    from perf.reference.decoder import matmul
    mm = functools.partial(matmul, precision="float32")
    w = _layer_leaves(arch, leaves, 1)
    y = jnp.asarray(np.random.default_rng(7).normal(size=(1, 200, 64)),
                    jnp.float32)
    logits = y[0] @ w["block_sparse_moe.router.weight"]
    gates, ids = router_gates(logits, 4, "sigmoid_bias",
                              w["block_sparse_moe.router_bias"], 2.5)
    np.testing.assert_allclose(np.asarray(gates.sum(-1)), 2.5, rtol=1e-5)
    dense = np.zeros((200, 16), np.float32)
    np.put_along_axis(dense, np.asarray(ids), np.asarray(gates), -1)
    ref = np.asarray(arch.router_weights(y, w, CFG, mm))[0]
    np.testing.assert_allclose(dense, ref, rtol=1e-5, atol=1e-6)
    _, plain_ids = router_gates(logits, 4, "sigmoid_bias", None, 2.5)
    moved = sum(len(set(a) - set(b)) for a, b in zip(
        np.asarray(ids).tolist(), np.asarray(plain_ids).tolist()))
    assert 0.1 * 800 < moved < 0.9 * 800      # the bias steers the choice
    s = np.asarray(jax.nn.sigmoid(logits))
    assert {tuple(sorted(r)) for r in np.asarray(plain_ids).tolist()} == \
        {tuple(sorted(r)) for r in np.argsort(-s, -1)[:, :4].tolist()}
    soft, soft_ids = router_gates(logits, 4)
    topv, topi = jax.lax.top_k(logits, 4)
    assert bool(jnp.all(soft_ids == topi))
    np.testing.assert_allclose(np.asarray(soft),
                               np.asarray(jax.nn.softmax(topv, -1)))
    with pytest.raises(ValueError, match="router rule"):
        router_gates(logits, 4, "argmax")


def test_the_four_shares_and_the_shared_expert_once_are_the_whole_layer(
        arch, leaves):
    """Guide section 4's test: the four chips' routed parts plus what
    every chip computes alike (the shared expert), counted once, are what
    the uncut reference gives for the whole layer — from the program's
    expert layer told its ids, and from the reference's own cut."""
    from paddle_tpu.distributed.moe import gated_experts_forward
    from perf.reference.decoder import matmul
    mm = functools.partial(matmul, precision="float32")
    whole = dict(CFG, num_experts=16, published={"num_experts": 16})
    w = {n[len("model.layers_1."):]: a for n, a in weights.make_some(
        whole, SEED, [n for n, _, _ in arch.layer_leaves(whole, 1)],
        jnp.float32).items()}
    y = jnp.asarray(np.random.default_rng(6).normal(size=(1, 29, 64)),
                    jnp.float32)
    with jax.default_matmul_precision("highest"):
        full = arch._experts(y, w, whole, mm)
        parts, ours = [], []
        for first in (0, 4, 8, 12):
            cut = dict(w)
            for n in ("block_sparse_moe.w_in", "block_sparse_moe.w_out"):
                cut[n] = w[n][first:first + 4]
            parts.append(arch._experts(y, cut, whole, mm,
                                       held=range(first, first + 4)))
            local = np.full(16, 4, np.int32)
            local[first:first + 4] = np.arange(4)
            out, counts = gated_experts_forward(
                y[0], w["block_sparse_moe.router.weight"],
                cut["block_sparse_moe.w_in"],
                cut["block_sparse_moe.w_out"], top_k=4, local_of=local,
                rule="sigmoid_bias",
                router_bias=w["block_sparse_moe.router_bias"], scaling=2.5)
            ours.append(out[None])
            assert int(counts[2]) == 29 * 4
        shared = arch._gated(y, w["shared_mlp.input_linear.weight"],
                             w["shared_mlp.output_linear.weight"], mm)
        scale = float(jnp.abs(full + shared).max())
        for four in (parts, ours):
            assert float(jnp.abs(sum(four) + shared
                                 - (full + shared)).max()) <= TOL * scale
        assert float(jnp.abs(parts[0] - full).max()) > 0.05 * scale


def test_the_reference_gathers_or_walks_every_row_to_the_same_sum(
        arch, leaves):
    """``_experts`` takes an expert's rows gathered while they are a
    quarter of the positions or fewer, every row under its weights
    otherwise: a skewed router (every token picks experts 0-3) takes the
    second way and the seeded one the first, and both are the dense
    masked sum."""
    from perf.reference.decoder import matmul
    mm = functools.partial(matmul, precision="float32")
    w = _layer_leaves(arch, leaves, 1)
    y = jnp.asarray(np.random.default_rng(8).normal(size=(1, 64, 64)),
                    jnp.float32)
    skew = dict(w)
    skew["block_sparse_moe.router_bias"] = \
        w["block_sparse_moe.router_bias"].at[:4].add(10.0)
    for ww in (w, skew):
        weight = arch.router_weights(y, ww, CFG, mm)
        dense = sum(arch._gated(y, ww["block_sparse_moe.w_in"][e],
                                ww["block_sparse_moe.w_out"][e], mm)
                    * weight[..., e, None] for e in range(4))
        got = arch._experts(y, ww, CFG, mm)
        assert float(jnp.abs(got - dense).max()) <= \
            TOL * float(jnp.abs(dense).max())


# -- blocks over the latent pool ----------------------------------------------

def test_prefix_sharing_over_the_latent_pool(arch, model, leaves):
    """Two prompts with a common 32-token prefix: the second adopts the
    first's blocks (a hit, fewer chunks) and is served the reference's
    tokens; both agree with an engine that shares nothing."""
    head = _prompts([32], seed=9)[0]
    prompts = [np.concatenate([head, t]) for t in _prompts([9, 13], seed=10)]
    out = {}
    for share in (True, False):
        eng = _engine(model, prefix_cache=share)
        got = []
        with jax.default_matmul_precision("highest"):
            for p in prompts:                     # one after the other
                rid = eng.add_request(p, max_new_tokens=6)
                got.append(np.asarray(eng.run()[rid][1]))
        out[share] = got
        if share:
            assert eng._prefix.hits == 1
    for a, b, p in zip(out[True], out[False], prompts):
        assert a.tolist() == b.tolist()
        assert _served_gap(arch, leaves, p, a) <= TOL


def test_copy_on_write_export_and_import_over_the_latent_pool():
    from paddle_tpu.inference.kv_cache import (BlockAllocator, PagedKVPool,
                                               SequenceBlocks,
                                               deserialize_handoff,
                                               serialize_handoff)
    pool = PagedKVPool(2, 8, 4, 1, 40, jnp.float32, latent=True)
    assert pool.kpools[0].shape == (8, 4, 128) and pool.row_width == 40
    assert pool.nbytes == 2 * 8 * 4 * 128 * 4         # what is stored
    rows = jnp.asarray(np.random.default_rng(0).normal(size=(4, 128)),
                       jnp.float32)
    pool.kpools = [p.at[1].set(rows * (i + 1))
                   for i, p in enumerate(pool.kpools)]
    alloc = BlockAllocator(8)
    seq = SequenceBlocks(alloc, 4)
    seq.ensure_capacity(4)
    child = seq.fork()
    src, dst = child.ensure_writable(0, pool.copy_block)
    assert (src, dst) == (1, 2) and pool.cow_copies == 1
    for i, p in enumerate(pool.kpools):
        assert bool(jnp.all(p[2] == rows * (i + 1)))
    payload = pool.export_blocks([1, 2])
    assert payload["v"] == [] and len(payload["k"]) == 2
    wire = deserialize_handoff(serialize_handoff({"kv": payload, "t": 3}))
    other = PagedKVPool(2, 8, 4, 1, 40, jnp.float32, latent=True)
    other.import_blocks(wire["kv"], [5, 6])
    for a, b in zip(pool.kpools, other.kpools):
        assert bool(jnp.all(a[1] == b[5])) and bool(jnp.all(a[2] == b[6]))
    kv = PagedKVPool(2, 8, 4, 1, 128, jnp.float32)
    with pytest.raises(ValueError, match="k/v layers"):
        kv.import_blocks(wire["kv"], [5, 6])
    pool.reset()
    assert not pool.vpools and float(jnp.abs(pool.kpools[0]).max()) == 0.0


def test_park_resume_and_handoff_carry_latent_blocks(model):
    """What moves a request's blocks moves a latent pool's: a session
    parked in the tier and resumed, and a prompt prefilled on one engine
    and decoded on another, serve the tokens of an undisturbed run."""
    from paddle_tpu.inference.kv_tier import KVTierManager
    from paddle_tpu.observability.fleet import LocalStore
    prompt = _prompts([27], seed=12)[0]
    with jax.default_matmul_precision("highest"):
        eng = _engine(model)
        rid = eng.add_request(prompt, max_new_tokens=8)
        want = list(eng.run()[rid][1])
        eng = _engine(model, kv_tier=KVTierManager(store=LocalStore()))
        rid = eng.add_request(prompt, max_new_tokens=8)
        for _ in range(200):
            eng.step()
            slot = next(i for i, r in enumerate(eng._active)
                        if r is not None and r.rid == rid)
            if slot not in eng._prefilling and \
                    len(eng._active[slot].out) >= 3:
                break
        assert eng.park(rid) is not None and eng.pending == 0
        eng.resume(rid)
        assert list(eng.run()[rid][1]) == want
        first = _engine(model, role="prefill")
        rid = first.add_request(prompt, max_new_tokens=8, prefill_only=True)
        first.run()
        payload = first.export_handoff(rid)
        assert payload["kv"]["v"] == []
        second = _engine(model, role="decode")
        rid = second.add_request(prompt, max_new_tokens=8, handoff=payload)
        assert list(second.run()[rid][1]) == want


def test_what_the_latent_pool_refuses(model):
    from paddle_tpu.inference.kv_cache import PagedKVPool
    from paddle_tpu.models import HybridConfig
    with pytest.raises(ValueError, match="no per-head scale"):
        PagedKVPool(2, 8, 4, 1, 40, jnp.float32, quant="int8", latent=True)
    with pytest.raises(ValueError, match="one row a token"):
        PagedKVPool(2, 8, 4, 2, 40, jnp.float32, latent=True)
    with pytest.raises(ValueError, match="no per-head scale"):
        _engine(model, quant_kv="int8")
    with pytest.raises(NotImplementedError, match="one kind of block pool"):
        HybridConfig.tiny(layer_types=("attention", "latent_attention",
                                       "mamba"), kv_lora_rank=32,
                          qk_nope_head_dim=16, qk_rope_head_dim=8,
                          v_head_dim=16)
    with pytest.raises(NotImplementedError, match="partial rotary"):
        HybridConfig.tiny(position_embedding_type="partial")
    with pytest.raises(ValueError, match="kv_lora_rank"):
        HybridConfig.tiny(layer_types=("latent_attention",) * 3)
    with pytest.raises(NotImplementedError, match="attn_mask"):
        model(jnp.zeros((1, 4), jnp.int32), jnp.ones((1, 1, 4, 4), bool))
    with pytest.raises(TypeError, match="latent PagedKVPool"):
        from paddle_tpu.inference.kv_cache import PagedCache
        kv = PagedKVPool(1, 8, 4, 1, 128, jnp.float32)
        model.model.layers[0].self_attn(
            jnp.zeros((1, 2, 64)), PagedCache(
                kv.kpools[0], kv.vpools[0], jnp.zeros((1, 2), jnp.int32)),
            jnp.asarray([0]))


def test_the_pool_gauges_report_the_latent_pool(model):
    eng = _engine(model, prefix_cache=False)
    stored = 3 * eng._num_blocks * 4 * 128 * 4        # 3 layers, float32
    assert common.total("paddle_tpu_serving_kv_pool_bytes") >= stored
    assert eng._pool.nbytes == stored
    rid = eng.add_request(_prompts([21])[0], max_new_tokens=2)
    eng.step()
    assert eng._allocator.used_blocks == -(-(21 + 2) // 4)
    eng.run()
    assert eng._allocator.used_blocks == 0 and rid is not None


# -- what the layer-pattern model takes from a config -------------------------

def test_a_stated_head_size_and_rotary_positions_in_the_hybrid_model():
    """``head_dim`` stated (q_proj is heads x head_dim wide whatever the
    hidden size) and ``position_embedding_type`` ``rope`` for the
    grouped-query kind: the engine's chunked prefill + decode gives the
    uncached forward's tokens, and the positions matter."""
    import paddle_tpu as pp
    from paddle_tpu.models import (HybridConfig, HybridForCausalLM,
                                   LlamaConfig)
    assert LlamaConfig.tiny().head_dim == 16
    assert LlamaConfig.tiny(head_dim=24).head_dim == 24
    pp.seed(3)
    cfg = HybridConfig.tiny(layer_types=("attention", "mamba", "attention"),
                            head_dim=24, position_embedding_type="rope",
                            attention_multiplier=None)
    m = HybridForCausalLM(cfg)
    assert tuple(m.model.layers[0].self_attn.q_proj.weight.shape) == \
        (64, 4 * 24)
    prompt = _prompts([29], seed=11)[0]
    eng = _engine(m)
    rid = eng.add_request(prompt, max_new_tokens=6)
    with jax.default_matmul_precision("highest"):
        toks = np.asarray(eng.run()[rid][1])
        lg = np.asarray(m(jnp.asarray(
            np.concatenate([prompt, toks])[None])))[0]
    at = lg[len(prompt) - 1:len(prompt) - 1 + len(toks)]
    assert ((at.max(-1) - at[np.arange(6), toks])
            / np.abs(at).max(-1)).max() <= TOL
    cfg.position_embedding_type = "nope"
    m.model._rope = (None, None)
    for layer in m.model.layers:
        if layer.kind == "attention":
            layer.self_attn.rotary = False
    with jax.default_matmul_precision("highest"):
        nope = np.asarray(m(jnp.asarray(
            np.concatenate([prompt, toks])[None])))[0]
    assert np.abs(nope - lg).max() > 1e-3 * np.abs(lg).max()


# -- a decode step dispatched before the one before it is read ----------------

@pytest.mark.parametrize("k", [1, 4])
def test_dispatch_then_collect_serves_the_references_tokens(
        arch, model, leaves, k):
    """Mixed prompt lengths over three slots, one offered late, so chunks
    run between decode steps whose tokens the host has yet to read: the
    latent cache goes on from the device's own tokens, and every request
    gets the reference's."""
    eng = _engine(model, steps_per_sync=k)
    prompts = _prompts((37, 9, 20, 26), seed=40 + k)
    budgets = (7, 12, 9, 6)
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


def test_a_session_parked_between_two_steps_holds_what_was_unread(model):
    """``park`` with a decode dispatch unread reads it first: the payload
    carries those tokens too, and the resumed session serves the tokens
    of an undisturbed run."""
    from paddle_tpu.inference.kv_tier import KVTierManager
    from paddle_tpu.observability.fleet import LocalStore
    prompt = _prompts([27], seed=13)[0]
    with jax.default_matmul_precision("highest"):
        eng = _engine(model)
        rid = eng.add_request(prompt, max_new_tokens=10)
        want = list(eng.run()[rid][1])
        tier = KVTierManager(store=LocalStore())
        eng = _engine(model, kv_tier=tier)
        rid = eng.add_request(prompt, max_new_tokens=10)
        while eng._inflight is None or len(eng._active[0].out) < 3:
            eng.step()
        held = len(eng._active[0].out)
        key = eng.park(rid)
        assert key is not None and eng._inflight is None
        snap = tier.fetch(key)
        assert list(snap["tokens_out"]) == want[:held + 1]
        assert snap["pos"] == len(prompt) + held
        eng.resume(rid)
        assert list(eng.run()[rid][1]) == want
