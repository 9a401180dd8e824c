"""Gated grouped-query attention with head norms, three sliding-window
layers (rotary) to one full layer (no rotary), sandwich norms, a dense
layer before expert layers — the ``afmoe`` configuration of the
layer-pattern model — against the plain reference of perf/archs/afmoe.py,
at a test's size on the CPU with seeded weights: the full forward, chunked
prefill then decode **past the window** over a ring table (logits, rows of
unequal length in one batch), the serving engine with its two block
groups, the decode kernel and the chunk walk under a lower bound, the
ring's safety over random shapes, what the window group refuses, the
engine a model without windows builds, and the chip's share of an expert
layer.

Tolerances.  Everything here is float32 under ``highest``; the program
and the reference are two orderings of the same sums (a walk over tiles
of a ring of blocks against a masked softmax over a slice of keys, a
grouped product against a gather a held expert at a time), so logits
agree to ~1e-6 of the largest |logit| and the limit is TOL = 5e-6.  A
window one position short or long moves them by a hundred times that.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perf import common, weights

TOL = 5e-6
S, F = "sliding_attention", "full_attention"
# S S S F S behind nothing: a dense layer first, a whole period, and a
# window layer after the full one
CFG = dict(arch="afmoe", hidden_size=64, num_hidden_layers=5,
           layer_types=[S, S, S, F, S], num_dense_layers=1,
           intermediate_size=96, moe_intermediate_size=32,
           num_shared_experts=1, num_attention_heads=4,
           num_key_value_heads=2, head_dim=16, sliding_window=8,
           num_experts=4, published={"num_experts": 16},
           num_experts_per_tok=4, route_scale=2.448, rope_theta=10000,
           mup_enabled=True, max_position_embeddings=512,
           rms_norm_eps=1e-5, vocab_size=128, tie_word_embeddings=False,
           torch_dtype="float32")
# a ring of ceil((8 + 8) / 4) + 1 = 5 blocks = 20 positions: a row of 40+
# tokens goes round it twice and more
ENGINE = dict(slots=3, max_len=96, kv_block_size=4, prefill_chunk=8,
              prefill_buckets=(8,))
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
    assert model.config.layer_windows == (8, 8, 8, 0, 8)
    assert model.attention_windows() == [8, 8, 8, 0, 8]
    assert model.config.embedding_multiplier == 8.0     # sqrt(64)


def test_full_layers_turn_no_rotary_and_window_layers_do(model):
    """A layer's attention under other rotary tables (the positions'
    angles reversed): the full layer's output does not change (nothing
    of it reads a table), a window layer's does; and only window layers
    hold a window."""
    layers = model.model.layers
    assert [ly.self_attn.rotary for ly in layers] == [
        True, True, True, False, True]
    assert [ly.self_attn.window for ly in layers] == [8, 8, 8, None, 8]
    x = jnp.asarray(np.random.default_rng(1).normal(size=(1, 12, 64)),
                    jnp.float32)
    rope = model.model._rope
    for i, moves in ((3, False), (1, True)):
        at = layers[i].self_attn
        a = np.asarray(at(x, *rope)._data)
        b = np.asarray(at(x, *(t[::-1] for t in rope))._data)
        assert (np.abs(a - b).max() > 1e-3) == moves


@pytest.mark.parametrize("what", ["output gate", "head norms",
                                  "post norms", "embedding scale"])
def test_a_term_left_out_fails_the_tolerance(arch, leaves, what):
    """The program with one term of a layer made trivial — the output
    gate a constant half, the head norms' gains one, the two post norms'
    gains one, the embedding unscaled — is a hundred times TOL or more
    away from the reference."""
    model = arch.build(CFG, SEED, jax.devices()[0])
    state = model.state_dict(keep_vars=True)
    hit = {"output gate": ("self_attn.gate_proj.weight",),
           "head norms": ("q_norm.weight", "k_norm.weight"),
           "post norms": ("post_attention_layernorm.weight",
                          "post_mlp_layernorm.weight"),
           "embedding scale": ()}[what]
    for n, t in state.items():
        if n.endswith(hit) and hit:
            fill = 0.0 if what == "output gate" else 1.0
            t._set_data(jnp.full(t.shape, fill, jnp.float32))
    if what == "embedding scale":
        model.config.embedding_multiplier = 1.0
    ids = np.stack(_prompts([37]))
    ref = _ref_logits(arch, leaves, ids)
    with jax.default_matmul_precision("highest"):
        got = np.asarray(model(jnp.asarray(ids)))
    assert np.abs(got - ref).max() > 100 * TOL * np.abs(ref).max()


# -- chunked prefill then decode past the window: logits ----------------------

def _paged_logits(model, rows, decode, block=4, chunk=8, ring=5):
    """Every row's logits at every position through the paged path:
    each row's first ``len - decode`` tokens in chunks of ``chunk`` (B =
    1, the last one padded), then ``decode`` single-token steps over all
    rows at once (rows of unequal length side by side), teacher-forced;
    the window layers' table a ring of ``ring`` blocks a row, named over
    and over, as the engine lays it out."""
    from paddle_tpu.inference.kv_cache import PagedCache, StepInfo
    cfg = model.config
    B, mb = len(rows), -(-max(map(len, rows)) // block) + 2
    shape = (cfg.num_key_value_heads, cfg.head_dim)
    pools, tables = [], []
    for w in cfg.layer_windows:
        n = 1 + B * (ring if w else mb)
        pools.append([jnp.zeros((n, block) + shape, jnp.float32)] * 2)
    full = 1 + np.arange(B * mb, dtype=np.int32).reshape(B, mb)
    rings = 1 + np.arange(B * ring, dtype=np.int32).reshape(B, ring)
    tables = (jnp.asarray(full),
              jnp.asarray(rings[:, np.arange(mb) % ring]))
    out = [np.zeros((len(r), cfg.vocab_size), np.float32) for r in rows]

    @jax.jit
    def run(ids, pools, bt, pos, valid):
        caches = [PagedCache(k, v, bt[bool(w)])
                  for (k, v), w in zip(pools, cfg.layer_windows)]
        caches.append(StepInfo(valid))
        lg, new = model(ids, None, caches, pos)
        return getattr(lg, "_data", lg), [
            [c.k._data, c.v._data] for c in new[:len(pools)]]

    def forward(ids, bt, pos, valid):
        lg, pools[:] = run(jnp.asarray(ids), pools, bt,
                           jnp.asarray(pos, jnp.int32),
                           jnp.asarray(valid, jnp.int32))
        return np.asarray(lg)

    with jax.default_matmul_precision("highest"):
        for b, row in enumerate(rows):
            for start in range(0, len(row) - decode, chunk):
                n = min(chunk, len(row) - decode - start)
                ids = np.zeros((1, chunk), np.int32)
                ids[0, :n] = row[start:start + n]
                lg = forward(ids, tuple(t[b:b + 1] for t in tables),
                             [start], [n])
                out[b][start:start + n] = lg[0, :n]
        for step in range(decode):
            pos = [len(r) - decode + step for r in rows]
            ids = np.asarray([[r[p]] for r, p in zip(rows, pos)], np.int32)
            lg = forward(ids, tables, pos, [1] * B)
            for b, p in enumerate(pos):
                out[b][p] = lg[b, 0]
    return out


def test_chunked_prefill_then_decode_past_the_window_is_the_references(
        arch, model, leaves):
    """Rows of 47, 22 and 61 tokens (prefilled in chunks of 8 with a
    padded tail, the last 9 decoded side by side): the ring of 20
    positions goes round three times under the longest, and every logit
    of every row is the reference's.  The same logits against a
    reference whose window is 7 or 9 fail the tolerance a hundredfold."""
    rows = _prompts([47, 22, 61], seed=3)
    got = _paged_logits(model, rows, decode=9)
    # causal: one padded batch gives every row's reference
    ids = np.zeros((3, 64), np.int32)
    for b, row in enumerate(rows):
        ids[b, :len(row)] = row
    ref = _ref_logits(arch, leaves, ids)
    off = [_ref_logits(arch, leaves, ids, dict(CFG, sliding_window=w))
           for w in (7, 9)]
    for b, (row, lg) in enumerate(zip(rows, got)):
        n = len(row)
        assert np.abs(lg - ref[b, :n]).max() <= TOL * np.abs(ref[b, :n]).max()
        for wrong in off:
            assert np.abs(lg - wrong[b, :n]).max() > \
                100 * TOL * np.abs(wrong[b, :n]).max()


# -- the engine: two block groups ---------------------------------------------

@pytest.mark.parametrize("lengths", [
    (16, 32),       # whole prefill chunks (8)
    (21, 5, 43),    # a padded tail in the last chunk; three lengths
    (61,),          # eight chunks: the ring goes round three times
])
def test_the_engine_serves_the_references_tokens_past_the_window(
        arch, model, leaves, lengths):
    eng = _engine(model)
    assert eng._prefix is None              # sharing is off by itself
    assert eng._window == 8 and eng._ring == 5
    assert eng._pool.window_layers == (0, 1, 2, 4)
    assert [p.shape[0] for p in eng._pool.kpools] == [
        eng._num_window_blocks] * 3 + [eng._num_blocks] + [
        eng._num_window_blocks]
    prompts = _prompts(lengths, seed=len(lengths))
    with jax.default_matmul_precision("highest"):
        rids = [eng.add_request(p, max_new_tokens=11) for p in prompts]
        res = eng.run()
    for rid, p in zip(rids, prompts):
        assert len(res[rid][1]) == 11
        assert _served_gap(arch, leaves, p, res[rid][1]) <= TOL
    # both groups' blocks are back; a request never held more than a ring
    assert eng._allocator.used_blocks == 0
    assert eng._allocator_w.used_blocks == 0
    assert eng._blocks_used_peak_w <= 5 * len(lengths)
    assert eng._blocks_used_peak >= -(-(max(lengths) + 11) // 4)


def test_admission_defers_on_either_group_and_counts_which(model):
    """Two requests where a group has blocks for one: the second waits
    until the first retires, the deferral is counted under the group
    that lacked them, and nothing of the other group is held meanwhile."""
    deferred = lambda: common.series(
        "paddle_tpu_serving_admissions_deferred_total")
    for group, over in (("window", dict(num_window_blocks=1 + 5 + 2)),
                        ("full", dict(num_kv_blocks=1 + 12 + 3))):
        eng = _engine(model, **over)
        before = deferred().get(group, 0)
        other = deferred().get("full" if group == "window" else "window", 0)
        rids = [eng.add_request(p, max_new_tokens=6)
                for p in _prompts([40, 38], seed=4)]
        eng.step()
        eng.step()      # the second admission finds the group short
        assert deferred()[group] == before + 1
        assert eng._allocator_w.used_blocks == 5
        assert eng._allocator.used_blocks == -(-(40 + 6) // 4)
        res = eng.run()
        assert all(len(res[r][1]) == 6 for r in rids)
        assert deferred().get(
            "full" if group == "window" else "window", 0) == other
        assert eng._allocator.used_blocks == 0
        assert eng._allocator_w.used_blocks == 0
        assert common.total("paddle_tpu_serving_kv_window_blocks_free") \
            == eng._num_window_blocks - 1


def test_a_32k_request_holds_one_ring_of_289_blocks_for_its_life(model):
    """The cell's engine over this model: window 4096, blocks of 16,
    chunks of 512.  A 32,768-token prompt with 768 tokens to come is
    admitted with 2,096 full-group blocks and 289 window-group blocks,
    its window table names those 289 over all 2,096 entries, and the
    window group's peak is 289."""
    import copy
    from paddle_tpu.inference.serving import ContinuousBatchingEngine
    from paddle_tpu.models import HybridForCausalLM
    from paddle_tpu.nn import LazyGuard
    cfg = copy.copy(model.config)
    cfg.layer_windows = tuple(4096 if w else 0 for w in cfg.layer_windows)
    cfg.max_position_embeddings = 33552
    with LazyGuard():
        big = HybridForCausalLM(cfg)
    eng = ContinuousBatchingEngine(
        big, slots=2, max_len=33552, kv_block_size=16, prefill_chunk=512,
        prefill_buckets=(512,), num_kv_blocks=2200, num_window_blocks=300)
    assert eng._ring == 289
    prompt = np.zeros(32768, np.int32)
    eng.add_request(prompt, max_new_tokens=768)
    eng.add_request(prompt, max_new_tokens=768)
    eng.step()                              # admission only: no dispatch
    assert eng._allocator.used_blocks == 2096
    assert eng._allocator_w.used_blocks == eng._blocks_used_peak_w == 289
    named = eng._bt_w[0]
    assert len(set(named[:2096])) == 289 and not named[2096:].any()
    assert (named[:2096] == named[:289][np.arange(2096) % 289]).all()
    # the second request's ring does not fit: it waits, holding nothing
    assert eng._admit(1, eng._queue[0]) is False
    assert eng._allocator_w.used_blocks == 289
    assert eng._allocator.used_blocks == 2096
    # a request shorter than a ring holds what its length needs
    eng2 = ContinuousBatchingEngine(
        big, slots=2, max_len=33552, kv_block_size=16, prefill_chunk=512,
        prefill_buckets=(512,), num_kv_blocks=2200, num_window_blocks=300)
    eng2.add_request(np.zeros(1000, np.int32), max_new_tokens=24)
    eng2.step()
    assert eng2._allocator_w.used_blocks == 64 == \
        eng2._allocator.used_blocks


def test_no_dispatch_overwrites_a_key_a_later_query_may_see():
    """The ring over random shapes, by the engine's own admission: for a
    window, a block size, a chunk width and a request drawn at random,
    replay every write the engine's dispatches make through the window
    table (a chunk's padded tail and the positions a fused decode writes
    ahead included) and check before each dispatch's queries that every
    position they may see — the last ``window`` up to the query — is
    still what its table entry holds."""
    from paddle_tpu.inference.serving import ContinuousBatchingEngine
    from paddle_tpu.models import HybridConfig, HybridForCausalLM
    from paddle_tpu.nn import LazyGuard
    rng = np.random.default_rng(46)
    for _ in range(12):
        bs = int(rng.choice([2, 4, 8]))
        window = int(rng.integers(3, 40))
        chunk = int(rng.integers(2, 24))
        K = int(rng.choice([1, 1, 3]))
        Lp, new = int(rng.integers(1, 150)), int(rng.integers(1, 60))
        max_len = -(-(Lp + new + K + 1) // bs) * bs + bs
        max_len = max(max_len, chunk + 1)
        cfg = HybridConfig.tiny(
            num_hidden_layers=2, layer_types=("attention",) * 2,
            ffn_types=("dense",) * 2,
            dense_intermediate_size=32, layer_windows=(window, 0),
            max_position_embeddings=1024)
        with LazyGuard():
            tiny = HybridForCausalLM(cfg)
        eng = ContinuousBatchingEngine(
            tiny, slots=1, max_len=max_len, kv_block_size=bs,
            prefill_chunk=chunk, prefill_buckets=(chunk,),
            steps_per_sync=K)
        eng.add_request(np.zeros(Lp, np.int32), max_new_tokens=new)
        eng.step()
        table = eng._bt_w[0]
        assert eng._allocator_w.used_blocks == \
            min(-(-(Lp + -(-new // K) * K) // bs), eng._ring)
        holds = {}                      # (block, slot) -> position
        written = 0                     # real positions written so far

        def write(lo, n):
            for p in range(lo, lo + n):
                lb = p // bs
                blk = int(table[lb]) if lb < len(table) else 0
                holds[(blk, p % bs)] = p

        def sees(t):
            for j in range(max(0, t - window + 1), t + 1):
                assert holds.get((int(table[j // bs]), j % bs)) == j, \
                    (window, bs, chunk, K, Lp, new, t, j)

        for start in range(0, Lp, chunk):
            write(start, chunk)         # the padded tail is written too
            written = min(start + chunk, Lp)
            for t in range(start, written):
                sees(t)
        pos = Lp
        for _ in range(-(-new // K)):
            for k in range(K):          # a fused decode's steps in turn
                write(pos + k, 1)
                sees(pos + k)
            pos += K


def test_aot_warmup_compiles_both_programs_over_both_groups(
        arch, model, leaves):
    """``aot_warmup`` over the two groups, then the compiled programs
    serve the reference's tokens; each program carries the model's
    scopes, a layer's attention by its kind inside ``attn``, and the
    chunk program the walk's."""
    from perf import program_spans
    eng = _engine(model)
    stats = eng.aot_warmup()
    assert {"serving.decode", "serving.prefill_chunk[8]"} <= set(stats)
    for compiled in (eng._decode_compiled, eng._prefill_chunk_compiled):
        text = compiled.as_text()
        found = set(program_spans.scope_by_instruction(
            text, arch.SCOPES).values())
        assert {"attn", "moe", "mlp", "lm_head_ce"} <= found
        assert "/attn/attn_window/" in text and "/attn/attn_full/" in text
        assert set(program_spans.scope_by_instruction(
            text, arch.WINDOW_SCOPES).values()) == set(arch.WINDOW_SCOPES)
    assert "/attn/attn_window/" + arch.CHUNK_ATTENTION in \
        eng._prefill_chunk_compiled.as_text()
    prompt = _prompts([27], seed=2)[0]
    with jax.default_matmul_precision("highest"):
        rid = eng.add_request(prompt, max_new_tokens=6)
        toks = eng.run()[rid][1]
    assert _served_gap(arch, leaves, prompt, toks) <= TOL


def test_recover_rebuilds_both_groups(model):
    eng = _engine(model)
    eng.add_request(_prompts([20])[0], max_new_tokens=3)
    for _ in range(3):      # admit, then two of the prompt's chunks
        eng.step()
    assert float(jnp.abs(eng._pool.kpools[0]).max()) > 0
    assert eng._allocator_w.used_blocks == 5
    eng._recover(RuntimeError("injected"))
    assert all(float(jnp.abs(p).max()) == 0 for p in eng._pool.kpools)
    assert eng._allocator.used_blocks == 0
    assert eng._allocator_w.used_blocks == 0 and not eng._bt_w.any()
    assert [p.shape[0] for p in eng._pool.kpools].count(
        eng._num_window_blocks) == 4


def test_what_is_refused_over_a_window_group(model):
    from paddle_tpu.inference.kv_cache import PagedKVPool
    from paddle_tpu.models import HybridConfig
    eng = _engine(model)
    rid = eng.add_request(_prompts([9])[0], max_new_tokens=2)
    for what in (lambda: eng.park(rid), lambda: eng.export_handoff(rid),
                 lambda: eng.add_request(_prompts([9])[0],
                                         prefill_only=True)):
        with pytest.raises(ValueError, match="second block group"):
            what()
    eng.run()
    for over in (dict(spec_decode=2), dict(quant_kv="int8"),
                 dict(role="prefill")):
        with pytest.raises(ValueError, match="second block group"):
            _engine(model, **over)
    for what in (lambda: eng._pool.copy_block(1, 2),
                 lambda: eng._pool.export_blocks([1])):
        with pytest.raises(RuntimeError, match="window group"):
            what()
    with pytest.raises(ValueError, match="num_window_blocks"):
        _engine(model, num_window_blocks=4).add_request(
            _prompts([40])[0], max_new_tokens=4)
    with pytest.raises(ValueError, match="window group"):
        PagedKVPool(2, 8, 4, 2, 16, jnp.float32, quant="int8",
                    window_layers=(0,), window_blocks=8)
    with pytest.raises(NotImplementedError, match="several sizes"):
        HybridConfig.tiny(layer_types=("attention",) * 3,
                          layer_windows=(8, 16, 0))
    with pytest.raises(NotImplementedError, match="not grouped-query"):
        HybridConfig.tiny(layer_windows=(8, 0, 0))


# -- a model without windows: the engine it has always built ------------------

def test_a_model_without_windows_builds_one_group_one_table_and_the_kernel_call_it_had(
        monkeypatch):
    """``LlamaForCausalLM`` and a hybrid model with no window: one block
    group, one allocator, one table (an array, not a pair), every pool
    of ``num_kv_blocks`` blocks, and the decode program's call of the
    paged kernel has no ``window`` — so what such a model lowers is
    what it lowered before windows existed."""
    from paddle_tpu.inference.serving import ContinuousBatchingEngine
    from paddle_tpu.models import (HybridConfig, HybridForCausalLM,
                                   LlamaConfig, LlamaForCausalLM)
    from paddle_tpu.ops.pallas import paged_attention as PA
    calls = []
    real = PA.paged_decode_attention

    def spy(*args, **kw):
        calls.append(kw.get("window"))
        return real(*args, **kw, interpret=True)

    monkeypatch.setattr(PA, "paged_decode_eligible", lambda *a, **k: True)
    monkeypatch.setattr(PA, "paged_decode_attention", spy)
    for m in (LlamaForCausalLM(LlamaConfig.tiny()),
              HybridForCausalLM(HybridConfig.tiny(
                  layer_types=("attention",) * 3))):
        eng = ContinuousBatchingEngine(
            m, slots=2, max_len=64, kv_block_size=8, prefill_chunk=16,
            prefill_buckets=(16,), num_kv_blocks=17)
        assert eng._window == 0 and eng._ring == 0
        assert eng._allocator_w is None and eng._bt_w is None
        assert eng._pool.window_layers == ()
        assert {p.shape[0] for p in eng._pool.kpools} == {17}
        *_, bt = eng._paged_dummies()
        assert not isinstance(bt, tuple) and bt.shape == (2, 8)
        assert isinstance(eng._tables(slice(0, 1)), np.ndarray)
        calls.clear()
        eng.add_request(np.arange(5, dtype=np.int32), max_new_tokens=3)
        eng.run()
        assert calls and all(w is None for w in calls)
        with pytest.raises(ValueError, match="no sliding-window layer"):
            ContinuousBatchingEngine(m, slots=2, max_len=64,
                                     prefill_buckets=(16,),
                                     num_window_blocks=9)
    # and the kernel's jaxpr without a window is the one it had: a call
    # with ``window=None`` and one without the keyword trace alike
    q = jnp.zeros((2, 4, 128), jnp.float32)
    pool = jnp.zeros((9, 8, 2, 128), jnp.float32)
    bt = jnp.zeros((2, 4), jnp.int32)
    ln = jnp.asarray([5, 20], jnp.int32)
    plain = jax.make_jaxpr(functools.partial(real, interpret=True))(
        q, pool, pool, bt, ln)
    none = jax.make_jaxpr(functools.partial(real, interpret=True,
                                            window=None))(
        q, pool, pool, bt, ln)
    bound = jax.make_jaxpr(functools.partial(real, interpret=True,
                                             window=16))(
        q, pool, pool, bt, ln)
    assert str(plain) == str(none) != str(bound)


# -- the readers under a lower bound ------------------------------------------

def _ring_case(rng, B=4, bs=8, window=24, ring=5, kvh=2, h=4, hd=128):
    """Pools and a ring table with rows of unequal length (0, under the
    window, past it once and past it several times), each logical
    position's key written where the table says: an older position's
    slot holds the newest position that came round to it."""
    lengths = np.asarray([0, 17, 45, 95], np.int32)[:B]
    mb = -(-int(lengths.max()) // bs) + 1
    rings = 1 + np.arange(B * ring, dtype=np.int32).reshape(B, ring)
    bt = rings[:, np.arange(mb) % ring]
    kp = np.zeros((1 + B * ring, bs, kvh, hd), np.float32)
    vp = np.zeros_like(kp)
    keys = rng.normal(size=(B, mb * bs, kvh, hd)).astype(np.float32)
    vals = rng.normal(size=(B, mb * bs, kvh, hd)).astype(np.float32)
    for b in range(B):
        for p in range(int(lengths[b])):
            kp[bt[b, p // bs], p % bs] = keys[b, p]
            vp[bt[b, p // bs], p % bs] = vals[b, p]
    q = rng.normal(size=(B, h, hd)).astype(np.float32)
    return q, kp, vp, bt, lengths, keys, vals


def _plain(q, keys, vals, length, window):
    """One row's attention over positions ``length - window <= j <
    length`` of the keys as they were written, a head at a time."""
    lo = max(0, length - window)
    g = q.shape[0] // keys.shape[1]
    out = np.zeros_like(q)
    for head in range(q.shape[0]):
        k, v = keys[lo:length, head // g], vals[lo:length, head // g]
        s = k @ q[head] / np.sqrt(q.shape[-1])
        p = np.exp(s - s.max())
        out[head] = (p / p.sum()) @ v
    return out


def test_the_decode_kernel_the_walk_and_the_gather_agree_under_a_window():
    """Interpreted, the decode kernel with ``window`` over a ring table
    is the plain softmax over each row's last ``window`` positions — and
    so are the chunk walk and the gather fallback with the same lower
    bound; without the bound the ring's older entries (which hold newer
    keys) would be read as what they are not."""
    from paddle_tpu.inference import kv_cache as KV
    from paddle_tpu.ops.pallas import paged_attention as PA
    rng = np.random.default_rng(7)
    q, kp, vp, bt, lengths, keys, vals = _ring_case(rng)
    window = 24
    want = np.stack([_plain(q[b], keys[b], vals[b], int(lengths[b]), window)
                     if lengths[b] else np.zeros_like(q[b])
                     for b in range(len(lengths))])
    with jax.default_matmul_precision("highest"):
        got = np.asarray(PA.paged_decode_attention(
            jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp),
            jnp.asarray(bt), jnp.asarray(lengths), window=window,
            interpret=True))
        qpos = jnp.asarray(np.maximum(lengths - 1, 0))[:, None]
        walk = np.asarray(PA.paged_chunk_attention(
            jnp.asarray(q)[:, None], jnp.asarray(kp), jnp.asarray(vp),
            jnp.asarray(bt), qpos, window=window))[:, 0]
        # the gather fallback (what a caller's mask or an int8 pool
        # takes: here a mask that hides nothing) writes the step's key
        # first: hand it the newest position's own key and value again
        last = np.maximum(lengths - 1, 0)
        k1 = keys[np.arange(len(last)), last][:, None]
        v1 = vals[np.arange(len(last)), last][:, None]
        cache = KV.PagedCache(jnp.asarray(kp), jnp.asarray(vp),
                              jnp.asarray(bt))
        gather = KV.paged_cache_attention(
            jnp.asarray(q)[:, None], jnp.asarray(k1), jnp.asarray(v1),
            cache, jnp.asarray(last), window=window,
            attn_mask=jnp.ones((len(last), 1, 1, bt.shape[1] * kp.shape[1]),
                               bool))[0]
        gather = np.asarray(getattr(gather, "_data", gather))[:, 0]
        unbounded = np.asarray(PA.paged_decode_attention(
            jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp),
            jnp.asarray(bt), jnp.asarray(lengths), interpret=True))
    live = lengths > 0
    for name, a in (("kernel", got), ("walk", walk), ("gather", gather)):
        assert np.abs(a[live] - want[live]).max() < 2e-6, name
    assert np.abs(unbounded[3] - want[3]).max() > 1e-2


@pytest.mark.parametrize("window,tile,S,mask,int8,path", [
    (24, 512, 4, False, False, "walk"),      # a layer with a window walks
    (24, 512, 1, False, False, "walk"),      # ... a single query too
    (None, 32, 4, False, False, "walk"),     # a span over several tiles
    (None, 512, 4, False, False, "fallback"),  # one tile: nothing to skip
    (None, 104, 4, False, False, "fallback"),  # ... the table exactly
    (None, 32, 4, True, False, "fallback"),  # a caller's mask gathers
    (24, 32, 4, True, False, "fallback"),    # ... under a window too
    (None, 32, 4, False, True, "fallback"),  # an int8 pool gathers
    (None, 32, 1, False, False, "fallback"),  # one query, no kernel here
])
def test_a_chunk_walks_where_it_has_a_window_or_a_table_past_one_tile(
        monkeypatch, window, tile, S, mask, int8, path):
    """``paged_cache_attention`` chooses between the walk and the gather
    from what it sees — the layer's window, the span's queries and the
    table against one tile of the walk — and no model states it."""
    from paddle_tpu.inference import kv_cache as KV
    from paddle_tpu.ops.pallas import paged_attention as PA
    rng = np.random.default_rng(9)
    q, kp, vp, bt, lengths, keys, vals = _ring_case(rng)
    assert bt.shape[1] * kp.shape[1] == 104
    B = len(lengths)
    took = []
    monkeypatch.setattr(PA, "record_path", took.append)
    monkeypatch.setattr(PA, "_WALK_TILE_TOKENS", tile)
    x = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)
    if int8:
        (kq, ks), (vq, vs) = KV._quantize_kv(jnp.asarray(kp)), \
            KV._quantize_kv(jnp.asarray(vp))
        cache = KV.PagedCache(kq, vq, jnp.asarray(bt), ks, vs)
    else:
        cache = KV.PagedCache(jnp.asarray(kp), jnp.asarray(vp),
                              jnp.asarray(bt))
    am = jnp.ones((B, 1, S, bt.shape[1] * kp.shape[1]), bool) if mask \
        else None
    KV.paged_cache_attention(
        x(B, S, q.shape[1], q.shape[2]), x(B, S, *kp.shape[2:]),
        x(B, S, *kp.shape[2:]), cache, jnp.asarray(lengths),
        window=window, attn_mask=am)
    assert took == [path]


@pytest.mark.parametrize("offsets,S", [
    ([24], 16),          # a last chunk padded past the prompt's end
    ([40], 16),          # ... and past the table's (those go to scratch)
    ([0, 13, 41], 4),    # a verify: rows at offsets of their own
    ([47, 5, 30], 1 + 2),
])
def test_the_walk_and_the_gather_agree_over_a_table_of_three_tiles(
        monkeypatch, offsets, S):
    """The two readers of a span without a window, through the rule: a
    table of three tiles (``_WALK_TILE_TOKENS`` made 16) takes the walk,
    the same call with the tile past the table the gather; the outputs
    and the pools they leave agree to rounding, padded queries and all."""
    from paddle_tpu.inference import kv_cache as KV
    from paddle_tpu.ops.pallas import paged_attention as PA
    rng = np.random.default_rng(11)
    bs, kvh, h, hd, mb, B = 8, 2, 4, 32, 6, len(offsets)
    x = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)
    kp, vp = x(1 + B * mb, bs, kvh, hd), x(1 + B * mb, bs, kvh, hd)
    bt = jnp.asarray(1 + rng.permutation(B * mb).reshape(B, mb), jnp.int32)
    q, k, v = x(B, S, h, hd), x(B, S, kvh, hd), x(B, S, kvh, hd)
    took, got = [], {}
    monkeypatch.setattr(PA, "record_path", took.append)
    for name, tile in (("walk", 16), ("fallback", 1 << 30)):
        monkeypatch.setattr(PA, "_WALK_TILE_TOKENS", tile)
        with jax.default_matmul_precision("highest"):
            out, new = KV.paged_cache_attention(
                q, k, v, KV.PagedCache(kp, vp, bt), jnp.asarray(offsets))
        got[name] = [np.asarray(getattr(a, "_data", a))
                     for a in (out, new.k, new.v)]
    assert took == ["walk", "fallback"]
    assert np.abs(got["walk"][0] - got["fallback"][0]).max() < 2e-6
    assert np.abs(got["fallback"][0]).max() > 1e-2
    for a, b in zip(got["walk"][1:], got["fallback"][1:]):
        assert np.array_equal(a, b)


def test_the_walk_over_a_chunk_is_causal_attention_bounded_by_the_window():
    """A chunk of 16 queries from position 40 of a row: the walk over the
    paged context equals the plain masked softmax with and without a
    window, tile by tile (the tile as it stands covers this table in one;
    ``_WALK_TILE_TOKENS`` made 16 walks it in several)."""
    from paddle_tpu.ops.pallas import paged_attention as PA
    rng = np.random.default_rng(8)
    bs, kvh, h, hd, n, S, start = 8, 2, 4, 32, 56, 16, 40
    mb = n // bs
    kp = np.zeros((1 + mb, bs, kvh, hd), np.float32)
    vp = np.zeros_like(kp)
    keys = rng.normal(size=(n, kvh, hd)).astype(np.float32)
    vals = rng.normal(size=(n, kvh, hd)).astype(np.float32)
    bt = 1 + np.arange(mb, dtype=np.int32)[None]
    kp[1:] = keys.reshape(mb, bs, kvh, hd)
    vp[1:] = vals.reshape(mb, bs, kvh, hd)
    q = rng.normal(size=(1, S, h, hd)).astype(np.float32)
    qpos = jnp.asarray(start + np.arange(S))[None]
    for tile in (PA._WALK_TILE_TOKENS, 16):
        for window in (None, 12):
            old = PA._WALK_TILE_TOKENS
            PA._WALK_TILE_TOKENS = tile
            try:
                with jax.default_matmul_precision("highest"):
                    got = np.asarray(PA.paged_chunk_attention(
                        jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp),
                        jnp.asarray(bt), qpos, window=window))[0]
            finally:
                PA._WALK_TILE_TOKENS = old
            for t in range(S):
                want = _plain(q[0, t], keys, vals, start + t + 1,
                              window or n)
                assert np.abs(got[t] - want).max() < 2e-6, (tile, window, t)


# -- the chip's share ---------------------------------------------------------

def test_the_sixteen_shares_and_the_shared_expert_once_are_the_whole_layer(
        arch):
    """Guide section 4's test at this model's cut (sixteen chips a
    layer, here sixteen shares of one expert each): the shares' routed
    parts plus what every chip computes alike (the shared expert),
    counted once, are what the uncut reference gives for the whole layer
    — from the program's expert layer told its ids, and from the
    reference's own cut."""
    from paddle_tpu.distributed.moe import gated_experts_forward
    from perf.archs import sarvam_mla
    from perf.reference.decoder import matmul
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
        for e in range(16):
            cut = dict(w)
            for n in ("block_sparse_moe.w_in", "block_sparse_moe.w_out"):
                cut[n] = w[n][e:e + 1]
            parts.append(sarvam_mla._experts(y, cut, view, mm, held=[e]))
            local = np.full(16, 1, np.int32)
            local[e] = 0
            out, counts = gated_experts_forward(
                y[0], w["block_sparse_moe.router.weight"],
                cut["block_sparse_moe.w_in"],
                cut["block_sparse_moe.w_out"], top_k=4, local_of=local,
                rule="sigmoid_bias",
                router_bias=w["block_sparse_moe.router_bias"],
                scaling=2.448)
            ours.append(out[None])
            assert int(counts[2]) == 29 * 4
        shared = sarvam_mla._gated(
            y, w["shared_mlp.input_linear.weight"],
            w["shared_mlp.output_linear.weight"], mm)
        scale = float(jnp.abs(full + shared).max())
        for sixteen in (parts, ours):
            assert float(jnp.abs(sum(sixteen) + shared
                                 - (full + shared)).max()) <= TOL * scale
        assert float(jnp.abs(sum(parts[:4]) - full).max()) > 0.05 * scale
    # the reference's ``_ffn`` at the cut: the first four shares' routed
    # part and the shared expert
    m = jnp.asarray(np.random.default_rng(8).normal(size=(1, 29, 64)),
                    jnp.float32)
    cut = dict(w)
    for n in ("block_sparse_moe.w_in", "block_sparse_moe.w_out"):
        cut[n] = w[n][:4]
    with jax.default_matmul_precision("highest"):
        a = arch._ffn(m, cut, CFG, 1, mm)
        b = sarvam_mla._experts(m, cut, arch._as_sarvam(CFG), mm) + \
            sarvam_mla._gated(m, w["shared_mlp.input_linear.weight"],
                              w["shared_mlp.output_linear.weight"], mm)
    assert float(jnp.abs(a - b).max()) <= TOL * float(jnp.abs(b).max())
