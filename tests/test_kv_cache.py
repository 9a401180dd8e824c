"""Paged KV cache (ISSUE 9 tentpole): block allocator invariants, COW
fork isolation, prefix-trie reuse, paged-attention numerics vs the
static path, Pallas kernel parity, and the paged serving engine's
greedy equivalence (chunked prefill, prefix reuse, speculative decode)
plus the serving.kv_alloc chaos drill — all CPU-runnable."""

import numpy as np
import pytest

import jax.numpy as jnp

import paddle_tpu as pp
from paddle_tpu.inference.kv_cache import (BlockAllocator, PagedCache,
                                           PagedKVPool, PrefixCache,
                                           SequenceBlocks,
                                           paged_cache_attention)
from paddle_tpu.inference.serving import (ContinuousBatchingEngine,
                                          _ngram_propose)
from paddle_tpu.models import LlamaConfig, LlamaForCausalLM


def _tiny_model(max_positions=128):
    pp.seed(0)
    cfg = LlamaConfig.tiny(vocab_size=256, hidden_size=64,
                           intermediate_size=128, num_hidden_layers=2,
                           num_attention_heads=4, num_key_value_heads=2,
                           max_position_embeddings=max_positions)
    return LlamaForCausalLM(cfg)


@pytest.fixture(scope="module")
def tiny_model():
    return _tiny_model()


def _reference(model, prompt, n):
    out = model.generate(np.asarray(prompt, np.int32)[None],
                         max_new_tokens=n, do_sample=False)
    return list(np.asarray(out)[0, len(prompt):])


def _paged_engine(model, **over):
    kw = dict(slots=2, max_len=64, prefill_buckets=(16, 32),
              kv_block_size=4, prefill_chunk=8)
    kw.update(over)
    return ContinuousBatchingEngine(model, **kw)


def _kernel_case(B=3, h=4, kvh=2, hd=16, bs=16, mb=40, lengths=(5, 9, 16),
                 dtype=jnp.float32, quant=False, inactive=(), seed=3):
    """Operands of one paged decode call: each row's live table entries
    name blocks of its own, the rest name whatever the draw gave; an
    ``inactive`` row is as the engine leaves it (length 1, row zeroed)."""
    rng = np.random.default_rng(seed)
    nb = 1 + B * mb
    q = jnp.asarray(rng.normal(size=(B, h, hd)), dtype)
    kp = rng.normal(size=(nb, bs, kvh, hd))
    vp = rng.normal(size=(nb, bs, kvh, hd))
    bt = rng.permutation(np.arange(1, nb)).reshape(B, mb).astype(np.int32)
    lengths = np.asarray(lengths, np.int32)
    for b in inactive:
        bt[b], lengths[b] = 0, 1
    ks = vs = None
    if quant:
        from paddle_tpu.inference.kv_cache import _quantize_kv
        (kp, ks), (vp, vs) = (_quantize_kv(jnp.asarray(kp, jnp.float32)),
                              _quantize_kv(jnp.asarray(vp, jnp.float32)))
    else:
        kp, vp = jnp.asarray(kp, dtype), jnp.asarray(vp, dtype)
    return q, kp, vp, jnp.asarray(bt), jnp.asarray(lengths), ks, vs


# a chunk is 256 tokens (16 blocks of 16, 32 of 8, 4 of 64) or the table
_KERNEL_CASES = {
    "table_shorter_than_a_chunk": dict(bs=4, mb=4, lengths=(5, 9, 16)),
    "chunk_edges_bs16": dict(B=6, lengths=(1, 255, 256, 257, 600, 640)),
    "chunk_edges_bs8": dict(B=4, bs=8, mb=70, lengths=(1, 256, 300, 560)),
    "chunk_edges_bs64": dict(B=4, bs=64, mb=10,
                             lengths=(64, 255, 257, 640)),
    "bf16": dict(dtype=jnp.bfloat16, lengths=(17, 256, 513)),
    "gqa_group_1": dict(h=2, kvh=2, lengths=(3, 300, 640)),
    "gqa_group_2": dict(h=4, kvh=2, lengths=(3, 300, 640)),
    "gqa_group_4": dict(h=8, kvh=2, lengths=(3, 300, 640)),
    "inactive_row_beside_live": dict(B=4, lengths=(400, 1, 31, 1),
                                     inactive=(1, 3)),
    "int8_pool": dict(quant=True, lengths=(7, 257, 500)),
}


class TestBlockAllocator:
    def test_alloc_free_roundtrip(self):
        a = BlockAllocator(5)
        bids = [a.alloc() for _ in range(4)]
        assert sorted(bids) == [1, 2, 3, 4]   # 0 is scratch
        assert a.free_blocks == 0 and a.used_blocks == 4
        for b in bids:
            assert a.free(b) is True
        assert a.free_blocks == 4 and a.used_blocks == 0

    def test_exhaustion_returns_none(self):
        a = BlockAllocator(3)
        assert a.alloc() is not None and a.alloc() is not None
        assert a.alloc() is None   # exhaustion is a value, not a raise

    def test_double_free_raises(self):
        a = BlockAllocator(3)
        b = a.alloc()
        a.free(b)
        with pytest.raises(RuntimeError, match="double free"):
            a.free(b)

    def test_scratch_block_protected(self):
        a = BlockAllocator(3)
        with pytest.raises(RuntimeError, match="reserved"):
            a.free(0)

    def test_refcount_sharing(self):
        a = BlockAllocator(3)
        b = a.alloc()
        a.ref(b)
        assert a.refcount(b) == 2
        assert a.free(b) is False      # still held
        assert a.free(b) is True       # last ref
        assert a.free_blocks == 2


class TestSequenceBlocks:
    def test_ensure_capacity_all_or_nothing(self):
        a = BlockAllocator(4)          # 3 usable
        s = SequenceBlocks(a, block_size=4)
        assert s.ensure_capacity(8)    # 2 blocks
        assert len(s.bids) == 2
        t = SequenceBlocks(a, block_size=4)
        assert not t.ensure_capacity(8)   # needs 2, only 1 free
        assert t.bids == [] and a.free_blocks == 1   # nothing leaked

    def test_fork_shares_then_cow_isolates(self):
        a = BlockAllocator(8)
        s = SequenceBlocks(a, 4)
        s.ensure_capacity(8)
        child = s.fork()
        assert child.bids == s.bids
        assert all(a.refcount(b) == 2 for b in s.bids)
        copies = []
        out = s.ensure_writable(0, copier=lambda src, dst:
                                copies.append((src, dst)))
        assert out is not None and copies == [out]
        assert s.bids[0] != child.bids[0]        # parent moved off
        assert a.refcount(child.bids[0]) == 1    # child now sole holder
        assert s.ensure_writable(0) is None      # private → no-op

    def test_release_frees_everything(self):
        a = BlockAllocator(6)
        s = SequenceBlocks(a, 4)
        s.ensure_capacity(20)
        s.release()
        assert a.used_blocks == 0 and s.bids == []

    def test_randomized_invariants_never_leak(self):
        """Random alloc/fork/append/write/free sequences: refcount
        conservation holds at every step and full release drains the
        pool — no leak, no double free, COW never fails to isolate."""
        rng = np.random.default_rng(0)
        a = BlockAllocator(64)
        live = []
        for _ in range(300):
            op = rng.integers(0, 4)
            if op == 0 or not live:                      # new sequence
                s = SequenceBlocks(a, 4)
                if s.ensure_capacity(int(rng.integers(1, 12))):
                    live.append(s)
            elif op == 1:                                # fork
                live.append(live[rng.integers(len(live))].fork())
            elif op == 2:                                # grow + write
                s = live[rng.integers(len(live))]
                s.ensure_capacity(s.capacity +
                                  int(rng.integers(1, 8)))
                for i in range(len(s.bids)):
                    if a.free_blocks == 0:
                        break   # COW legitimately needs headroom
                    s.ensure_writable(i)
            else:                                        # retire
                live.pop(rng.integers(len(live))).release()
            used = sum(a.refcount(b) > 0
                       for b in range(1, a.num_blocks))
            assert used == a.used_blocks
            assert a.used_blocks + a.free_blocks == a.num_blocks - 1
        for s in live:
            s.release()
        assert a.used_blocks == 0

    def test_cow_fork_never_sees_parent_writes_device(self):
        """Device-level COW isolation: after a fork, the parent's later
        writes land in a COW copy — the child's gathered view is
        bitwise the pre-fork content."""
        a = BlockAllocator(8)
        pool = PagedKVPool(num_layers=1, num_blocks=8, block_size=4,
                           kv_heads=2, head_dim=8, dtype=jnp.float32)
        s = SequenceBlocks(a, 4)
        s.ensure_capacity(4)
        bid = s.bids[0]
        original = np.arange(4 * 2 * 8, dtype=np.float32).reshape(4, 2, 8)
        pool.kpools[0] = pool.kpools[0].at[bid].set(original)
        child = s.fork()
        assert s.ensure_writable(0, pool.copy_block) is not None
        pool.kpools[0] = pool.kpools[0].at[s.bids[0]].set(-1.0)
        child_view = np.asarray(pool.kpools[0][child.bids[0]])
        np.testing.assert_array_equal(child_view, original)
        parent_view = np.asarray(pool.kpools[0][s.bids[0]])
        assert (parent_view == -1.0).all()
        assert pool.cow_copies == 1


class TestPrefixCache:
    def test_register_match_roundtrip(self):
        a = BlockAllocator(16)
        c = PrefixCache(4, a)
        toks = np.arange(10, dtype=np.int32)
        bids = [a.alloc(), a.alloc()]
        assert c.register(toks, bids) == 2   # two FULL blocks of 4
        got = c.match(toks)
        assert got == bids
        assert c.hits == 1
        # trie holds its own ref
        assert all(a.refcount(b) == 2 for b in bids)

    def test_partial_and_miss(self):
        a = BlockAllocator(16)
        c = PrefixCache(4, a)
        toks = np.arange(8, dtype=np.int32)
        bids = [a.alloc(), a.alloc()]
        c.register(toks, bids)
        other = np.concatenate([toks[:4], 99 + np.arange(4)])
        assert c.match(other) == bids[:1]    # first block matches
        assert c.match(np.arange(100, 108)) == []
        assert c.misses == 1

    def test_register_dedupes_same_content(self):
        a = BlockAllocator(16)
        c = PrefixCache(4, a)
        toks = np.arange(4, dtype=np.int32)
        b1, b2 = a.alloc(), a.alloc()
        assert c.register(toks, [b1]) == 1
        assert c.register(toks, [b2]) == 0   # content already cached
        assert a.refcount(b1) == 2 and a.refcount(b2) == 1

    def test_evict_lru_only_unreferenced(self):
        a = BlockAllocator(16)
        c = PrefixCache(4, a)
        t1, t2 = np.arange(4), 50 + np.arange(4)
        b1, b2 = a.alloc(), a.alloc()
        c.register(t1, [b1])
        c.register(t2, [b2])
        a.free(b1)   # cache is now b1's only holder; b2 still shared
        c.match(t1)  # refresh b1 → b2 becomes the LRU candidate, but
        #              it's referenced, so eviction takes b1 anyway
        assert c.evict(2) == 1
        assert c.match(t1) == [] and c.match(t2) == [b2]
        assert c.evictions == 1


class TestPagedAttentionNumerics:
    def _setup(self, rng, B, kvh, hd, max_len, bs, pos):
        from paddle_tpu.generation import StaticCache
        mb = max_len // bs
        nb = 1 + B * mb
        ks = np.zeros((B, max_len, kvh, hd), np.float32)
        vs = np.zeros((B, max_len, kvh, hd), np.float32)
        kp = np.zeros((nb, bs, kvh, hd), np.float32)
        vp = np.zeros((nb, bs, kvh, hd), np.float32)
        bt = np.arange(1, nb, dtype=np.int32).reshape(B, mb)
        for b in range(B):
            for p in range(pos[b]):
                kr = rng.normal(size=(kvh, hd)).astype(np.float32)
                vr = rng.normal(size=(kvh, hd)).astype(np.float32)
                ks[b, p] = kr
                vs[b, p] = vr
                kp[bt[b, p // bs], p % bs] = kr
                vp[bt[b, p // bs], p % bs] = vr
        static = StaticCache(jnp.asarray(ks), jnp.asarray(vs))
        paged = PagedCache(jnp.asarray(kp), jnp.asarray(vp),
                           jnp.asarray(bt))
        return static, paged

    def test_decode_bitwise_matches_static(self):
        from paddle_tpu.core.dispatch import unwrap
        from paddle_tpu.generation import static_cache_attention
        rng = np.random.default_rng(1)
        B, kvh, h, hd, bs = 2, 2, 4, 8, 4
        pos = np.array([5, 11], np.int32)
        static, paged = self._setup(rng, B, kvh, hd, 32, bs, pos)
        q = jnp.asarray(rng.normal(size=(B, 1, h, hd)), jnp.float32)
        k = jnp.asarray(rng.normal(size=(B, 1, kvh, hd)), jnp.float32)
        v = jnp.asarray(rng.normal(size=(B, 1, kvh, hd)), jnp.float32)
        out_s, _ = static_cache_attention(q, k, v, static,
                                          jnp.asarray(pos))
        out_p, new_cache = paged_cache_attention(q, k, v, paged,
                                                 jnp.asarray(pos))
        np.testing.assert_array_equal(np.asarray(unwrap(out_s)),
                                      np.asarray(unwrap(out_p)))
        # the write landed through the block table
        kp = np.asarray(unwrap(new_cache.k))
        bt = np.asarray(unwrap(paged.block_table))
        row0 = kp[bt[0, pos[0] // bs], pos[0] % bs]
        np.testing.assert_array_equal(row0, np.asarray(k)[0, 0])

    def test_prefill_chunk_matches_static(self):
        from paddle_tpu.core.dispatch import unwrap
        from paddle_tpu.generation import static_cache_attention
        rng = np.random.default_rng(2)
        B, kvh, h, hd, bs, S = 1, 2, 4, 8, 4, 3
        pos = np.array([5], np.int32)
        static, paged = self._setup(rng, B, kvh, hd, 32, bs, pos)
        q = jnp.asarray(rng.normal(size=(B, S, h, hd)), jnp.float32)
        k = jnp.asarray(rng.normal(size=(B, S, kvh, hd)), jnp.float32)
        v = jnp.asarray(rng.normal(size=(B, S, kvh, hd)), jnp.float32)
        out_s, _ = static_cache_attention(q, k, v, static, 5)
        out_p, _ = paged_cache_attention(q, k, v, paged,
                                         jnp.asarray([5], jnp.int32))
        np.testing.assert_array_equal(np.asarray(unwrap(out_s)),
                                      np.asarray(unwrap(out_p)))

    @pytest.mark.parametrize("case", sorted(_KERNEL_CASES))
    def test_pallas_kernel_matches_gather_fallback(self, case):
        """The chunked walk (interpret mode) against gather + softmax."""
        import jax
        from paddle_tpu.ops.pallas.paged_attention import \
            paged_decode_attention
        q, kp, vp, bt, lengths, ks, vs = _kernel_case(**_KERNEL_CASES[case])
        out = paged_decode_attention(q, kp, vp, bt, lengths, interpret=True,
                                     k_scale=ks, v_scale=vs)
        assert out.dtype == q.dtype and out.shape == q.shape
        B, h, hd = q.shape
        _, bs, kvh, _ = kp.shape
        mb = bt.shape[1]
        if ks is not None:      # the kernel's own dequantization
            kp = (kp.astype(jnp.float32) * ks[..., None]).astype(q.dtype)
            vp = (vp.astype(jnp.float32) * vs[..., None]).astype(q.dtype)
        kb = jnp.repeat(kp[bt].reshape(B, mb * bs, kvh, hd),
                        h // kvh, axis=2).astype(jnp.float32)
        vb = jnp.repeat(vp[bt].reshape(B, mb * bs, kvh, hd),
                        h // kvh, axis=2).astype(jnp.float32)
        scores = jnp.einsum("bhd,bkhd->bhk", q.astype(jnp.float32),
                            kb) / np.sqrt(hd)
        mask = jnp.arange(mb * bs)[None, None, :] < \
            lengths[:, None, None]
        probs = jax.nn.softmax(jnp.where(mask, scores, -1e30), axis=-1)
        ref = jnp.einsum("bhk,bkhd->bhd", probs, vb)
        atol = 2e-2 if q.dtype == jnp.bfloat16 else 1e-5
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(ref), atol=atol)

    @pytest.mark.parametrize("kvh,dtype,ok", [
        (8, "bfloat16", True), (2, "bfloat16", True), (1, "float32", True),
        (1, "bfloat16", False), (2, "int8", False)])
    def test_pallas_kernel_eligibility_by_pool(self, monkeypatch, kvh,
                                               dtype, ok):
        """A DMA'd block is sliced on whole 32-bit sublane words: the
        kv heads of the pool have to fill them."""
        import jax
        from paddle_tpu.ops.pallas.paged_attention import \
            paged_decode_eligible
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        monkeypatch.delenv("PADDLE_TPU_PAGED_ATTN", raising=False)
        pool = jax.ShapeDtypeStruct((9, 16, kvh, 128), dtype)
        assert paged_decode_eligible(128, 16, jnp.bfloat16)
        assert paged_decode_eligible(128, 16, jnp.bfloat16,
                                     pool=pool) is ok

    @pytest.mark.parametrize("case", ["chunk_edges_bs16", "int8_pool"])
    def test_pallas_kernel_costs_what_is_live(self, case):
        """Poison every pool block no live table entry names and point
        every dead table entry at one: a fetch of either would show."""
        from paddle_tpu.ops.pallas.paged_attention import \
            paged_decode_attention
        q, kp, vp, bt, lengths, ks, vs = _kernel_case(**_KERNEL_CASES[case])
        clean = paged_decode_attention(q, kp, vp, bt, lengths,
                                       interpret=True, k_scale=ks,
                                       v_scale=vs)
        bs, mb = kp.shape[1], bt.shape[1]
        live_entry = np.arange(mb)[None, :] * bs < np.asarray(lengths)[:, None]
        dead = np.setdiff1d(np.arange(kp.shape[0]),
                            np.asarray(bt)[live_entry])
        assert dead.size and not live_entry.all()
        bt = jnp.where(live_entry, bt, int(dead[0]))
        if ks is None:
            kp, vp = kp.at[dead].set(jnp.nan), vp.at[dead].set(jnp.nan)
        else:
            kp, vp = kp.at[dead].set(127), vp.at[dead].set(127)
            ks, vs = ks.at[dead].set(jnp.nan), vs.at[dead].set(jnp.nan)
        out = paged_decode_attention(q, kp, vp, bt, lengths,
                                     interpret=True, k_scale=ks, v_scale=vs)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(clean))


class TestPagedEngineParity:
    def test_single_request_chunked_prefill(self, tiny_model):
        rng = np.random.default_rng(10)
        prompt = rng.integers(0, 256, (17,))   # 3 chunks of 8
        eng = _paged_engine(tiny_model)
        rid = eng.add_request(prompt, max_new_tokens=8)
        assert eng.run()[rid][1] == _reference(tiny_model, prompt, 8)

    def test_prefill_goes_to_the_oldest_admission_not_the_lowest_slot(
            self, tiny_model):
        """A prompt under way in slot 1 is not overtaken by a later
        arrival that lands in slot 0, freed a moment before: by slot
        number the order of two first tokens hung on which slot was free
        when the second request came (``serve-rag``'s two clusters of
        TTFT, PERF.md §6 PR 37)."""
        rng = np.random.default_rng(21)
        eng = _paged_engine(tiny_model)
        a = eng.add_request(rng.integers(0, 256, (6,)), max_new_tokens=3)
        while eng._active[0] is None or 0 in eng._prefilling:
            eng.step()                       # a: slot 0, first token out
        long = rng.integers(0, 256, (40,))   # 5 chunks of 8
        b = eng.add_request(long, max_new_tokens=2)
        while eng.request_status(a) is None:
            eng.step()                       # b admitted to slot 1; a retires
        assert list(eng._prefilling) == [1]
        c = eng.add_request(rng.integers(0, 256, (6,)), max_new_tokens=2)
        results = eng.run()
        assert results[b][1] == _reference(tiny_model, long, 2)
        first = {r: eng.request_status(r).timings["first_token"]
                 for r in (b, c)}
        assert eng.request_status(c).timings["admitted"] > \
            eng.request_status(b).timings["admitted"]
        assert first[b] < first[c]

    @pytest.mark.slow
    def test_multi_slot_reuse(self, tiny_model):
        rng = np.random.default_rng(11)
        prompts = [rng.integers(0, 256, (n,)) for n in (5, 13, 17, 30)]
        eng = _paged_engine(tiny_model)
        rids = [eng.add_request(p, max_new_tokens=6) for p in prompts]
        results = eng.run()
        for rid, p in zip(rids, prompts):
            assert results[rid][1] == _reference(tiny_model, p, 6), \
                f"request {rid} diverged"

    def test_streaming_admission_interleaves_prefill(self, tiny_model):
        """A request added mid-decode chunk-prefills INTERLEAVED with
        the running slot's decode — and both match the oracle."""
        rng = np.random.default_rng(12)
        eng = _paged_engine(tiny_model)
        first = rng.integers(0, 256, (8,))
        r0 = eng.add_request(first, max_new_tokens=10)
        for _ in range(4):
            eng.step()
        late = rng.integers(0, 256, (20,))    # 3 chunks while r0 decodes
        r1 = eng.add_request(late, max_new_tokens=4)
        results = eng.run()
        assert results[r0][1] == _reference(tiny_model, first, 10)
        assert results[r1][1] == _reference(tiny_model, late, 4)

    def test_long_prompt_beyond_bucket_bound(self, tiny_model):
        """Paged mode drops the bucket bound: a prompt longer than the
        largest bucket chunk-prefills fine."""
        rng = np.random.default_rng(13)
        prompt = rng.integers(0, 256, (40,))   # > largest bucket 32
        eng = _paged_engine(tiny_model)
        rid = eng.add_request(prompt, max_new_tokens=5)
        assert eng.run()[rid][1] == _reference(tiny_model, prompt, 5)

    @pytest.mark.slow
    def test_steps_per_sync_parity(self, tiny_model):
        rng = np.random.default_rng(14)
        prompts = [rng.integers(0, 256, (n,)) for n in (6, 11)]
        eng = _paged_engine(tiny_model, steps_per_sync=4)
        rids = [eng.add_request(p, max_new_tokens=7) for p in prompts]
        results = eng.run()
        for rid, p in zip(rids, prompts):
            assert results[rid][1] == _reference(tiny_model, p, 7)

    def test_eos_frees_slot_early(self, tiny_model):
        rng = np.random.default_rng(15)
        prompt = rng.integers(0, 256, (8,))
        ref = _reference(tiny_model, prompt, 12)
        eng = _paged_engine(tiny_model, slots=1, eos_token_id=ref[3])
        r0 = eng.add_request(prompt, max_new_tokens=12)
        r1 = eng.add_request(rng.integers(0, 256, (7,)),
                             max_new_tokens=3)
        results = eng.run()
        assert results[r0][1] == ref[:4]
        assert len(results[r1][1]) == 3

    @pytest.mark.slow  # two-engine replay compile; CI serving gate runs it
    def test_prefix_reuse_skips_prefill_and_matches(self, tiny_model):
        from paddle_tpu.observability import default_registry
        rng = np.random.default_rng(16)
        shared = rng.integers(0, 256, (24,))
        p1 = np.concatenate([shared, rng.integers(0, 256, (4,))])
        p2 = np.concatenate([shared, rng.integers(0, 256, (3,))])
        eng = _paged_engine(tiny_model)
        r1 = eng.add_request(p1, max_new_tokens=5)
        out1 = eng.run()[r1][1]
        chunks_before = default_registry().get(
            "paddle_tpu_serving_prefill_chunks_total").value()
        r2 = eng.add_request(p2, max_new_tokens=5)
        out2 = eng.run()[r2][1]
        chunks_after = default_registry().get(
            "paddle_tpu_serving_prefill_chunks_total").value()
        assert out1 == _reference(tiny_model, p1, 5)
        assert out2 == _reference(tiny_model, p2, 5)
        st = eng.request_status(r2)
        assert st.timings["prefix_tokens_reused"] >= 16
        # 27-token prompt = 4 chunks cold, but only 1 with 24 reused
        assert chunks_after - chunks_before == 1

    def test_padded_chunk_tail_near_max_len(self, tiny_model):
        """Regression: a prefill chunk whose padded tail runs past
        max_len must route those writes to the scratch block — clamping
        them into the sequence's last real block corrupted live prompt
        KV when every block was allocated (prompt 17 + chunk 16 +
        max_len 20 reproduces the original divergence)."""
        rng = np.random.default_rng(19)
        prompt = rng.integers(0, 256, (17,))
        eng = ContinuousBatchingEngine(
            tiny_model, slots=1, max_len=20, prefill_buckets=(16,),
            kv_block_size=4, prefill_chunk=16)
        rid = eng.add_request(prompt, max_new_tokens=2)
        assert eng.run()[rid][1] == _reference(tiny_model, prompt, 2)

    @pytest.mark.parametrize("spec", [0, 3])
    def test_a_chunk_over_a_table_past_one_tile_walks_and_serves_the_gathers_tokens(
            self, monkeypatch, spec):
        """An engine whose ``max_len`` passes one tile of the walk takes
        ``paged_chunk_attention`` for its prefill chunk (and for a
        speculative verify, ``S = k + 1``) and serves what the same
        engine serves with the rule held to the gather; prompts end on
        both sides of the tile's edge.  Decode (``S == 1``, no kernel on
        the CPU) gathers on both sides; with drafts every step is a
        verify."""
        from paddle_tpu.ops.pallas import paged_attention as PA
        model = _tiny_model(max_positions=768)
        tile = PA._WALK_TILE_TOKENS
        rng = np.random.default_rng(23)
        prompts = [rng.integers(0, 256, (n,))
                   for n in (40, tile - 12, tile, tile + 1, tile + 88)]
        took, served = [], {}
        monkeypatch.setattr(PA, "record_path", took.append)
        for side, t in (("walk", tile), ("gather", 1 << 30)):
            monkeypatch.setattr(PA, "_WALK_TILE_TOKENS", t)
            del took[:]
            eng = ContinuousBatchingEngine(
                model, slots=2, max_len=768, prefill_buckets=(128,),
                kv_block_size=16, prefill_chunk=128, spec_decode=spec)
            rids = [eng.add_request(p, max_new_tokens=6) for p in prompts]
            out = eng.run()
            served[side] = [out[r][1] for r in rids]
            assert ("walk" in took) == (side == "walk"), took
            if not spec:                 # decode gathers on either side;
                assert "fallback" in took    # a verify is its own decode
        assert served["walk"] == served["gather"]
        assert all(len(t) == 6 for t in served["walk"])

    def test_sampling_near_zero_temperature(self, tiny_model):
        rng = np.random.default_rng(17)
        prompt = rng.integers(0, 256, (9,))
        eng = _paged_engine(tiny_model, do_sample=True, temperature=1e-6)
        rid = eng.add_request(prompt, max_new_tokens=6)
        assert eng.run()[rid][1] == _reference(tiny_model, prompt, 6)

    def test_int8_paged_runs(self, tiny_model):
        rng = np.random.default_rng(18)
        eng = _paged_engine(tiny_model, int8_weights=True)
        rid = eng.add_request(rng.integers(0, 256, (10,)),
                              max_new_tokens=4)
        out = eng.run()[rid][1]
        assert len(out) == 4 and all(0 <= t < 256 for t in out)

    def test_timings_fields_always_present(self, tiny_model):
        eng = ContinuousBatchingEngine(tiny_model, slots=1, max_len=48,
                                       prefill_buckets=(16,))
        rid = eng.add_request(np.arange(6), max_new_tokens=2)
        eng.run()
        t = eng.request_status(rid).timings
        assert t["prefix_tokens_reused"] == 0.0
        assert t["speculative_accept_rate"] == 0.0

    def test_pool_too_small_rejected_at_submission(self, tiny_model):
        eng = _paged_engine(tiny_model, num_kv_blocks=4)
        with pytest.raises(ValueError, match="num_kv_blocks"):
            eng.add_request(np.arange(20), max_new_tokens=8)


class TestSpeculativeDecoding:
    def test_ngram_proposer(self):
        hist = np.array([7, 1, 2, 3, 9, 1, 2], np.int32)
        draft = _ngram_propose(hist, k=3, max_n=3)
        assert list(draft) == [3, 9, 1]     # continuation after [1, 2]
        assert _ngram_propose(np.array([1, 2, 3]), 3) is None

    @pytest.mark.slow  # spec-decode verify compile; CI serving gate runs it
    def test_spec_parity_and_accept_rate(self, tiny_model):
        rng = np.random.default_rng(20)
        base = np.tile(rng.integers(0, 256, (6,)), 5)   # repetitive
        plain = rng.integers(0, 256, (11,))
        eng = _paged_engine(tiny_model, max_len=128, spec_decode=4)
        r0 = eng.add_request(base, max_new_tokens=12)
        r1 = eng.add_request(plain, max_new_tokens=10)
        results = eng.run()
        assert results[r0][1] == _reference(tiny_model, base, 12)
        assert results[r1][1] == _reference(tiny_model, plain, 10)
        st = eng.request_status(r0)
        assert "speculative_accept_rate" in st.timings
        assert 0.0 <= st.timings["speculative_accept_rate"] <= 1.0

    def test_spec_eos_truncates_like_greedy(self, tiny_model):
        rng = np.random.default_rng(21)
        prompt = np.tile(rng.integers(0, 256, (5,)), 4)
        ref = _reference(tiny_model, prompt, 12)
        eos = ref[5]
        stop = ref.index(eos)
        eng = _paged_engine(tiny_model, max_len=128, spec_decode=4,
                            eos_token_id=eos)
        rid = eng.add_request(prompt, max_new_tokens=12)
        assert eng.run()[rid][1] == ref[:stop + 1]

    def test_spec_requires_greedy(self, tiny_model):
        with pytest.raises(ValueError, match="greedy"):
            _paged_engine(tiny_model, spec_decode=3, do_sample=True)


class TestChaosKvAlloc:
    def test_kv_alloc_fault_sheds_load_then_recovers(self, tiny_model):
        """Armed serving.kv_alloc exhaustion defers admission (no crash,
        no retirement); once the fault passes, the request admits and
        completes correctly — the bounded-admission path absorbed it."""
        from paddle_tpu import robustness
        from paddle_tpu.observability import default_registry
        rng = np.random.default_rng(30)
        prompt = rng.integers(0, 256, (9,))
        eng = _paged_engine(tiny_model)
        robustness.clear_faults()
        robustness.inject("serving.kv_alloc", times=2)
        try:
            rid = eng.add_request(prompt, max_new_tokens=4)
            eng.step()
            assert eng.request_status(rid) is None   # still queued
            assert len(eng._queue) == 1
            fails = default_registry().get(
                "paddle_tpu_serving_kv_alloc_failures_total").value()
            assert fails >= 1
            assert robustness.fault_stats("serving.kv_alloc")["fires"] \
                >= 1
            results = eng.run()
        finally:
            robustness.clear_faults()
        assert results[rid][1] == _reference(tiny_model, prompt, 4)

    def test_genuine_exhaustion_defers_until_blocks_free(self, tiny_model):
        """A pool sized for ~one request serves two sequentially: the
        second waits queued while the first holds the blocks, then
        completes (prefix cache evicts to make room)."""
        rng = np.random.default_rng(31)
        p1 = rng.integers(0, 256, (12,))
        p2 = rng.integers(0, 256, (12,))
        eng = _paged_engine(tiny_model, slots=2, num_kv_blocks=8,
                            max_len=32, prefill_buckets=(16,))
        r1 = eng.add_request(p1, max_new_tokens=4)   # 4 blocks
        r2 = eng.add_request(p2, max_new_tokens=4)
        results = eng.run()
        assert results[r1][1] == _reference(tiny_model, p1, 4)
        assert results[r2][1] == _reference(tiny_model, p2, 4)

    def test_engine_step_fault_recovery_paged(self, tiny_model):
        """The generic engine_step chaos drill on the paged engine: the
        in-flight batch fails, pools/allocator rebuild, and the next
        request is served correctly."""
        from paddle_tpu import robustness
        rng = np.random.default_rng(32)
        prompt = rng.integers(0, 256, (8,))
        eng = _paged_engine(tiny_model)
        robustness.clear_faults()
        robustness.inject("serving.engine_step", nth=2, times=1)
        try:
            r1 = eng.add_request(prompt, max_new_tokens=6)
            eng.run()
        finally:
            robustness.clear_faults()
        assert eng.request_status(r1) == "error"
        assert eng._allocator.used_blocks == 0
        r2 = eng.add_request(prompt, max_new_tokens=6)
        assert eng.run()[r2][1] == _reference(tiny_model, prompt, 6)

    def test_paged_attention_path_counter(self, tiny_model):
        from paddle_tpu.observability import default_registry
        _paged_engine(tiny_model).analyze()   # traces the decode path
        m = default_registry().get("paddle_tpu_paged_attention_path_total")
        series = {"/".join(k): c.value() for k, c in m.series()}
        assert series.get("fallback", 0) >= 1   # CPU routes fallback
