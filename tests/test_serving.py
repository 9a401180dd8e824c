"""Continuous-batching serving engine (VERDICT r4 Weak #4 / Next #6):
slot reuse, chunked prefill, per-slot positions, int8 weight-only mode
— all CPU-runnable, parity-checked against model.generate.  (The block
allocator, prefix trie and speculation live in tests/test_kv_cache.py.)"""

import os
import re

import numpy as np
import pytest

import paddle_tpu as pp
from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
from paddle_tpu.inference.serving import (ContinuousBatchingEngine,
                                          quantize_weights_int8)


@pytest.fixture(scope="module")
def tiny_model():
    pp.seed(0)
    cfg = LlamaConfig.tiny(vocab_size=256, hidden_size=64,
                           intermediate_size=128, num_hidden_layers=2,
                           num_attention_heads=4, num_key_value_heads=2,
                           max_position_embeddings=128)
    return LlamaForCausalLM(cfg)


def _reference(model, prompt, n):
    out = model.generate(np.asarray(prompt, np.int32)[None],
                         max_new_tokens=n, do_sample=False)
    return list(np.asarray(out)[0, len(prompt):])


class TestContinuousBatching:
    def test_single_request_matches_generate(self, tiny_model):
        rng = np.random.default_rng(0)
        prompt = rng.integers(0, 256, (12,))
        eng = ContinuousBatchingEngine(tiny_model, slots=2, max_len=64,
                                       prefill_buckets=(16, 32))
        rid = eng.add_request(prompt, max_new_tokens=8)
        results = eng.run()
        assert results[rid][1] == _reference(tiny_model, prompt, 8)

    @pytest.mark.slow
    def test_slot_reuse_more_requests_than_slots(self, tiny_model):
        """5 requests through 2 slots: all finish, all match the
        sequential generate oracle, different prompt lengths exercise
        one-chunk and two-chunk prefills."""
        rng = np.random.default_rng(1)
        prompts = [rng.integers(0, 256, (n,))
                   for n in (5, 13, 17, 9, 30)]
        eng = ContinuousBatchingEngine(tiny_model, slots=2, max_len=64,
                                       prefill_buckets=(16, 32))
        rids = [eng.add_request(p, max_new_tokens=6) for p in prompts]
        results = eng.run()
        assert len(results) == 5
        for rid, p in zip(rids, prompts):
            assert results[rid][1] == _reference(tiny_model, p, 6), \
                f"request {rid} (len {len(p)}) diverged"

    def test_streaming_admission(self, tiny_model):
        """Requests added WHILE others decode still complete correctly
        (the continuous part of continuous batching)."""
        rng = np.random.default_rng(2)
        eng = ContinuousBatchingEngine(tiny_model, slots=2, max_len=64,
                                       prefill_buckets=(16,))
        first = rng.integers(0, 256, (8,))
        r0 = eng.add_request(first, max_new_tokens=10)
        for _ in range(4):
            eng.step()
        late = rng.integers(0, 256, (6,))
        r1 = eng.add_request(late, max_new_tokens=4)
        results = eng.run()
        assert results[r0][1] == _reference(tiny_model, first, 10)
        assert results[r1][1] == _reference(tiny_model, late, 4)

    def test_eos_frees_slot_early(self, tiny_model):
        """A sequence hitting EOS retires its slot; the next queued
        request then runs in it."""
        rng = np.random.default_rng(3)
        prompt = rng.integers(0, 256, (8,))
        ref = _reference(tiny_model, prompt, 12)
        eos = ref[3]  # force an early stop at a token we know appears
        eng = ContinuousBatchingEngine(tiny_model, slots=1, max_len=64,
                                       prefill_buckets=(16,),
                                       eos_token_id=eos)
        r0 = eng.add_request(prompt, max_new_tokens=12)
        p2 = rng.integers(0, 256, (7,))
        r1 = eng.add_request(p2, max_new_tokens=3)
        results = eng.run()
        assert results[r0][1] == ref[:4]      # stopped AT the eos token
        assert len(results[r1][1]) == 3       # second request ran after

    def test_request_bounds_rejected(self, tiny_model):
        eng = ContinuousBatchingEngine(tiny_model, slots=1, max_len=64,
                                       prefill_buckets=(16,),
                                       num_kv_blocks=3)
        with pytest.raises(ValueError, match="reserved"):
            eng.add_request(np.zeros(10, np.int32), max_new_tokens=60)
        # the empty pool (2 blocks of 16 beside the scratch block) could
        # never hold it: rejected at submission, not starved in the queue
        with pytest.raises(ValueError, match="KV blocks"):
            eng.add_request(np.zeros(30, np.int32), max_new_tokens=8)
        assert not eng.pending

    def test_empty_prompt_rejected(self, tiny_model):
        """No last prompt position to sample the first token at: the
        final chunk would read the logits of a pad row."""
        eng = ContinuousBatchingEngine(tiny_model, slots=1, max_len=64,
                                       prefill_buckets=(16,))
        for empty in ([], np.zeros((0,), np.int32)):
            with pytest.raises(ValueError, match="empty prompt"):
                eng.add_request(empty, max_new_tokens=4)
        assert not eng.pending


class TestInt8Serving:
    def test_quantize_split(self, tiny_model):
        from paddle_tpu.core.functional import params_of
        params = params_of(tiny_model)
        keep, quant = quantize_weights_int8(params, min_size=1024)
        assert quant, "no weights selected for int8"
        for name, (w8, scale) in quant.items():
            assert w8.dtype == np.int8 and int(np.abs(w8).max()) <= 127
            # dequantized weight close to original (per-channel symmetric)
            deq = np.asarray(w8, np.float32) * np.asarray(scale)
            orig = np.asarray(params[name], np.float32)
            err = np.abs(deq - orig).max() / (np.abs(orig).max() + 1e-9)
            assert err < 0.02, (name, err)

    def test_int8_decode_runs_and_stays_close(self, tiny_model):
        """int8 weight-only decode produces a plausible continuation:
        identical first tokens to bf16 greedy for a short horizon (tiny
        model, 1% weight error — argmax ties aside this should hold for
        the first few steps)."""
        rng = np.random.default_rng(5)
        prompt = rng.integers(0, 256, (10,))
        eng = ContinuousBatchingEngine(tiny_model, slots=1, max_len=64,
                                       prefill_buckets=(16,),
                                       int8_weights=True)
        rid = eng.add_request(prompt, max_new_tokens=4)
        results = eng.run()
        assert len(results[rid][1]) == 4
        assert all(0 <= t < 256 for t in results[rid][1])


class TestChunkedDecode:
    def test_steps_per_sync_parity(self, tiny_model):
        """K decode steps fused per host sync produce the SAME tokens as
        step-by-step decode (and as model.generate)."""
        rng = np.random.default_rng(9)
        prompts = [rng.integers(0, 256, (n,)) for n in (6, 11, 14)]
        eng = ContinuousBatchingEngine(tiny_model, slots=2, max_len=64,
                                       prefill_buckets=(16,),
                                       steps_per_sync=4)
        rids = [eng.add_request(p, max_new_tokens=7) for p in prompts]
        results = eng.run()
        for rid, p in zip(rids, prompts):
            assert results[rid][1] == _reference(tiny_model, p, 7), \
                f"chunked decode diverged for request {rid}"

    def test_chunk_headroom_enforced(self, tiny_model):
        eng = ContinuousBatchingEngine(tiny_model, slots=1, max_len=32,
                                       prefill_buckets=(16,),
                                       steps_per_sync=8)
        with pytest.raises(ValueError, match="rounded"):
            eng.add_request(np.zeros(16, np.int32), max_new_tokens=10)

    def test_constructor_validation(self, tiny_model):
        with pytest.raises(ValueError, match="RoPE"):
            ContinuousBatchingEngine(tiny_model, max_len=4096,
                                     prefill_buckets=(16,))
        with pytest.raises(ValueError, match="bucket"):
            ContinuousBatchingEngine(tiny_model, max_len=16,
                                     prefill_buckets=(16,))

    def test_train_mode_restored_on_close(self, tiny_model):
        tiny_model.train()
        try:
            with ContinuousBatchingEngine(tiny_model, slots=1, max_len=48,
                                          prefill_buckets=(8,)) as eng:
                assert not tiny_model.training
                rid = eng.add_request(np.arange(6), max_new_tokens=2)
                eng.run()
            assert tiny_model.training
        finally:
            tiny_model.eval()


class TestOneEngine:
    """There is one engine: what ``ContinuousBatchingEngine(model)``
    builds is the paged engine, held to ``generate()`` token for token,
    and nothing selects another."""

    @pytest.mark.parametrize("over", [
        {}, {"steps_per_sync": 4}, {"prefix_cache": False},
        {"kv_block_size": 8}, {"kv_block_size": 64}],
        ids=["plain", "sync4", "no_prefix", "block8", "block64"])
    def test_default_matches_generate_past_the_largest_bucket(
            self, tiny_model, monkeypatch, over):
        """No engine argument, no environment: a prompt longer than the
        largest entry of ``prefill_buckets`` (the old default engine
        refused it) walks in chunks and equals the reference."""
        monkeypatch.delenv("PADDLE_TPU_PAGED_KV", raising=False)
        from paddle_tpu.inference.kv_cache import PagedKVPool
        rng = np.random.default_rng(40)
        prompts = [rng.integers(0, 256, (n,)) for n in (41, 12)]
        eng = ContinuousBatchingEngine(tiny_model, slots=2, max_len=64,
                                       prefill_buckets=(8, 16), **over)
        assert isinstance(eng._pool, PagedKVPool)
        assert eng._chunk == 16
        rids = [eng.add_request(p, max_new_tokens=8) for p in prompts]
        results = eng.run()
        for rid, p in zip(rids, prompts):
            assert results[rid][1] == _reference(tiny_model, p, 8), \
                f"request {rid} (len {len(p)}) diverged"

    def test_no_argument_builds_a_paged_pool(self):
        """``ContinuousBatchingEngine(model)``, every default: the pool
        holds each slot's worst case in blocks of 16 beside the scratch
        block, and the chunk is the largest default bucket."""
        from paddle_tpu.inference.kv_cache import PagedKVPool
        pp.seed(0)
        cfg = LlamaConfig.tiny(vocab_size=64, hidden_size=32,
                               intermediate_size=64, num_hidden_layers=1,
                               num_attention_heads=2, num_key_value_heads=1,
                               max_position_embeddings=1024)
        model = LlamaForCausalLM(cfg)
        eng = ContinuousBatchingEngine(model)
        assert isinstance(eng._pool, PagedKVPool)
        assert eng._num_blocks == 1 + 8 * 64 and eng._chunk == 256
        prompt = np.arange(1, 20) % 64
        rid = eng.add_request(prompt, max_new_tokens=3)
        assert eng.run()[rid][1] == _reference(model, prompt, 3)

    def test_no_switch(self, tiny_model, monkeypatch):
        """``paged_kv=False`` raises; the retired environment variable
        is not read; ``paged_kv=True`` (the benchmark's traffic file
        still passes it) builds the same engine."""
        kw = dict(slots=2, max_len=64, prefill_buckets=(16,))
        with pytest.raises(ValueError, match="paged_kv=False"):
            ContinuousBatchingEngine(tiny_model, paged_kv=False, **kw)
        monkeypatch.setenv("PADDLE_TPU_PAGED_KV", "0")
        eng = ContinuousBatchingEngine(tiny_model, **kw)
        same = ContinuousBatchingEngine(tiny_model, paged_kv=True, **kw)
        for e in (eng, same):
            assert not hasattr(e, "paged") and hasattr(e, "_pool")
        assert eng._cache_extra() == same._cache_extra()
        prompt = np.random.default_rng(41).integers(0, 256, (12,))
        rid = eng.add_request(prompt, max_new_tokens=8)
        assert eng.run()[rid][1] == _reference(tiny_model, prompt, 8)

    def test_source_holds_one_engine(self):
        """The fork does not grow back: no engine switch, no second
        step, and one place that hands the pools to a batched program."""
        root = os.path.join(os.path.dirname(__file__), "..", "paddle_tpu")
        with open(os.path.join(root, "inference", "serving.py")) as f:
            src = f.read()
        for gone in ("self.paged", "paged_kv_enabled", "PADDLE_TPU_PAGED_KV",
                     "_step_inner_paged", "_emit_first_unpaged",
                     "_insert_compiled", "StaticCache"):
            assert gone not in src, gone
        with open(os.path.join(root, "inference", "kv_cache.py")) as f:
            assert "PADDLE_TPU_PAGED_KV" not in f.read()
        # pools are donated to a program and taken back in two places:
        # the one-row chunk and the batched dispatch both steps share
        assert len(re.findall(r"pool\.vscales\) = ", src)) == 2
        assert src.count("self._dispatch_batched(") == 2


class TestSampling:
    def test_near_zero_temperature_matches_greedy(self, tiny_model):
        """do_sample with temperature -> 0 degenerates to argmax: exact
        parity with the greedy reference at every step."""
        rng = np.random.default_rng(11)
        prompt = rng.integers(0, 256, (9,))
        eng = ContinuousBatchingEngine(tiny_model, slots=1, max_len=48,
                                       prefill_buckets=(16,),
                                       do_sample=True, temperature=1e-6)
        rid = eng.add_request(prompt, max_new_tokens=6)
        results = eng.run()
        assert results[rid][1] == _reference(tiny_model, prompt, 6)

    def test_sampling_varies_with_seed_and_stays_in_vocab(self, tiny_model):
        rng = np.random.default_rng(12)
        prompt = rng.integers(0, 256, (8,))
        outs = []
        for seed in (0, 1):
            eng = ContinuousBatchingEngine(
                tiny_model, slots=1, max_len=48, prefill_buckets=(16,),
                do_sample=True, temperature=1.0, top_k=50, seed=seed)
            rid = eng.add_request(prompt, max_new_tokens=12)
            outs.append(eng.run()[rid][1])
        assert all(0 <= t < 256 for o in outs for t in o)
        assert outs[0] != outs[1], "two seeds produced identical samples"

    def test_sampled_chunked_decode(self, tiny_model):
        """Sampling + steps_per_sync compose (key threads the scan)."""
        rng = np.random.default_rng(13)
        eng = ContinuousBatchingEngine(
            tiny_model, slots=2, max_len=48, prefill_buckets=(16,),
            do_sample=True, temperature=0.8, top_p=0.95, seed=3,
            steps_per_sync=4)
        rids = [eng.add_request(rng.integers(0, 256, (n,)), 8)
                for n in (6, 10)]
        results = eng.run()
        assert all(len(results[r][1]) == 8 for r in rids)


def _dispatch_kinds():
    """{kind: count} of the batched decode dispatches so far."""
    from paddle_tpu.observability import default_registry
    c = default_registry().get("paddle_tpu_serving_decode_dispatches_total")
    return {k[0]: v.value() for k, v in c.series()} if c else {}


def _kinds_since(before):
    now = _dispatch_kinds()
    return {k: now.get(k, 0) - before.get(k, 0)
            for k in ("overlapped", "waited")}


def _spy_decode(eng):
    """Every call of the engine's decode program: (``from_host``,
    ``active``) as the host uploaded them, in dispatch order."""
    calls, program = [], eng._decode_paged

    def spy(*args):
        calls.append((np.asarray(args[9]).copy(), np.asarray(args[11]).copy()))
        return program(*args)
    eng._decode_paged = spy
    return calls


class TestDispatchThenCollect:
    """A decode step is dispatched from the device's own tokens before
    the one before it is read (PR 40): same tokens, other order."""

    @pytest.mark.parametrize("k", [1, 4])
    def test_interleaved_chunks_serve_the_references_tokens(self,
                                                            tiny_model, k):
        """Six prompts of mixed lengths over three slots, two of them
        offered while others decode: chunks of later prompts run between
        the decode steps of earlier ones, and every request gets the
        tokens of a run of its own."""
        rng = np.random.default_rng(50 + k)
        prompts = [rng.integers(0, 256, (n,)) for n in (5, 37, 17, 9, 30, 21)]
        budgets = [9, 6, 11, 5, 8, 7]
        eng = ContinuousBatchingEngine(tiny_model, slots=3, max_len=96,
                                       prefill_buckets=(16,),
                                       steps_per_sync=k)
        before = _dispatch_kinds()
        rids = [eng.add_request(p, max_new_tokens=b)
                for p, b in zip(prompts[:4], budgets)]
        for _ in range(7):
            eng.step()
        rids += [eng.add_request(p, max_new_tokens=b)
                 for p, b in zip(prompts[4:], budgets[4:])]
        results = eng.run()
        for rid, p, b in zip(rids, prompts, budgets):
            assert results[rid][1] == _reference(tiny_model, p, b), rid
        kinds = _kinds_since(before)
        assert kinds["overlapped"] > 0 and kinds["waited"] > 0
        assert eng._inflight is None and not eng.pending

    def test_a_run_of_decode_steps_overlaps_all_but_its_first(self,
                                                              tiny_model):
        eng = ContinuousBatchingEngine(tiny_model, slots=1, max_len=64,
                                       prefill_buckets=(16,))
        calls = _spy_decode(eng)
        before = _dispatch_kinds()
        prompt = np.random.default_rng(60).integers(0, 256, (9,))
        rid = eng.add_request(prompt, max_new_tokens=8)
        assert eng.run()[rid][1] == _reference(tiny_model, prompt, 8)
        # the first token is the prompt's last chunk's; seven decode steps
        assert _kinds_since(before) == {"waited": 1, "overlapped": 6}
        # the first takes the chunk's token from the host, the others go
        # on from the device's
        assert [bool(f[0]) for f, _ in calls] == [True] + [False] * 6

    def test_every_speculative_dispatch_waits(self, tiny_model):
        """The verify drafts from the history the host holds: it is
        issued with nothing unread and read in its own step."""
        eng = ContinuousBatchingEngine(tiny_model, slots=2, max_len=96,
                                       prefill_buckets=(16,), spec_decode=2)
        rng = np.random.default_rng(61)
        prompts = [np.tile(rng.integers(0, 256, (4,)), 5),
                   rng.integers(0, 256, (11,))]
        before = _dispatch_kinds()
        rids = [eng.add_request(p, max_new_tokens=10) for p in prompts]
        unread = []
        while eng.pending:
            eng.step()
            unread.append(eng._inflight)
        kinds = _kinds_since(before)
        assert kinds["overlapped"] == 0 and kinds["waited"] > 0
        assert unread and all(d is None for d in unread)
        res = {rid: out for rid, _, out in eng.finished()}
        for rid, p in zip(rids, prompts):
            assert res[rid] == _reference(tiny_model, p, 10)

    def test_eos_costs_one_wasted_row_step_and_nothing_reaches_out(
            self, tiny_model):
        """An ``eos`` is learned one collect late: the row runs once more
        in the dispatch already issued, its tokens are dropped, and no
        block is handed out while a dispatch is unread, so what the
        wasted row-step writes lands in blocks its request still held
        when it was issued."""
        rng = np.random.default_rng(62)
        prompts = [rng.integers(0, 256, (n,)) for n in (7, 12, 19, 6)]
        refs = [_reference(tiny_model, p, 12) for p in prompts]
        eos = refs[0][4]
        want = [r[:r.index(eos) + 1] if eos in r else r for r in refs]
        assert len(want[0]) <= 5
        eng = ContinuousBatchingEngine(tiny_model, slots=2, max_len=64,
                                       prefill_buckets=(16,),
                                       eos_token_id=int(eos))
        calls = _spy_decode(eng)
        admit, unread_at_admit = eng._admit, []

        def admit_spy(slot, req):
            unread_at_admit.append(eng._inflight)
            return admit(slot, req)
        eng._admit = admit_spy
        dropped = []
        emit = eng._emit_decoded

        def emit_spy(slots_, rows, *a):
            dropped.append(len(slots_))
            return emit(slots_, rows, *a)
        eng._emit_decoded = emit_spy
        rids = [eng.add_request(p, max_new_tokens=12) for p in prompts]
        res = eng.run()
        for rid, w in zip(rids, want):
            assert res[rid][1] == w
            assert eng.request_status(rid) == "ok"
        assert len(unread_at_admit) == 4 and \
            all(d is None for d in unread_at_admit)
        # at least one dispatch carried a row that the collect dropped
        rows = [int(a.sum()) for _, a in calls]
        assert len(rows) == len(dropped) and \
            any(n < r for n, r in zip(dropped, rows))

    def test_a_budget_ended_row_is_absent_from_the_next_dispatch(
            self, tiny_model):
        eng = ContinuousBatchingEngine(tiny_model, slots=2, max_len=64,
                                       prefill_buckets=(16,))
        calls = _spy_decode(eng)
        rng = np.random.default_rng(63)
        short, long_ = rng.integers(0, 256, (8,)), rng.integers(0, 256, (8,))
        r0 = eng.add_request(short, max_new_tokens=3)
        r1 = eng.add_request(long_, max_new_tokens=9)
        res = eng.run()
        assert res[r0][1] == _reference(tiny_model, short, 3)
        assert res[r1][1] == _reference(tiny_model, long_, 9)
        # slot 0 decodes twice after its chunk's token, slot 1 eight times:
        # no dispatch carries a row whose budget the one before it ended
        per_slot = np.sum([a for _, a in calls], axis=0)
        assert per_slot.tolist() == [2, 8]

    def test_pending_while_a_dispatch_is_unread_and_run_returns_it(
            self, tiny_model):
        eng = ContinuousBatchingEngine(tiny_model, slots=1, max_len=64,
                                       prefill_buckets=(16,))
        prompt = np.random.default_rng(64).integers(0, 256, (6,))
        rid = eng.add_request(prompt, max_new_tokens=3)
        want = _reference(tiny_model, prompt, 3)
        seen = False
        for _ in range(20):
            eng.step()
            d = eng._inflight
            if d is not None and eng._budget[0] - d.steps <= 0:
                # the request's last tokens are on the device, unread
                seen = True
                assert eng.pending and eng.request_status(rid) is None
                assert list(eng.finished()) == []
                assert len(eng._active[0].out) < 3
                break
        assert seen
        assert eng.run()[rid][1] == want and not eng.pending
        assert eng.request_status(rid).timings["generated"] == 3

    def test_token_stamps_are_not_earlier_than_their_read(self, tiny_model):
        import time
        eng = ContinuousBatchingEngine(tiny_model, slots=2, max_len=64,
                                       prefill_buckets=(16,),
                                       steps_per_sync=2)
        read, reads = eng._read, []

        def read_spy(d):
            out = read(d)
            reads.append(time.perf_counter())
            return out
        eng._read = read_spy
        rng = np.random.default_rng(65)
        rids = [eng.add_request(rng.integers(0, 256, (n,)), max_new_tokens=9)
                for n in (5, 20)]
        eng.run()
        stamps = sorted({t for rid in rids for t, _ in
                         eng.request_status(rid).token_times[1:]})
        # one clock reading a collect, taken once the host has the tokens
        assert len(stamps) == len(reads)
        for t, done, nxt in zip(stamps, reads, reads[1:] + [float("inf")]):
            assert done <= t < nxt

    def test_the_drain_points_see_the_collected_state(self, tiny_model):
        """``park``, ``checkpoint_sessions`` and ``export_handoff``
        between two steps first read what is unread: a payload holds the
        tokens and the write head of the same moment."""
        from paddle_tpu.inference.kv_tier import KVTierManager
        from paddle_tpu.observability.fleet import LocalStore
        prompt = np.random.default_rng(66).integers(0, 256, (10,))
        want = _reference(tiny_model, prompt, 12)
        tier = KVTierManager(store=LocalStore())
        eng = ContinuousBatchingEngine(tiny_model, slots=2, max_len=64,
                                       prefill_buckets=(16,), kv_tier=tier)
        rid = eng.add_request(prompt, max_new_tokens=12)
        while eng._inflight is None or len(eng._active[0].out) < 3:
            eng.step()
        assert eng.checkpoint_sessions() == 1 and eng._inflight is None
        snap = tier.fetch(f"rid{rid}")
        out = list(eng._active[0].out)
        assert list(snap["tokens_out"]) == out == want[:len(out)]
        assert snap["pos"] == len(prompt) + len(out) - 1 == eng._pos[0]
        assert snap["last_token"] == out[-1]
        while eng._inflight is None:
            eng.step()
        n = len(eng._active[0].out)
        assert eng.park(rid) is not None and eng._inflight is None
        req, _key = eng._parked[rid]
        assert len(req.out) > n and req.out == want[:len(req.out)]
        eng.resume(rid)
        assert eng.run()[rid][1] == want
        # a prompt prefilled here for another engine, beside a decode
        other = np.random.default_rng(67).integers(0, 256, (9,))
        r0 = eng.add_request(other, max_new_tokens=8)
        r1 = eng.add_request(prompt, max_new_tokens=8, prefill_only=True)
        while eng.request_status(r1) is None:
            eng.step()
        payload = eng.export_handoff(r1)
        assert eng._inflight is None and payload["first_token"] == want[0]
        assert eng.run()[r0][1] == _reference(tiny_model, other, 8)
