"""Session survivability (ISSUE 19): the KV tier manager (HBM -> host
RAM -> peer store), parkable/resumable sessions, and replica-death
serving recovery without recompute.

Lean tier-manager tests (no model build) run in tier-1; the
engine/router drills that prefill real KV are ``@slow`` and run
unfiltered in CI's session-survivability gate."""

import numpy as np
import pytest

import paddle_tpu as pp
from paddle_tpu.inference.kv_tier import (KVTierManager, prefix_block_key,
                                          session_key)
from paddle_tpu.observability.fleet import LocalStore
from paddle_tpu.robustness import clear_faults, fault_stats, inject


@pytest.fixture(autouse=True)
def _clean_faults():
    clear_faults()
    yield
    clear_faults()


def _payload(seed=0, nblocks=2, dtype=np.float32):
    """A handoff-shaped session payload with a small paged-KV export."""
    rng = np.random.default_rng(seed)
    kv = {"block_size": 8, "dtype": np.dtype(dtype).name,
          "k": [rng.standard_normal((nblocks, 8, 2, 4)).astype(dtype)
                for _ in range(2)],
          "v": [rng.standard_normal((nblocks, 8, 2, 4)).astype(dtype)
                for _ in range(2)]}
    return {"session": True, "block_size": 8, "pos": 14,
            "last_token": 42, "kv": kv}


def _assert_kv_equal(a, b):
    for part in ("k", "v"):
        assert len(a[part]) == len(b[part])
        for x, y in zip(a[part], b[part]):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


class TestTierManager:
    def test_host_roundtrip(self):
        tier = KVTierManager()          # no peer store: host-only
        p = _payload()
        assert tier.spill("s1", p)
        assert tier.has("s1")
        st = tier.stats()
        assert st["host_entries"] == 1 and st["peer_entries"] == 0
        back = tier.fetch("s1")
        assert back is not None
        assert int(back["pos"]) == 14 and int(back["last_token"]) == 42
        _assert_kv_equal(back["kv"], p["kv"])

    def test_write_through_and_peer_fetch_after_host_loss(self):
        """Spill replicates to the peer store immediately; with
        host_capacity_bytes=0 nothing survives in host RAM, so the
        fetch must come back from the peer tier — and re-admit to
        host on the way."""
        tier = KVTierManager(store=LocalStore(), host_capacity_bytes=0)
        p = _payload(seed=1)
        assert tier.spill("s1", p)
        st = tier.stats()
        assert st["host_entries"] == 0 and st["peer_entries"] == 1
        back = tier.fetch("s1")
        assert back is not None
        _assert_kv_equal(back["kv"], p["kv"])

    def test_host_lru_eviction_bounded_by_capacity(self):
        """Host tier is an LRU cache over the peer store: with room
        for roughly one entry, the older spill is evicted from host
        but both stay fetchable (the evictee via the peer)."""
        tier = KVTierManager(store=LocalStore())
        a, b = _payload(seed=2), _payload(seed=3)
        assert tier.spill("a", a)
        # bound host capacity to just over one entry's bytes
        tier.host_capacity_bytes = tier.stats()["host_bytes"] + 16
        assert tier.spill("b", b)
        st = tier.stats()
        assert st["host_entries"] == 1 and st["peer_entries"] == 2
        _assert_kv_equal(tier.fetch("a")["kv"], a["kv"])
        _assert_kv_equal(tier.fetch("b")["kv"], b["kv"])

    def test_discard(self):
        tier = KVTierManager(store=LocalStore())
        tier.spill("s1", _payload())
        tier.discard("s1")
        assert not tier.has("s1")
        assert tier.fetch("s1") is None
        assert tier.stats()["peer_entries"] == 0

    def test_corrupt_peer_part_reads_as_miss(self):
        """A flipped chunk fails the adler32 check: fetch degrades to
        a miss (None) — never a wrong payload."""
        store = LocalStore()
        tier = KVTierManager(store=store, host_capacity_bytes=0)
        assert tier.spill("s1", _payload(seed=4))
        store.set("kvtier/s1/p0", b"\x00garbage\x00")
        assert tier.fetch("s1") is None

    def test_spill_fault_returns_false(self):
        tier = KVTierManager(store=LocalStore())
        inject("kv_tier.spill", times=1)
        assert tier.spill("s1", _payload()) is False
        assert not tier.has("s1")
        assert fault_stats("kv_tier.spill")["fires"] == 1
        # next spill (fault exhausted) goes through
        assert tier.spill("s1", _payload())

    def test_fetch_fault_reads_as_miss_then_recovers(self):
        tier = KVTierManager(store=LocalStore())
        tier.spill("s1", _payload(seed=5))
        inject("kv_tier.fetch", times=1)
        assert tier.fetch("s1") is None      # fault -> miss, no hang
        assert fault_stats("kv_tier.fetch")["fires"] == 1
        assert tier.fetch("s1") is not None  # fault exhausted -> hit

    def test_key_helpers(self):
        toks = [1, 2, 3, 4]
        k1, k2 = prefix_block_key(toks), prefix_block_key(list(toks))
        assert k1 == k2 and k1.startswith("pfx/")
        assert prefix_block_key([1, 2, 3, 5]) != k1
        assert session_key(7) == "sess/7"


class TestQuantTierRoundTrip:
    """ISSUE 19 satellite: quantized KV survives the tier bitwise —
    int8 payloads and their scales ride spill -> host -> peer ->
    promote unchanged, and promote into a higher-precision pool is a
    plain dequantizing import."""

    def _quant_export(self):
        import jax.numpy as jnp
        from paddle_tpu.inference.kv_cache import PagedKVPool
        rng = np.random.default_rng(0)
        fp = {"block_size": 8, "dtype": "float32"}
        for part in ("k", "v"):
            fp[part] = [np.stack([rng.standard_normal((8, 2, 4))
                                  .astype(np.float32) for _ in range(2)])
                        for _ in range(2)]
        pool = PagedKVPool(2, 6, 8, 2, 4, jnp.float32, quant="int8")
        pool.import_blocks(fp, [1, 2])
        return pool.export_blocks([1, 2])

    def test_int8_scales_bitwise_through_peer(self):
        import jax.numpy as jnp
        from paddle_tpu.inference.kv_cache import PagedKVPool
        exp = self._quant_export()
        assert exp["k"][0].dtype == np.int8 and "k_scale" in exp
        # host_capacity_bytes=0 forces the peer leg of the round trip
        tier = KVTierManager(store=LocalStore(), host_capacity_bytes=0)
        assert tier.spill("q", {"kv": exp, "block_size": 8})
        kv = tier.fetch("q")["kv"]
        pool2 = PagedKVPool(2, 6, 8, 2, 4, jnp.float32, quant="int8")
        pool2.import_blocks(kv, [3, 4])
        exp2 = pool2.export_blocks([3, 4])
        for part in ("k", "v", "k_scale", "v_scale"):
            for x, y in zip(exp[part], exp2[part]):
                np.testing.assert_array_equal(np.asarray(x),
                                              np.asarray(y))

    def test_mixed_precision_promote_into_bf16_pool(self):
        import jax.numpy as jnp
        from paddle_tpu.inference.kv_cache import PagedKVPool
        exp = self._quant_export()
        tier = KVTierManager(store=LocalStore(), host_capacity_bytes=0)
        tier.spill("q", {"kv": exp, "block_size": 8})
        kv = tier.fetch("q")["kv"]
        pool = PagedKVPool(2, 6, 8, 2, 4, jnp.bfloat16)
        pool.import_blocks(kv, [1, 2])
        got = pool.export_blocks([1, 2])
        deq = np.asarray(exp["k"][0], np.float32) \
            * np.asarray(exp["k_scale"][0])[..., None]
        np.testing.assert_allclose(np.asarray(got["k"][0], np.float32),
                                   deq, rtol=0.02, atol=0.02)


# ---------------------------------------------------------------------
# engine / router drills (real prefill; slow)
# ---------------------------------------------------------------------

@pytest.fixture(scope="module")
def tiny_model():
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
    pp.seed(0)
    cfg = LlamaConfig.tiny(vocab_size=256, hidden_size=64,
                           intermediate_size=128, num_hidden_layers=2,
                           num_attention_heads=4, num_key_value_heads=2,
                           max_position_embeddings=128)
    return LlamaForCausalLM(cfg)


ENGINE_KW = dict(slots=2, max_len=64, prefill_buckets=(32,),
                 kv_block_size=8, prefill_chunk=16)


def _build(model, tier=None, **over):
    from paddle_tpu.inference.serving import ContinuousBatchingEngine
    kw = {**ENGINE_KW, **over}
    return ContinuousBatchingEngine(model, kv_tier=tier, **kw)


def _step_until_out(eng, rid, n):
    """Step until request ``rid`` has >= n decoded tokens and is out
    of its prefill phase (parkable)."""
    for _ in range(400):
        eng.step()
        slot = next((i for i, r in enumerate(eng._active)
                     if r is not None and r.rid == rid), None)
        if slot is not None and slot not in eng._prefilling \
                and len(eng._active[slot].out) >= n:
            return
        if slot is None and not eng.pending:
            raise AssertionError(f"request {rid} finished before "
                                 f"{n} tokens")
    raise AssertionError("request never reached decode")


def _reference_outs(model, prompts, max_new=8):
    eng = _build(model)
    rids = [eng.add_request(p, max_new_tokens=max_new) for p in prompts]
    res = eng.run()
    outs = [res[r][1] for r in rids]
    eng.close()
    return outs


@pytest.mark.slow
class TestSessionParkResume:
    def test_park_resume_token_identity_and_timings(self, tiny_model):
        prompt = np.arange(1, 17, dtype=np.int32)
        [ref_out] = _reference_outs(tiny_model, [prompt])
        tier = KVTierManager(store=LocalStore())
        eng = _build(tiny_model, tier=tier)
        rid = eng.add_request(prompt, max_new_tokens=8)
        _step_until_out(eng, rid, 3)
        key = eng.park(rid)
        assert key is not None
        assert eng.parked_rids() == [rid]
        assert eng.pending == 0        # caller-parked: run() may exit
        assert tier.has(key)
        eng.resume(rid)
        out = eng.run()[rid][1]
        assert out == ref_out
        t = eng.request_status(rid).timings
        assert t["parked_s"] > 0
        assert t["resume_s"] >= 0
        assert t["decode_s"] >= 0      # park gap excluded, never < 0
        assert t["ttft_s"] > 0         # anchored at FIRST token only
        eng.close()

    def test_recompute_fallback_token_identity(self, tiny_model):
        """kv_tier.fetch fault at resume: the engine re-prefills from
        the original prompt + decoded tokens — same tokens come out,
        and finished() still reports the ORIGINAL prompt."""
        prompt = np.arange(1, 17, dtype=np.int32)
        [ref_out] = _reference_outs(tiny_model, [prompt])
        eng = _build(tiny_model, tier=KVTierManager())
        rid = eng.add_request(prompt, max_new_tokens=8)
        _step_until_out(eng, rid, 3)
        eng.park(rid)
        inject("kv_tier.fetch", times=1)
        eng.resume(rid)
        clear_faults()
        res = eng.run()
        assert res[rid][1] == ref_out
        assert np.array_equal(res[rid][0], prompt)
        t = eng.request_status(rid).timings
        assert t["parked_s"] > 0 and t["decode_s"] >= 0
        eng.close()

    def test_auto_park_oversubscribed_slots(self, tiny_model):
        """slots=1 serving 3 sessions with auto_park_s=0: the engine
        parks/resumes on its own and every output stays identical."""
        prompts = [np.arange(1 + i, 17 + i, dtype=np.int32)
                   for i in range(3)]
        refs = []
        for p in prompts:           # sequential single-slot reference
            refs.extend(_reference_outs(tiny_model, [p]))
        eng = _build(tiny_model, tier=KVTierManager(), slots=1,
                     auto_park_s=0.0)
        rids = [eng.add_request(p, max_new_tokens=8) for p in prompts]
        out = eng.run()
        for rid, ref in zip(rids, refs):
            assert out[rid][1] == ref
        eng.close()

    def test_quant_kv_park_resume_bitwise(self, tiny_model):
        """int8 paged pools park and resume bitwise: the quantized
        blocks + scales survive the tier, so the resumed decode is
        token-identical to the undisturbed int8 engine."""
        prompt = np.arange(1, 17, dtype=np.int32)
        ref = _build(tiny_model, quant_kv="int8")
        r = ref.add_request(prompt, max_new_tokens=8)
        ref_out = ref.run()[r][1]
        ref.close()
        eng = _build(tiny_model, tier=KVTierManager(store=LocalStore()),
                     quant_kv="int8")
        rid = eng.add_request(prompt, max_new_tokens=8)
        _step_until_out(eng, rid, 3)
        assert eng.park(rid) is not None
        eng.resume(rid)
        assert eng.run()[rid][1] == ref_out
        eng.close()

    def test_prefix_demote_promote(self, tiny_model):
        """Cold prefix-cache blocks demote to the tier on eviction and
        promote back at the next affine admission — the reuse counter
        proves the prefill was skipped, not recomputed."""
        tier = KVTierManager()
        eng = _build(tiny_model, tier=tier, slots=1, num_kv_blocks=13)
        shared = np.arange(1, 25, dtype=np.int32)   # 3 full blocks
        p1 = np.concatenate([shared, [30, 31]]).astype(np.int32)
        p2 = np.concatenate([shared, [40, 41]]).astype(np.int32)
        eng.add_request(p1, max_new_tokens=6)
        eng.run()
        assert eng._prefix.evict(8) > 0        # demote-before-free
        assert tier.stats()["host_entries"] > 0
        r2 = eng.add_request(p2, max_new_tokens=6)
        eng.run()
        t = eng.request_status(r2).timings
        assert t["prefix_tokens_reused"] >= 8  # promoted, not re-prefilled
        eng.close()

    def test_park_requires_tier(self, tiny_model):
        eng = _build(tiny_model)
        with pytest.raises(ValueError):
            eng.park(0)
        eng.close()


@pytest.mark.slow
class TestRouterSurvivability:
    def _series(self, name):
        from paddle_tpu.observability import default_registry
        m = default_registry().get(name)
        return {"/".join(k) or "all": c.value() for k, c in m.series()} \
            if m is not None else {}

    def _run_death_drill(self, tiny_model, fault=None):
        """Kill a replica mid-decode with sessions checkpointed to the
        tier every step; survivors must finish every request
        token-identically (via migration, or — under ``fault`` — via
        fresh-prefill fallback)."""
        from paddle_tpu.inference.router import ServingRouter
        prompts = [np.arange(1 + i, 17 + i, dtype=np.int32)
                   for i in range(4)]
        refs = _reference_outs(tiny_model, prompts)
        rt = ServingRouter(tiny_model, replicas=2,
                           engine_kwargs=dict(ENGINE_KW),
                           kv_tier=KVTierManager(store=LocalStore()),
                           session_checkpoint_steps=1)
        rids = [rt.add_request(p, max_new_tokens=8) for p in prompts]
        victim = None
        for _ in range(500):
            rt.step()
            for rep in rt._replicas.values():
                if rep.dead:
                    continue
                eng = rep.engine
                ready = [r for i, r in enumerate(eng._active)
                         if r is not None and i not in eng._prefilling
                         and len(r.out) >= 2]
                if ready:
                    victim = rep.id
                    break
            if victim is not None:
                break
        assert victim is not None, "no replica reached decode"
        if fault:
            inject(fault, times=8)
        rt.kill_replica(victim)
        if fault:
            clear_faults()
        out = rt.run()
        for rid, ref in zip(rids, refs):
            assert out[rid][1] == ref, f"request {rid} diverged"
        return rt

    def test_replica_death_migrates_sessions(self, tiny_model):
        before = self._series(
            "paddle_tpu_router_requeues_total").get("session_migrate",
                                                    0.0)
        self._run_death_drill(tiny_model)
        after = self._series(
            "paddle_tpu_router_requeues_total").get("session_migrate",
                                                    0.0)
        assert after > before      # at least one session skipped re-prefill

    def test_migrate_fault_falls_back_to_prefill(self, tiny_model):
        """session.migrate faults: the router degrades to fresh
        prefill — slower, never wrong, never hung."""
        self._run_death_drill(tiny_model, fault="session.migrate")

    def test_fleet_park_resume(self, tiny_model):
        from paddle_tpu.inference.router import ServingRouter
        prompts = [np.arange(1 + i, 17 + i, dtype=np.int32)
                   for i in range(2)]
        refs = _reference_outs(tiny_model, prompts)
        rt = ServingRouter(tiny_model, replicas=2,
                           engine_kwargs=dict(ENGINE_KW),
                           kv_tier=KVTierManager(store=LocalStore()))
        rids = [rt.add_request(p, max_new_tokens=8) for p in prompts]
        parked = None
        for _ in range(500):
            rt.step()
            for rid in rids:
                freq = rt._requests[rid]
                if freq.phase != "decode":
                    continue
                rep = rt._replicas[freq.replica]
                req = next(
                    (r for i, r in enumerate(rep.engine._active)
                     if r is not None and r.rid == freq.engine_rid
                     and i not in rep.engine._prefilling), None)
                if req is not None and len(req.out) >= 2 \
                        and rt.park(rid):
                    parked = rid
                    break
            if parked is not None:
                break
        assert parked is not None, "no session reached parkable decode"
        assert parked in rt.parked_rids()
        rt.run()                      # drain the other request
        assert rt.resume(parked)      # possibly onto the OTHER replica
        out = rt.run()
        assert out[parked][1] == refs[rids.index(parked)]
