"""Serving fleet router (ISSUE 12 tentpole): prefix-affine routing,
prefill/decode disaggregation with paged-KV handoff, SLO elasticity,
and fleet-grade failure drills — all in-process, CPU-runnable, parity
checked against the single engine (greedy outputs must be
token-identical no matter how the fleet schedules them)."""

import numpy as np
import pytest

import paddle_tpu as pp
from paddle_tpu import robustness
from paddle_tpu.inference.kv_cache import (deserialize_handoff,
                                           fetch_handoff,
                                           publish_handoff,
                                           serialize_handoff)
from paddle_tpu.inference.router import (ServingRouter, SloAutoscaleRule,
                                         SloAutoscaler,
                                         fleet_serve_replicas)
from paddle_tpu.inference.serving import ContinuousBatchingEngine
from paddle_tpu.models import LlamaConfig, LlamaForCausalLM

BS = 8          # kv block size used throughout
ENGINE_KW = dict(slots=2, max_len=64, prefill_buckets=(32,),
                 kv_block_size=BS, prefill_chunk=8)


@pytest.fixture(scope="module")
def tiny_model():
    pp.seed(0)
    cfg = LlamaConfig.tiny(vocab_size=256, hidden_size=64,
                           intermediate_size=128, num_hidden_layers=2,
                           num_attention_heads=4, num_key_value_heads=2,
                           max_position_embeddings=128)
    return LlamaForCausalLM(cfg)


@pytest.fixture(scope="module")
def workload():
    rng = np.random.default_rng(7)
    shared = rng.integers(0, 256, (2 * BS,))      # two full shared blocks
    prompts = [np.concatenate(
        [shared, rng.integers(0, 256, (n,))]).astype(np.int32)
        for n in (3, 5, 7, 4, 6, 9)]
    return prompts


@pytest.fixture(scope="module")
def reference(tiny_model, workload):
    """Single paged engine greedy outputs — the oracle every fleet
    topology must reproduce."""
    eng = ContinuousBatchingEngine(tiny_model, **ENGINE_KW)
    rids = [eng.add_request(p, max_new_tokens=6) for p in workload]
    res = eng.run()
    return [res[r][1] for r in rids]


def _run(router, prompts, max_new=6):
    rids = [router.add_request(p, max_new_tokens=max_new)
            for p in prompts]
    res = router.run()
    return [res[r][1] for r in rids], rids


# ------------------------------------------------------------ routing key
class TestRoutingKey:
    def _router(self, n=2):
        def factory(role):
            class _Stub:
                slots = 2
                pending = 0
                role_ = role

                def close(self):
                    pass
            return _Stub()
        return ServingRouter(engine_factory=factory, replicas=n,
                             engine_kwargs=dict(kv_block_size=BS),
                             warm_on_spawn=False)

    def test_chain_is_full_block_prefix(self):
        r = self._router()
        p = np.arange(BS * 2 + 3, dtype=np.int32)
        chain = r._chain(p)
        assert len(chain) == 2 and len(chain[0]) == BS
        # sub-block prompts key on the whole prompt
        assert r._chain(np.arange(3, dtype=np.int32)) == ((0, 1, 2),)

    def test_ring_is_deterministic_and_affinity_sticks(self):
        r = self._router()
        p = np.arange(BS * 2, dtype=np.int32)
        chain = r._chain(p)
        first = r._ring_lookup(chain).id
        assert r._ring_lookup(chain).id == first     # consistent
        r._register_chain(chain, first)
        # a longer prompt sharing the prefix follows it
        p2 = np.concatenate([p, np.arange(BS, dtype=np.int32)])
        assert r._affine_lookup(r._chain(p2)).id == first
        # an unrelated chain has no affinity
        assert r._affine_lookup(
            r._chain(np.arange(100, 100 + BS, dtype=np.int32))) is None

    def test_affinity_cap_resets_not_grows(self):
        r = self._router()
        r._affinity_cap = 8
        for i in range(30):
            r._register_chain(
                r._chain(np.arange(i, i + BS, dtype=np.int32)), "m0")
        assert r._trie_nodes <= 8

    def test_dead_replica_falls_out_of_ring_and_affinity(self):
        r = self._router(2)
        p = np.arange(BS, dtype=np.int32)
        chain = r._chain(p)
        target = r._ring_lookup(chain).id
        r._register_chain(chain, target)
        r._replicas[target].dead = True
        r._rebuild_ring()
        assert r._affine_lookup(chain) is None
        got = r._ring_lookup(chain)
        assert got is not None and got.id != target

    def test_fleet_serve_env_knob(self, monkeypatch):
        monkeypatch.delenv("PADDLE_TPU_FLEET_SERVE", raising=False)
        assert fleet_serve_replicas() == 0
        monkeypatch.setenv("PADDLE_TPU_FLEET_SERVE", "3")
        assert fleet_serve_replicas() == 3
        monkeypatch.setenv("PADDLE_TPU_FLEET_SERVE", "bogus")
        assert fleet_serve_replicas(2) == 2


# ---------------------------------------------------------- token identity
class TestFleetEquivalence:
    def test_mixed_fleet_matches_single_engine(self, tiny_model,
                                               workload, reference):
        router = ServingRouter(tiny_model, replicas=2,
                               engine_kwargs=ENGINE_KW,
                               warm_on_spawn=False)
        got, rids = _run(router, workload)
        assert got == reference
        # repeated shared-prefix prompts actually rode affinity
        from paddle_tpu.observability import default_registry
        m = default_registry().get("paddle_tpu_router_affinity_total")
        kinds = {"/".join(k): c.value() for k, c in m.series()}
        assert kinds.get("affine", 0) >= 1

    def test_disaggregated_fleet_matches_single_engine(
            self, tiny_model, workload, reference):
        router = ServingRouter(tiny_model, replicas=2,
                               prefill_replicas=1,
                               engine_kwargs=ENGINE_KW,
                               decode_kwargs=dict(steps_per_sync=4),
                               warm_on_spawn=False)
        got, rids = _run(router, workload)
        assert got == reference
        st = router.request_status(rids[-1])
        assert st == "ok"
        assert st.timings["handoff_s"] > 0      # a real block transfer
        assert st.timings["route_s"] > 0

    def test_disaggregated_spec_decode_matches(self, tiny_model,
                                               workload, reference):
        """Acceptance: handoff is greedy-token-identical across paged +
        spec-decode configs — the resumed request's history feeds the
        n-gram proposer exactly as a locally-prefilled one's would."""
        router = ServingRouter(tiny_model, replicas=2,
                               prefill_replicas=1,
                               engine_kwargs=ENGINE_KW,
                               decode_kwargs=dict(spec_decode=3),
                               warm_on_spawn=False)
        got, _ = _run(router, workload)
        assert got == reference

    def test_timings_always_carry_route_and_handoff(self, tiny_model):
        """Satellite: route_s / handoff_s are ALWAYS present — 0.0 on
        an unrouted engine request."""
        eng = ContinuousBatchingEngine(tiny_model, **ENGINE_KW)
        rid = eng.add_request(np.arange(9, dtype=np.int32),
                              max_new_tokens=2)
        eng.run()
        t = eng.request_status(rid).timings
        assert t["route_s"] == 0.0 and t["handoff_s"] == 0.0

    def test_spill_when_affine_target_saturated(self, tiny_model,
                                                workload):
        router = ServingRouter(tiny_model, replicas=2,
                               engine_kwargs=ENGINE_KW,
                               spill_threshold=1, warm_on_spawn=False)
        from paddle_tpu.observability import default_registry
        m = default_registry().get("paddle_tpu_router_affinity_total")

        def spills():
            return {"/".join(k): c.value()
                    for k, c in m.series()}.get("spill", 0)
        before = spills()
        got, _ = _run(router, workload)
        assert spills() > before
        # spilled requests still produced 6 tokens each
        assert all(len(o) == 6 for o in got)


# ------------------------------------------------------------ handoff wire
class TestHandoffTransport:
    def test_export_import_roundtrip(self, tiny_model):
        """export → serialize → deserialize → import → re-export is
        bit-identical (the transfer is a copy, not a transform)."""
        from paddle_tpu.inference.kv_cache import PagedKVPool
        rng = np.random.default_rng(3)
        pool = PagedKVPool(2, 12, BS, 2, 16, np.float32)
        # write recognizable content through the public scatter path
        seed = {"block_size": BS,
                "k": [rng.normal(size=(3, BS, 2, 16)).astype(np.float32)
                      for _ in range(2)],
                "v": [rng.normal(size=(3, BS, 2, 16)).astype(np.float32)
                      for _ in range(2)]}
        pool.import_blocks(seed, [4, 5, 6])
        payload = pool.export_blocks([4, 5, 6])
        blob = serialize_handoff({"first_token": 7, "tokens": 24,
                                  "block_size": BS, "kv": payload})
        back = deserialize_handoff(blob)
        assert back["first_token"] == 7 and back["tokens"] == 24
        for a, b in zip(back["kv"]["k"], seed["k"]):
            np.testing.assert_array_equal(a, b)
        # import into DIFFERENT ids on a second pool, re-export, compare
        pool2 = PagedKVPool(2, 12, BS, 2, 16, np.float32)
        pool2.import_blocks(back["kv"], [1, 2, 9])
        again = pool2.export_blocks([1, 2, 9])
        for a, b in zip(again["v"], seed["v"]):
            np.testing.assert_array_equal(a, b)

    def test_partial_import_offset(self):
        from paddle_tpu.inference.kv_cache import PagedKVPool
        rng = np.random.default_rng(4)
        pool = PagedKVPool(1, 8, BS, 2, 16, np.float32)
        seed = {"block_size": BS,
                "k": [rng.normal(size=(4, BS, 2, 16)).astype(np.float32)],
                "v": [rng.normal(size=(4, BS, 2, 16)).astype(np.float32)]}
        pool.import_blocks(seed, [1, 2, 3, 4])
        # a receiver holding the first 2 blocks imports only the tail
        pool2 = PagedKVPool(1, 8, BS, 2, 16, np.float32)
        pool2.import_blocks(seed, [5, 6], src_start=2)
        got = pool2.export_blocks([5, 6])
        np.testing.assert_array_equal(got["k"][0], seed["k"][0][2:4])

    def test_geometry_mismatch_raises(self):
        from paddle_tpu.inference.kv_cache import PagedKVPool
        pool = PagedKVPool(1, 8, BS, 2, 16, np.float32)
        bad = {"block_size": 4,
               "k": [np.zeros((1, 4, 2, 16), np.float32)],
               "v": [np.zeros((1, 4, 2, 16), np.float32)]}
        with pytest.raises(ValueError, match="geometry"):
            pool.import_blocks(bad, [1])

    def test_bfloat16_survives_serialization(self):
        import jax.numpy as jnp
        a = np.asarray(jnp.arange(8, dtype=jnp.bfloat16))
        blob = serialize_handoff({"kv": {"block_size": BS, "k": [a],
                                         "v": [a]}})
        back = deserialize_handoff(blob)
        assert str(back["kv"]["k"][0].dtype) == "bfloat16"
        np.testing.assert_array_equal(back["kv"]["k"][0], a)

    def test_store_publish_fetch(self):
        from paddle_tpu.observability.fleet import LocalStore
        store = LocalStore()
        payload = {"first_token": 3,
                   "kv": {"block_size": BS,
                          "k": [np.ones((1, BS, 2, 16), np.float32)],
                          "v": [np.zeros((1, BS, 2, 16), np.float32)]}}
        publish_handoff(store, "obs/handoff/r0", payload)
        assert fetch_handoff(store, "missing") is None
        got = fetch_handoff(store, "obs/handoff/r0")
        assert got["first_token"] == 3
        np.testing.assert_array_equal(got["kv"]["k"][0],
                                      payload["kv"]["k"][0])

    def test_engine_rejects_malformed_handoff_requests(self, tiny_model):
        """The default engine takes both ends of a transfer (no engine
        argument selects it); what it refuses is a request that is both
        ends at once, or a payload cut to another block size."""
        eng = ContinuousBatchingEngine(tiny_model, slots=1, max_len=64,
                                       prefill_buckets=(16,),
                                       kv_block_size=BS)
        with pytest.raises(ValueError, match="two ends"):
            eng.add_request(np.arange(8), max_new_tokens=2,
                            prefill_only=True,
                            handoff={"block_size": BS})
        with pytest.raises(ValueError, match="block_size"):
            eng.add_request(np.arange(8), max_new_tokens=2,
                            handoff={"block_size": BS * 2})
        assert not eng.pending
        rid = eng.add_request(np.arange(1, 9), max_new_tokens=2,
                              prefill_only=True)
        eng.run()
        assert eng.request_status(rid) == "prefilled"
        eng.discard_handoff(rid)


# ------------------------------------------------------------------ chaos
class TestFleetChaos:
    def test_dispatch_fault_retries_to_completion(self, tiny_model,
                                                  workload, reference):
        robustness.inject("router.dispatch", times=2)
        try:
            router = ServingRouter(tiny_model, replicas=2,
                                   engine_kwargs=ENGINE_KW,
                                   warm_on_spawn=False)
            got, _ = _run(router, workload)
            stats = robustness.fault_stats("router.dispatch")
        finally:
            robustness.clear_faults()
        assert stats["fires"] == 2
        assert got == reference

    def test_kv_transfer_fault_falls_back_to_fresh_prefill(
            self, tiny_model, workload, reference):
        robustness.inject("router.kv_transfer", times=1)
        try:
            router = ServingRouter(tiny_model, replicas=2,
                                   prefill_replicas=1,
                                   engine_kwargs=ENGINE_KW,
                                   warm_on_spawn=False)
            got, _ = _run(router, workload)
            stats = robustness.fault_stats("router.kv_transfer")
        finally:
            robustness.clear_faults()
        assert stats["fires"] == 1
        assert got == reference
        from paddle_tpu.observability import default_registry
        m = default_registry().get("paddle_tpu_router_handoffs_total")
        kinds = {"/".join(k): c.value() for k, c in m.series()}
        assert kinds.get("fallback", 0) >= 1

    def test_replica_kill_fault_point_mid_run(self, tiny_model,
                                              workload, reference):
        """Acceptance drill: a replica dies mid-decode (chaos point);
        every in-flight request re-routes and completes with CORRECT
        output."""
        robustness.inject("serving.replica_kill", nth=5, times=1)
        try:
            router = ServingRouter(tiny_model, replicas=2,
                                   engine_kwargs=ENGINE_KW,
                                   warm_on_spawn=False)
            got, _ = _run(router, workload)
            stats = robustness.fault_stats("serving.replica_kill")
        finally:
            robustness.clear_faults()
        assert stats["fires"] == 1
        assert len(router.replicas()) == 1      # one replica is gone
        assert got == reference                 # nothing was lost

    def test_kill_replica_api_mid_decode(self, tiny_model, workload,
                                         reference):
        router = ServingRouter(tiny_model, replicas=2,
                               engine_kwargs=ENGINE_KW,
                               warm_on_spawn=False)
        rids = [router.add_request(p, max_new_tokens=6)
                for p in workload]
        for _ in range(6):                      # some decode happened
            router.step()
        victim = next(r for r, rep in router._replicas.items()
                      if rep.assigned)
        router.kill_replica(victim)
        res = router.run()
        assert [res[r][1] for r in rids] == reference

    def test_partition_probabilistic_dispatch_failures(
            self, tiny_model, workload):
        """Router partition drill: half of all dispatches fail for a
        while; everything still completes (bounded retries absorb a
        flaky network, they don't mask a dead one)."""
        robustness.fault_registry()._rng.seed(5)
        robustness.inject("router.dispatch", probability=0.5, times=4)
        try:
            router = ServingRouter(tiny_model, replicas=2,
                                   engine_kwargs=ENGINE_KW,
                                   max_dispatch_retries=10,
                                   warm_on_spawn=False)
            got, rids = _run(router, workload)
        finally:
            robustness.clear_faults()
        assert all(len(o) == 6 for o in got)

    def test_router_queue_bounded(self, tiny_model):
        router = ServingRouter(tiny_model, replicas=1,
                               engine_kwargs=ENGINE_KW, max_queue=2,
                               warm_on_spawn=False)
        router.add_request(np.arange(8), max_new_tokens=2)
        router.add_request(np.arange(8), max_new_tokens=2)
        with pytest.raises(robustness.QueueFullError):
            router.add_request(np.arange(8), max_new_tokens=2)
        router.run()


# ------------------------------------------------------------- elasticity
class TestElasticity:
    def test_autoscaler_scales_up_on_queue_pressure(self, tiny_model,
                                                    workload):
        asc = SloAutoscaler(queue_high=2, cooldown_s=0.0,
                            interval_s=0.0, max_replicas=3)
        router = ServingRouter(tiny_model, replicas=1,
                               engine_kwargs=ENGINE_KW, autoscaler=asc,
                               warm_on_spawn=False)
        rids = [router.add_request(p, max_new_tokens=4)
                for p in workload]
        assert asc.evaluate_once() == "up"
        assert len(router.replicas()) == 2
        res = router.run()
        assert all(len(res[r][1]) == 4 for r in rids)

    def test_autoscaler_attainment_breach_scales_up(self, tiny_model):
        from paddle_tpu.observability.metrics import MetricsRegistry
        reg = MetricsRegistry()
        slo = reg.counter("paddle_tpu_serving_slo_total",
                          labelnames=("kind", "result"))
        asc = SloAutoscaler(registry=reg, ttft_floor=0.9,
                            min_requests=4, cooldown_s=0.0,
                            interval_s=0.0, max_replicas=2)
        router = ServingRouter(tiny_model, replicas=1,
                               engine_kwargs=ENGINE_KW, autoscaler=asc,
                               warm_on_spawn=False)
        asc.evaluate_once(now=0.0)              # snapshot baseline
        slo.labels(kind="ttft", result="hit").inc(2)
        slo.labels(kind="ttft", result="miss").inc(6)
        assert asc.evaluate_once(now=1.0) == "up"
        assert len(router.replicas()) == 2

    def test_autoscaler_drains_when_idle_and_respects_min(
            self, tiny_model):
        asc = SloAutoscaler(cooldown_s=0.0, interval_s=0.0,
                            min_replicas=1)
        router = ServingRouter(tiny_model, replicas=2,
                               engine_kwargs=ENGINE_KW, autoscaler=asc,
                               warm_on_spawn=False)
        assert asc.evaluate_once(now=0.0) == "down"
        router.step()                           # drain completes
        assert len(router.replicas()) == 1
        assert asc.evaluate_once(now=1.0) is None   # min_replicas floor

    def test_drain_finishes_in_flight_then_releases(self, tiny_model,
                                                    workload):
        router = ServingRouter(tiny_model, replicas=2,
                               engine_kwargs=ENGINE_KW,
                               warm_on_spawn=False)
        rids = [router.add_request(p, max_new_tokens=5)
                for p in workload]
        for _ in range(3):
            router.step()
        victim = next(r for r, rep in router._replicas.items()
                      if rep.assigned)
        assert router.drain(victim)
        res = router.run()
        assert all(len(res[r][1]) == 5 for r in rids)
        assert victim not in router.replicas()  # released after drain

    def test_never_drains_last_decoder(self, tiny_model):
        router = ServingRouter(tiny_model, replicas=2,
                               prefill_replicas=1,
                               engine_kwargs=ENGINE_KW,
                               warm_on_spawn=False)
        decoder = next(r for r, role in router.replicas().items()
                       if role == "decode")
        assert not router.drain(decoder)

    def test_cooldown_spaces_actions(self, tiny_model):
        asc = SloAutoscaler(queue_high=1, cooldown_s=100.0,
                            interval_s=0.0, max_replicas=4)
        router = ServingRouter(tiny_model, replicas=1,
                               engine_kwargs=ENGINE_KW, autoscaler=asc,
                               warm_on_spawn=False)
        router.add_request(np.arange(8), max_new_tokens=2)
        router.add_request(np.arange(8), max_new_tokens=2)
        assert asc.evaluate_once(now=0.0) == "up"
        assert asc.evaluate_once(now=10.0) is None   # inside cooldown
        router.run()


# --------------------------------------------------- watchdog integration
class TestWatchdogRule:
    def _attainment_registry(self, value, kind="ttft"):
        from paddle_tpu.observability.metrics import MetricsRegistry
        reg = MetricsRegistry()
        g = reg.gauge("paddle_tpu_slo_attainment",
                      labelnames=("kind", "host"))
        g.labels(kind=kind, host="r0").set(value)
        return reg

    def test_slo_attainment_rule_breaches_below_floor(self):
        from paddle_tpu.observability.watchdog import SloAttainmentRule
        rule = SloAttainmentRule(floor=0.9)
        assert rule.evaluate(self._attainment_registry(0.5), 0)
        assert rule.evaluate(self._attainment_registry(0.95), 0) is None
        # NaN (no verdicts yet) stays silent
        assert rule.evaluate(self._attainment_registry(float("nan")),
                             0) is None

    def test_rule_constructible_from_spec(self):
        from paddle_tpu.observability.watchdog import (SloAttainmentRule,
                                                       rules_from_spec)
        rules = rules_from_spec("slo_attainment:kind=tpot,floor=0.95")
        assert isinstance(rules[0], SloAttainmentRule)
        assert rules[0].kind == "tpot" and rules[0].floor == 0.95

    def test_autoscale_rule_spawns_replica_on_breach(self, tiny_model):
        router = ServingRouter(tiny_model, replicas=1,
                               engine_kwargs=ENGINE_KW,
                               warm_on_spawn=False)
        rule = SloAutoscaleRule(router, floor=0.9, max_replicas=2,
                                scale_cooldown_s=100.0)
        reg = self._attainment_registry(0.4)
        detail = rule.evaluate(reg, now=0.0)
        assert detail and "spawned replica" in detail
        assert len(router.replicas()) == 2
        # self-cooldown: next breach alerts but does not spawn again
        detail = rule.evaluate(reg, now=1.0)
        assert detail and "spawned" not in detail


# ------------------------------------------------------------ fleet table
class TestFleetTableServingColumns:
    def test_table_renders_role_queue_slots(self):
        import time as _time
        from paddle_tpu.observability.fleet import (FLEET_SCHEMA,
                                                    FleetAggregator)
        from paddle_tpu.observability.metrics import MetricsRegistry
        agg = FleetAggregator()
        for host, role, queue, active in (("p0", "prefill", 3, 1),
                                          ("d0", "decode", 0, 2)):
            reg = MetricsRegistry()
            reg.gauge("paddle_tpu_serving_replica_role",
                      labelnames=("role",)).labels(role=role).set(1)
            reg.gauge("paddle_tpu_serving_queue_depth").set(queue)
            reg.gauge("paddle_tpu_serving_active_slots").set(active)
            reg.gauge("paddle_tpu_serving_slots").set(2)
            agg.ingest({"schema": FLEET_SCHEMA, "host": host,
                        "time": _time.time(), "seq": 1,
                        "metrics": reg.collect()})
        table = agg.table()
        assert "role" in table and "queue" in table and "slots" in table
        prow = next(ln for ln in table.splitlines()
                    if ln.startswith("p0"))
        assert "prefill" in prow and "3.00" in prow and "1/2" in prow
        drow = next(ln for ln in table.splitlines()
                    if ln.startswith("d0"))
        assert "decode" in drow and "2/2" in drow

    def test_engine_publishes_role_gauge(self, tiny_model):
        from paddle_tpu.observability import default_registry
        ContinuousBatchingEngine(tiny_model, slots=1, max_len=64,
                                 prefill_buckets=(16,), role="prefill")
        m = default_registry().get("paddle_tpu_serving_replica_role")
        roles = {k[0]: c.value() for k, c in m.series()}
        assert roles.get("prefill") == 1.0


# ---------------------------------------- multi-process worker loop (ISSUE 13)
class TestReplicaWorker:
    """`python -m paddle_tpu.inference.router --store ... --role ...`
    driveability: the worker loop's store protocol exercised in-process
    over a LocalStore (no sockets — the TCPStore path shares the exact
    serialize_handoff blobs these tests round-trip)."""

    def test_mixed_worker_round_trip(self, tiny_model, workload,
                                     reference):
        from paddle_tpu.inference.router import (ReplicaWorker,
                                                 fetch_result,
                                                 submit_request)
        from paddle_tpu.observability.fleet import LocalStore
        store = LocalStore()
        eng = ContinuousBatchingEngine(tiny_model, **ENGINE_KW)
        w = ReplicaWorker(store, eng, role="mixed", worker_id="m0")
        assert store.check("serve/worker/m0")       # announced
        seqs = [submit_request(store, "m0", p, 6) for p in workload]
        for _ in range(600):
            if all(fetch_result(store, "m0", s) is not None
                   for s in seqs):
                break
            w.poll()
        outs = [list(fetch_result(store, "m0", s)["tokens"])
                for s in seqs]
        assert outs == reference
        assert all(fetch_result(store, "m0", s)["status"] == "ok"
                   for s in seqs)
        eng.close()

    @pytest.mark.slow
    def test_prefill_decode_pipeline_over_store(self, tiny_model,
                                                workload, reference):
        """Disaggregation through the store: a prefill worker parks and
        publishes the prompt KV; a decode worker resumes from the
        fetched handoff — token-identical to the single engine."""
        from paddle_tpu.inference.router import (ReplicaWorker,
                                                 fetch_result,
                                                 submit_request)
        from paddle_tpu.observability.fleet import LocalStore
        store = LocalStore()
        pw = ReplicaWorker(
            store, ContinuousBatchingEngine(tiny_model, role="prefill",
                                            **ENGINE_KW),
            role="prefill", worker_id="p0")
        dw = ReplicaWorker(
            store, ContinuousBatchingEngine(tiny_model, role="decode",
                                            **ENGINE_KW),
            role="decode", worker_id="d0")
        prompt = workload[0]
        s1 = submit_request(store, "p0", prompt, 6)
        for _ in range(600):
            if fetch_result(store, "p0", s1) is not None:
                break
            pw.poll()
        handoff = fetch_result(store, "p0", s1)
        assert "kv" in handoff and "first_token" in handoff
        s2 = submit_request(store, "d0", prompt, 6, handoff=handoff)
        for _ in range(600):
            if fetch_result(store, "d0", s2) is not None:
                break
            dw.poll()
        assert list(fetch_result(store, "d0", s2)["tokens"]) == \
            reference[0]
        pw.engine.close(), dw.engine.close()

    def test_stop_key_exits_serve_forever(self, tiny_model):
        from paddle_tpu.inference.router import ReplicaWorker
        from paddle_tpu.observability.fleet import LocalStore
        store = LocalStore()
        eng = ContinuousBatchingEngine(tiny_model, **ENGINE_KW)
        w = ReplicaWorker(store, eng, role="mixed", worker_id="s0")
        store.set("serve/s0/stop", b"1")
        assert w.serve_forever(max_steps=50) == 0
        assert w.should_stop()
        eng.close()


# ------------------------------------- asymmetric + quantized fleets (ISSUE 13)
class TestDecodeSlots:
    def test_asymmetric_fleet_token_identical(self, tiny_model,
                                              workload, reference):
        """Decode tier sized independently of the prefill tier
        (decode holds sequences for their whole decode phase; prefill
        slots turn over per prompt) — still token-identical."""
        router = ServingRouter(
            tiny_model, replicas=2, prefill_replicas=1,
            engine_kwargs=ENGINE_KW,
            prefill_kwargs=dict(slots=1),
            decode_kwargs=dict(slots=6, steps_per_sync=2),
            warm_on_spawn=False)
        assert router._replicas["p0"].engine.slots == 1
        assert router._replicas["d1"].engine.slots == 6
        outs, _ = _run(router, workload)
        assert outs == reference
        router.close()


class TestMixedQuantFleet:
    @pytest.mark.slow
    def test_bf16_prefill_quant_decode_works(self, tiny_model,
                                             workload, reference):
        """Mixed-precision disaggregation: fp prefill replica, int8-KV
        decode replica.  The handoff quantizes at the import boundary —
        the fleet completes every request (high token agreement; exact
        identity is not promised across a precision boundary)."""
        router = ServingRouter(
            tiny_model, replicas=2, prefill_replicas=1,
            engine_kwargs=ENGINE_KW,
            decode_kwargs=dict(quant_kv="int8"),
            warm_on_spawn=False)
        outs, rids = _run(router, workload)
        assert all(len(o) == 6 for o in outs)
        assert all(str(router.request_status(r)) == "ok" for r in rids)
        matched = sum(sum(1 for a, b in zip(o, ref) if a == b)
                      for o, ref in zip(outs, reference))
        total = sum(len(r) for r in reference)
        # deterministic 31/36 on the tiny random model: the int8 KV
        # boundary flips a few near-tie argmaxes — the floor guards
        # against collapse, the bench parity gate holds the hard bar
        assert matched / total >= 0.8, (matched, total)
        router.close()

    @pytest.mark.slow
    def test_quant_prefill_bf16_decode_works(self, tiny_model,
                                             workload, reference):
        """The reverse boundary: int8-KV prefill exports a quantized
        payload; the fp decode replica dequantizes via the shipped
        scales on import."""
        router = ServingRouter(
            tiny_model, replicas=2, prefill_replicas=1,
            engine_kwargs=ENGINE_KW,
            prefill_kwargs=dict(quant_kv="int8"),
            warm_on_spawn=False)
        outs, rids = _run(router, workload)
        assert all(len(o) == 6 for o in outs)
        assert all(str(router.request_status(r)) == "ok" for r in rids)
        router.close()

    @pytest.mark.slow
    def test_fully_quant_fleet_handoff_stays_int8(self, tiny_model,
                                                  workload):
        """Homogeneous quantized fleet: the wire payload itself is int8
        + scales (half the bytes of the fp payload at these shapes)."""
        from paddle_tpu.observability import default_registry
        before = 0
        m = default_registry().get("paddle_tpu_router_handoff_bytes_total")
        if m is not None:
            before = m.value()
        kw = dict(ENGINE_KW)
        kw["quant_kv"] = "int8"
        router = ServingRouter(
            tiny_model, replicas=2, prefill_replicas=1,
            engine_kwargs=kw, warm_on_spawn=False)
        outs, rids = _run(router, workload)
        assert all(len(o) == 6 for o in outs)
        m = default_registry().get("paddle_tpu_router_handoff_bytes_total")
        assert m is not None and m.value() > before
        router.close()
