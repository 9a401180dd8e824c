"""Request forensics (ISSUE 20): scheduler decision provenance, the
per-request cause attribution (``explain``), tail aggregation, store
federation, the ``tail_regression`` watchdog rule, and the CLI.

Pure-function and LocalStore-federation tests run in tier-1; the
engine/router chaos drills that must name the injected cause as
dominant are ``@slow`` and run unfiltered in CI's request-forensics
gate."""

import json

import numpy as np
import pytest

import paddle_tpu as pp
from paddle_tpu.observability import forensics
from paddle_tpu.observability.fleet import (FleetAggregator, LocalStore,
                                            MetricsPublisher)
from paddle_tpu.observability.forensics import (CAUSES, DECISION_KINDS,
                                                MAX_ALTERNATIVES,
                                                attribute,
                                                collect_decisions,
                                                decision_events,
                                                decisions_to_chrome,
                                                dominant_cause,
                                                emit_decision, explain,
                                                extract_decisions,
                                                inject_decisions,
                                                observe_retirement,
                                                summarize_attributions,
                                                tail_report)
from paddle_tpu.observability.metrics import MetricsRegistry
from paddle_tpu.observability.recorder import flight_recorder
from paddle_tpu.observability.watchdog import (TailRegressionRule,
                                               rules_from_spec)
from paddle_tpu.robustness import clear_faults, inject


@pytest.fixture(autouse=True)
def _clean_ring_and_faults():
    flight_recorder().clear()
    clear_faults()
    yield
    flight_recorder().clear()
    clear_faults()


def _ev(kind, t, seq, **fields):
    """A hand-built recorder-event dict, as dumps/federation carry."""
    return {"kind": f"decision.{kind}", "time": t, "seq": seq, **fields}


def _retire(rid, t, seq, timings, status="completed", **fields):
    return _ev("retire", t, seq, rid=rid, chosen=status, status=status,
               source="router", timings=timings, **fields)


# ----------------------------------------------------------- timings canon
class TestTimingsSchema:
    def test_request_timings_always_complete(self):
        """Every TIMING_KEYS key is present on a freshly-enqueued
        request — phases never reached read 0.0, so attribution and
        bench folds need no feature detection (and no downstream
        setdefault patches)."""
        from paddle_tpu.inference.serving import (TIMING_KEYS, _Request,
                                                  _request_timings)
        req = _Request(rid=0, prompt=np.arange(4, dtype=np.int32),
                       max_new_tokens=2)
        t = _request_timings(req)
        assert set(t) == set(TIMING_KEYS)
        assert t["queue_s"] == 0.0 and t["resume_s"] == 0.0
        assert t["route_s"] == 0.0 and t["handoff_s"] == 0.0

    def test_attribute_accepts_bare_schema(self):
        from paddle_tpu.inference.serving import (_Request,
                                                  _request_timings)
        req = _Request(rid=0, prompt=np.arange(4, dtype=np.int32),
                       max_new_tokens=2)
        causes = attribute(_request_timings(req))
        assert set(causes) == set(CAUSES)
        assert dominant_cause(causes) == "none"


# ------------------------------------------------------------------- emit
class TestEmit:
    def test_alternatives_bounded_with_overflow_count(self):
        alts = [{"replica": f"r{i}", "load": i} for i in range(12)]
        emit_decision("route", rid=1, chosen={"replica": "r0"},
                      alternatives=alts, policy="least_loaded")
        [dec] = decision_events()
        assert dec.kind == "route" and dec.rid == 1
        assert len(dec.alternatives) == MAX_ALTERNATIVES
        assert dec.fields["alternatives_dropped"] == 4
        assert dec.fields["policy"] == "least_loaded"

    def test_knob_off_is_a_noop(self, monkeypatch):
        monkeypatch.setenv("PADDLE_TPU_FORENSICS", "0")
        emit_decision("route", rid=1, chosen="r0")
        assert decision_events() == []
        # the overage counter is not even created when off
        reg = MetricsRegistry()
        observe_retirement({"ttft_s": 3.0, "queue_s": 2.0},
                           targets={"ttft": 0.5, "tpot": 0.0},
                           registry=reg)
        assert reg.get("paddle_tpu_slo_overage_seconds_total") is None

    def test_every_kind_round_trips(self):
        for i, kind in enumerate(DECISION_KINDS):
            emit_decision(kind, rid=i, chosen="x")
        decs = decision_events()
        assert [d.kind for d in decs] == list(DECISION_KINDS)
        # rid filter is string-tolerant (JSON round-trips int rids)
        assert decision_events(rid="3")[0].kind == DECISION_KINDS[3]


# -------------------------------------------------------------- attribute
class TestAttribute:
    def test_route_share_is_route_minus_queue(self):
        causes = attribute({"queue_s": 2.0, "route_s": 2.5})
        assert causes["queue_wait"] == pytest.approx(2.0)
        assert causes["route"] == pytest.approx(0.5)
        assert dominant_cause(causes) == "queue_wait"

    def test_resume_path_heuristic(self):
        promote = attribute({"resume_s": 0.4, "handoff_s": 0.1})
        assert promote["cold_resume.promote"] == pytest.approx(0.4)
        recompute = attribute({"resume_s": 0.4})
        assert recompute["cold_resume.recompute"] == pytest.approx(0.4)
        assert dominant_cause(recompute) == "cold_resume.recompute"

    def test_resume_decision_event_wins_over_heuristic(self):
        evs = decision_events([_ev("resume", 1.0, 1, rid=0,
                                   chosen="recompute",
                                   path="recompute")])
        causes = attribute({"resume_s": 0.4, "handoff_s": 0.1}, evs)
        assert causes["cold_resume.recompute"] == pytest.approx(0.4)
        assert causes["cold_resume.promote"] == 0.0

    def test_requeue_folds_final_life_queue_and_route(self):
        """A retried request's final-life queue wait and router
        overhead exist only because of the requeue: they fold into the
        requeue cause instead of double-counting as queue/route."""
        evs = decision_events([_ev("requeue", 1.0, 1, rid=0,
                                   chosen="recompute",
                                   reason="replica_death",
                                   wasted_s=2.0)])
        causes = attribute({"queue_s": 1.0, "route_s": 3.5,
                            "attempts": 2.0}, evs)
        assert causes["requeue"] == pytest.approx(3.5)   # 1.0 + 2.5
        assert causes["queue_wait"] == 0.0
        assert causes["route"] == 0.0
        assert dominant_cause(causes) == "requeue"

    def test_requeue_wasted_can_exceed_route_window(self):
        evs = decision_events([_ev("requeue", 1.0, 1, rid=0,
                                   wasted_s=4.0)])
        causes = attribute({"queue_s": 1.0, "route_s": 3.5}, evs)
        assert causes["requeue"] == pytest.approx(5.0)   # 1.0 + 4.0

    def test_requeue_from_attempts_alone(self):
        # bench path: timings only, no events — attempts > 1 is enough
        causes = attribute({"queue_s": 0.5, "route_s": 2.0,
                            "attempts": 2.0})
        assert causes["requeue"] == pytest.approx(2.0)
        assert dominant_cause(causes) == "requeue"

    def test_all_productive_time_is_dominant_none(self):
        causes = attribute({"prefill_s": 1.0, "decode_s": 2.0})
        assert dominant_cause(causes) == "none"

    def test_summarize_shape_and_cold_share(self):
        rep = summarize_attributions([
            attribute({"queue_s": 3.0, "prefill_s": 1.0}),
            attribute({"resume_s": 1.0, "decode_s": 1.0}),
        ])
        assert rep["requests"] == 2
        assert rep["dominant_cause"] == "queue_wait"
        assert set(rep["causes"]) == set(CAUSES)
        assert rep["cold_resume_share"] == pytest.approx(
            rep["causes"]["cold_resume.recompute"]["share"])
        total_share = sum(v["share"] for v in rep["causes"].values())
        assert total_share == pytest.approx(1.0, abs=1e-4)


# ---------------------------------------------------------------- explain
_TIMINGS = {"queue_s": 2.0, "route_s": 2.5, "ttft_s": 3.0,
            "prefill_s": 0.4, "decode_s": 0.6, "total_s": 3.6,
            "generated": 4.0}


class TestExplain:
    def test_explain_joins_events_and_retire_timings(self):
        evs = [_ev("route", 1.0, 1, rid=7, chosen={"replica": "r0"},
                   alternatives=[{"replica": "r1", "load": 3}]),
               _ev("admit", 2.0, 2, rid=7, chosen="slot", slot=0),
               _retire(7, 3.0, 3, _TIMINGS)]
        exp = explain(7, events=evs, targets={"ttft": 0.5, "tpot": 0.0})
        assert exp is not None
        assert exp.status == "completed"
        assert exp.dominant_cause == "queue_wait"
        assert exp.overage["ttft"] == pytest.approx(2.5)
        table = exp.table()
        assert "dominant cause: queue_wait" in table
        assert "decisions:" in table and "route" in table

    def test_explain_unknown_rid_is_none(self):
        assert explain("nope", events=[]) is None

    def test_router_retire_beats_engine_local(self):
        engine = dict(_TIMINGS, queue_s=9.0)
        evs = [_ev("retire", 1.0, 1, rid=7, chosen="completed",
                   status="completed", source="engine", routed=True,
                   timings=engine),
               _retire(7, 2.0, 2, _TIMINGS)]
        exp = explain(7, events=evs, targets={"ttft": 0.0, "tpot": 0.0})
        assert exp.timings["queue_s"] == 2.0


# ------------------------------------------------------------ tail report
class TestTailReport:
    def test_window_skips_routed_engine_retires(self):
        evs = [
            # engine-local retire of a ROUTED request: must not count
            _ev("retire", 1.0, 1, rid=7, chosen="completed",
                status="completed", source="engine", routed=True,
                timings=dict(_TIMINGS, queue_s=99.0)),
            _retire(7, 2.0, 2, _TIMINGS),
            _retire(8, 3.0, 3, {"queue_s": 0.1, "prefill_s": 1.0,
                                "decode_s": 1.0, "total_s": 2.2,
                                "ttft_s": 1.2, "generated": 3.0}),
        ]
        rep = tail_report(10, events=evs,
                          targets={"ttft": 0.5, "tpot": 0.0})
        assert rep["window"] == 2 and rep["requests"] == 2
        assert rep["dominant_cause"] == "queue_wait"
        assert rep["overage_s"]["ttft"] == pytest.approx(2.5 + 0.7)
        assert rep["p99_total_s"] == pytest.approx(3.6)
        text = forensics.render_tail_report(rep)
        assert "dominant cause: queue_wait" in text

    def test_observe_retirement_feeds_overage_counter(self):
        reg = MetricsRegistry()
        over = observe_retirement(_TIMINGS,
                                  targets={"ttft": 0.5, "tpot": 0.1},
                                  registry=reg)
        assert over["ttft"] == pytest.approx(2.5)
        m = reg.get("paddle_tpu_slo_overage_seconds_total")
        by = {labels: child.value() for labels, child in m.series()}
        # TTFT overage split across overhead causes proportionally:
        # queue_wait 2.0 / route 0.5 of 2.5 overhead
        assert by[("ttft", "queue_wait")] == pytest.approx(2.0)
        assert by[("ttft", "route")] == pytest.approx(0.5)
        # TPOT overage lands on decode: 0.6/3 - 0.1 per token * 3
        assert by[("tpot", "decode")] == pytest.approx(0.3)

    def test_tail_regression_rule_names_dominant_cause(self):
        reg = MetricsRegistry()
        ctr = reg.counter("paddle_tpu_slo_overage_seconds_total",
                          labelnames=("kind", "cause"))
        rule = TailRegressionRule(min_overage_s=0.1, growth=2.0)
        assert rule.evaluate(reg, 0.0) is None          # snapshot
        ctr.labels(kind="ttft", cause="route").inc(0.05)
        assert rule.evaluate(reg, 1.0) is None          # baseline
        ctr.labels(kind="ttft", cause="queue_wait").inc(1.0)
        ctr.labels(kind="ttft", cause="route").inc(0.1)
        detail = rule.evaluate(reg, 2.0)
        assert detail is not None
        assert "dominant cause: queue_wait" in detail
        assert "flipped from route" in detail

    def test_rule_registered_in_spec_parser(self):
        [rule] = rules_from_spec("tail_regression:min_overage_s=0.2")
        assert isinstance(rule, TailRegressionRule)
        assert rule.min_overage_s == pytest.approx(0.2)


# ------------------------------------------------- federation (two hosts)
class TestFederation:
    def test_two_hosts_merge_and_aggregator_side_explain(self):
        """Satellite: two synthetic hosts publish decision windows over
        one LocalStore; the aggregator-side explain() joins a request
        whose route decision and retirement live on DIFFERENT hosts."""
        store = LocalStore()
        h0 = [_ev("route", 1.0, 1, rid=7, chosen={"replica": "r1"},
                  alternatives=[{"replica": "r0", "load": 5}])]
        h1 = [_ev("admit", 1.5, 1, rid=7, chosen="slot", slot=0),
              _retire(7, 2.0, 2, _TIMINGS)]
        assert inject_decisions(store, "obs/forensics/h0", host="h0",
                                events=h0) == 1
        assert inject_decisions(store, "obs/forensics/h1", host="h1",
                                events=h1) == 2
        store.set("obs/hosts", b"h0,h1")
        merged = collect_decisions(store)
        assert [e["host"] for e in merged] == ["h0", "h1", "h1"]
        exp = explain(7, events=merged,
                      targets={"ttft": 0.5, "tpot": 0.0})
        assert exp.dominant_cause == "queue_wait"
        assert {d.host for d in exp.events} == {"h0", "h1"}

    def test_publisher_to_aggregator_roundtrip(self):
        emit_decision("route", rid=3, chosen={"replica": "r0"})
        emit_decision("retire", rid=3, chosen="completed",
                      status="completed", source="router",
                      timings=_TIMINGS)
        store = LocalStore()
        pub = MetricsPublisher(store, registry=MetricsRegistry(),
                               host="solo", interval=999,
                               publish_goodput=False)
        pub.publish_once()
        agg = FleetAggregator(store=store)
        assert agg.poll() == ["solo"]
        evs = agg.decision_events()
        assert len(evs) == 2 and all(e["host"] == "solo" for e in evs)
        exp = agg.explain(3)
        assert exp is not None and exp.dominant_cause == "queue_wait"

    def test_publish_decisions_knob_off_writes_nothing(self):
        emit_decision("route", rid=3, chosen="r0")
        store = LocalStore()
        pub = MetricsPublisher(store, registry=MetricsRegistry(),
                               host="solo", interval=999,
                               publish_goodput=False,
                               publish_decisions=False)
        pub.publish_once()
        assert not [k for k in store._kv if "forensics" in k]

    def test_extract_is_tolerant(self):
        store = LocalStore()
        assert extract_decisions(store, "obs/forensics/gone") is None
        store.set("bad", b"not json at all")
        assert extract_decisions(store, "bad") is None
        store.set("old", json.dumps({"schema": 99,
                                     "events": []}).encode())
        assert extract_decisions(store, "old") is None
        store.set("mangled", json.dumps({"schema": 1,
                                         "events": "?"}).encode())
        assert extract_decisions(store, "mangled") is None


# ---------------------------------------------------------------- perfetto
class TestChromeExport:
    def test_instants_and_flow_chain_per_rid(self):
        evs = [_ev("route", 1.0, 1, rid=5, chosen={"replica": "r0"}),
               _ev("handoff", 2.0, 2, rid=5, chosen="ok"),
               _retire(5, 3.0, 3, _TIMINGS)]
        out = decisions_to_chrome(evs, pid=2)
        inst = [e for e in out if e["ph"] == "i"]
        assert len(inst) == 3
        assert all(e["cat"] == "forensics" and e["pid"] == 2
                   for e in inst)
        # retire timings stay out of args (they are bulky and live in
        # the tail report, not the timeline)
        assert all("timings" not in e["args"] for e in inst)
        flow = [e for e in out if e["ph"] in ("s", "t", "f")]
        assert [e["ph"] for e in flow] == ["s", "t", "f"]
        assert {e["id"] for e in flow} == {"forensics-5"}
        assert flow[-1]["bp"] == "e"

    def test_aggregator_export_includes_decisions(self, tmp_path):
        emit_decision("route", rid=3, chosen={"replica": "r0"})
        emit_decision("retire", rid=3, chosen="completed",
                      status="completed", source="router",
                      timings=_TIMINGS)
        store = LocalStore()
        MetricsPublisher(store, registry=MetricsRegistry(), host="solo",
                         interval=999,
                         publish_goodput=False).publish_once()
        agg = FleetAggregator(store=store)
        agg.poll()
        doc = agg.export_chrome(str(tmp_path / "trace.json"))
        names = [e.get("name") for e in doc["traceEvents"]]
        assert "decision.route" in names and "decision.retire" in names


# --------------------------------------------------------------------- CLI
class TestCli:
    def _events_file(self, tmp_path):
        evs = [_ev("route", 1.0, 1, rid=7, chosen={"replica": "r0"}),
               _retire(7, 2.0, 2, _TIMINGS)]
        path = tmp_path / "events.json"
        path.write_text(json.dumps(evs))
        return str(path)

    def test_explain_renders_dominant_cause(self, tmp_path, capsys):
        rc = forensics.main(["--events", self._events_file(tmp_path),
                             "--explain", "7"])
        out = capsys.readouterr().out
        assert rc == 0
        assert "dominant cause: queue_wait" in out
        assert "decisions:" in out

    def test_tail_renders_report(self, tmp_path, capsys):
        rc = forensics.main(["--events", self._events_file(tmp_path),
                             "--tail", "5"])
        out = capsys.readouterr().out
        assert rc == 0 and "tail report over 1 retirements" in out

    def test_unknown_rid_exits_2(self, tmp_path, capsys):
        path = tmp_path / "empty.json"
        path.write_text("[]")
        rc = forensics.main(["--events", str(path), "--explain", "9"])
        assert rc == 2

    def test_reads_flight_recorder_dump(self, tmp_path, capsys):
        """The CI drill path: the engine dumps its ring as JSONL (with
        a header line) and the CLI explains straight from the file."""
        emit_decision("admit", rid=4, chosen="slot", slot=1)
        emit_decision("retire", rid=4, chosen="completed",
                      status="completed", source="router",
                      timings=_TIMINGS)
        dump = tmp_path / "ring.jsonl"
        flight_recorder().dump(file=str(dump), reason="forensics-test")
        rc = forensics.main(["--events", str(dump), "--explain", "4"])
        out = capsys.readouterr().out
        assert rc == 0 and "dominant cause: queue_wait" in out


# ------------------------------------------------------- bench comparison
class TestBenchCompare:
    @staticmethod
    def _record(dom, cold):
        return {"value": 100.0,
                "detail": {"tail_attribution": {
                    "requests": 4, "dominant_cause": dom,
                    "cold_resume_share": cold, "causes": {}}}}

    def test_dominant_cause_flip_is_a_regression(self):
        import bench
        prev = self._record("queue_wait", 0.0)
        assert bench.compare_serve_records(
            self._record("queue_wait", 0.0), prev) == []
        regs = bench.compare_serve_records(
            self._record("requeue", 0.0), prev)
        assert any("dominant_cause flipped" in r for r in regs)
        # flipping TO "none" (overhead vanished) is an improvement
        assert bench.compare_serve_records(
            self._record("none", 0.0), prev) == []

    def test_cold_resume_share_growth_is_a_regression(self):
        import bench
        prev = self._record("queue_wait", 0.1)
        regs = bench.compare_serve_records(
            self._record("queue_wait", 0.5), prev, tolerance=0.25)
        assert any("cold_resume_share" in r for r in regs)
        assert bench.compare_serve_records(
            self._record("queue_wait", 0.3), prev, tolerance=0.25) == []

    def test_guarded_when_either_side_lacks_the_section(self):
        import bench
        prev = self._record("queue_wait", 0.0)
        cur = {"value": 100.0, "detail": {}}
        assert not any("tail_attribution" in r for r in
                       bench.compare_serve_records(cur, prev))


# ---------------------------------------------------------------------
# engine / router chaos drills (real prefill; slow — the CI forensics
# gate runs them unfiltered): each injected failure must surface as the
# MATCHING dominant cause in explain()
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
    for _ in range(400):
        eng.step()
        slot = next((i for i, r in enumerate(eng._active)
                     if r is not None and r.rid == rid), None)
        if slot is not None and slot not in eng._prefilling \
                and len(eng._active[slot].out) >= n:
            return
    raise AssertionError("request never reached decode")


class _SpyStore(LocalStore):
    def __init__(self):
        super().__init__()
        self.set_keys = []

    def set(self, key, value):
        self.set_keys.append(key)
        return super().set(key, value)


@pytest.mark.slow
class TestForensicsDrills:
    def test_kv_alloc_exhaustion_names_queue_wait(self, tiny_model):
        import time
        from paddle_tpu.inference.kv_tier import KVTierManager
        eng = _build(tiny_model, tier=KVTierManager())
        rid = eng.add_request(np.arange(1, 17, dtype=np.int32),
                              max_new_tokens=4)
        inject("serving.kv_alloc", times=3)
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < 0.35:
            eng.step()
        clear_faults()
        eng.run()
        exp = forensics.explain(rid, status=eng.request_status(rid))
        assert exp.dominant_cause == "queue_wait", exp.causes
        deferred = decision_events(rid=rid, kind="admit")
        assert any(d.chosen == "defer" and
                   d.fields.get("reason") == "kv_alloc_exhausted"
                   for d in deferred)
        eng.close()

    def test_tier_fetch_miss_names_cold_resume_recompute(self,
                                                         tiny_model):
        from paddle_tpu.inference.kv_tier import KVTierManager
        eng = _build(tiny_model,
                     tier=KVTierManager(store=LocalStore()))
        rid = eng.add_request(np.arange(1, 17, dtype=np.int32),
                              max_new_tokens=8)
        _step_until_out(eng, rid, 3)
        assert eng.park(rid) is not False
        inject("kv_tier.fetch", times=1)
        assert eng.resume(rid) is not False
        clear_faults()
        eng.run()
        exp = forensics.explain(rid, status=eng.request_status(rid))
        assert exp.dominant_cause == "cold_resume.recompute", exp.causes
        paths = [d.fields.get("path")
                 for d in decision_events(rid=rid, kind="resume")]
        assert "recompute" in paths
        eng.close()

    def test_replica_death_names_requeue(self, tiny_model):
        from paddle_tpu.inference.kv_tier import KVTierManager
        from paddle_tpu.inference.router import ServingRouter
        prompts = [np.arange(1 + i, 17 + i, dtype=np.int32)
                   for i in range(3)]
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
                if any(r is not None and i not in rep.engine._prefilling
                       and len(r.out) >= 2
                       for i, r in enumerate(rep.engine._active)):
                    victim = rep.id
                    break
            if victim is not None:
                break
        assert victim is not None, "no replica reached decode"
        rt.kill_replica(victim)
        rt.run()
        doms = {rid: forensics.explain(
            rid, status=rt.request_status(rid)).dominant_cause
            for rid in rids}
        assert "requeue" in doms.values(), doms
        # death recovery emits a requeue decision either way: a
        # migrated session says so, a recomputed one blames the death
        reasons = {d.fields.get("reason")
                   for d in decision_events(kind="requeue")}
        assert reasons & {"replica_death", "session_migrate"}, reasons

    def test_observation_only_token_identity_and_zero_wire(
            self, tiny_model, monkeypatch):
        """Forensics on vs. off decodes identical tokens, and with no
        publisher attached nothing forensics-shaped touches the store
        — emission is ring-only."""
        from paddle_tpu.inference.kv_tier import KVTierManager
        prompts = [np.arange(1 + i, 13 + i, dtype=np.int32)
                   for i in range(2)]

        def run_once():
            spy = _SpyStore()
            eng = _build(tiny_model, tier=KVTierManager(store=spy))
            rids = [eng.add_request(p, max_new_tokens=6)
                    for p in prompts]
            res = eng.run()
            outs = [res[r][1] for r in rids]
            eng.close()
            return outs, spy

        pp.seed(0)
        outs_on, spy_on = run_once()
        assert len(decision_events(kind="retire")) >= 2
        assert not [k for k in spy_on.set_keys if "forensics" in k]

        flight_recorder().clear()
        monkeypatch.setenv("PADDLE_TPU_FORENSICS", "0")
        pp.seed(0)
        outs_off, _ = run_once()
        assert decision_events() == []       # knob-off: ring untouched
        assert outs_on == outs_off           # tokens untouched either way
