"""Distributed tracing + SLO watchdog (ISSUE 5): span tree semantics,
head-based sampling, explicit context propagation across threads
(device_prefetch, dataloader, serving engine loop) and across a
simulated 2-worker TCPStore handoff, flight-recorder trace stamping +
snapshot, request_status timing fields, Perfetto export shape, watchdog
rule triggers over synthetic metric streams, and the Prometheus
cumulative-bucket exposition PromQL relies on."""

import json
import threading

import numpy as np
import pytest

import paddle_tpu as pp
from paddle_tpu.observability import (MetricsRegistry, FlightRecorder,
                                      Tracer, Watchdog, flight_recorder,
                                      render_prometheus, tracer)
from paddle_tpu.observability.tracing import SpanContext
from paddle_tpu.observability.watchdog import (HeartbeatGapRule,
                                               QueueSaturationRule,
                                               RecompileStormRule,
                                               SkipStreakRule,
                                               StepTimeDriftRule,
                                               rules_from_spec)


@pytest.fixture()
def tr():
    """The process tracer (the one instrumentation writes to), cleared
    around each test so span assertions see only their own work."""
    t = tracer()
    t.clear()
    yield t
    t.clear()


# ------------------------------------------------------------ span basics
class TestSpanTree:
    def test_nesting_assigns_parent_and_shared_trace(self, tr):
        with tr.span("root", kind="outer") as root:
            with tr.span("child") as child:
                with tr.span("grandchild") as grand:
                    pass
        spans = {s["name"]: s for s in tr.finished_spans()}
        assert spans["child"]["parent_id"] == root.span_id
        assert spans["grandchild"]["parent_id"] == child.span_id
        assert len({s["trace_id"] for s in spans.values()}) == 1
        assert spans["root"]["attrs"]["kind"] == "outer"
        assert grand.trace_id == root.trace_id

    def test_sibling_traces_are_distinct(self, tr):
        with tr.span("a"):
            pass
        with tr.span("b"):
            pass
        a, b = tr.finished_spans()
        assert a["trace_id"] != b["trace_id"]

    def test_escaping_exception_stamped_as_error_attr(self, tr):
        with pytest.raises(ValueError):
            with tr.span("doomed"):
                raise ValueError("nope")
        (s,) = tr.finished_spans(name="doomed")
        assert s["attrs"]["error"] == "ValueError"

    def test_manual_span_lifetime_and_end_idempotent(self, tr):
        s = tr.start_span("manual", rid=7)
        s.end()
        t1 = s.t1
        s.end()                       # second end must not re-record
        assert s.t1 == t1
        assert len(tr.finished_spans(name="manual")) == 1

    def test_add_span_retroactive_endpoints(self, tr):
        parent = tr.start_span("p")
        tr.add_span("retro", 10.0, 12.5, parent=parent)
        parent.end()
        (s,) = tr.finished_spans(name="retro")
        assert (s["t0"], s["t1"]) == (10.0, 12.5)
        assert s["parent_id"] == parent.span_id

    def test_sampling_zero_disables_and_noops(self):
        t = Tracer(sample=0.0)
        assert not t.enabled
        with t.span("x") as s:
            s.set_attribute("a", 1)   # must not raise
        assert s.context is None
        assert t.finished_spans() == []

    def test_unsampled_root_children_inherit_decision(self):
        t = Tracer(sample=1e-12)      # root draw virtually never samples
        with t.span("root") as root:
            with t.span("child"):
                pass
        assert root.sampled is False
        assert t.finished_spans() == []

    def test_context_header_round_trip(self):
        ctx = SpanContext("ab" * 8, "cd" * 8, True)
        assert SpanContext.from_header(ctx.to_header()) == ctx
        off = SpanContext("ab" * 8, "cd" * 8, False)
        assert SpanContext.from_header(off.to_header()).sampled is False

    def test_ring_is_bounded(self):
        t = Tracer(capacity=8)
        for i in range(50):
            with t.span(f"s{i}"):
                pass
        assert len(t.finished_spans()) == 8

    def test_slowest_traces_ranked_by_root_duration(self, tr):
        fast = tr.start_span("fast")
        fast.t0 = 0.0
        fast.end(end_time=0.1)
        slow = tr.start_span("slow")
        slow.t0 = 0.0
        tr.add_span("slow.child", 0.0, 4.0, parent=slow)
        slow.end(end_time=5.0)
        traces = tr.slowest_traces(1)
        assert traces[0]["root"] == "slow"
        assert traces[0]["seconds"] == pytest.approx(5.0)
        assert {s["name"] for s in traces[0]["spans"]} == \
            {"slow", "slow.child"}


# -------------------------------------------------- recorder integration
class TestRecorderStamping:
    def test_events_under_span_carry_trace_ids(self, tr):
        fr = flight_recorder()
        with tr.span("work") as s:
            fr.record("inner_tick", i=1)
        fr.record("outer_tick", i=2)
        inner = [e for e in fr.snapshot() if e["kind"] == "inner_tick"][-1]
        outer = [e for e in fr.snapshot() if e["kind"] == "outer_tick"][-1]
        assert inner["trace_id"] == s.trace_id
        assert inner["span_id"] == s.span_id
        assert "trace_id" not in outer

    def test_snapshot_does_not_clear(self):
        fr = FlightRecorder(capacity=8)
        for i in range(5):
            fr.record("tick", i=i)
        assert [e["i"] for e in fr.snapshot(2)] == [3, 4]
        assert len(fr) == 5                 # ring untouched
        assert [e["i"] for e in fr.snapshot()] == list(range(5))


# ------------------------------------------------- cross-thread propagation
class TestThreadPropagation:
    def test_device_prefetch_worker_joins_callers_trace(self, tr):
        from paddle_tpu.io import device_prefetch
        with tr.span("train.loop") as outer:
            batches = list(device_prefetch(
                ({"x": np.ones((2, 2), np.float32)} for _ in range(3)),
                depth=1))
        assert len(batches) == 3
        places = tr.finished_spans(name="prefetch.place")
        assert len(places) == 3
        assert all(p["trace_id"] == outer.trace_id for p in places)
        assert all(p["thread"] != outer.thread for p in places)

    def test_dataloader_prefetch_thread_joins_callers_trace(self, tr):
        from paddle_tpu.io.dataloader import DataLoader

        class _DS:
            def __len__(self):
                return 8

            def __getitem__(self, i):
                return np.full((2,), i, np.float32)

        with tr.span("epoch") as outer:
            dl = DataLoader(_DS(), batch_size=4, num_workers=0)
            batches = [b for b in dl]
        assert len(batches) == 2
        spans = tr.finished_spans(name="dataloader.batch")
        assert spans and all(s["trace_id"] == outer.trace_id
                             for s in spans)

    def test_attach_explicit_context_on_plain_thread(self, tr):
        with tr.span("submitter") as outer:
            ctx = tr.current_context()
        seen = {}

        def work():
            with tr.attach(ctx):
                with tr.span("worker.task") as s:
                    seen["trace"] = s.trace_id
        th = threading.Thread(target=work)
        th.start()
        th.join()
        assert seen["trace"] == outer.trace_id
        (s,) = tr.finished_spans(name="worker.task")
        assert s["parent_id"] == outer.span_id


# ------------------------------------------------ serving engine tracing
@pytest.fixture(scope="module")
def tiny_model():
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
    pp.seed(0)
    cfg = LlamaConfig.tiny(vocab_size=128, hidden_size=32,
                           intermediate_size=64, num_hidden_layers=2,
                           num_attention_heads=2, num_key_value_heads=2,
                           max_position_embeddings=128)
    return LlamaForCausalLM(cfg)


class TestServingTracing:
    def test_request_lifecycle_spans_across_engine_thread(self, tr,
                                                          tiny_model):
        """Requests enqueued on the main thread, engine loop driven on a
        DIFFERENT thread: the request's root span must still own the
        prefill/decode children (context rides the request object)."""
        from paddle_tpu.inference.serving import ContinuousBatchingEngine
        eng = ContinuousBatchingEngine(tiny_model, slots=2, max_len=64,
                                       prefill_buckets=(16,))
        rng = np.random.default_rng(0)
        rids = [eng.add_request(rng.integers(0, 128, (5,)),
                                max_new_tokens=3) for _ in range(2)]
        th = threading.Thread(target=eng.run)
        th.start()
        th.join(timeout=120)
        assert not th.is_alive()
        requests = tr.finished_spans(name="serving.request")
        assert len(requests) == 2
        by_trace = {r["trace_id"]: r for r in requests}
        prefills = tr.finished_spans(name="serving.prefill")
        assert len(prefills) == 2
        for child in prefills:
            root = by_trace[child["trace_id"]]
            assert child["parent_id"] == root["span_id"]
        for r in requests:
            assert r["attrs"]["status"] == "ok"
            assert r["attrs"]["generated"] == 3
            # decode leaves no span per request: the root carries one
            # (stamp, tokens) per emission instead
            stamps = r["attrs"]["token_stamps"]
            assert sum(n for _, n in stamps) == 3 and len(stamps) > 1
        # retirement events are stamped with the request trace ids
        retires = [e for e in flight_recorder().snapshot()
                   if e["kind"] == "serving.retire"
                   and e.get("trace_id") in by_trace]
        assert len(retires) >= 2
        # satellite: retired statuses self-describe their lifecycle
        for rid in rids:
            st = eng.request_status(rid)
            assert st == "ok"
            t = st.timings
            assert 0 < t["queue_s"] <= t["ttft_s"] <= t["total_s"]
            assert t["admitted"] <= t["first_token"] <= t["retired"]
            assert st.trace_id in by_trace

    def test_timeout_status_keeps_partial_timings(self, tr, tiny_model):
        from paddle_tpu.inference.serving import ContinuousBatchingEngine
        eng = ContinuousBatchingEngine(tiny_model, slots=1, max_len=64,
                                       prefill_buckets=(16,))
        rid = eng.add_request(np.arange(5), max_new_tokens=3,
                              timeout_s=-1.0)   # already expired
        eng.run()
        st = eng.request_status(rid)
        assert st == "timeout"
        assert st.timings["enqueued"] > 0
        assert st.timings["admitted"] == 0.0    # never reached a slot
        # canonical schema: every TIMING_KEYS key is present; a phase
        # never reached reads 0.0 (ISSUE 20 timings hardening)
        assert st.timings["queue_s"] == 0.0


# ------------------------------------------------ train step span tree
class TestTrainStepTracing:
    def test_step_children_and_accum_nesting(self, tr, tiny_model):
        from paddle_tpu.jit import TrainStep
        opt = pp.optimizer.SGD(learning_rate=1e-2,
                               parameters=tiny_model.parameters())
        step = TrainStep(tiny_model, opt, accum_steps=2)
        ids = np.zeros((2, 8), np.int32)
        step({"input_ids": ids, "labels": ids})
        spans = {s["span_id"]: s for s in tr.finished_spans()}
        by_name = {s["name"]: s for s in spans.values()}
        root = by_name["train.step"]
        for child in ("train.h2d", "train.dispatch", "train.guard"):
            assert by_name[child]["parent_id"] == root["span_id"]
        # the microbatch scan is ONE program inside the dispatch: no
        # span of its own, the count rides the dispatch span
        assert by_name["train.dispatch"]["attrs"]["microbatches"] == 2
        assert "train.accum_microbatches" not in by_name

    def test_record_event_nests_under_active_span(self, tr):
        from paddle_tpu import profiler as prof
        with tr.span("outer") as outer:
            with prof.RecordEvent("annotated", event_type="Forward"):
                pass
        (s,) = tr.finished_spans(name="annotated")
        assert s["parent_id"] == outer.span_id
        assert s["attrs"]["cat"] == "Forward"


# ------------------------------------------- cross-host (TCPStore) handoff
class TestStoreHandoff:
    def test_two_worker_store_context_stitches_one_trace(self, tr):
        """Simulated 2-worker handoff: 'worker 0' roots a generation
        span and injects its context into the store; 'worker 1'
        (separate thread + separate client connection) extracts it and
        parents its own work under it — both sides land in ONE trace."""
        from paddle_tpu.distributed.elastic import free_port
        from paddle_tpu.distributed.tcp_store import TCPStore
        from paddle_tpu.observability.tracing import (extract_context,
                                                      inject_context)
        port = free_port()
        try:
            master = TCPStore("127.0.0.1", port, is_master=True)
        except Exception as e:  # pragma: no cover - no native lib
            pytest.skip(f"native TCPStore unavailable: {e}")
        try:
            gen_span = tr.start_span("elastic.generation", generation=0)
            assert inject_context(master, key="trace/gen/0",
                                  ctx=gen_span.context)
            result = {}

            def worker_one():
                client = TCPStore("127.0.0.1", port, is_master=False)
                ctx = extract_context(client, key="trace/gen/0")
                tr.set_process_context(ctx)
                try:
                    with tr.span("worker.step") as s:
                        result["trace"] = s.trace_id
                finally:
                    tr.set_process_context(None)
                    client.close()
            th = threading.Thread(target=worker_one)
            th.start()
            th.join(timeout=30)
            gen_span.end()
            assert result["trace"] == gen_span.trace_id
            (ws,) = tr.finished_spans(name="worker.step")
            assert ws["parent_id"] == gen_span.span_id
            # store ops themselves were spanned (root_eligible=False:
            # none of them may pollute the slowest-trace root table)
            assert tr.finished_spans(name="store.set")
            roots = [t["root"] for t in tr.slowest_traces(10)]
            assert all(not r.startswith("store.") for r in roots)
        finally:
            master.close()

    def test_extract_absent_key_is_none(self, tr):
        class _FakeStore:
            def check(self, key):
                return False

            def get(self, key, wait=True):
                raise KeyError(key)
        from paddle_tpu.observability.tracing import extract_context
        assert extract_context(_FakeStore(), key="trace/none") is None


# ------------------------------------------------------- chrome export
class TestChromeExport:
    def test_export_shape_and_ids(self, tr, tmp_path):
        with tr.span("outer"):
            with tr.span("inner"):
                pass
        out = tmp_path / "trace.json"
        trace = tr.export_chrome(str(out))
        loaded = json.loads(out.read_text())
        assert loaded["traceEvents"] == trace["traceEvents"]
        xs = [e for e in trace["traceEvents"] if e["ph"] == "X"]
        assert {e["name"] for e in xs} == {"outer", "inner"}
        inner = next(e for e in xs if e["name"] == "inner")
        outer = next(e for e in xs if e["name"] == "outer")
        assert inner["args"]["parent_id"] == outer["args"]["span_id"]
        # containment: the child interval nests inside the parent's
        assert outer["ts"] <= inner["ts"]
        assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] \
            + 1e-3
        assert any(e["ph"] == "M" and e["name"] == "process_name"
                   for e in trace["traceEvents"])


# ------------------------------------------------------------ watchdog
class TestWatchdogRules:
    def _dog(self, reg, rules, **kw):
        kw.setdefault("cooldown", 0.0)
        rec = FlightRecorder(capacity=64)
        return Watchdog(rules=rules, registry=reg, recorder=rec, **kw), rec

    def test_step_time_drift_trips_and_dumps(self, capsys):
        reg = MetricsRegistry()
        h = reg.histogram("paddle_tpu_train_step_seconds")
        for _ in range(10):
            h.observe(0.01)
        wd, rec = self._dog(reg, [StepTimeDriftRule(factor=1.5,
                                                    min_samples=1)])
        assert wd.evaluate_once(now=1.0) == []      # seeds the baseline
        for _ in range(5):
            h.observe(0.1)                          # forced regression
        alerts = wd.evaluate_once(now=2.0)
        assert len(alerts) == 1
        assert "baseline" in alerts[0].detail
        assert reg.get("paddle_tpu_slo_breaches_total").labels(
            rule="step_time_drift").value() == 1
        assert [e for e in rec.snapshot()
                if e["kind"] == "slo_breach"]
        assert '"slo_alert"' in capsys.readouterr().err

    def test_drift_needs_min_samples(self):
        reg = MetricsRegistry()
        h = reg.histogram("paddle_tpu_train_step_seconds")
        for _ in range(3):
            h.observe(0.01)
        wd, _ = self._dog(reg, [StepTimeDriftRule(factor=1.5,
                                                  min_samples=5)])
        wd.evaluate_once(now=1.0)
        for _ in range(3):
            h.observe(1.0)            # huge, but under min_samples
        assert wd.evaluate_once(now=2.0) == []

    def test_recompile_storm(self):
        reg = MetricsRegistry()
        c = reg.counter("paddle_tpu_train_recompiles_total")
        wd, _ = self._dog(reg, [RecompileStormRule(max_delta=2)])
        c.inc(1)
        assert wd.evaluate_once(now=1.0) == []      # seeds
        c.inc(2)
        assert wd.evaluate_once(now=2.0) == []      # at threshold: ok
        c.inc(5)
        alerts = wd.evaluate_once(now=3.0)
        assert len(alerts) == 1 and "recompiles" in alerts[0].detail

    def test_queue_saturation_needs_consecutive_intervals(self):
        reg = MetricsRegistry()
        depth = [0.0]
        reg.gauge("paddle_tpu_serving_queue_depth").set_function(
            lambda: depth[0])
        wd, _ = self._dog(reg, [QueueSaturationRule(threshold=4,
                                                    consecutive=2)])
        depth[0] = 9
        assert wd.evaluate_once(now=1.0) == []      # streak 1
        depth[0] = 2
        assert wd.evaluate_once(now=2.0) == []      # streak reset
        depth[0] = 9
        assert wd.evaluate_once(now=3.0) == []
        alerts = wd.evaluate_once(now=4.0)          # streak 2
        assert len(alerts) == 1

    def test_skip_streak_sums_reason_labels(self):
        reg = MetricsRegistry()
        c = reg.counter("paddle_tpu_train_step_skipped_total",
                        labelnames=("reason",))
        wd, _ = self._dog(reg, [SkipStreakRule(max_delta=3)])
        assert wd.evaluate_once(now=1.0) == []
        c.labels(reason="nonfinite_loss").inc(2)
        c.labels(reason="nonfinite_grad").inc(3)
        alerts = wd.evaluate_once(now=2.0)
        assert len(alerts) == 1 and "skipped" in alerts[0].detail

    def test_heartbeat_gap_arms_only_after_progress(self):
        reg = MetricsRegistry()
        c = reg.counter("paddle_tpu_train_steps_total")
        wd, _ = self._dog(reg, [HeartbeatGapRule(max_gap_s=10)])
        assert wd.evaluate_once(now=0.0) == []      # value 0: unarmed
        assert wd.evaluate_once(now=100.0) == []    # still unarmed
        c.inc(5)
        assert wd.evaluate_once(now=101.0) == []    # progress seen
        assert wd.evaluate_once(now=105.0) == []    # inside the gap
        alerts = wd.evaluate_once(now=120.0)
        assert len(alerts) == 1 and "frozen" in alerts[0].detail
        c.inc()                                      # progress resumes
        assert wd.evaluate_once(now=121.0) == []

    def test_cooldown_suppresses_refires(self):
        reg = MetricsRegistry()
        depth = [99.0]
        reg.gauge("paddle_tpu_serving_queue_depth").set_function(
            lambda: depth[0])
        wd, _ = self._dog(reg, [QueueSaturationRule(threshold=4,
                                                    consecutive=1)],
                          cooldown=60.0)
        assert len(wd.evaluate_once(now=1.0)) == 1
        assert wd.evaluate_once(now=10.0) == []     # inside cooldown
        assert len(wd.evaluate_once(now=100.0)) == 1

    def test_broken_rule_does_not_kill_the_dog(self):
        class _Bad(StepTimeDriftRule):
            def evaluate(self, registry, now):
                raise RuntimeError("scrape exploded")
        reg = MetricsRegistry()
        depth = [99.0]
        reg.gauge("paddle_tpu_serving_queue_depth").set_function(
            lambda: depth[0])
        wd, _ = self._dog(reg, [_Bad(), QueueSaturationRule(
            threshold=4, consecutive=1)])
        assert len(wd.evaluate_once(now=1.0)) == 1  # good rule still ran

    def test_rules_from_spec(self):
        rules = rules_from_spec(
            "step_time_drift:factor=2.5,min_samples=10;"
            "queue_saturation:threshold=64;heartbeat_gap")
        assert [type(r).__name__ for r in rules] == \
            ["StepTimeDriftRule", "QueueSaturationRule",
             "HeartbeatGapRule"]
        assert rules[0].factor == 2.5 and rules[0].min_samples == 10
        assert rules[1].threshold == 64
        with pytest.raises(ValueError, match="unknown SLO rule"):
            rules_from_spec("no_such_rule:x=1")

    def test_slowest_traces_dumped_on_breach(self, capsys):
        t = Tracer(sample=1.0)
        with t.span("slow.root"):
            pass
        reg = MetricsRegistry()
        depth = [99.0]
        reg.gauge("paddle_tpu_serving_queue_depth").set_function(
            lambda: depth[0])
        wd, _ = self._dog(reg, [QueueSaturationRule(threshold=4,
                                                    consecutive=1)],
                          trace_source=t)
        assert len(wd.evaluate_once(now=1.0)) == 1
        err = capsys.readouterr().err
        assert '"slow_traces"' in err and "slow.root" in err


# --------------------------------------- exposition satellite (buckets)
class TestPrometheusBuckets:
    def test_histogram_quantile_math_works_from_exposition(self):
        """PromQL histogram_quantile needs cumulative le-buckets + +Inf;
        re-derive p90 from the rendered TEXT and check it brackets the
        true quantile — the Grafana path, end to end."""
        reg = MetricsRegistry()
        h = reg.histogram("paddle_tpu_q_seconds", "q",
                          buckets=(0.01, 0.05, 0.1, 0.5))
        for v in [0.02] * 80 + [0.3] * 20:
            h.observe(v)
        text = render_prometheus(reg)
        buckets = {}
        for line in text.splitlines():
            if line.startswith("paddle_tpu_q_seconds_bucket"):
                le = line.split('le="')[1].split('"')[0]
                buckets[le] = float(line.rsplit(" ", 1)[1])
        bounds = [k for k in buckets if k != "+Inf"]
        # cumulative and capped by +Inf == count
        cums = [buckets[b] for b in bounds]
        assert cums == sorted(cums)
        assert buckets["+Inf"] == 100
        assert "paddle_tpu_q_seconds_count 100" in text
        # histogram_quantile(0.9): rank 90 falls in the (0.1, 0.5] bucket
        target = 0.9 * buckets["+Inf"]
        prev_b, prev_c = 0.0, 0.0
        for b in bounds:
            if buckets[b] >= target:
                width = float(b) - prev_b
                est = prev_b + width * (target - prev_c) \
                    / (buckets[b] - prev_c)
                break
            prev_b, prev_c = float(b), buckets[b]
        assert 0.1 < est <= 0.5

    def test_histogram_quantile_from_federated_exposition(self):
        """ISSUE 11 satellite: the SAME histogram_quantile math over
        the FEDERATED (3-host, bucket-summed) exposition must match the
        estimate from one histogram that observed the pooled raw
        stream — federation must not bend quantiles."""
        from paddle_tpu.observability.fleet import (FleetAggregator,
                                                    LocalStore,
                                                    MetricsPublisher)
        bounds = (0.01, 0.05, 0.1, 0.5)
        per_host = ([0.02] * 30 + [0.3] * 5, [0.02] * 30 + [0.3] * 10,
                    [0.02] * 20 + [0.3] * 5)
        store = LocalStore()
        pooled = []
        for i, obs in enumerate(per_host):
            reg = MetricsRegistry()
            h = reg.histogram("paddle_tpu_q_seconds", "q",
                              buckets=bounds)
            for v in obs:
                h.observe(v)
            pooled.extend(obs)
            MetricsPublisher(store, registry=reg, host=f"h{i}",
                             interval=999, publish_goodput=False,
                             publish_traces=False).publish_once()
        agg = FleetAggregator(store=store)

        def quantile_from_text(text, q):
            buckets = {}
            for line in text.splitlines():
                if line.startswith("paddle_tpu_q_seconds_bucket"):
                    le = line.split('le="')[1].split('"')[0]
                    buckets[le] = float(line.rsplit(" ", 1)[1])
            target = q * buckets["+Inf"]
            prev_b, prev_c = 0.0, 0.0
            for b in [k for k in buckets if k != "+Inf"]:
                if buckets[b] >= target:
                    return prev_b + (float(b) - prev_b) * \
                        (target - prev_c) / (buckets[b] - prev_c)
                prev_b, prev_c = float(b), buckets[b]
            return float(b)

        fed_text = render_prometheus(agg)
        ref = MetricsRegistry()
        rh = ref.histogram("paddle_tpu_q_seconds", "q", buckets=bounds)
        for v in pooled:
            rh.observe(v)
        ref_text = render_prometheus(ref)
        assert f"paddle_tpu_q_seconds_count {len(pooled)}" in fed_text
        for q in (0.5, 0.9, 0.99):
            assert abs(quantile_from_text(fed_text, q)
                       - quantile_from_text(ref_text, q)) < 1e-12, q

    def test_jsonl_payload_keeps_quantile_summaries(self):
        from paddle_tpu.observability import render_json
        reg = MetricsRegistry()
        h = reg.histogram("paddle_tpu_q2_seconds")
        for _ in range(10):
            h.observe(0.02)
        payload = json.loads(render_json(reg))
        (fam,) = [m for m in payload["metrics"]
                  if m["name"] == "paddle_tpu_q2_seconds"]
        summary = fam["series"][0]["summary"]
        assert summary["count"] == 10
        assert {"p50", "p90", "p99"} <= set(summary)


# ------------------------------- the tracer on the device trace's clock
def _host_events(logdir):
    """{name: [(start_ns, end_ns)]} of one capture's host planes."""
    import glob

    import jax
    (path,) = glob.glob(f"{logdir}/plugins/profile/*/*.xplane.pb")
    out = {}
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    out.setdefault(e.name, []).append(
                        (e.start_ns, e.start_ns + e.duration_ns))
    return out


class TestProfilerBridge:
    def test_spans_and_record_event_reach_the_host_plane_once(
            self, tr, tmp_path):
        """Inside a profiler session a nested Tracer.span pair sits on
        the .xplane.pb's host plane by name, child inside parent, and a
        RecordEvent — which takes the same door — appears once."""
        import jax
        from paddle_tpu import profiler as prof
        jax.profiler.start_trace(str(tmp_path))
        try:
            with tr.span("bridge.outer", rid=7):
                with tr.span("bridge.inner"):
                    pass
                with prof.RecordEvent("bridge.annotated"):
                    pass
            tr.add_span("bridge.after_the_fact", 0.0, 1.0)
        finally:
            jax.profiler.stop_trace()
        ev = _host_events(tmp_path)
        ((o0, o1),) = ev["bridge.outer"]     # the name alone, no #rid=7#
        ((i0, i1),) = ev["bridge.inner"]
        assert o0 <= i0 <= i1 <= o1
        assert len(ev["bridge.annotated"]) == 1
        assert "bridge.after_the_fact" not in ev
        # and once in the ring, under the span that was active
        (s,) = tr.finished_spans(name="bridge.annotated")
        assert s["parent_id"] == tr.finished_spans(
            name="bridge.outer")[0]["span_id"]

    def test_opening_attributes_are_the_events_stats(self, tr, tmp_path):
        """The numbers and short strings a span is OPENED with go to the
        profiler as the event's stats, beside a name that stays the
        span's; what is set later, and what is neither, stays in the
        ring."""
        import glob

        import jax
        jax.profiler.start_trace(str(tmp_path))
        try:
            with tr.span("bridge.numbered", seq=41, kind="decode",
                         share=0.5, rows=[1, 2], note="x" * 65) as s:
                s.set_attribute("late", 7)
            with tr.span("bridge.bare"):
                pass
        finally:
            jax.profiler.stop_trace()
        (path,) = glob.glob(f"{tmp_path}/plugins/profile/*/*.xplane.pb")
        stats = {e.name: dict(e.stats)
                 for plane in jax.profiler.ProfileData.from_file(path).planes
                 if plane.name.startswith("/host:")
                 for line in plane.lines for e in line.events
                 if e.name.startswith("bridge.")}
        assert stats == {"bridge.numbered": {"seq": 41, "kind": "decode",
                                             "share": 0.5},
                         "bridge.bare": {}}
        (kept,) = tr.finished_spans(name="bridge.numbered")
        assert kept["attrs"] == {"seq": 41, "kind": "decode", "share": 0.5,
                                 "rows": [1, 2], "note": "x" * 65,
                                 "late": 7}

    def test_disabled_tracer_writes_nothing(self, tmp_path):
        import jax
        off = Tracer(sample=0.0)
        jax.profiler.start_trace(str(tmp_path))
        try:
            with off.span("bridge.off") as s:
                assert s.context is None
        finally:
            jax.profiler.stop_trace()
        assert "bridge.off" not in _host_events(tmp_path)
        assert off.finished_spans() == []

    def test_roots_outlive_the_ring(self):
        """A request's root (and its token_stamps) must still be there
        after 10 000 later step spans have flushed the main ring."""
        t = Tracer(capacity=4096, sample=1.0)
        root = t.start_span("serving.request", rid=3)
        root.set_attribute("token_stamps", [(1.0, 1), (2.0, 1)])
        root.end()
        for _ in range(10_000):
            with t.span("serving.step", root_eligible=False):
                pass
        assert not t.finished_spans(name="serving.request")
        (kept,) = t.finished_roots("serving.request")
        assert kept["attrs"]["token_stamps"] == [(1.0, 1), (2.0, 1)]
        assert t.finished_roots("serving.step") == []


PHASES = ("serving.schedule", "serving.admit", "serving.build",
          "serving.dispatch", "serving.sync", "serving.emit")
# the engine a user gets with no engine argument, and the blocks the
# other tests cut it to
ENGINES = {"default": dict(prefill_buckets=(16,)),
           "block8": dict(kv_block_size=8, prefill_chunk=16,
                          prefill_buckets=(16,))}


def _run_engine(model, n_requests, **kw):
    from paddle_tpu.inference.serving import ContinuousBatchingEngine
    eng = ContinuousBatchingEngine(model, slots=4, max_len=64, **kw)
    rng = np.random.default_rng(1)
    rids = [eng.add_request(rng.integers(0, 128, (5,)), max_new_tokens=6)
            for _ in range(n_requests)]
    eng.run()
    return eng, rids


def _children_by_step(tr):
    """[(step span, [child span names])] in the order the steps ran."""
    spans = tr.finished_spans()
    steps = [s for s in spans if s["name"] == "serving.step"]
    kids = {s["span_id"]: [] for s in steps}
    for s in spans:
        if s["parent_id"] in kids:
            kids[s["parent_id"]].append(s["name"])
    return [(s, sorted(kids[s["span_id"]])) for s in steps]


@pytest.mark.parametrize("kind", sorted(ENGINES))
class TestEngineStepPhases:
    def test_spans_per_step_do_not_grow_with_the_batch(self, tr,
                                                       tiny_model, kind):
        per_batch = {}
        for n in (1, 4):
            tr.clear()
            _run_engine(tiny_model, n, **ENGINES[kind])
            steps = _children_by_step(tr)
            decodes = [k for s, k in steps
                       if s["attrs"]["ran"] == "decode"
                       and s["attrs"]["decoding"] == n]
            assert decodes, [s["attrs"] for s, _ in steps]
            per_batch[n] = {tuple(k) for k in decodes}
            # no span hangs off a request per decode step
            assert not tr.finished_spans(name="serving.decode_step")
        assert per_batch[1] == per_batch[4]
        # a decode step dispatches first and collects afterwards: one
        # that found a dispatch unread reads and emits it after its own
        # dispatch, the first of a run has nothing to read yet
        issued = {"serving.schedule", "serving.build", "serving.dispatch"}
        assert {frozenset(names) for names in per_batch[4]} == {
            frozenset(issued),
            frozenset(issued | {"serving.sync", "serving.emit"})}
        # each phase once: expiry and the free-slot scan share ONE
        # serving.schedule span, so a decode step is the step and three
        # children, five once it has a dispatch to read
        assert {len(names) for names in per_batch[4]} == {3, 5}

    def test_every_phase_parents_to_a_step(self, tr, tiny_model, kind):
        _run_engine(tiny_model, 4, **ENGINES[kind])
        steps = _children_by_step(tr)
        seen = {name for _, kids in steps for name in kids}
        assert set(PHASES) <= seen, seen
        ran = {s["attrs"]["ran"] for s, _ in steps}
        assert {"admit", "decode"} <= ran
        assert "prefill_chunk" in ran
        for s, _ in steps:
            assert {"active", "queued"} <= set(s["attrs"])
        # steps are not roots of the slowest-traces table
        assert not tr.finished_roots("serving.step")
        # a chunk's dispatch and sync sit inside the request's prefill
        prefill_ids = {s["span_id"]
                       for s in tr.finished_spans(name="serving.prefill")}
        inside = {s["name"] for s in tr.finished_spans()
                  if s["parent_id"] in prefill_ids}
        assert inside == {"serving.dispatch", "serving.sync"}

    def test_token_stamps_on_root_and_status(self, tr, tiny_model, kind):
        from paddle_tpu.observability import default_registry
        itl = default_registry().get(
            "paddle_tpu_serving_inter_token_seconds")
        before = sum(c.count() for _, c in itl.series()) if itl else 0
        eng, rids = _run_engine(tiny_model, 4, **ENGINES[kind])
        roots = {r["attrs"]["rid"]: r
                 for r in tr.finished_roots("serving.request")}
        for rid in rids:
            st = eng.request_status(rid)
            stamps = roots[rid]["attrs"]["token_stamps"]
            assert stamps == st.token_times
            assert sum(n for _, n in stamps) == st.timings["generated"] == 6
            times = [t for t, _ in stamps]
            assert times == sorted(times)
            assert times[0] == st.timings["first_token"]
            assert 0 <= st.timings["retired"] - times[-1] < 0.5
        itl = default_registry().get(
            "paddle_tpu_serving_inter_token_seconds")
        # one observation per token after a request's first
        assert sum(c.count() for _, c in itl.series()) - before == 4 * 5


def _dispatch_counts():
    from paddle_tpu.observability import default_registry
    m = default_registry().get("paddle_tpu_serving_dispatches_total")
    return {k: c.value() for k, c in m.series()} if m is not None else {}


def _counted_since(before):
    return {k: v - before.get(k, 0) for k, v in _dispatch_counts().items()
            if v != before.get(k, 0)}


class TestDispatchPipeline:
    """Every program handed to the device is numbered from the host's
    dispatch to the read that waits for it, and counted by whether the
    device had run dry."""

    @pytest.mark.parametrize("spec", [0, 2])
    def test_seq_runs_through_every_kind_and_each_sync_names_its_dispatch(
            self, tr, tiny_model, spec):
        from paddle_tpu.inference.serving import ContinuousBatchingEngine
        eng = ContinuousBatchingEngine(tiny_model, slots=4, max_len=64,
                                       prefill_chunk=16, kv_block_size=8,
                                       prefill_buckets=(16,),
                                       spec_decode=spec)
        rng = np.random.default_rng(2)
        for n in (5, 21, 9):            # the second prompt is two chunks
            eng.add_request(rng.integers(0, 128, (n,)), max_new_tokens=6)
        eng.run()
        disp = [s["attrs"] for s in tr.finished_spans("serving.dispatch")]
        assert [d["seq"] for d in disp] == list(range(1, len(disp) + 1))
        kind = {d["seq"]: d["kind"] for d in disp}
        batched = "spec_verify" if spec else "decode"
        assert set(kind.values()) == {"prefill_chunk", batched}
        assert list(kind.values()).count("prefill_chunk") == 4
        read = [s["attrs"]["seq"] for s in tr.finished_spans("serving.sync")]
        assert read == sorted(read) and len(set(read)) == len(read)
        # every batched dispatch is read, a chunk's only when it was its
        # prompt's last
        assert [q for q in read if kind[q] == batched] == \
            [q for q in kind if kind[q] == batched]
        assert sum(kind[q] == "prefill_chunk" for q in read) == 3
        # a sync closes after the dispatch it names opened
        opened = {s["attrs"]["seq"]: s["t0"]
                  for s in tr.finished_spans("serving.dispatch")}
        assert all(s["t1"] >= opened[s["attrs"]["seq"]]
                   for s in tr.finished_spans("serving.sync"))

    def test_drained_and_fed_are_counted_where_the_program_is_called(
            self, tiny_model):
        """A stand-in decode program that takes a few tenths of a second:
        the first dispatch and one after a blocking read find the device
        drained, one issued with the dispatch before it unread and
        unfinished finds it fed."""
        import jax
        import jax.numpy as jnp
        from paddle_tpu.inference.serving import ContinuousBatchingEngine
        eng = ContinuousBatchingEngine(tiny_model, slots=4, max_len=64,
                                       prefill_buckets=(16,))
        real = eng._decode_compiled or eng._decode_paged

        @jax.jit
        def slow(toks):
            m = jax.lax.fori_loop(
                0, 400, lambda _, m: jnp.tanh(m @ m),
                jnp.full((256, 256), 1e-3, jnp.float32))
            return toks + (m[0, 0] > 2).astype(toks.dtype)   # + 0, late
        slow(jnp.zeros((eng.slots, eng.steps_per_sync),
                       jnp.int32)).block_until_ready()       # compiled

        def program(*args):
            got = real(*args)
            return (slow(got[0]), *got[1:])
        eng._decode_compiled, eng._decode_paged = None, program
        before = _dispatch_counts()
        eng.add_request(np.arange(5), max_new_tokens=8)
        eng.step()                      # admit
        eng.step()                      # the prompt's one chunk, and its read
        assert _counted_since(before) == {("prefill_chunk", "drained"): 1}
        eng.step()      # the first token was read: nothing left out there
        assert _counted_since(before)[("decode", "drained")] == 1
        unfinished = not eng._newest_out.is_ready()
        eng.step()      # issued with that one unread, then reads it
        assert unfinished, "the stand-in finished before it could be tested"
        assert _counted_since(before)[("decode", "fed")] == 1
        eng._collect()                  # a blocking read: the device is dry
        eng._newest_out.block_until_ready()
        drained = _counted_since(before)[("decode", "drained")]
        eng.step()
        assert _counted_since(before)[("decode", "drained")] == drained + 1
        eng.run()
        assert eng.request_status(0) == "ok"
        assert sum(_counted_since(before).values()) == eng._dispatch_seq


# ---------------------------------------------- stable names on the device
SCOPES = ("embed", "attn", "mlp", "lm_head_ce")


def _has_scope(text, scope):
    import re
    return re.search(r'op_name="[^"]*(?<![\w])%s(?![\w])' % scope, text)


class TestStableNames:
    def test_train_step_carries_every_scope_forward_and_backward(
            self, tiny_model):
        import re
        from paddle_tpu.jit import TrainStep
        opt = pp.optimizer.AdamW(learning_rate=1e-2,
                                 parameters=tiny_model.parameters())
        step = TrainStep(tiny_model, opt)
        ids = np.zeros((2, 8), np.int32)
        step.compile({"input_ids": ids, "labels": ids})
        text = step._compiled.as_text()
        for scope in SCOPES + ("optimizer",):
            assert _has_scope(text, scope), scope
        for scope in ("attn", "mlp", "lm_head_ce"):
            assert re.search(r"transpose\(jvp\(%s\)\)" % scope, text), scope

    def test_engine_programs_carry_the_model_scopes(self, tiny_model):
        from paddle_tpu.inference.serving import ContinuousBatchingEngine
        eng = ContinuousBatchingEngine(tiny_model, slots=2, max_len=64,
                                       **ENGINES["block8"])
        eng.aot_warmup()
        for prog in (eng._decode_compiled, eng._prefill_chunk_compiled):
            text = prog.as_text()
            for scope in SCOPES:
                assert _has_scope(text, scope), scope


def _pallas_call_names():
    """[(file, line, name or None)] of every pl.pallas_call site."""
    import ast
    import glob
    import os
    import paddle_tpu.ops.pallas as pkg
    sites = []
    for path in sorted(glob.glob(
            os.path.join(os.path.dirname(pkg.__file__), "*.py"))):
        for node in ast.walk(ast.parse(open(path).read())):
            if isinstance(node, ast.Call) and \
                    getattr(node.func, "attr", "") == "pallas_call":
                name = [k.value.value for k in node.keywords
                        if k.arg == "name"
                        and isinstance(k.value, ast.Constant)]
                sites.append((os.path.basename(path), node.lineno,
                              name[0] if name else None))
    return sites


KERNEL_NAMES = ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv",
                "rmsnorm_qkv", "fused_mlp", "fused_decoder",
                "paged_attention", "rmsnorm", "fused_ce_fwd",
                "fused_ce_bwd", "grouped_matmul", "sorted_gated_ffn",
                "quant_matmul", "latent_attention")


@pytest.mark.parametrize("kernel", KERNEL_NAMES)
def test_every_pallas_call_site_is_named_once(kernel):
    sites = _pallas_call_names()
    assert len(sites) == len(KERNEL_NAMES) and \
        all(name for _, _, name in sites), sites
    assert [name for _, _, name in sites].count(kernel) == 1


def test_capture_xla_trace_raises_with_the_cause(tmp_path, monkeypatch):
    import jax
    from paddle_tpu.observability.device_profiler import capture_xla_trace

    def refuse(logdir):
        raise OSError("no profiler on this platform")
    monkeypatch.setattr(jax.profiler, "start_trace", refuse)
    with pytest.raises(RuntimeError, match="no profiler on this platform"):
        capture_xla_trace(lambda: 0, logdir=str(tmp_path))
    monkeypatch.undo()
    assert capture_xla_trace(lambda: jax.numpy.ones(4) + 1,
                             logdir=str(tmp_path)) == str(tmp_path)
