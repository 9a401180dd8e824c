"""Observability subsystem (ISSUE 2): metrics registry semantics,
flight-recorder ring + crash dump, Prometheus/JSONL exposition,
TrainStep + serving-engine instrumentation, and the profiler satellite
fixes (per-session host-event sink, step_info zero-division, benchmark
on raise, RecordEvent event_type)."""

import json
import time
import urllib.request

import numpy as np
import pytest

import paddle_tpu as pp
from paddle_tpu import profiler as prof_mod
from paddle_tpu.observability import (Counter, FlightRecorder, Gauge,
                                      Histogram, JsonlSink,
                                      MetricsRegistry, default_registry,
                                      render_prometheus,
                                      start_metrics_server)


# ---------------------------------------------------------------- registry
class TestRegistry:
    def test_counter_basics(self):
        reg = MetricsRegistry()
        c = reg.counter("requests_total", "help text")
        c.inc()
        c.inc(4)
        assert c.value() == 5
        with pytest.raises(ValueError):
            c.inc(-1)

    def test_get_or_create_idempotent_and_type_checked(self):
        reg = MetricsRegistry()
        a = reg.counter("x_total")
        assert reg.counter("x_total") is a
        with pytest.raises(ValueError):
            reg.gauge("x_total")
        with pytest.raises(ValueError):
            reg.counter("x_total", labelnames=("shard",))

    def test_labels_create_children(self):
        reg = MetricsRegistry()
        c = reg.counter("hits_total", labelnames=("bucket",))
        c.labels(bucket="32").inc(2)
        c.labels(bucket="64").inc()
        series = dict(c.series())
        assert series[("32",)].value() == 2
        assert series[("64",)].value() == 1

    def test_label_cardinality_cap_collapses_to_overflow(self):
        reg = MetricsRegistry()
        c = reg.counter("c_total", labelnames=("user",), max_series=4)
        for i in range(20):
            c.labels(user=str(i)).inc()
        series = c.series()
        # 4 real children + exactly one overflow bin holding the tail
        assert len(series) == 5
        overflow = dict(series)[("__overflow__",)]
        assert overflow.value() == 16

    def test_gauge_lazy_value_resolved_at_read(self):
        g = Gauge("g")
        import jax.numpy as jnp
        g.set(jnp.asarray(2.5))          # device scalar, no sync on set
        assert g.value() == 2.5

    def test_gauge_set_function_pull_style(self):
        g = Gauge("depth")
        backing = [1, 2, 3]
        g.set_function(lambda: len(backing))
        assert g.value() == 3
        backing.append(4)
        assert g.value() == 4

    def test_histogram_bucket_math(self):
        h = Histogram("h", buckets=(1.0, 2.0, 5.0))
        for v in (0.5, 1.0, 1.5, 3.0, 10.0):
            h.observe(v)
        # cumulative per bound (le semantics: bound-inclusive) + inf tail
        assert h.cumulative_counts() == [2, 3, 4, 5]
        assert h.count() == 5
        assert h.sum() == pytest.approx(16.0)

    def test_histogram_quantiles_within_data_range(self):
        h = Histogram("h", buckets=(0.01, 0.1, 1.0))
        for v in [0.05] * 90 + [0.5] * 10:
            h.observe(v)
        assert 0.01 <= h.quantile(0.5) <= 0.1
        assert 0.1 <= h.quantile(0.99) <= 0.5   # clamped by observed max
        s = h.summary()
        assert s["count"] == 100
        assert s["p50"] <= s["p90"] <= s["p99"]

    def test_histogram_empty_quantile_nan(self):
        h = Histogram("h", buckets=(1.0,))
        assert h.quantile(0.5) != h.quantile(0.5)  # NaN

    def test_invalid_label_name_rejected(self):
        with pytest.raises(ValueError):
            Counter("c", labelnames=("9bad",))


# --------------------------------------------------------- flight recorder
class TestFlightRecorder:
    def test_ring_semantics(self):
        fr = FlightRecorder(capacity=3)
        for i in range(7):
            fr.record("tick", i=i)
        assert len(fr) == 3
        assert fr.total_recorded == 7
        assert [e["i"] for e in fr.events()] == [4, 5, 6]
        assert [e["i"] for e in fr.events(last=2)] == [5, 6]
        # seq keeps monotonically counting across the wrap
        assert [e["seq"] for e in fr.events()] == [5, 6, 7]

    def test_crash_dump_autofires(self, capsys):
        fr = FlightRecorder(capacity=8)
        with pytest.raises(RuntimeError, match="boom"):
            for i in range(5):
                with fr.instrumented("loop", iteration=i):
                    fr.record("work", i=i)
                    if i == 3:
                        raise RuntimeError("boom")
        err = capsys.readouterr().err
        lines = [json.loads(l) for l in err.strip().splitlines()]
        assert lines[0]["flight_recorder"]["reason"].startswith(
            "uncaught RuntimeError")
        crash = [l for l in lines[1:] if l.get("kind") == "crash"]
        assert crash and crash[0]["scope"] == "loop" \
            and crash[0]["iteration"] == 3
        # events survive in the ring for later inspection too
        assert fr.events()[-1]["kind"] == "crash"

    def test_dump_to_path(self, tmp_path):
        fr = FlightRecorder(capacity=4)
        fr.record("a", x=1)
        out = tmp_path / "fdr.jsonl"
        fr.dump(file=str(out), reason="test")
        lines = [json.loads(l) for l in out.read_text().splitlines()]
        assert lines[0]["flight_recorder"]["reason"] == "test"
        assert lines[1]["kind"] == "a"

    def test_nonserializable_fields_best_effort(self, tmp_path):
        fr = FlightRecorder(capacity=4)
        fr.record("odd", obj=object())
        out = tmp_path / "fdr.jsonl"
        fr.dump(file=str(out))     # must not raise
        assert "odd" in out.read_text()


# --------------------------------------------------------------- exposition
class TestExposition:
    def test_prometheus_text_golden(self):
        reg = MetricsRegistry()
        c = reg.counter("paddle_tpu_demo_total", "a counter",
                        labelnames=("kind",))
        c.labels(kind="x").inc(3)
        g = reg.gauge("paddle_tpu_demo_depth", "a gauge")
        g.set(1.5)
        h = reg.histogram("paddle_tpu_demo_seconds", "a histogram",
                          buckets=(0.1, 1.0))
        h.observe(0.05)
        h.observe(0.5)
        text = render_prometheus(reg)
        expected = "\n".join([
            "# HELP paddle_tpu_demo_total a counter",
            "# TYPE paddle_tpu_demo_total counter",
            'paddle_tpu_demo_total{kind="x"} 3',
            "# HELP paddle_tpu_demo_depth a gauge",
            "# TYPE paddle_tpu_demo_depth gauge",
            "paddle_tpu_demo_depth 1.5",
            "# HELP paddle_tpu_demo_seconds a histogram",
            "# TYPE paddle_tpu_demo_seconds histogram",
            'paddle_tpu_demo_seconds_bucket{le="0.1"} 1',
            'paddle_tpu_demo_seconds_bucket{le="1"} 2',
            'paddle_tpu_demo_seconds_bucket{le="+Inf"} 2',
            "paddle_tpu_demo_seconds_sum 0.55",
            "paddle_tpu_demo_seconds_count 2",
        ]) + "\n"
        assert text == expected

    def test_label_value_escaping(self):
        reg = MetricsRegistry()
        reg.counter("c_total", labelnames=("p",)).labels(
            p='a"b\\c\nd').inc()
        text = render_prometheus(reg)
        assert r'p="a\"b\\c\nd"' in text

    def test_http_endpoint_serves_metrics(self):
        reg = MetricsRegistry()
        reg.counter("paddle_tpu_http_total").inc(7)
        with start_metrics_server(port=0, registry=reg) as srv:
            with urllib.request.urlopen(srv.url, timeout=10) as resp:
                assert resp.headers["Content-Type"].startswith(
                    "text/plain")
                body = resp.read().decode()
            assert "paddle_tpu_http_total 7" in body
            json_url = srv.url + ".json"
            with urllib.request.urlopen(json_url, timeout=10) as resp:
                payload = json.loads(resp.read().decode())
            names = [m["name"] for m in payload["metrics"]]
            assert "paddle_tpu_http_total" in names

    def test_jsonl_sink_appends_snapshots(self, tmp_path):
        reg = MetricsRegistry()
        c = reg.counter("c_total")
        sink = JsonlSink(str(tmp_path / "m.jsonl"), registry=reg)
        c.inc()
        sink.write()
        c.inc()
        sink.write()
        lines = [json.loads(l) for l in
                 (tmp_path / "m.jsonl").read_text().splitlines()]
        vals = [m["series"][0]["value"] for snap in lines
                for m in snap["metrics"] if m["name"] == "c_total"]
        assert vals == [1, 2]


# ------------------------------------------- instrumentation: train/serving
@pytest.fixture(scope="module")
def tiny_model():
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
    pp.seed(0)
    cfg = LlamaConfig.tiny(vocab_size=128, hidden_size=32,
                           intermediate_size=64, num_hidden_layers=2,
                           num_attention_heads=2, num_key_value_heads=2,
                           max_position_embeddings=128)
    return LlamaForCausalLM(cfg)


class _FakeClock:
    """Deterministic perf_counter: every read advances 1ms."""

    def __init__(self):
        self.t = 100.0

    def __call__(self):
        self.t += 0.001
        return self.t


def _series_value(name, **labels):
    m = default_registry().get(name)
    assert m is not None, name
    want = tuple(str(labels[k]) for k in m.labelnames)
    return dict(m.series())[want].value()


class TestTrainStepTelemetry:
    def test_counters_under_monkeypatched_clock(self, tiny_model,
                                                monkeypatch):
        from paddle_tpu.jit import train_step as ts_mod
        clock = _FakeClock()
        monkeypatch.setattr(ts_mod.time, "perf_counter", clock)
        reg = default_registry()
        opt = pp.optimizer.SGD(learning_rate=1e-2,
                               parameters=tiny_model.parameters())
        step = ts_mod.TrainStep(tiny_model, opt)
        steps0 = reg.counter("paddle_tpu_train_steps_total").value()
        tokens0 = reg.counter("paddle_tpu_train_tokens_total").value()
        hist = reg.get("paddle_tpu_train_step_seconds")
        n0 = hist.count()
        ids = np.zeros((2, 8), np.int32)
        for _ in range(3):
            loss = step({"input_ids": ids, "labels": ids})
        assert reg.counter("paddle_tpu_train_steps_total").value() \
            == steps0 + 3
        assert reg.counter("paddle_tpu_train_tokens_total").value() \
            == tokens0 + 3 * 16
        assert hist.count() == n0 + 3
        # gauges hold the device scalars; resolved lazily at read
        assert reg.gauge("paddle_tpu_train_loss").value() \
            == pytest.approx(float(loss))
        assert reg.gauge("paddle_tpu_train_grad_norm").value() > 0

    def test_recompile_counter_fed_by_signature_monitor(self, tiny_model):
        reg = default_registry()
        opt = pp.optimizer.SGD(learning_rate=1e-2,
                               parameters=tiny_model.parameters())
        from paddle_tpu.jit import TrainStep
        step = TrainStep(tiny_model, opt)
        c0 = reg.counter("paddle_tpu_train_recompiles_total").value()
        a = {"input_ids": np.zeros((2, 8), np.int32),
             "labels": np.zeros((2, 8), np.int32)}
        b = {"input_ids": np.zeros((2, 16), np.int32),
             "labels": np.zeros((2, 16), np.int32)}
        step(a)
        step(a)      # same signature: no recompile counted
        assert reg.counter(
            "paddle_tpu_train_recompiles_total").value() == c0
        step(b)      # novel shape: retrace
        assert reg.counter(
            "paddle_tpu_train_recompiles_total").value() == c0 + 1
        assert len(step._signature_monitor.records) == 2


class TestServingTelemetry:
    def test_engine_counters_and_histograms(self, tiny_model,
                                            monkeypatch):
        from paddle_tpu.inference import serving as srv_mod
        clock = _FakeClock()
        monkeypatch.setattr(srv_mod.time, "perf_counter", clock)
        reg = default_registry()
        eng = srv_mod.ContinuousBatchingEngine(
            tiny_model, slots=2, max_len=64, prefill_buckets=(16, 32))
        # instruments exist once an engine does; snapshot baselines now
        tok0 = reg.counter("paddle_tpu_serving_tokens_total").value()
        adm0 = reg.counter("paddle_tpu_serving_admissions_total").value()
        ret0 = reg.counter(
            "paddle_tpu_serving_retirements_total").value()
        ttft0 = reg.get("paddle_tpu_serving_ttft_seconds").count()
        dec0 = reg.get("paddle_tpu_serving_decode_token_seconds").count()
        rng = np.random.default_rng(0)
        prompts = [rng.integers(0, 128, (n,)) for n in (5, 16, 20)]
        for p in prompts:
            eng.add_request(p, max_new_tokens=4)
        results = eng.run()
        assert len(results) == 3
        # every request: 1 prefill token + 3 decode tokens
        assert reg.counter("paddle_tpu_serving_tokens_total").value() \
            == tok0 + 3 * 4
        assert reg.counter(
            "paddle_tpu_serving_admissions_total").value() == adm0 + 3
        assert reg.counter(
            "paddle_tpu_serving_retirements_total").value() == ret0 + 3
        assert reg.get("paddle_tpu_serving_ttft_seconds").count() \
            == ttft0 + 3
        assert reg.get(
            "paddle_tpu_serving_decode_token_seconds").count() > dec0
        # occupancy gauges: drained engine → empty queue, no active slots
        assert _series_value("paddle_tpu_serving_queue_depth") == 0
        assert _series_value("paddle_tpu_serving_active_slots") == 0
        assert _series_value("paddle_tpu_serving_slots") == 2

    @pytest.mark.parametrize("prompt_len,chunks,pad", [
        (16, 1, 0), (10, 1, 6), (37, 3, 11)],
        ids=["exact", "padded", "three_chunks"])
    def test_prefill_chunk_and_pad_counters(self, tiny_model, prompt_len,
                                            chunks, pad):
        """One dispatch a chunk; only a prompt's last chunk pads, by
        what it lacks of the chunk width (the bucket counter went with
        bucketed prefill)."""
        reg = default_registry()
        chunk_c = reg.counter("paddle_tpu_serving_prefill_chunks_total")
        pad_c = reg.counter("paddle_tpu_serving_prefill_pad_tokens_total")
        from paddle_tpu.inference.serving import ContinuousBatchingEngine
        eng = ContinuousBatchingEngine(tiny_model, slots=2, max_len=64,
                                       prefill_buckets=(16,),
                                       prefix_cache=False)
        chunks0, pad0 = chunk_c.value(), pad_c.value()
        rng = np.random.default_rng(1)
        eng.add_request(rng.integers(0, 128, (prompt_len,)),
                        max_new_tokens=2)
        eng.run()
        assert chunk_c.value() == chunks0 + chunks
        assert pad_c.value() == pad0 + pad
        assert reg.get("paddle_tpu_serving_prefill_bucket_total") is None


# ------------------------------------------------------ profiler satellites
class TestProfilerSatellites:
    def test_per_session_sinks_no_crosstalk(self):
        """Regression (ISSUE 2 satellite 1): two overlapping profilers
        used to race over the module-global sink — whichever stopped
        first stole ALL events.  Now each session keeps its own."""
        p1 = prof_mod.Profiler(timer_only=True).start()
        p2 = prof_mod.Profiler(timer_only=True).start()
        with prof_mod.RecordEvent("shared_op"):
            pass
        p1.stop()          # stopping first must not steal p2's events
        with prof_mod.RecordEvent("late_op"):
            pass
        p2.stop()
        t1, t2 = p1.summary(), p2.summary()
        assert "shared_op" in t1
        assert "shared_op" in t2
        assert "late_op" in t2
        assert "late_op" not in t1     # after p1 stopped

    def test_sequential_profilers_independent(self):
        p1 = prof_mod.Profiler(timer_only=True).start()
        with prof_mod.RecordEvent("first_op"):
            pass
        p1.stop()
        p2 = prof_mod.Profiler(timer_only=True).start()
        with prof_mod.RecordEvent("second_op"):
            pass
        p2.stop()
        assert "second_op" not in p1.summary()
        assert "first_op" not in p2.summary()

    def test_outside_session_goes_to_global_fallback(self):
        with prof_mod.RecordEvent("orphan_op"):
            pass
        # no session was open: the event sits in the global fallback and
        # is NOT claimed by a later profiler session
        p = prof_mod.Profiler(timer_only=True).start()
        p.stop()
        assert "orphan_op" not in p.summary()
        assert any(n == "orphan_op"
                   for n, *_ in prof_mod._EVENTS.drain())

    def test_step_info_zero_total_time_no_crash(self, monkeypatch):
        p = prof_mod.Profiler(timer_only=True)
        monkeypatch.setattr(prof_mod.time, "perf_counter", lambda: 42.0)
        p.start()
        for _ in range(3):
            p.step(num_samples=8)     # fake clock: 0s per step
        p.stop()
        info = p.step_info()
        assert "ms/step" in info      # no ZeroDivisionError
        assert "samples/s" not in info

    def test_benchmark_reports_seconds_on_raise(self):
        with pytest.raises(RuntimeError):
            with prof_mod.benchmark() as box:
                time.sleep(0.001)
                raise RuntimeError("body failed")
        assert box["seconds"] > 0

    def test_record_event_type_in_summary_and_chrome(self, tmp_path):
        p = prof_mod.Profiler(timer_only=True).start()
        with prof_mod.RecordEvent("fwd_op", event_type="Forward"):
            pass
        p.stop()
        assert "Forward" in p.summary()
        out = str(tmp_path / "trace.json")
        p.export(out)
        events = prof_mod.load_profiler_result(out)["traceEvents"]
        assert any(e["name"] == "fwd_op" and e["cat"] == "Forward"
                   for e in events)

    def test_summary_has_runtime_metrics_section(self, tiny_model):
        # train telemetry exists in the default registry by now (earlier
        # tests in this module ran steps); a fresh profiler's summary
        # renders it next to the host-annotation table
        reg = default_registry()
        reg.counter("paddle_tpu_train_steps_total").inc()
        p = prof_mod.Profiler(timer_only=True).start()
        p.stop()
        table = p.summary()
        assert "runtime metrics (observability)" in table
        assert "paddle_tpu_train_steps_total" in table
