"""Device-time profiler + roofline-gap attribution (ISSUE 6 tentpole).

CPU-safe coverage of the whole layer: AOT compile observability
(lower/compile spans, per-target counters, executable cost/memory
introspection), the portable segment-timing fallback, the attribution
join against the PR-1 cost model, the HBM census/watermark monitor with
leak detection, the TrainStep/serving AOT integration, the new watchdog
rules, and the bench --compare helper.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.observability.device_profiler import (
    AttributionResult, DeviceMemoryMonitor, DeviceProfiler, Segment,
    aot_compile, compile_records, compiled_stats, detect_roofline,
    device_memory_monitor, llama_step_segments, signature_of)
from paddle_tpu.observability.metrics import MetricsRegistry, \
    default_registry
from paddle_tpu.observability.tracing import tracer


# ---------------------------------------------------------------- aot compile
class TestAotCompile:
    def test_compiled_matches_jit(self):
        x = jnp.arange(64, dtype=jnp.float32).reshape(8, 8)
        compiled, info = aot_compile(lambda a, b: a @ b, x, x,
                                     target="test.matmul")
        np.testing.assert_allclose(np.asarray(compiled(x, x)),
                                   np.asarray(x @ x), rtol=1e-6)
        assert info.lower_s >= 0 and info.compile_s >= 0
        assert info.target == "test.matmul"

    def test_cost_and_memory_analysis(self):
        x = jnp.ones((32, 32), jnp.float32)
        _, info = aot_compile(lambda a, b: jnp.tanh(a @ b), x, x,
                              target="test.cost")
        st = info.stats
        # 2*M*N*K matmul flops must be visible to XLA's own counter
        assert st.flops >= 2 * 32 * 32 * 32
        assert st.bytes_accessed > 0
        assert st.argument_bytes == 2 * 32 * 32 * 4
        assert st.peak_bytes >= st.argument_bytes

    def test_compile_counter_and_spans(self):
        x = jnp.ones((4, 4))
        aot_compile(lambda a: a + 1, x, target="test.counted")
        c = default_registry().get("paddle_tpu_compile_total")
        series = {"/".join(k): ch.value() for k, ch in c.series()}
        assert series.get("test.counted", 0) >= 1
        names = {s["name"] for s in tracer().finished_spans()}
        assert {"compile", "compile.lower", "compile.xla"} <= names

    def test_compile_records_carry_signature(self):
        x = jnp.ones((3, 5))
        aot_compile(lambda a: a * 2, x, target="test.sig")
        recs = compile_records(target="test.sig")
        assert recs and "float32[3, 5]" in recs[-1].signature

    def test_no_silent_retrace(self):
        """The AOT executable raises on a novel shape instead of
        recompiling — the serving-tier contract."""
        x = jnp.ones((4, 4))
        compiled, _ = aot_compile(lambda a: a.sum(), x, target="test.fixed")
        with pytest.raises(Exception):
            compiled(jnp.ones((8, 8)))

    def test_compiled_stats_defensive(self):
        class Broken:
            def cost_analysis(self):
                raise RuntimeError("nope")

            def memory_analysis(self):
                raise RuntimeError("nope")
        st = compiled_stats(Broken())
        assert st.flops == 0 and st.peak_bytes == 0


class TestSignature:
    def test_stable_and_shape_sensitive(self):
        a = {"x": jnp.ones((2, 3)), "y": jnp.zeros((4,), jnp.int32)}
        b = {"x": jnp.full((2, 3), 7.0), "y": jnp.ones((4,), jnp.int32)}
        assert signature_of(a) == signature_of(b)  # values don't matter
        c = {"x": jnp.ones((2, 4)), "y": jnp.zeros((4,), jnp.int32)}
        assert signature_of(a) != signature_of(c)

    def test_treedef_sensitive(self):
        assert signature_of({"x": jnp.ones(2)}) != \
            signature_of([jnp.ones(2)])


def test_detect_roofline_unknown_tpu_kind_raises():
    """A TPU the table does not know is an error; the CPU keeps the host
    roofline that ranks segments."""
    import types
    new = types.SimpleNamespace(platform="tpu", device_kind="TPU v9x")
    with pytest.raises(ValueError, match="TPU v9x"):
        detect_roofline(new)
    v5e = types.SimpleNamespace(platform="tpu", device_kind="TPU v5 lite")
    assert detect_roofline(v5e) == (197e12, 819e9)
    cpu = types.SimpleNamespace(platform="cpu", device_kind="cpu")
    assert detect_roofline(cpu)[0] < 1e12


def test_detect_roofline_env_override(monkeypatch):
    monkeypatch.setenv("PADDLE_TPU_PEAK_FLOPS", "123e12")
    monkeypatch.setenv("PADDLE_TPU_HBM_BW", "456e9")
    peak, bw = detect_roofline()
    assert peak == 123e12 and bw == 456e9


# ------------------------------------------------------------ segment timing
class TestDeviceProfiler:
    def test_fallback_timer_ranks_segments(self):
        prof = DeviceProfiler()
        small = jnp.ones((16, 16), jnp.float32)
        big = jnp.ones((256, 256), jnp.float32)
        prof.add_segment("small_mm", lambda a: a @ a, small)
        prof.add_segment("big_mm", lambda a: a @ a, big)
        res = prof.profile(reps=3, warmup=1, parent_span="test.profile")
        by_name = {r.name: r for r in res.segments}
        assert set(by_name) == {"small_mm", "big_mm"}
        assert all(r.device_s > 0 for r in res.segments)
        assert by_name["big_mm"].device_s > by_name["small_mm"].device_s

    def test_attribution_join(self):
        prof = DeviceProfiler()
        x = jnp.ones((64, 64), jnp.float32)
        prof.add_segment("mm", lambda a: a @ a, x)
        res = prof.profile(reps=2, warmup=1, parent_span="test.join")
        (r,) = res.segments
        # predicted roofline comes from the PR-1 cost model with THIS
        # profiler's peaks, and the gap is the measured/predicted join
        assert r.predicted_s > 0
        assert r.model_flops >= 2 * 64 * 64 * 64
        assert r.gap == pytest.approx(r.device_s / r.predicted_s)
        assert r.bound in ("compute", "memory")
        assert r.flops > 0          # XLA side of the join

    def test_table_renders_ranked(self):
        seg = [
            _report("worst", gap=9.0), _report("mid", gap=5.0),
            _report("best", gap=1.1),
        ]
        res = AttributionResult(segments=seg, peak_flops=1e12, hbm_bw=1e11)
        txt = res.table()
        assert "roofline-gap attribution" in txt
        assert txt.index("worst") < txt.index("mid") < txt.index("best")
        rows = res.to_dicts(top=2)
        assert [r["name"] for r in rows] == ["worst", "mid"]
        assert rows[0]["device_ms"] > 0 and rows[0]["predicted_ms"] > 0

    def test_untraceable_segment_skipped(self):
        prof = DeviceProfiler()
        prof.add(Segment("bad", lambda: (_ for _ in ()).throw(
            RuntimeError("boom")), ()))
        prof.add_segment("good", lambda a: a + 1, jnp.ones(4))
        res = prof.profile(reps=1, warmup=0, parent_span="test.skip")
        assert [r.name for r in res.segments] == ["good"]

    def test_segment_histogram_observed(self):
        prof = DeviceProfiler()
        prof.add_segment("histo_seg", lambda a: a * 2, jnp.ones(8))
        prof.profile(reps=1, warmup=0, parent_span="test.histo")
        h = default_registry().get("paddle_tpu_device_segment_seconds")
        series = {"/".join(k): ch for k, ch in h.series()}
        assert series["histo_seg"].count() >= 1


def _report(name, gap):
    from paddle_tpu.observability.device_profiler import SegmentReport
    return SegmentReport(name=name, count=1, group="op",
                         device_s=gap * 1e-4, compile_s=0.0, flops=1.0,
                         bytes_accessed=1.0, peak_bytes=1,
                         model_flops=1.0, model_bytes=1.0,
                         predicted_s=1e-4, gap=gap, bound="memory")


# ------------------------------------------------------- llama decomposition
@pytest.fixture(scope="module")
def tiny_llama():
    import paddle_tpu as pp
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
    pp.seed(0)
    model = LlamaForCausalLM(LlamaConfig.tiny())
    ids = np.random.default_rng(0).integers(
        0, 256, (2, 16)).astype(np.int32)
    return model, {"input_ids": ids, "labels": ids}


class TestLlamaSegments:
    def test_op_groups(self, tiny_llama):
        model, batch = tiny_llama
        segs = llama_step_segments(model, batch)
        names = {s.name for s in segs}
        assert {"embed", "rmsnorm", "attention", "mlp",
                "lm_head_ce"} <= names
        assert len(segs) >= 5
        by_name = {s.name: s for s in segs}
        # counts reflect the model's composition (L=2 for tiny)
        assert by_name["attention"].count == 2
        assert by_name["rmsnorm"].count == 5       # 2 per block + final

    def test_no_grad_variant(self, tiny_llama):
        model, batch = tiny_llama
        segs = llama_step_segments(model, batch, grad=False)
        assert not any("fwdbwd" in s.name for s in segs)
        assert len(segs) >= 5

    def test_rejects_non_llama(self):
        llama_like = object()
        with pytest.raises(ValueError):
            llama_step_segments(llama_like, {})

    def test_profile_and_trace_nesting(self, tiny_llama, tmp_path):
        model, batch = tiny_llama
        prof = DeviceProfiler()
        for seg in llama_step_segments(model, batch, grad=False):
            prof.add(seg)
        res = prof.profile(reps=1, warmup=1, parent_span="train.step")
        assert len(res.ranked()) >= 5
        assert all(r.device_s > 0 and r.predicted_s > 0 and r.gap > 0
                   for r in res.segments)
        trace = tracer().export_chrome(str(tmp_path / "trace.json"))
        spans = {e["args"]["span_id"]: e for e in trace["traceEvents"]
                 if e.get("ph") == "X" and e.get("args", {}).get("span_id")}

        def ancestors(e):
            out, p = [], e["args"].get("parent_id")
            while p and p in spans:
                out.append(spans[p]["name"])
                p = spans[p]["args"].get("parent_id")
            return out
        dev = [e for e in spans.values()
               if e["name"].startswith("device.")]
        assert dev, "no device segments exported"
        assert any("train.step" in ancestors(e) for e in dev)


def test_profiler_summary_device_section(capsys):
    from paddle_tpu.profiler import Profiler
    res = AttributionResult(segments=[_report("seg_a", 3.0)],
                            peak_flops=1e12, hbm_bw=1e11)
    prof = Profiler(timer_only=True)
    prof.start()
    prof.stop()
    prof.add_device_profile(res)
    table = prof.summary()
    assert "roofline-gap attribution" in table
    assert "seg_a" in table


# --------------------------------------------------------------- HBM census
class TestMemoryMonitor:
    def test_sample_and_watermark(self):
        reg = MetricsRegistry()
        mon = DeviceMemoryMonitor(registry=reg)
        keep = jnp.ones((128, 128), jnp.float32)   # keep a buffer live
        v = mon.sample()
        assert v > 0
        assert reg.get("paddle_tpu_device_live_bytes").value() == v
        assert mon.watermark >= v
        mon.sample(live_bytes=v // 2)
        assert mon.watermark >= v                  # watermark is monotone
        del keep

    def test_census_groups_by_shape(self):
        keep = [jnp.ones((33, 7), jnp.float32) for _ in range(3)]
        jax.block_until_ready(keep)
        rows = DeviceMemoryMonitor.census(top=50)
        match = [r for r in rows
                 if r["shape"] == [33, 7] and r["dtype"] == "float32"]
        assert match and match[0]["count"] >= 3
        assert match[0]["bytes"] >= 3 * 33 * 7 * 4
        del keep

    def test_leak_detection_fires_on_monotone_growth(self):
        reg = MetricsRegistry()
        mon = DeviceMemoryMonitor(registry=reg, leak_window=4,
                                  leak_min_bytes=100)
        for b in (1000, 1200, 1400, 1700):
            mon.sample(live_bytes=b)
        assert reg.get(
            "paddle_tpu_device_memory_leak_total").value() == 1
        # window cleared after firing: no immediate re-fire
        mon.sample(live_bytes=1800)
        assert reg.get(
            "paddle_tpu_device_memory_leak_total").value() == 1

    def test_leak_detector_quiet_on_stable(self):
        reg = MetricsRegistry()
        mon = DeviceMemoryMonitor(registry=reg, leak_window=4,
                                  leak_min_bytes=100)
        for b in (1000, 1200, 1100, 1300, 1250, 1400):
            mon.sample(live_bytes=b)
        assert reg.get(
            "paddle_tpu_device_memory_leak_total").value() == 0

    def test_process_monitor_singleton(self):
        assert device_memory_monitor() is device_memory_monitor()


# -------------------------------------------------------- TrainStep AOT path
class TestTrainStepAot:
    @pytest.fixture(scope="class")
    def compiled_step(self, tiny_llama):
        import paddle_tpu as pp
        from paddle_tpu.jit import TrainStep
        model, batch = tiny_llama
        opt = pp.optimizer.SGD(learning_rate=1e-2,
                               parameters=model.parameters())
        step = TrainStep(model, opt)
        info = step.compile(batch)
        return step, batch, info

    def test_compile_info_and_executable_gauges(self, compiled_step):
        step, batch, info = compiled_step
        assert info.stats.flops > 0
        assert info.stats.peak_bytes > 0
        g = default_registry().get("paddle_tpu_xla_flops")
        series = {"/".join(k) for k, _ in g.series()}
        assert any("TrainStep" in s for s in series)

    def test_dispatches_through_compiled(self, compiled_step):
        step, batch, _ = compiled_step
        placed = step._place_batch(batch)
        assert step._dispatch_fn(placed, step._key) is step._compiled
        l0 = float(step(batch))
        l1 = float(step(batch))
        assert np.isfinite(l0) and np.isfinite(l1) and l1 < l0

    def test_mfu_gauge_armed(self, compiled_step):
        step, batch, _ = compiled_step
        step(batch)
        g = default_registry().get("paddle_tpu_train_mfu")
        assert g is not None and g.value() > 0

    def test_novel_shape_falls_back_to_jit(self, compiled_step):
        step, batch, _ = compiled_step
        short = {k: v[:, :8] for k, v in batch.items()}
        loss = float(step(short))          # must not raise
        assert np.isfinite(loss)

    def test_train_compile_span(self, compiled_step):
        names = {s["name"] for s in tracer().finished_spans()}
        assert "train.compile" in names

    def test_watermark_sampled_during_steps(self, compiled_step):
        step, batch, _ = compiled_step
        step(batch)
        g = default_registry().get("paddle_tpu_device_live_bytes")
        assert g is not None and g.value() > 0


# ---------------------------------------------------------- serving AOT path
def test_serving_aot_warmup(tiny_llama):
    from paddle_tpu.inference.serving import ContinuousBatchingEngine
    model, _ = tiny_llama
    rng = np.random.default_rng(0)
    with ContinuousBatchingEngine(model, slots=2, max_len=64,
                                  prefill_buckets=(16,)) as eng:
        stats = eng.aot_warmup()
        assert set(stats) == {"serving.decode",
                              "serving.prefill_chunk[16]"}
        assert stats["serving.decode"].flops > 0
        assert eng._decode_compiled is not None
        assert eng._prefill_chunk_compiled is not None
        rids = [eng.add_request(rng.integers(0, 256, (5,)),
                                max_new_tokens=4) for _ in range(3)]
        results = eng.run()
        assert len(results) == 3
        assert all(len(toks) >= 1 for _, toks in results.values())
        assert all(eng.request_status(r) == "ok" for r in rids)
    c = default_registry().get("paddle_tpu_compile_total")
    series = {"/".join(k): ch.value() for k, ch in c.series()}
    assert series.get("serving.decode", 0) >= 1


# ------------------------------------------------------------ watchdog rules
class TestNewWatchdogRules:
    def test_mfu_drift_breaches_on_drop(self):
        from paddle_tpu.observability.watchdog import MfuDriftRule
        reg = MetricsRegistry()
        g = reg.gauge("paddle_tpu_train_mfu", "")
        rule = MfuDriftRule(factor=0.8)
        assert rule.evaluate(reg, 0.0) is None     # gauge at 0: unarmed
        g.set(0.50)
        assert rule.evaluate(reg, 1.0) is None     # seeds baseline
        g.set(0.48)
        assert rule.evaluate(reg, 2.0) is None     # within factor
        g.set(0.20)
        detail = rule.evaluate(reg, 3.0)
        assert detail and "MFU" in detail

    def test_mfu_drift_ema_tracks_slow_change(self):
        from paddle_tpu.observability.watchdog import MfuDriftRule
        reg = MetricsRegistry()
        g = reg.gauge("paddle_tpu_train_mfu", "")
        rule = MfuDriftRule(factor=0.8, alpha=0.5)
        for v in (0.50, 0.47, 0.44, 0.42, 0.40):
            g.set(v)
            assert rule.evaluate(reg, 0.0) is None  # gradual: no breach

    def test_compile_storm_breaches_on_churn(self):
        from paddle_tpu.observability.watchdog import CompileStormRule
        reg = MetricsRegistry()
        c = reg.counter("paddle_tpu_compile_total", "",
                        labelnames=("target",))
        rule = CompileStormRule(max_delta=3)
        assert rule.evaluate(reg, 0.0) is None     # seeds
        c.labels(target="a").inc(2)
        assert rule.evaluate(reg, 1.0) is None     # 2 <= 3
        c.labels(target="b").inc(5)
        detail = rule.evaluate(reg, 2.0)
        assert detail and "compiles" in detail

    def test_rules_from_spec_and_defaults(self):
        from paddle_tpu.observability.watchdog import (
            CompileStormRule, MfuDriftRule, default_rules,
            rules_from_spec)
        rules = rules_from_spec(
            "mfu_drift:factor=0.5;compile_storm:max_delta=10")
        assert isinstance(rules[0], MfuDriftRule)
        assert rules[0].factor == 0.5
        assert isinstance(rules[1], CompileStormRule)
        assert rules[1].max_delta == 10
        kinds = {type(r) for r in default_rules()}
        assert {MfuDriftRule, CompileStormRule} <= kinds

    def test_watchdog_fires_mfu_alert_end_to_end(self):
        from paddle_tpu.observability.recorder import FlightRecorder
        from paddle_tpu.observability.watchdog import (MfuDriftRule,
                                                       Watchdog)
        reg = MetricsRegistry()
        g = reg.gauge("paddle_tpu_train_mfu", "")
        wd = Watchdog(rules=[MfuDriftRule(factor=0.8)], registry=reg,
                      recorder=FlightRecorder(capacity=16), cooldown=0.0)
        g.set(0.5)
        assert wd.evaluate_once(now=1.0) == []
        g.set(0.1)
        alerts = wd.evaluate_once(now=2.0)
        assert len(alerts) == 1 and alerts[0].rule == "mfu_drift"


# ------------------------------------------------------------- bench compare
class TestBenchCompare:
    def test_flags_value_regression(self):
        import bench
        cur = {"value": 0.40, "detail": {"step_time_s": 0.30}}
        prev = {"value": 0.50, "detail": {"step_time_s": 0.30}}
        regs = bench.compare_records(cur, prev, tolerance=0.05)
        assert len(regs) == 1 and "value" in regs[0]

    def test_flags_step_time_regression(self):
        import bench
        cur = {"value": 0.50, "detail": {"step_time_s": 0.40}}
        prev = {"value": 0.50, "detail": {"step_time_s": 0.30}}
        regs = bench.compare_records(cur, prev, tolerance=0.05)
        assert len(regs) == 1 and "step_time_s" in regs[0]

    def test_within_tolerance_ok(self):
        import bench
        cur = {"value": 0.49, "detail": {"step_time_s": 0.305}}
        prev = {"value": 0.50, "detail": {"step_time_s": 0.30}}
        assert bench.compare_records(cur, prev, tolerance=0.05) == []

    def test_prev_record_reads_artifacts(self, tmp_path):
        import json

        import bench
        # the newest round with a parsed value wins; a failed round (no
        # parsed payload) and an unreadable file are passed over
        (tmp_path / "BENCH_r01.json").write_text(json.dumps({"rc": 1}))
        (tmp_path / "BENCH_r02.json").write_text(json.dumps(
            {"parsed": {"value": 0.41, "detail": {}}}))
        (tmp_path / "BENCH_r03.json").write_text(json.dumps(
            {"parsed": {"value": 0.52, "detail": {"step_time_s": 0.3}}}))
        (tmp_path / "BENCH_r04.json").write_text("{truncated")
        prev = bench._prev_record(str(tmp_path))
        assert prev is not None and prev["value"] == pytest.approx(0.52)
        assert bench._prev_record(str(tmp_path / "nowhere")) is None

    def test_unknown_tpu_kind_has_no_peak(self):
        """A TPU the tables do not know is an error, not a v5p."""
        import types

        import bench
        v5e = types.SimpleNamespace(platform="tpu", device_kind="TPU v5 lite")
        new = types.SimpleNamespace(platform="tpu", device_kind="TPU v9x")
        assert bench._peak_flops(v5e) == 197e12
        with pytest.raises(ValueError, match="TPU v9x"):
            bench._peak_flops(new)
