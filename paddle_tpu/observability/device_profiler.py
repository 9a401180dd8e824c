"""Device-time profiler and roofline-gap attribution.

The host-side spans (tracing.py) stop at the ``jit`` dispatch boundary:
``train.dispatch`` says the compiled program took 212 ms, not which op
group inside it ate the time.  This module closes that gap with four
pieces:

* **Compile observability** — :func:`aot_compile` runs the explicit
  ``jit(fn).lower(...).compile()`` pipeline under ``compile.lower`` /
  ``compile.xla`` spans, counts compiles per target
  (``paddle_tpu_compile_total{target}``), records per-signature
  :class:`CompileInfo` entries (the content-addressed key a persistent
  AOT cache needs — ROADMAP item 5), and introspects the compiled
  executable: measured FLOPs, HBM bytes and peak device memory land in
  ``paddle_tpu_xla_flops`` / ``_xla_bytes_accessed`` /
  ``_xla_peak_bytes`` gauges labelled by executable.

* **Device timing** — :class:`DeviceProfiler` times named sub-segments
  of a step (op groups: rmsnorm, attention, MLP, lm-head+CE, …) as
  AOT-compiled executables under ``block_until_ready`` — the portable
  fallback that works on every backend.  ``capture_xla_trace`` wraps
  the real ``jax.profiler`` XPlane capture for offline TensorBoard /
  Perfetto analysis, and raises where the platform's profiler fails.  Each timed segment
  becomes a ``device.<name>`` child span of the enclosing step span, so
  the Perfetto export shows host and device time in one view.

* **Roofline-gap attribution** — :meth:`DeviceProfiler.profile` joins
  the measured device times against the PR-1 static cost model
  (``analysis.passes.cost_model``): each segment gets a predicted
  roofline time ``max(flops/peak, bytes/bw)`` and a **gap ratio**
  (measured / predicted).  The ranked table is the fusion target list
  for ROADMAP item 2 — the groups furthest below roofline are where
  block-level megakernels pay.

* **HBM accounting** — :class:`DeviceMemoryMonitor` samples live device
  bytes (``device.memory_stats()`` on TPU, ``jax.live_arrays()``
  elsewhere) into ``paddle_tpu_device_live_bytes`` and a monotone
  watermark gauge, groups live buffers by shape/dtype
  (:meth:`census`), and fires ``paddle_tpu_device_memory_leak_total``
  when live bytes grow strictly for a whole window.

Env knobs: ``PADDLE_TPU_PEAK_FLOPS`` / ``PADDLE_TPU_HBM_BW`` override
roofline detection; ``PADDLE_TPU_DEVICE_WATERMARK`` (default on) and
``PADDLE_TPU_WATERMARK_INTERVAL`` (default 1) control the per-step
sampling TrainStep does.
"""

from __future__ import annotations

import dataclasses
import os
import threading
import time
import warnings
from collections import deque
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp

__all__ = ["ExecutableStats", "CompileInfo", "aot_compile", "compiled_stats",
           "compile_records", "record_compile_info", "signature_of",
           "detect_roofline",
           "Segment", "SegmentReport", "AttributionResult", "DeviceProfiler",
           "segment_records", "record_segment_report",
           "DeviceMemoryMonitor", "device_memory_monitor",
           "llama_step_segments", "capture_xla_trace"]

# bf16 peak FLOP/s and HBM bytes/s per TPU generation (public specs);
# longest-substring match against device_kind, same scheme bench.py used
TPU_ROOFLINES: Dict[str, Tuple[float, float]] = {
    "v4": (275e12, 1228e9),
    "v5 lite": (197e12, 819e9), "v5e": (197e12, 819e9),
    "v5": (459e12, 2765e9), "v5p": (459e12, 2765e9),
    "v6 lite": (918e12, 1638e9), "v6e": (918e12, 1638e9),
    "trillium": (918e12, 1638e9),
}
# non-TPU fallback: a laptop-class core — the point on CPU is the
# RANKING (which group is furthest below ITS roofline), not absolute MFU
_HOST_ROOFLINE = (2e11, 5e10)


def detect_roofline(device=None, fallback: Optional[Tuple[float, float]]
                    = None) -> Tuple[float, float]:
    """(peak_flops, hbm_bytes_per_s) for ``device`` (default: device 0).
    ``PADDLE_TPU_PEAK_FLOPS`` / ``PADDLE_TPU_HBM_BW`` override either
    number; ``fallback`` replaces the host default for unknown kinds
    (bench.py passes the v5p numbers to keep its MFU denominator)."""
    if device is None:
        device = jax.devices()[0]
    kind = getattr(device, "device_kind", "").lower()
    peak = bw = None
    for key, val in sorted(TPU_ROOFLINES.items(), key=lambda kv: -len(kv[0])):
        if key in kind:
            peak, bw = val
            break
    if peak is None:
        if getattr(device, "platform", "") == "tpu":
            raise ValueError(
                f"no roofline for TPU device_kind {device.device_kind!r}: "
                f"add its published peaks to TPU_ROOFLINES "
                f"(known: {sorted(TPU_ROOFLINES)})")
        else:
            peak, bw = fallback if fallback is not None else _HOST_ROOFLINE
    env_peak = os.environ.get("PADDLE_TPU_PEAK_FLOPS")
    env_bw = os.environ.get("PADDLE_TPU_HBM_BW")
    if env_peak:
        peak = float(env_peak)
    if env_bw:
        bw = float(env_bw)
    return float(peak), float(bw)


# -- compiled-executable introspection ---------------------------------------
@dataclasses.dataclass
class ExecutableStats:
    """What XLA says about a compiled module: measured (post-fusion)
    FLOPs and bytes from ``cost_analysis()``, buffer sizes from
    ``memory_analysis()``.  Zeros where the backend reports nothing."""

    flops: float = 0.0
    bytes_accessed: float = 0.0
    transcendentals: float = 0.0
    argument_bytes: int = 0
    output_bytes: int = 0
    temp_bytes: int = 0
    alias_bytes: int = 0
    code_bytes: int = 0

    @property
    def peak_bytes(self) -> int:
        """Peak device-memory footprint of one execution: arguments +
        outputs + XLA temp allocations (aliased bytes counted once)."""
        return max(0, self.argument_bytes + self.output_bytes
                   + self.temp_bytes - self.alias_bytes)


def compiled_stats(compiled) -> ExecutableStats:
    """Introspect a compiled executable (``lowered.compile()`` result).
    Defensive: every backend reports a different subset; absent numbers
    stay 0 rather than raising."""
    st = ExecutableStats()
    try:
        ca = compiled.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0] if ca else {}
        st.flops = float(ca.get("flops", 0.0) or 0.0)
        st.bytes_accessed = float(ca.get("bytes accessed", 0.0) or 0.0)
        st.transcendentals = float(ca.get("transcendentals", 0.0) or 0.0)
    except Exception:
        pass
    try:
        ma = compiled.memory_analysis()
        if ma is not None:
            st.argument_bytes = int(getattr(ma, "argument_size_in_bytes", 0))
            st.output_bytes = int(getattr(ma, "output_size_in_bytes", 0))
            st.temp_bytes = int(getattr(ma, "temp_size_in_bytes", 0))
            st.alias_bytes = int(getattr(ma, "alias_size_in_bytes", 0))
            st.code_bytes = int(getattr(ma,
                                        "generated_code_size_in_bytes", 0))
    except Exception:
        pass
    return st


def signature_of(tree) -> str:
    """Stable string signature of a pytree's structure + leaf avals —
    the same thing jax.jit keys its executable cache on, and the
    content-addressed key a persistent AOT cache would use."""
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    parts = []
    for leaf in leaves:
        try:
            parts.append(f"{np.result_type(leaf)}{list(np.shape(leaf))}")
        except Exception:
            parts.append(type(leaf).__name__)
    return f"{treedef}|{';'.join(parts)}"


@dataclasses.dataclass
class CompileInfo:
    """One explicit compile: target name, argument signature, phase wall
    times, and what XLA measured about the result.  ``cached=True``
    marks a persistent-cache hit (``compile_cache``): no trace or XLA
    compile happened — ``compile_s`` is the deserialize-and-load time."""

    target: str
    signature: str
    lower_s: float
    compile_s: float
    stats: ExecutableStats
    cached: bool = False

    @property
    def total_s(self) -> float:
        return self.lower_s + self.compile_s


_COMPILE_LOG: deque = deque(maxlen=512)
_COMPILE_LOCK = threading.Lock()


def record_compile_info(info: CompileInfo):
    """Append an externally-produced record to the compile log — the
    compile-cache hit path uses this so ``compile_records()`` still
    lists every executable a boot acquired, while
    ``paddle_tpu_compile_total`` keeps meaning 'explicit XLA
    compiles'."""
    with _COMPILE_LOCK:
        _COMPILE_LOG.append(info)


def compile_records(target: Optional[str] = None) -> List[CompileInfo]:
    """Recent :class:`CompileInfo` entries (optionally one target's) —
    (target, signature) is exactly the key a persistent AOT artifact
    cache is addressed by."""
    with _COMPILE_LOCK:
        records = list(_COMPILE_LOG)
    if target is not None:
        records = [r for r in records if r.target == target]
    return records


def _compile_metrics(registry=None):
    if registry is None:
        from paddle_tpu.observability.metrics import default_registry
        registry = default_registry()
    return {
        "compiles": registry.counter(
            "paddle_tpu_compile_total",
            "explicit XLA compiles (trace+lower+compile) per target",
            labelnames=("target",)),
        "seconds": registry.histogram(
            "paddle_tpu_compile_seconds",
            "wall time of compile phases (lower = trace+StableHLO, "
            "xla = backend compile)", labelnames=("phase",)),
        "flops": registry.gauge(
            "paddle_tpu_xla_flops",
            "XLA cost_analysis FLOPs of the most recent compile of this "
            "executable", labelnames=("executable",)),
        "bytes": registry.gauge(
            "paddle_tpu_xla_bytes_accessed",
            "XLA cost_analysis bytes accessed (post-fusion HBM traffic)",
            labelnames=("executable",)),
        "peak": registry.gauge(
            "paddle_tpu_xla_peak_bytes",
            "peak device-memory footprint (args + outputs + temps) of "
            "this executable", labelnames=("executable",)),
    }


def aot_compile(fn: Callable, *args, target: str = "fn",
                donate_argnums=(), registry=None,
                **kwargs) -> Tuple[Any, CompileInfo]:
    """Explicit ``lower → compile`` with full observability.

    ``fn`` may be a plain callable (wrapped in ``jax.jit``) or an
    already-jitted function (its own donation/static config is kept).
    Returns ``(compiled_executable, CompileInfo)``.  The executable is
    called like the original function but never retraces — a shape
    mismatch raises instead of silently recompiling, which is the
    contract a serving tier wants."""
    from paddle_tpu.observability.tracing import tracer

    jfn = fn if hasattr(fn, "lower") else jax.jit(
        fn, donate_argnums=donate_argnums)
    metrics = _compile_metrics(registry)
    tr = tracer()
    with tr.span("compile", target=target):
        t0 = time.perf_counter()
        with tr.span("compile.lower", target=target):
            lowered = jfn.lower(*args, **kwargs)
        t1 = time.perf_counter()
        with tr.span("compile.xla", target=target):
            compiled = lowered.compile()
        t2 = time.perf_counter()
    stats = compiled_stats(compiled)
    info = CompileInfo(target=target,
                       signature=signature_of((args, kwargs)),
                       lower_s=t1 - t0, compile_s=t2 - t1, stats=stats)
    with _COMPILE_LOCK:
        _COMPILE_LOG.append(info)
    metrics["compiles"].labels(target=target).inc()
    metrics["seconds"].labels(phase="lower").observe(info.lower_s)
    metrics["seconds"].labels(phase="xla").observe(info.compile_s)
    if stats.flops:
        metrics["flops"].labels(executable=target).set(stats.flops)
    if stats.bytes_accessed:
        metrics["bytes"].labels(executable=target).set(stats.bytes_accessed)
    if stats.peak_bytes:
        metrics["peak"].labels(executable=target).set(stats.peak_bytes)
    try:
        from paddle_tpu.observability.recorder import flight_recorder
        flight_recorder().record("compile", target=target,
                                 lower_s=round(info.lower_s, 4),
                                 compile_s=round(info.compile_s, 4),
                                 flops=stats.flops)
    except Exception:
        pass
    return compiled, info


def capture_xla_trace(fn: Callable[[], Any],
                      logdir: Optional[str] = None) -> str:
    """``jax.profiler`` XPlane capture around ``fn()`` — the
    full-fidelity device trace (HLO timelines, per-fusion device time;
    the tracer's spans on its host plane) for offline analysis.
    Returns the logdir holding the capture.  A profiler that cannot
    start or stop, or that wrote no ``.xplane.pb``, raises
    ``RuntimeError`` with the cause: the caller decides whether its own
    numbers can do without the capture."""
    import glob
    import tempfile
    if logdir is None:
        logdir = tempfile.mkdtemp(prefix="paddle_tpu_xla_trace_")
    try:
        jax.profiler.start_trace(logdir)
    except Exception as e:
        raise RuntimeError(
            f"jax.profiler could not start a trace in {logdir}: {e}") from e
    try:
        out = fn()
        jax.block_until_ready(out)
    finally:
        try:
            jax.profiler.stop_trace()
        except Exception as e:
            raise RuntimeError(
                f"jax.profiler could not stop the trace in {logdir}: "
                f"{e}") from e
    if not glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                     recursive=True):
        raise RuntimeError(f"jax.profiler wrote no .xplane.pb under "
                           f"{logdir}")
    return logdir


# -- segment timing + roofline-gap attribution -------------------------------
@dataclasses.dataclass
class Segment:
    """One instrumented sub-segment of a step: a pure function plus the
    example args it runs on.  ``count`` is how many times the op group
    occurs per full step (L attention calls per forward, …) so totals
    approximate the step's composition."""

    name: str
    fn: Callable
    args: tuple
    kwargs: dict = dataclasses.field(default_factory=dict)
    count: int = 1
    group: str = "op"


@dataclasses.dataclass
class SegmentReport:
    """Measured-vs-predicted roofline coordinates of one segment."""

    name: str
    count: int
    group: str
    device_s: float            # measured wall time per call (min of reps)
    compile_s: float
    flops: float               # XLA cost_analysis (post-fusion)
    bytes_accessed: float
    peak_bytes: int
    model_flops: float         # PR-1 static cost model (pre-fusion)
    model_bytes: float
    predicted_s: float         # roofline lower bound from the cost model
    gap: float                 # device_s / predicted_s (1.0 = at roofline)
    bound: str                 # "compute" | "memory" | "?"

    @property
    def total_device_s(self) -> float:
        return self.device_s * self.count

    @property
    def excess_s(self) -> float:
        """Absolute time above roofline across all occurrences — the
        megakernel prize for this group."""
        return max(0.0, self.device_s - self.predicted_s) * self.count

    def to_dict(self) -> dict:
        return {"name": self.name, "count": self.count, "group": self.group,
                "device_ms": self.device_s * 1e3,
                "predicted_ms": self.predicted_s * 1e3,
                "gap": self.gap, "bound": self.bound,
                "excess_ms": self.excess_s * 1e3,
                "flops": self.flops, "bytes_accessed": self.bytes_accessed,
                "peak_bytes": self.peak_bytes,
                "compile_s": self.compile_s}


@dataclasses.dataclass
class AttributionResult:
    """The joined table: every profiled segment with measured device
    time, predicted roofline time, and gap ratio, rankable by gap."""

    segments: List[SegmentReport]
    peak_flops: float
    hbm_bw: float
    xla_trace_dir: Optional[str] = None

    def ranked(self) -> List[SegmentReport]:
        """Furthest-below-roofline first — the fusion target list."""
        return sorted(self.segments, key=lambda s: -s.gap)

    def to_dicts(self, top: Optional[int] = None) -> List[dict]:
        rows = [s.to_dict() for s in self.ranked()]
        return rows[:top] if top else rows

    def table(self) -> str:
        lines = [
            "-- roofline-gap attribution (measured device time vs "
            "predicted roofline) --",
            f"{'segment':20s} {'n':>3s} {'device(ms)':>11s} "
            f"{'roofline(ms)':>13s} {'gap':>8s} {'bound':>8s} "
            f"{'excess(ms)':>11s}"]
        for s in self.ranked():
            gap = f"{s.gap:8.1f}" if s.gap != float("inf") else "     inf"
            lines.append(
                f"{s.name:20s} {s.count:3d} {s.device_s * 1e3:11.3f} "
                f"{s.predicted_s * 1e3:13.4f} {gap} {s.bound:>8s} "
                f"{s.excess_s * 1e3:11.3f}")
        lines.append(
            f"roofline: {self.peak_flops / 1e12:.1f} TFLOP/s, "
            f"{self.hbm_bw / 1e9:.0f} GB/s; gap = measured/roofline "
            "(unfused model bytes -> predicted is conservative); rank "
            "order = fusion target list")
        return "\n".join(lines)


_SEGMENT_BUCKETS = (1e-5, 2.5e-5, 1e-4, 2.5e-4, 1e-3, 2.5e-3, 1e-2,
                    2.5e-2, 0.1, 0.25, 1.0, 2.5, 10.0)


# process-wide segment-timing log mirroring the compile log above:
# segment timings used to be fire-and-forget (alive only inside the
# AttributionResult a single profile() call returned) — every measured
# row now also lands here so the measurement ledger and tests consume
# structured SegmentReports instead of parsing summary tables
_SEGMENT_LOG: deque = deque(maxlen=512)
_SEGMENT_LOCK = threading.Lock()


def record_segment_report(report: SegmentReport):
    """Append an externally-produced row to the segment log (mirrors
    :func:`record_compile_info`)."""
    with _SEGMENT_LOCK:
        _SEGMENT_LOG.append(report)


def segment_records(name: Optional[str] = None) -> List[SegmentReport]:
    """Recent :class:`SegmentReport` rows across every profiler in the
    process (optionally one segment's) — the structured counterpart of
    :func:`compile_records` for measured device time."""
    with _SEGMENT_LOCK:
        records = list(_SEGMENT_LOG)
    if name is not None:
        records = [r for r in records if r.name == name]
    return records


def _primary_shape_dtype(args) -> Tuple[tuple, str]:
    """The ledger shape/dtype key of a segment: its highest-rank array
    leaf (ties: the larger one) — for every llama segment that is the
    activation ``x``, which is exactly what a query site knows."""
    best = None
    for leaf in jax.tree_util.tree_leaves(args):
        shape = getattr(leaf, "shape", None)
        dtype = getattr(leaf, "dtype", None)
        if shape is None or dtype is None:
            continue
        size = 1
        for dim in shape:
            size *= max(1, int(dim))
        rank = len(shape)
        if best is None or (rank, size) > (best[0], best[1]):
            best = (rank, size, tuple(shape), str(dtype))
    if best is None:
        return (), ""
    return best[2], best[3]


class DeviceProfiler:
    """Times instrumented sub-segments of a step on the device and
    attributes the roofline gap per op group.

        prof = DeviceProfiler()
        for seg in llama_step_segments(model, batch):
            prof.add(seg)
        result = prof.profile(reps=3)
        print(result.table())          # ranked fusion target list
    """

    def __init__(self, peak_flops: Optional[float] = None,
                 hbm_bw: Optional[float] = None, registry=None):
        det_peak, det_bw = detect_roofline()
        self.peak_flops = float(peak_flops) if peak_flops else det_peak
        self.hbm_bw = float(hbm_bw) if hbm_bw else det_bw
        self._segments: List[Segment] = []
        if registry is None:
            from paddle_tpu.observability.metrics import default_registry
            registry = default_registry()
        self._registry = registry
        self._records: List[SegmentReport] = []
        self._seg_hist = registry.histogram(
            "paddle_tpu_device_segment_seconds",
            "measured per-call device time of profiled step segments",
            labelnames=("segment",), buckets=_SEGMENT_BUCKETS)

    def add(self, segment: Segment) -> "DeviceProfiler":
        self._segments.append(segment)
        return self

    def records(self, name: Optional[str] = None) -> List[SegmentReport]:
        """Every :class:`SegmentReport` this profiler measured, across
        all its ``profile()`` calls (optionally one segment's) — the
        structured accessor mirroring :func:`compile_records`, so the
        measurement ledger and tests get rows, not tables."""
        records = list(self._records)
        if name is not None:
            records = [r for r in records if r.name == name]
        return records

    def add_segment(self, name: str, fn: Callable, *args, count: int = 1,
                    group: str = "op", **kwargs) -> "DeviceProfiler":
        return self.add(Segment(name, fn, args, kwargs, count, group))

    def _feed_ledger(self, seg: Segment, report: SegmentReport):
        """Measurement-ledger feeder (PADDLE_TPU_CALIBRATION=1): every
        measured segment lands with its roofline prediction, keyed by
        the activation shape and the fusion tier active when it was
        measured — so 'decoder_block under tier=fused' and 'under
        tier=decoder' are distinct populations the measured tier router
        can compare."""
        from paddle_tpu.observability import calibration
        if not calibration.enabled():
            return
        try:
            from paddle_tpu.ops.pallas.fused_block import fused_block_tier
            tier = fused_block_tier()
        except Exception:
            tier = "-"
        try:
            shape, dtype = _primary_shape_dtype(seg.args)
            calibration.ledger().record(
                seg.name, shape, dtype,
                measured_s=report.device_s,
                predicted_s=report.predicted_s,
                layout=f"tier={tier}", provenance="device_profiler",
                save=False)
        except Exception:
            pass

    def _save_ledger(self):
        from paddle_tpu.observability import calibration
        if calibration.enabled():
            calibration.ledger().save()

    def _predict(self, seg: Segment):
        """Static roofline prediction from the PR-1 cost model; zeros
        when the segment can't be traced abstractly (the join then
        reports gap=inf, which still ranks it for a look)."""
        try:
            import paddle_tpu.analysis as analysis
            report = analysis.check(
                seg.fn, *seg.args, passes=["cost-model"],
                options={"peak_flops": self.peak_flops,
                         "hbm_bw": self.hbm_bw}, **seg.kwargs)
            cost = report.extras.get("cost")
            if cost is None:
                return 0.0, 0.0, 0.0, "?"
            pred = cost.roofline_seconds()
            bound = "compute" if cost.compute_bound else "memory"
            return pred, float(cost.total_flops), float(cost.total_bytes), \
                bound
        except Exception:
            return 0.0, 0.0, 0.0, "?"

    def profile(self, reps: int = 3, warmup: int = 1,
                parent_span: str = "train.step",
                capture_xla: bool = False) -> AttributionResult:
        """Compile + time every registered segment.  The whole pass
        runs under a span named ``parent_span`` (attr
        ``phase=device_profile``) and each segment's timed region is a
        ``device.<name>`` child — the Perfetto export shows the device
        decomposition nested under the step."""
        from paddle_tpu.observability.tracing import tracer
        tr = tracer()
        reports: List[SegmentReport] = []
        trace_dir = None
        with tr.span(parent_span, phase="device_profile"):
            for seg in self._segments:
                try:
                    compiled, info = aot_compile(
                        seg.fn, *seg.args, target=seg.name,
                        registry=self._registry, **seg.kwargs)
                except Exception:
                    continue      # an untraceable segment must not kill
                for _ in range(max(0, warmup)):
                    jax.block_until_ready(compiled(*seg.args))
                times = []
                with tr.span(f"device.{seg.name}", reps=reps,
                             count=seg.count) as sp:
                    for _ in range(max(1, reps)):
                        t0 = time.perf_counter()
                        out = compiled(*seg.args)
                        jax.block_until_ready(out)
                        times.append(time.perf_counter() - t0)
                    device_s = min(times)
                    sp.set_attribute("device_ms", device_s * 1e3)
                self._seg_hist.labels(segment=seg.name).observe(device_s)
                pred_s, mflops, mbytes, bound = self._predict(seg)
                gap = device_s / pred_s if pred_s > 0 else float("inf")
                report = SegmentReport(
                    name=seg.name, count=seg.count, group=seg.group,
                    device_s=device_s, compile_s=info.total_s,
                    flops=info.stats.flops,
                    bytes_accessed=info.stats.bytes_accessed,
                    peak_bytes=info.stats.peak_bytes,
                    model_flops=mflops, model_bytes=mbytes,
                    predicted_s=pred_s, gap=gap, bound=bound)
                reports.append(report)
                self._records.append(report)
                record_segment_report(report)
                self._feed_ledger(seg, report)
            if capture_xla and self._segments:
                seg = self._segments[0]
                try:
                    trace_dir = capture_xla_trace(
                        lambda: seg.fn(*seg.args, **seg.kwargs))
                except RuntimeError as e:
                    # the segment timings above do not depend on the
                    # capture: keep them, and say why it is missing
                    warnings.warn(f"DeviceProfiler: no XLA trace: {e}")
        if reports:
            self._save_ledger()
        return AttributionResult(segments=reports,
                                 peak_flops=self.peak_flops,
                                 hbm_bw=self.hbm_bw,
                                 xla_trace_dir=trace_dir)


def llama_step_segments(model, batch: Dict[str, Any],
                        grad: bool = True) -> List[Segment]:
    """Decompose a Llama-family CausalLM step into its op groups — the
    granularity ROADMAP item 2's megakernels would fuse at.  Forward
    groups: embed, rmsnorm, attention, SwiGLU MLP, a whole decoder
    block (composite), and the fused lm-head+CE; ``grad=True`` adds
    fwd+bwd variants of attention and MLP (the step is fwd+bwd, and
    the backward's roofline differs)."""
    from paddle_tpu.core.dispatch import unwrap
    from paddle_tpu.core.functional import functional_call, params_of

    inner = getattr(model, "model", None)
    layers = getattr(inner, "layers", None)
    if inner is None or not layers:
        raise ValueError(
            f"{type(model).__name__} is not a Llama-family CausalLM "
            "(need .model.layers); build Segments by hand instead")
    cfg = model.config
    layer0 = layers[0]
    ids = jnp.asarray(np.asarray(batch["input_ids"], np.int32))
    labels = jnp.asarray(np.asarray(batch["labels"], np.int32))
    b, s = ids.shape
    d = cfg.hidden_size
    L = cfg.num_hidden_layers

    attn_p = params_of(layer0.self_attn)
    dtype = next(iter(attn_p.values())).dtype
    x = jax.random.normal(jax.random.PRNGKey(0), (b, s, d)).astype(dtype)
    cos = unwrap(inner.rope_cos)
    sin = unwrap(inner.rope_sin)

    embed_p = params_of(inner.embed_tokens)
    norm_p = params_of(layer0.input_layernorm)
    mlp_p = params_of(layer0.mlp)
    block_p = params_of(layer0)
    if model.lm_head is not None:
        head_p = params_of(model.lm_head)
        w_of = lambda p: p["weight"]
    else:                       # tied embeddings: lm-head is embedT
        head_p = {"weight": unwrap(inner.embed_tokens.weight)}
        w_of = lambda p: p["weight"].T

    def embed_fn(p, i):
        return unwrap(functional_call(inner.embed_tokens, p, i))

    def rmsnorm_fn(p, h):
        return unwrap(functional_call(layer0.input_layernorm, p, h))

    def attn_fn(p, h, c, si):
        return unwrap(functional_call(layer0.self_attn, p, h, c, si))

    def mlp_fn(p, h):
        return unwrap(functional_call(layer0.mlp, p, h))

    def norm_qkv_fn(ps, h):
        # the fusion boundary ROADMAP-2 targets: input rmsnorm + the
        # three projections, routed exactly like the decoder layer
        # (fused Pallas kernel when PADDLE_TPU_FUSED_BLOCK allows) —
        # flip the knob between profiler runs for before/after numbers
        pn, pa = ps
        from paddle_tpu.ops.pallas import fused_block as FB
        wq, wk, wv = (pa["q_proj.weight"], pa["k_proj.weight"],
                      pa["v_proj.weight"])
        rows = 1
        for dim in h.shape[:-1]:
            rows *= int(dim)
        if FB.fused_block_enabled() and FB.fused_qkv_eligible(
                rows, int(h.shape[-1]), int(wq.shape[-1]),
                int(wk.shape[-1]), int(wv.shape[-1]), h.dtype):
            return FB.fused_rmsnorm_qkv(h, pn["weight"], wq, wk, wv,
                                        epsilon=cfg.rms_norm_eps)
        xn = unwrap(functional_call(layer0.input_layernorm, pn, h))
        return xn @ wq, xn @ wk, xn @ wv

    def block_fn(p, h, c, si):
        return unwrap(functional_call(layer0, p, h, c, si))

    def block_fused_fn(p, h):
        # the whole-decoder-block fusion boundary (ISSUE 15): routed
        # exactly like LlamaDecoderLayer.forward — with
        # PADDLE_TPU_FUSED_BLOCK=decoder and eligible shapes the block
        # runs as ONE Pallas megakernel, otherwise the per-segment /
        # unfused layer; flip the knob between profiler runs for the
        # before/after attribution row
        from paddle_tpu.ops.pallas import fused_block as FB
        nh = cfg.num_attention_heads
        nkvh = cfg.num_key_value_heads
        hd = cfg.head_dim
        fcols = int(p["mlp.gate_proj.weight"].shape[-1])
        rows = 1
        for dim in h.shape[:-1]:
            rows *= int(dim)
        if FB.fused_decoder_enabled() and FB.fused_decoder_eligible(
                int(h.shape[0]), int(h.shape[1]), int(h.shape[-1]),
                nh * hd, nkvh * hd, hd, fcols, h.dtype) and \
                int(cos.shape[0]) >= int(h.shape[1]):
            return FB.fused_decoder_block(
                h, p["input_layernorm.weight"],
                p["self_attn.q_proj.weight"], p["self_attn.k_proj.weight"],
                p["self_attn.v_proj.weight"], cos, sin,
                p["self_attn.o_proj.weight"],
                p["post_attention_layernorm.weight"],
                p["mlp.gate_proj.weight"], p["mlp.up_proj.weight"],
                p["mlp.down_proj.weight"], num_heads=nh,
                num_kv_heads=nkvh, epsilon=cfg.rms_norm_eps)
        return unwrap(functional_call(layer0, p, h, cos, sin))

    def head_fn(p, h, lbl):
        from paddle_tpu.nn import functional as F
        loss = F.fused_linear_cross_entropy(
            h.reshape(-1, d), w_of(p), lbl.reshape(-1))
        return unwrap(loss)

    segs = [
        Segment("embed", embed_fn, (embed_p, ids), count=1, group="memory"),
        Segment("rmsnorm", rmsnorm_fn, (norm_p, x), count=2 * L + 1),
        Segment("rmsnorm_qkv", norm_qkv_fn, ((norm_p, attn_p), x),
                count=L, group="fused_boundary"),
        Segment("attention", attn_fn, (attn_p, x, cos, sin), count=L),
        Segment("mlp", mlp_fn, (mlp_p, x), count=L),
        Segment("decoder_block", block_fn, (block_p, x, cos, sin),
                count=L, group="composite"),
        Segment("decoder_block_fused", block_fused_fn, (block_p, x),
                count=L, group="fused_boundary"),
        Segment("lm_head_ce", head_fn, (head_p, x, labels), count=1),
    ]
    if grad:
        attn_vg = jax.value_and_grad(
            lambda p, h, c, si:
            attn_fn(p, h, c, si).astype(jnp.float32).sum(),
            argnums=(0, 1))
        mlp_vg = jax.value_and_grad(
            lambda p, h: mlp_fn(p, h).astype(jnp.float32).sum(),
            argnums=(0, 1))
        segs += [
            Segment("attention_fwdbwd", attn_vg, (attn_p, x, cos, sin),
                    count=L, group="fwdbwd"),
            Segment("mlp_fwdbwd", mlp_vg, (mlp_p, x), count=L,
                    group="fwdbwd"),
        ]
    return segs


# -- HBM live-buffer census + watermark --------------------------------------
class DeviceMemoryMonitor:
    """Live device-memory accounting: ``sample()`` reads the current
    live bytes (``device.memory_stats()`` when the backend has it, else
    a ``jax.live_arrays()`` sweep), updates the live/watermark gauges,
    and runs leak detection — live bytes growing STRICTLY for a whole
    window of samples by at least ``leak_min_bytes`` fires the leak
    counter and a flight-recorder event.  ``census()`` groups live
    buffers by dtype/shape, largest first — the "what is holding my
    HBM" table."""

    def __init__(self, registry=None, leak_window: int = 16,
                 leak_min_bytes: int = 16 << 20):
        if registry is None:
            from paddle_tpu.observability.metrics import default_registry
            registry = default_registry()
        self._live = registry.gauge(
            "paddle_tpu_device_live_bytes",
            "bytes currently held by live device buffers")
        self._buffers = registry.gauge(
            "paddle_tpu_device_live_buffers",
            "count of live device buffers")
        self._watermark_g = registry.gauge(
            "paddle_tpu_device_hbm_watermark_bytes",
            "high-water mark of live device bytes seen by sampling")
        self._leaks = registry.counter(
            "paddle_tpu_device_memory_leak_total",
            "leak-detector firings: live bytes grew strictly for a "
            "whole sampling window")
        self.leak_window = max(2, int(leak_window))
        self.leak_min_bytes = int(leak_min_bytes)
        self._window: deque = deque(maxlen=self.leak_window)
        self._watermark = 0
        self._lock = threading.Lock()

    # measurement -----------------------------------------------------------
    @staticmethod
    def measure() -> Tuple[int, int]:
        """(live_bytes, buffer_count).  TPU/GPU backends report
        allocator truth via memory_stats; elsewhere the live-array
        sweep is the portable estimate."""
        try:
            stats = [d.memory_stats() for d in jax.devices()
                     if hasattr(d, "memory_stats")]
            stats = [s for s in stats if s and "bytes_in_use" in s]
            if stats:
                return (sum(int(s["bytes_in_use"]) for s in stats),
                        len(jax.live_arrays()))
        except Exception:
            pass
        try:
            arrs = jax.live_arrays()
            return sum(int(a.nbytes) for a in arrs), len(arrs)
        except Exception:
            return 0, 0

    @property
    def watermark(self) -> int:
        return self._watermark

    def sample(self, live_bytes: Optional[int] = None,
               buffers: Optional[int] = None, step=None) -> int:
        """One sampling tick (TrainStep calls this per step).  The
        ``live_bytes`` override exists for tests and for callers that
        already measured."""
        if live_bytes is None:
            live_bytes, buffers = self.measure()
        with self._lock:
            self._live.set(float(live_bytes))
            if buffers is not None:
                self._buffers.set(float(buffers))
            if live_bytes > self._watermark:
                self._watermark = live_bytes
                self._watermark_g.set(float(live_bytes))
            self._window.append(int(live_bytes))
            if len(self._window) == self.leak_window:
                w = list(self._window)
                grew = all(b > a for a, b in zip(w, w[1:]))
                if grew and w[-1] - w[0] >= self.leak_min_bytes:
                    self._leaks.inc()
                    self._window.clear()
                    try:
                        from paddle_tpu.observability.recorder import \
                            flight_recorder
                        flight_recorder().record(
                            "device.memory_leak", step=step,
                            growth_bytes=w[-1] - w[0],
                            window=self.leak_window,
                            live_bytes=int(live_bytes))
                    except Exception:
                        pass
        return int(live_bytes)

    @staticmethod
    def census(top: int = 10) -> List[dict]:
        """Live buffers grouped by (dtype, shape), largest total bytes
        first — name the tensors, not just the total."""
        groups: Dict[Tuple[str, tuple], List[int]] = {}
        try:
            arrs = jax.live_arrays()
        except Exception:
            arrs = []
        for a in arrs:
            try:
                key = (str(a.dtype), tuple(a.shape))
                g = groups.setdefault(key, [0, 0])
                g[0] += 1
                g[1] += int(a.nbytes)
            except Exception:
                continue
        rows = [{"dtype": k[0], "shape": list(k[1]), "count": c,
                 "bytes": b} for k, (c, b) in groups.items()]
        rows.sort(key=lambda r: -r["bytes"])
        return rows[:top]


_MONITOR: Optional[DeviceMemoryMonitor] = None
_MONITOR_LOCK = threading.Lock()


def device_memory_monitor() -> DeviceMemoryMonitor:
    """Process-wide monitor (TrainStep's per-step watermark sampling
    writes here; tests may build private instances)."""
    global _MONITOR
    if _MONITOR is None:
        with _MONITOR_LOCK:
            if _MONITOR is None:
                _MONITOR = DeviceMemoryMonitor()
    return _MONITOR
