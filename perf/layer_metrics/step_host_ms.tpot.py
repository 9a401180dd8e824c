"""Median over the benchmark's ``bench.engine_step`` spans of (span - the
time the device was busy inside it), ms: what the host adds to a step."""
import numpy as np

from perf import trace_reduce


def read(obs):
    tr = obs["trace"]
    spans = trace_reduce.host_spans(tr, "bench.engine_step") if tr else []
    if not spans:
        return None
    busy = trace_reduce.busy_inside(tr, spans)
    return float(np.median([(d - b) / 1e6
                            for (_, d), b in zip(spans, busy)]))
