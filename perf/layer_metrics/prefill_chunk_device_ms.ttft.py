"""Median device time of one execution of the prefill-chunk program, ms."""
import numpy as np

from perf import trace_reduce


def read(obs):
    ds = trace_reduce.program_ns(obs["trace"], "prefill_chunk") \
        if obs["trace"] else []
    return float(np.median(ds) / 1e6) if ds else None
