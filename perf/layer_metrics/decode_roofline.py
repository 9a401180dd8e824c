"""The decode program's share of its memory roofline, %: the bytes a step
must move (the architecture's count, perf/flops.py: every block weight
and the head once, the keys and values of the live contexts, and whatever
turns on the rows that were decoding) over the chip's HBM bandwidth, over
the median device time of the decode program."""
import numpy as np

from perf import flops, trace_reduce


def read(obs):
    if obs["trace"] is None:
        return None
    ds = trace_reduce.program_ns(obs["trace"], "decode")
    if not ds:
        return None
    need = flops.decode_step_bytes(obs["cell"]["config"],
                                   obs["live_kv_tokens"],
                                   live_rows=obs.get("live_rows"))
    least_s = need / obs["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least_s / (float(np.median(ds)) / 1e9)
