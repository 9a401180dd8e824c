"""Device time under the scope ``kda`` (the delta rule's chunked scan
alone: the decays inside a chunk, the triangular solve, the scan over
chunks) inside one execution of the prefill-chunk program, summed over
its KDA layers, ms, median over executions."""
import os

from perf import common

_recurrence = common.load_by_path(os.path.join(
    os.path.dirname(__file__), "kda_device_ms.tpot.py"), "perf_recurrence_ms")


def read(obs):
    return _recurrence.read(obs, "prefill_chunk")
