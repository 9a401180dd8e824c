"""The prefill chunk's ``kda`` scope against its roofline, %: the larger
of the operations the delta rule needs over one chunk, counted as the
recurrence, over the chip's peak and the bytes it must move over its HBM
bandwidth (the architecture's ``kda_scan_cost``: one slot's state in and
out, the positions' inputs and outputs), over the scope's device time in
one execution."""
import os

from perf import common

_recurrence = common.load_by_path(os.path.join(
    os.path.dirname(__file__), "kda_device_ms.tpot.py"), "perf_recurrence_ms")


def read(obs):
    ms = _recurrence.read(obs, "prefill_chunk")
    count = getattr(common.arch_of(obs["cell"]["config"]),
                    "kda_scan_cost", None)
    if not ms or count is None:
        return None
    chunk = obs["cell"]["traffic"]["system"]["engine"]["prefill_chunk"]
    ops, moved = count(obs["cell"]["config"], chunk)
    least_s = max(ops / obs["peaks"]["bf16_flops"],
                  moved / obs["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least_s / (ms / 1e3)
