"""The prefill chunk's ``ssm`` scope against its roofline, %: the larger
of the operations the Mamba layers of one chunk need over the chip's peak
and the bytes they must move over its HBM bandwidth (the architecture's
counts: projections, the recurrence counted as the recurrence, the
convolution; weights, one slot's state, the activations), over the
scope's device time in one execution."""
import os

from perf import common

_scope = common.load_by_path(os.path.join(
    os.path.dirname(__file__), "ssm_device_ms.tpot.py"), "perf_scope_ms")


def read(obs):
    ms = _scope.read(obs, "prefill_chunk", "ssm")
    count = getattr(common.arch_of(obs["cell"]["config"]),
                    "ssm_scan_cost", None)
    if not ms or count is None:
        return None
    chunk = obs["cell"]["traffic"]["system"]["engine"]["prefill_chunk"]
    ops, moved = count(obs["cell"]["config"], chunk)
    least_s = max(ops / obs["peaks"]["bf16_flops"],
                  moved / obs["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least_s / (ms / 1e3)
