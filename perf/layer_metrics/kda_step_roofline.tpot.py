"""The decode program's ``kda`` scope against its memory roofline, %: the
bytes the delta rule's update of one step must move (the architecture's
``kda_step_bytes``: every live row's float32 state read and written, its
q, k, v, log-decay and beta in and o out, a KDA layer) over the chip's HBM
bandwidth, over the scope's device time in one execution."""
import os

from perf import common

_recurrence = common.load_by_path(os.path.join(
    os.path.dirname(__file__), "kda_device_ms.tpot.py"), "perf_recurrence_ms")


def read(obs):
    ms = _recurrence.read(obs, "decode")
    count = getattr(common.arch_of(obs["cell"]["config"]),
                    "kda_step_bytes", None)
    if not ms or count is None:
        return None
    steps = obs["cell"]["traffic"]["system"]["engine"].get(
        "steps_per_sync", 1)
    need = steps * count(obs["cell"]["config"], obs.get("live_rows") or 0.0)
    return 100.0 * need / obs["peaks"]["hbm_bytes_per_s"] / (ms / 1e3)
