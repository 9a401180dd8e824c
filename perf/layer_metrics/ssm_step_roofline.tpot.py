"""The decode program's ``ssm`` scope against its memory roofline, %: the
bytes the Mamba layers of one step must move (the architecture's count:
their weights once, the live rows' recurrent state read and written) over
the chip's HBM bandwidth, over the scope's device time in one
execution."""
import os

from perf import common

_scope = common.load_by_path(os.path.join(
    os.path.dirname(__file__), "ssm_device_ms.tpot.py"), "perf_scope_ms")


def read(obs):
    ms = _scope.read(obs, "decode", "ssm")
    count = getattr(common.arch_of(obs["cell"]["config"]),
                    "ssm_step_bytes", None)
    if not ms or count is None:
        return None
    need = count(obs["cell"]["config"], obs.get("live_rows") or 0.0)
    return 100.0 * need / obs["peaks"]["hbm_bytes_per_s"] / (ms / 1e3)
