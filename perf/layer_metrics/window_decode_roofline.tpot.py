"""The decode kernel in the sliding-window layers against its memory
roofline, %, over the traced window's decode executions: the stored keys
and values those layers must read — each live row's last
``sliding_window`` positions and no more, the program's own sum a
dispatch (``serving.kv_live``: the architecture's ``window_live`` and
``window_decode_bytes``) — over the chip's HBM bandwidth, over the
``paged_attention`` kernel's device time under ``attn_window``, both
summed over the executions (the need is linear in the positions, so the
sum over executions is the executions times the need at the mean).  A
kernel that walked a row's whole length would read low here by the ratio
of the lengths to the windows."""
import os

from perf import common

_window = common.load_by_path(os.path.join(
    os.path.dirname(__file__), "window_attn_device_ms.tpot.py"),
    "perf_window_runs")


def read(obs):
    runs = _window.window_runs(obs)
    arch = common.arch_of(obs["cell"]["config"])
    live = getattr(arch, "window_live", lambda: None)()
    if not runs or live is None:
        return None
    ns = [_window.under(r, arch.WINDOW_SCOPES[0], arch.DECODE_KERNEL)
          for r in runs]
    if not any(ns):
        return None
    steps = obs["cell"]["traffic"]["system"]["engine"].get(
        "steps_per_sync", 1)
    least_s = steps * arch.window_decode_bytes(
        obs["cell"]["config"], live[1]) / obs["peaks"]["hbm_bytes_per_s"]
    return 100.0 * len(ns) * least_s / (sum(ns) / 1e9)
