"""The ``latent_attention`` kernel against its roofline, %, over the
traced window's decode executions: the larger of the stored latent rows
of the live cached tokens over the chip's HBM bandwidth and the absorbed
form's operations over its peak (the architecture's
``latent_decode_cost``: every live cached token's row read once a layer,
2 H (row + rank) operations a pair), over the kernel's device time, both
summed over the executions.  The live cached tokens are the harness's
mean over the window (``live_kv_tokens``: both needs are linear in them,
so the sum over executions is the executions times the need at the
mean)."""
import os

from perf import common

_kernel = common.load_by_path(os.path.join(
    os.path.dirname(__file__), "latent_attention_device_ms.tpot.py"),
    "perf_latent_kernel")


def read(obs):
    runs = _kernel.kernel_runs(obs)
    count = getattr(common.arch_of(obs["cell"]["config"]),
                    "latent_decode_cost", None)
    if not runs or count is None:
        return None
    steps = obs["cell"]["traffic"]["system"]["engine"].get(
        "steps_per_sync", 1)
    ops, moved = count(obs["cell"]["config"], obs["live_kv_tokens"])
    least_s = steps * max(ops / obs["peaks"]["bf16_flops"],
                          moved / obs["peaks"]["hbm_bytes_per_s"])
    return 100.0 * len(runs) * least_s / (sum(runs) / 1e9)
