"""Held experts that at least one row chose, a layer a decode step: the
mean of the program's ``paddle_tpu_moe_experts_touched`` (its sum over its
layer-steps) since the process started."""
from perf import common


def read(obs):
    got = common.series("paddle_tpu_moe_experts_touched")
    if not got.get("layer_steps"):
        return None
    return got["sum"] / got["layer_steps"]
