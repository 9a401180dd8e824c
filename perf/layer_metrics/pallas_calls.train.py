"""tpu_custom_call count in the compiled train step: a silent re-route of
a kernel to XLA shows here first."""
from perf import common


def read(obs):
    prog = obs["programs"].get("train")
    return None if prog is None else float(common.pallas_calls(prog))
