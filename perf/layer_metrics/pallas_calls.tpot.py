"""tpu_custom_call count in the compiled decode program (the paged
attention kernel): a silent re-route to XLA shows here first."""
from perf import common


def read(obs):
    prog = obs["programs"].get("decode")
    return None if prog is None else float(common.pallas_calls(prog))
