"""Share of the decode steps' token-expert picks that landed on an expert
held here, %: ``paddle_tpu_moe_local_picks_total`` over
``paddle_tpu_moe_picks_total`` (half of the experts are held: near 50)."""
from perf import common


def read(obs):
    picks = common.total("paddle_tpu_moe_picks_total")
    if not picks:
        return None
    return 100.0 * common.total("paddle_tpu_moe_local_picks_total") / picks
