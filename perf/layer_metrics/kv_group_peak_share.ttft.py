"""The fuller block group's peak share of its ids, %: of the full group
(blocks held at the fullest over used + free) and the window group (the
same over its own gauges), the larger — the group admission waits on
first.  A program without a window group reads nothing."""
from perf import common


def _share(prefix):
    size = common.total(prefix + "_used") + common.total(prefix + "_free")
    return common.total(prefix + "_used_peak") / size if size else None


def read(obs):
    shares = [_share("paddle_tpu_serving_kv_blocks"),
              _share("paddle_tpu_serving_kv_window_blocks")]
    if None in shares or not shares[1]:
        return None
    return 100.0 * max(shares)
