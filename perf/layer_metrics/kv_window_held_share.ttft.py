"""What the sliding-window layers held at their fullest over what they
would have held under one table, %: the window group's peak of blocks in
requests' rings (``paddle_tpu_serving_kv_window_blocks_used_peak``) over
the full group's peak (``paddle_tpu_serving_kv_blocks_used_peak``: a
block a 16 positions of every admitted request, which is what one table
gives every layer).  The allocator's saving: 100 means a ring bought
nothing (every request shorter than one).  A program without a window
group reads nothing."""
from perf import common


def read(obs):
    held = common.total("paddle_tpu_serving_kv_window_blocks_used_peak")
    one_table = common.total("paddle_tpu_serving_kv_blocks_used_peak")
    if not held or not one_table:
        return None
    return 100.0 * held / one_table
