"""1 - union of device-op intervals / traced window, device 0, %."""
from perf import trace_reduce


def read(obs):
    return None if obs["trace"] is None else \
        100.0 * trace_reduce.idle_share(obs["trace"])
