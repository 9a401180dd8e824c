"""Device-idle time in which the host sat inside a ``serving.sync`` (the
device has nothing to run and the host is waiting for a copy), % of the
traced window: of each idle gap of device 0 that ends at an execution of
the engine's, the part before the dispatch that enqueued it began that a
sync span covers — every gap split by time (perf/pipeline_spans.py)."""
from perf import pipeline_spans


def read(obs):
    return pipeline_spans.idle_share(obs, "host_blocked")
