"""Device-idle time in which the host was at work on something other than
the next program's upload, % of the traced window: of each idle gap of
device 0, the part before the ``serving.dispatch`` that enqueued the
execution the gap ends at began, less what a ``serving.sync`` covers —
emit, the caller's loop, schedule, admit, build; a gap that ends at no
execution of the engine's goes here whole (perf/pipeline_spans.py)."""
from perf import pipeline_spans


def read(obs):
    return pipeline_spans.idle_share(obs, "host_working")
