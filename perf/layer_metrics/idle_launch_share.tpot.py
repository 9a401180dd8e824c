"""Device-idle time after the ``serving.dispatch`` span that enqueued an
execution ended and before the device went on — the runtime's launch, and
the bubbles between one execution's own operations —, % of the traced
window: every gap split by time (perf/pipeline_spans.py)."""
from perf import pipeline_spans


def read(obs):
    return pipeline_spans.idle_share(obs, "launch")
