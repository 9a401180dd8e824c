"""Device-idle time inside the ``serving.dispatch`` span that enqueued the
execution the gap ends at (the argument uploads and the compiled call), %
of the traced window: every gap split by time (perf/pipeline_spans.py)."""
from perf import pipeline_spans


def read(obs):
    return pipeline_spans.idle_share(obs, "upload")
