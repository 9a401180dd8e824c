"""How long after the device is done and the host is asking the tokens
arrive, ms: the median, over the traced window's decode dispatches, of the
end of the ``serving.sync`` that carries the dispatch's ``seq`` less the
later of that span's start and the end of the dispatch's execution on the
device (perf/pipeline_spans.py pairs the two by order and ``seq``)."""
from perf import pipeline_spans


def read(obs):
    return pipeline_spans.median_ms(obs, "copyback_ns")
