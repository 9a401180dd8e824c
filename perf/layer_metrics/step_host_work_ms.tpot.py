"""What the host does a step when it is not waiting, ms: the median, over
the traced window's ``serving.step`` spans that hold a ``serving.dispatch``
of kind ``decode``, of the span less the ``serving.sync`` spans inside it
(the step's self time: schedule, build, upload and call, emit).  The number
to hold against ``decode_device_ms.tpot``: the pipeline keeps the device
fed while this and the copy-back together stay under the program's time."""
from perf import pipeline_spans


def read(obs):
    return pipeline_spans.median_ms(obs, "step_work_ns")
