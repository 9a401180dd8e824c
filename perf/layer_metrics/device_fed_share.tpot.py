"""Share of the programs the engine handed the device — decode steps,
prefill chunks and speculative verifies alike — that found it fed, %: the
``fed`` series of ``paddle_tpu_serving_dispatches_total{kind,device}`` over
``fed`` + ``drained``, since the process began.  A dispatch is ``drained``
when the newest output any earlier one returned was already complete
(``jax.Array.is_ready()`` just before the call): the device had nothing of
the engine's left and idles until this program starts.  Counted with the
profiler on or off."""
from perf import pipeline_spans


def read(obs):
    return pipeline_spans.fed_share()
