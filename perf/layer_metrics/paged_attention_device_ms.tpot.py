"""Device time of the ``paged_attention`` kernel inside one execution of
the decode program, summed over its layers (and the scan's steps), ms,
median over executions.  A Pallas call's event carries the kernel's
``name``; a decode program that carries the program's names but routes
attention to XLA reads 0."""
import numpy as np

from perf import program_spans


def read(obs):
    if not obs.get("trace") or \
            program_spans.program_scopes(obs, "decode") is None:
        return None
    runs = program_spans.per_execution(obs["trace"], "decode",
                                       program_spans.kernel_of)
    return float(np.median([r.get("paged_attention", 0.0)
                            for r in runs]) / 1e6) if runs else None
