"""Device time under the scope ``attn`` (norm, QKV, rope, attention over
the paged pool, output projection) inside one execution of the
prefill-chunk program, ms, median over executions."""
import numpy as np

from perf import program_spans


def read(obs):
    scopes = program_spans.program_scopes(obs, "prefill_chunk") \
        if obs.get("trace") else None
    if scopes is None:
        return None
    runs = program_spans.per_execution(obs["trace"], "prefill_chunk",
                                       scopes.get)
    return float(np.median([r.get("attn", 0.0) for r in runs]) / 1e6) \
        if runs else None
