"""Device ms a train step spends under the scope ``mlp`` (norm, MLP,
residual), forward and backward, own time as ``trace_reduce.op_totals``
counts it; an operation fused across scopes goes to the first of
``program_spans.SCOPES``."""
from perf import program_spans


def read(obs):
    return program_spans.scope_ms_per_step(obs, "mlp")
