"""Own time of the window's device operations under none of the five
scopes (``program_spans.SCOPES``) over that of all of them, %: the
coverage guard of the ``*_device_ms.train`` metrics."""
from perf import program_spans, trace_reduce


def read(obs):
    scopes = program_spans.program_scopes(obs, "train") \
        if obs.get("trace") else None
    if scopes is None:
        return None
    by = program_spans.ns_by_label(
        trace_reduce.op_totals(obs["trace"]),
        lambda n: scopes.get(n, program_spans.UNSCOPED))
    total = sum(by.values())
    return 100.0 * by.get(program_spans.UNSCOPED, 0.0) / total \
        if total else None
