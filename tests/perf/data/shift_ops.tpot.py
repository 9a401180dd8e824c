"""Instructions of the compiled decode program under the toy
architecture's own scope ``shift`` (tests/perf/data/two_kinds_arch.py): a
reader that passes a tuple of its own to ``program_spans``.  The tests
copy this file into perf/layer_metrics/ of a scratch checkout."""
from perf import program_spans

SCOPES = ("shift", "attn", "mlp")


def read(obs):
    scopes = program_spans.program_scopes(obs, "decode", SCOPES)
    if scopes is None:
        return None
    return float(sum(s == "shift" for s in scopes.values())) or None
