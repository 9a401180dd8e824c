"""Device-idle time under the engine's ``serving.admit`` spans (prefix match,
block allocation, the block-table row), % of the traced window: each idle
gap of device 0 goes to the program's leaf span that covers most of it
(perf/program_spans.py)."""
from perf import program_spans


def read(obs):
    return program_spans.idle_share_under(obs, "serving.admit")
