"""Device-idle time under no phase span of the program (the caller's loop
between ``step()`` calls), % of the traced window. With the six
``idle_*_share.tpot`` it sums to ``device_idle_share.tpot``."""
from perf import program_spans


def read(obs):
    return program_spans.idle_share_under(obs, program_spans.OUTSIDE)
