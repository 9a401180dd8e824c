"""Device-idle time under the engine's ``serving.schedule`` spans (expiry,
auto-park, the free-slot scan, chunk or decode), % of the traced window:
each idle gap of device 0 goes to the program's leaf span that covers most
of it (perf/program_spans.py)."""
from perf import program_spans


def read(obs):
    return program_spans.idle_share_under(obs, "serving.schedule")
