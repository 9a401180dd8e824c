"""Device time under the scope ``moe`` (norm, router, the held experts'
grouped products, the shared expert and the residual) inside one execution
of the decode program, summed over its layers, ms, median over
executions."""
import os

from perf import common

_scope = common.load_by_path(os.path.join(
    os.path.dirname(__file__), "ssm_device_ms.tpot.py"), "perf_scope_ms")


def read(obs):
    return _scope.read(obs, "decode", "moe")
