"""Device time under the scope ``ssm`` (norm, in_proj, convolution, the
chunked scan, gated norm, out_proj, residual) inside one execution of the
prefill-chunk program, summed over its Mamba layers, ms, median over
executions."""
import os

from perf import common

_scope = common.load_by_path(os.path.join(
    os.path.dirname(__file__), "ssm_device_ms.tpot.py"), "perf_scope_ms")


def read(obs):
    return _scope.read(obs, "prefill_chunk", "ssm")
