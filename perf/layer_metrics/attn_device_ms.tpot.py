"""Device time under the scope ``attn`` (norm, the query and latent
projections, the rotary turn, the cache write, the attention kernel and
the output projection) inside one execution of the decode program, summed
over its layers, ms, median over executions."""
import os

from perf import common

_scope = common.load_by_path(os.path.join(
    os.path.dirname(__file__), "ssm_device_ms.tpot.py"), "perf_scope_ms")


def read(obs):
    return _scope.read(obs, "decode", "attn")
