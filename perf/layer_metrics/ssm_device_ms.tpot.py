"""Device time under the scope ``ssm`` (a Mamba layer's norm, in_proj,
convolution, one-token state update, gated norm, out_proj and residual)
inside one execution of the decode program, summed over its layers, ms,
median over executions."""
import numpy as np

from perf import common, program_spans


def scope_runs(obs, program):
    """[{scope: ns}] an execution of ``program`` inside the window, by the
    architecture's own scopes; None where the program carries none.  Kept
    on ``obs``: the scope readers of one run share one reduction."""
    kept = obs.setdefault("_scope_runs", {})
    if program not in kept:
        arch = common.arch_of(obs["cell"]["config"])
        scopes = program_spans.program_scopes(
            obs, program, getattr(arch, "SCOPES", program_spans.SCOPES))
        # a kernel the compiler writes in an operation's place carries its
        # own name and no scope: the architecture says whose it is
        kernels = getattr(arch, "KERNEL_SCOPES", {})
        kept[program] = None if scopes is None else \
            program_spans.per_execution(
                obs["trace"], program, lambda name: scopes.get(name)
                or kernels.get(program_spans.kernel_of(name)))
    return kept[program]


def read(obs, program="decode", scope="ssm"):
    if not obs.get("trace"):
        return None
    runs = scope_runs(obs, program)
    if not runs or not any(scope in r for r in runs):
        return None
    return float(np.median([r.get(scope, 0.0) for r in runs]) / 1e6)
