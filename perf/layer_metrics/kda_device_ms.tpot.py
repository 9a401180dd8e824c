"""Device time under the scope ``kda`` (the delta rule's one-token state
update alone: decay, ``S'^T k``, the update and the read-out; not the
projections, the convolution or the gates around it) inside one execution
of the decode program, summed over its KDA layers, ms, median over
executions.  The scope is nested in ``ssm`` and is the architecture's to
name (``RECURRENCE``); the reduction over it is these readers' own, apart
from the one the ``ssm`` / ``attn`` / ``moe`` readers share."""
import numpy as np

from perf import common, program_spans


def recurrence_runs(obs, program):
    """[ns under the recurrence's scope] an execution of ``program``
    inside the window; None without a trace, where the architecture
    names no such scope, or where the program carries none.  Kept on
    ``obs`` under a key of its own: the four ``kda_*`` readers of one run
    share one reduction a program."""
    kept = obs.setdefault("_recurrence_runs", {})
    if program not in kept:
        scope = getattr(common.arch_of(obs["cell"]["config"]), "RECURRENCE",
                        None)
        scopes = program_spans.program_scopes(obs, program, (scope,)) \
            if obs.get("trace") and scope else None
        kept[program] = None if scopes is None else [
            r.get(scope, 0.0) for r in program_spans.per_execution(
                obs["trace"], program, scopes.get)] or None
    return kept[program]


def read(obs, program="decode"):
    runs = recurrence_runs(obs, program)
    return float(np.median(runs) / 1e6) if runs else None
