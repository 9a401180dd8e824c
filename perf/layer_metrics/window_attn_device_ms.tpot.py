"""Device time under the scope ``attn_window`` (a sliding-window layer's
attention: the five projections, the head norms, the rotary turn, the
cache write, the decode kernel over the ring and the gate and output
projection; nested in ``attn``) inside one execution of the decode
program, summed over its window layers, ms, median over executions.  The
scopes are the architecture's to name (``WINDOW_SCOPES``); the reduction
over them is these readers' own, apart from the one the ``attn`` / ``moe``
readers share."""
import numpy as np

from perf import common, program_spans


def window_runs(obs, program="decode"):
    """[{(scope, kernel): ns}] an execution of ``program`` inside the
    window: an operation's own time by which of the architecture's
    ``WINDOW_SCOPES`` it lies under (None: neither) and, for a Pallas
    call, the kernel's name.  None without a trace, where the
    architecture names no such scopes, or where the program carries
    none.  Kept on ``obs``: the window readers of one run share one
    reduction a program."""
    kept = obs.setdefault("_window_runs", {})
    if program not in kept:
        names = getattr(common.arch_of(obs["cell"]["config"]),
                        "WINDOW_SCOPES", None)
        scopes = program_spans.program_scopes(obs, program, tuple(names)) \
            if obs.get("trace") and names else None
        kept[program] = None if scopes is None else \
            program_spans.per_execution(
                obs["trace"], program,
                lambda n: (scopes.get(n), program_spans.kernel_of(n))) \
            or None
    return kept[program]


def under(run, scope, kernel=None):
    """ns of one execution under ``scope`` (of ``kernel`` alone)."""
    return sum(ns for (s, k), ns in run.items()
               if s == scope and kernel in (None, k))


def read(obs):
    runs = window_runs(obs)
    if not runs:
        return None
    scope = common.arch_of(obs["cell"]["config"]).WINDOW_SCOPES[0]
    ns = [under(r, scope) for r in runs]
    return float(np.median(ns) / 1e6) if any(ns) else None
