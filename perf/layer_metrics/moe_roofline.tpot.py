"""The decode program's ``moe`` scope against its memory roofline, %,
over the traced window's decode executions: the bytes their expert layers
must read (the architecture's count: the held experts each dispatch
touched, by the program's own count of that dispatch, and a layer's shared
expert, router and norm) over the chip's HBM bandwidth, over the scope's
device time in the same executions, both summed.  A dispatch's count
reaches the host just after its execution ends (the program's
``serving.moe_counts`` annotation); an execution with no count of its own
before the next one ends is left out of both sums."""
import bisect
import os

from perf import common

_scope = common.load_by_path(os.path.join(
    os.path.dirname(__file__), "ssm_device_ms.tpot.py"), "perf_scope_ms")


def matched(obs):
    """[(moe ns, touched, layer_steps)] a decode execution inside the
    window that has a count of its own; None where there is no trace, no
    scope or no count."""
    arch = common.arch_of(obs["cell"]["config"])
    runs = _scope.scope_runs(obs, "decode") if obs.get("trace") else None
    counts = getattr(arch, "dispatch_counts", lambda: None)()
    if not runs or counts is None:
        return None
    trace = obs["trace"]
    lo, hi = trace.window()
    # the executions ``scope_runs`` reduced, in its order, then by time
    ends = [s + d for name, s, d in trace.modules.get(trace.device0, [])
            if "decode" in name and s >= lo and s + d <= hi]
    ends, runs = zip(*sorted(zip(ends, runs), key=lambda p: p[0]))
    at = [c[0] for c in counts[1]]
    out = []
    for i, (run, end) in enumerate(zip(runs, ends)):
        j = bisect.bisect_left(at, end)
        if j < len(at) and (i + 1 == len(ends) or at[j] < ends[i + 1]):
            out.append((run.get("moe", 0.0), *counts[1][j][1:]))
    return out or None


def read(obs):
    pairs = matched(obs)
    count = getattr(common.arch_of(obs["cell"]["config"]),
                    "moe_step_bytes", None)
    if not pairs or count is None:
        return None
    need = sum(count(obs["cell"]["config"], touched, layer_steps)
               for _, touched, layer_steps in pairs)
    ns = sum(p[0] for p in pairs)
    return 100.0 * need / obs["peaks"]["hbm_bytes_per_s"] / (ns / 1e9) \
        if ns else None
