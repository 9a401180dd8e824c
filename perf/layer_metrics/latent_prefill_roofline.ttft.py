"""A prefill chunk's attention proper against its arithmetic's peak, %,
over the traced window's prefill-chunk executions: the operations the
causal pairs each chunk really has need in the cheaper (expanded) form
(the architecture's ``latent_prefill_cost`` of the chunk's start and
tokens: the same work whatever implements it, so a form that does more
reads lower) over the chip's peak, over the device time of the chunk's
attention (the scope the architecture names ``CHUNK_ATTENTION``), both
summed over the executions.  A chunk's start and tokens are the program's
``serving.prefill_context`` annotation, written just after its dispatch:
chunks execute in dispatch order and the profiler stops after the drain,
so the n-th annotation from the trace's end belongs to the n-th execution
from its end."""
from perf import common, program_spans


def matched(obs):
    """[(attention ns, start, tokens)] a prefill-chunk execution inside
    the window; None where there is no trace, scope or annotation."""
    arch = common.arch_of(obs["cell"]["config"])
    scope = getattr(arch, "CHUNK_ATTENTION", None)
    found = getattr(arch, "chunk_contexts", lambda: None)()
    scopes = program_spans.program_scopes(obs, "prefill_chunk", (scope,)) \
        if obs.get("trace") and scope else None
    if scopes is None or found is None:
        return None
    trace = obs["trace"]
    lo, hi = trace.window()
    execs = [(s, d) for name, s, d in trace.modules.get(trace.device0, [])
             if "prefill_chunk" in name]
    context = dict(zip(sorted(s for s, _ in execs)[::-1],
                       [c[1:] for c in found[1]][::-1]))
    runs = program_spans.per_execution(trace, "prefill_chunk", scopes.get)
    inside = [s for s, d in execs if s >= lo and s + d <= hi]
    return [(run.get(scope, 0.0), *context[s])
            for run, s in zip(runs, inside) if s in context] or None


def read(obs):
    pairs = matched(obs)
    if not pairs:
        return None
    cfg = obs["cell"]["config"]
    count = common.arch_of(cfg).latent_prefill_cost
    need = sum(count(cfg, start, tokens) for _, start, tokens in pairs)
    ns = sum(p[0] for p in pairs)
    return 100.0 * need / obs["peaks"]["bf16_flops"] / (ns / 1e9) \
        if ns else None
