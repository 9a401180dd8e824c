"""99th percentile, over every output token after a request's first, of
(this emission - the previous emission) / tokens in this emission, ms:
the gap a user sees before each token, from the ``token_stamps`` the
engine leaves on a request's ``serving.request`` root span.  Over the
requests that were due before the profiler started (its start and stop
stall the host)."""
import numpy as np


def read(obs):
    from paddle_tpu.observability.tracing import tracer
    roots = getattr(tracer(), "finished_roots", None)
    if roots is None:
        return None
    stamps = {}      # newest root of a rid wins: rids restart per engine
    for root in roots("serving.request"):
        if "token_stamps" in root["attrs"]:
            stamps[root["attrs"].get("rid")] = root["attrs"]["token_stamps"]
    gaps = []
    for r in obs["requests"]:
        if r.get("ok") and r["rid"] in stamps \
                and r["due_s"] < obs.get("untraced_until", float("inf")):
            st = stamps[r["rid"]]
            for (t0, _), (t1, n) in zip(st, st[1:]):
                gaps += [(t1 - t0) / n * 1e3] * n
    return float(np.percentile(gaps, 99)) if gaps else None
