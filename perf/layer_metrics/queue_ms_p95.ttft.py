"""95th percentile of (admitted - due), ms: the engine's ``admitted``
stamp against the generator's schedule, over the requests that were due
before the profiler started (its start and stop stall the host)."""
import numpy as np


def read(obs):
    q = [(r["admitted"] - r["due_s"]) * 1e3 for r in obs["requests"]
         if r.get("admitted") is not None
         and r["due_s"] < obs.get("untraced_until", float("inf"))]
    return float(np.percentile(q, 95)) if q else None
