"""Open-loop chat traffic: Poisson arrivals at a fixed rate, log-normal
prompt and output lengths, every prompt unique.

Every seed offers the same work: the ``round(rate * seconds)`` gaps are the
exponential distribution's quantiles and the lengths the clipped
log-normal's quantiles (a fixed set, so no seed draws a heavier tail than
another), in the order the mix's own ``schedule_seed`` gives; ``--seed``
decides the tokens (and the weights).  A 95th percentile over some 130
requests is set by the few moments at which long prompts arrive together:
with the order left to ``--seed``, three seeds' ``ttft_p95_ms`` spread by
a fifth (PERF.md, PR 23), far more than two runs of one seed.  So runs of
different seeds differ as two runs of one seed do.
"""

import math
from statistics import NormalDist

import numpy as np


def _lognormal_set(n, median, sigma, lo, hi):
    nd = NormalDist()
    q = [(i + 0.5) / n for i in range(n)]
    v = [median * math.exp(sigma * nd.inv_cdf(p)) for p in q]
    return np.clip(np.rint(v), lo, hi).astype(np.int64)


def requests(params, cfg, seed, seconds):
    """[{"due_s", "prompt", "max_new"}] sorted by ``due_s`` < seconds."""
    n = max(1, int(round(params["rate_per_s"] * seconds)))
    rng = np.random.default_rng([int(params["schedule_seed"]), 7])
    tokens = np.random.default_rng([int(seed), 7])
    gaps = -np.log(1.0 - (np.arange(n) + 0.5) / n) / params["rate_per_s"]
    due = np.cumsum(rng.permutation(gaps))
    due -= due[0] / 2          # the first request soon after the start
    due *= seconds / (due[-1] + gaps.mean() / 2)    # the last inside it
    p, o = params["prompt"], params["output"]
    plen = rng.permutation(_lognormal_set(n, p["median"], p["sigma"],
                                          p["min"], p["max"]))
    olen = rng.permutation(_lognormal_set(n, o["median"], o["sigma"],
                                          o["min"], o["max"]))
    out = []
    for i in range(n):
        prompt = tokens.integers(0, cfg["vocab_size"], int(plen[i]),
                                 dtype=np.int32)
        out.append({"due_s": float(due[i]), "prompt": prompt,
                    "max_new": int(olen[i])})
    return out
