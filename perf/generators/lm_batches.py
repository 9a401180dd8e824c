"""Training batches: ``batch`` rows of ``seq + 1`` token ids, uniform over
the vocabulary, a fresh batch every step and every row different.  Step
``i`` of seed ``s`` is always the same batch."""

import numpy as np


def batch(params, cfg, seed, step):
    rng = np.random.default_rng([int(seed), int(step)])
    ids = rng.integers(0, cfg["vocab_size"],
                       (params["batch"], params["seq"] + 1), dtype=np.int32)
    return {"input_ids": ids[:, :-1], "labels": ids[:, 1:]}
