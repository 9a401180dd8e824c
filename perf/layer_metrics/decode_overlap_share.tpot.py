"""Share of the engine's batched decode dispatches that went out while the
host had yet to read the one before, %: the ``overlapped`` kind of
``paddle_tpu_serving_decode_dispatches_total`` over both kinds, since the
process began (a dispatch that ``waited`` found everything before it read:
the first of a run of decode steps, one after a prefill chunk's step, every
speculative verify)."""
from perf import common


def read(obs):
    kinds = common.series("paddle_tpu_serving_decode_dispatches_total")
    issued = sum(kinds.values())
    if not issued:
        return None
    return 100.0 * kinds.get("overlapped", 0.0) / issued
