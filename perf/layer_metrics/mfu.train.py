"""Model FLOP/s utilisation, %: forward + backward operations a token
needs (perf/flops.py, recomputation not counted) x tokens/s/chip over the
chip's bf16 peak (perf/peaks.json)."""
from perf import flops


def read(obs):
    cell = obs["cell"]
    per_token = flops.train_flops_per_token(
        cell["config"], cell["traffic"]["params"]["seq"])
    return 100.0 * per_token * obs["tokens_per_s_per_chip"] \
        / obs["peaks"]["bf16_flops"]
