"""paddle_tpu_train_recompiles_total over the window (expected 0)."""


def read(obs):
    return float(obs["recompiles"])
