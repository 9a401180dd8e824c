"""ERNIE encoder pretraining MFU on the chip — the BASELINE ERNIE-4.5
config-matrix slot's encoder half (the decoder half is the MoE bench's
ERNIE-4.5-style heterogeneous-MoE program).

Full masked-LM train step (fwd + bwd + AdamW fp32-master) of an
ERNIE-3.0-base-proportioned encoder (L12 d768 h12, tied-embedding MLM
head) at b32 s512 bf16, 15% mask rate — the knowledge-masking
pretraining shape.  Prints one JSON line.
"""

from __future__ import annotations

import json
import time

import numpy as np


def main():
    import jax
    import paddle_tpu as pp
    from paddle_tpu.jit import TrainStep
    from paddle_tpu.models.ernie import ErnieConfig, ErnieForMaskedLM
    from bench import _PEAK

    dev = jax.devices()[0]
    on_tpu = dev.platform == "tpu"
    if on_tpu:
        cfg = ErnieConfig(
            vocab_size=40000, hidden_size=768, num_hidden_layers=12,
            num_attention_heads=12, intermediate_size=3072,
            hidden_dropout_prob=0.0, attention_probs_dropout_prob=0.0,
            max_position_embeddings=512, dtype="bfloat16")
        import os
        batch = int(os.environ.get("PT_ERNIE_BATCH", "32"))
        seq, iters, warmup = 512, 10, 3
    else:
        cfg = ErnieConfig.tiny()
        batch, seq, iters, warmup = 2, 32, 2, 1

    pp.seed(0)
    model = ErnieForMaskedLM(cfg)
    opt = pp.optimizer.AdamW(learning_rate=1e-4,
                             parameters=model.parameters(),
                             multi_precision=True)
    step = TrainStep(model, opt)
    n_params = sum(int(np.prod(a.shape)) for a in step.params.values())

    rng = np.random.default_rng(0)
    ids = rng.integers(0, cfg.vocab_size, (batch, seq))
    labels = np.where(rng.random((batch, seq)) < 0.15, ids, -100)
    batch_dict = {"input_ids": ids, "labels": labels}
    for _ in range(warmup):
        loss = step(batch_dict)
    jax.block_until_ready(loss)
    windows = []
    for _ in range(3):
        t0 = time.perf_counter()
        for _ in range(iters):
            loss = step(batch_dict)
        jax.block_until_ready(loss)
        windows.append((time.perf_counter() - t0) / iters)
    dt = min(windows)

    tokens = batch * seq
    flops_per_token = 6 * n_params + \
        12 * cfg.num_hidden_layers * seq * cfg.hidden_size
    kind = getattr(dev, "device_kind", "").lower()
    peak = next((v for k, v in sorted(_PEAK.items(),
                                      key=lambda kv: -len(kv[0]))
                 if k in kind), 197e12)
    mfu = flops_per_token * tokens / dt / peak
    print(json.dumps({
        "metric": "ernie_mlm_pretrain_mfu", "value": round(mfu, 4),
        "unit": "fraction_of_peak",
        "detail": {"params": n_params,
                   "tokens_per_sec_per_chip": round(tokens / dt, 1),
                   "step_time_s": round(dt, 4),
                   "step_time_mean_s": round(sum(windows) / len(windows),
                                             4),
                   "batch": batch, "seq": seq,
                   "device": getattr(dev, "device_kind", dev.platform),
                   "final_loss": float(loss)}}), flush=True)


if __name__ == "__main__":
    main()
