"""Llama long-context train MFU at seq 4096 / 8192 (BASELINE config
matrix + VERDICT r3 #1/#4).

At long sequence the attention term dominates and the Pallas flash
kernels (forward, dq, dk/dv) must carry the step; this bench measures
the FULL train step (fwd+bwd+AdamW) per sequence length.

Prints one JSON line per seq.
Run on the TPU chip; falls back to a tiny CPU smoke shape off-TPU.

One process per chip: the parent never touches jax.  Every measurement
(and the plan listing, which has to ask jax for the platform) runs in a
child that owns the chip for its lifetime; children run one at a time.
"""

from __future__ import annotations

import json
import time

import numpy as np


def run_one(cfg, batch, seq, iters=8, warmup=2, remat=False,
            remat_policy=None):
    import jax
    import paddle_tpu as pp
    from bench import _peak_flops
    from paddle_tpu.jit import TrainStep
    from paddle_tpu.models import LlamaForCausalLM

    pp.seed(0)
    model = LlamaForCausalLM(cfg)
    opt = pp.optimizer.AdamW(learning_rate=1e-4,
                             parameters=model.parameters(),
                             multi_precision=True)
    step = TrainStep(model, opt, remat=remat, remat_policy=remat_policy)
    n_params = sum(int(np.prod(a.shape)) for a in step.params.values())
    rng = np.random.default_rng(0)
    ids = rng.integers(0, cfg.vocab_size, (batch, seq + 1))
    batch_dict = {"input_ids": ids[:, :-1], "labels": ids[:, 1:]}
    for _ in range(warmup):
        step(batch_dict)
    jax.block_until_ready(step.params)
    t0 = time.perf_counter()
    for _ in range(iters):
        step(batch_dict)
    jax.block_until_ready(step.params)
    dt = (time.perf_counter() - t0) / iters
    tokens = batch * seq
    flops_per_token = 6 * n_params + \
        12 * cfg.num_hidden_layers * seq * cfg.hidden_size
    dev = jax.devices()[0]
    mfu = flops_per_token * tokens / dt / _peak_flops(dev)
    return mfu, tokens / dt, dt


def _plans(on_tpu):
    if on_tpu:
        # same Llama-3-8B-proportioned single-chip model as bench.py;
        # long context: batch shrinks with seq so activations fit HBM
        base = dict(vocab_size=32000, hidden_size=2048,
                    intermediate_size=7168, num_hidden_layers=8,
                    num_attention_heads=16, num_key_value_heads=8,
                    rope_theta=500000.0, dtype="bfloat16")
        # s8192 b1 runs WITHOUT remat: flash attention keeps activations
        # O(seq*d) so the 584M model's fwd residuals fit the 16G chip at
        # b1, and dropping remat is worth +32% (0.242 -> 0.322 measured;
        # both checkpoint policies measured identical, so recompute —
        # not policy choice — was the cost; remat sweep via
        # PT_SEQ_REMAT/PT_SEQ_POLICY for larger-than-memory configs)
        return base, [
            dict(seq=4096, batch=2, remat=False, remat_policy=None),
            dict(seq=8192, batch=1, remat=False, remat_policy=None),
        ]
    base = dict(vocab_size=256, hidden_size=128, intermediate_size=256,
                num_hidden_layers=2, num_attention_heads=4,
                num_key_value_heads=2, dtype="float32")
    return base, [dict(seq=256, batch=2, remat=False, remat_policy=None)]


def _child(seq: int):
    """One measurement per process: a fresh 584M model + full AdamW state
    twice in one process OOMs the 16G chip (freeing is async).

    PT_SEQ_BATCH / PT_SEQ_REMAT / PT_SEQ_POLICY override the plan for
    remat-policy sweeps (VERDICT r4 Next #2)."""
    import os
    import jax
    from paddle_tpu.models import LlamaConfig
    on_tpu = jax.devices()[0].platform == "tpu"
    base, plans = _plans(on_tpu)
    plan = next(p for p in plans if p["seq"] == seq)
    if os.environ.get("PT_SEQ_BATCH"):
        plan["batch"] = int(os.environ["PT_SEQ_BATCH"])
    if os.environ.get("PT_SEQ_REMAT"):
        plan["remat"] = os.environ["PT_SEQ_REMAT"] == "1"
    if os.environ.get("PT_SEQ_POLICY"):
        pol = os.environ["PT_SEQ_POLICY"]
        plan["remat_policy"] = None if pol == "none" else pol
    cfg = LlamaConfig(max_position_embeddings=seq, **base)
    mfu, tps, dt = run_one(cfg, plan["batch"], seq,
                           remat=plan["remat"],
                           remat_policy=plan["remat_policy"])
    print("RESULT " + json.dumps({
        "mfu": mfu, "tps": tps, "dt": dt, "batch": plan["batch"],
        "remat": plan["remat"]}), flush=True)


def _list_plans():
    """Child mode: print the sequence lengths this platform measures."""
    import jax
    _, plans = _plans(jax.devices()[0].platform == "tpu")
    print("PLANS " + json.dumps([p["seq"] for p in plans]), flush=True)


def main():
    import subprocess
    import sys

    listing = subprocess.run([sys.executable, __file__, "--plans"],
                             capture_output=True, text=True, check=True)
    seqs = json.loads(next(
        ln for ln in listing.stdout.splitlines()
        if ln.startswith("PLANS "))[len("PLANS "):])
    for seq in seqs:
        proc = subprocess.run(
            [sys.executable, __file__, "--child", str(seq)],
            capture_output=True, text=True, timeout=3000)
        line = next((ln for ln in proc.stdout.splitlines()
                     if ln.startswith("RESULT ")), None)
        if line is None:
            print(json.dumps({
                "metric": f"llama_s{seq}_mfu", "value": None,
                "error": proc.stderr[-500:]}), flush=True)
            continue
        r = json.loads(line[len("RESULT "):])
        print(json.dumps({
            "metric": f"llama_s{seq}_mfu",
            "value": round(r["mfu"], 4), "unit": "fraction_of_peak",
            "detail": {"batch": r["batch"], "seq": seq,
                       "tokens_per_sec_per_chip": round(r["tps"], 1),
                       "step_time_s": round(r["dt"], 4),
                       "remat": r["remat"]}}), flush=True)


if __name__ == "__main__":
    import sys
    if len(sys.argv) > 1 and sys.argv[1] == "--child":
        _child(int(sys.argv[2]))
    elif len(sys.argv) > 1 and sys.argv[1] == "--plans":
        _list_plans()
    else:
        main()
