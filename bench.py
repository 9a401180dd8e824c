"""Benchmark: Llama pretraining MFU on the available chip(s).

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline"}.
Baseline (BASELINE.md): Llama-3-8B pretraining >= 40% MFU on v5p; on a single
chip we measure a Llama-proportioned model that fits one chip's HBM and
report model FLOPs utilisation of the full fwd+bwd+update step.

The ``detail`` payload carries the device-observability evidence next to
the headline: AOT compile-phase times and the executable's XLA-measured
FLOPs / bytes / peak HBM, plus the device-profiler's roofline-gap
attribution (the ranked fusion target list) and the live-byte watermark.
``--compare`` re-checks the fresh run against the newest BENCH_r*.json:
a headline drop (or step-time rise) beyond ``--tolerance`` prints a
``bench_compare`` line to stderr and exits 1.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import re
import sys
import time

import numpy as np

# bf16 peak FLOP/s per chip by TPU generation
_PEAK = {
    "v4": 275e12,
    "v5 lite": 197e12, "v5e": 197e12,
    "v5": 459e12, "v5p": 459e12,
    "v6 lite": 918e12, "v6e": 918e12, "trillium": 918e12,
}


def _peak_flops(device) -> float:
    kind = getattr(device, "device_kind", "").lower()
    for key, val in sorted(_PEAK.items(), key=lambda kv: -len(kv[0])):
        if key in kind:
            return val
    if getattr(device, "platform", "") == "tpu":
        raise ValueError(
            f"no peak FLOP/s for TPU device_kind {device.device_kind!r}: "
            f"add its published peak to _PEAK (known: {sorted(_PEAK)})")
    return 459e12  # CPU smoke denominator (the baseline hardware's peak)


def _prev_record(directory=None):
    """Parsed payload of the latest successful BENCH_r*.json (headline +
    detail) in ``directory`` (default: beside this file), so fresh runs
    can be compared against trajectory."""
    best_round, best = -1, None
    here = directory or os.path.dirname(os.path.abspath(__file__))
    for path in glob.glob(os.path.join(here, "BENCH_r*.json")):
        m = re.search(r"BENCH_r(\d+)\.json$", path)
        if not m:
            continue
        try:
            with open(path) as f:
                rec = json.load(f)
            parsed = rec.get("parsed") or {}
            val = parsed.get("value")
        except Exception:
            continue
        if val is not None and int(m.group(1)) > best_round:
            best_round, best = int(m.group(1)), parsed
    return best


def _prev_value():
    prev = _prev_record()
    return float(prev["value"]) if prev else None


def _prev_serve_record():
    """Parsed payload of the latest BENCH_serve_r*.json — the serving
    trajectory's newest point (bench_serve.py emits them)."""
    best_round, best = -1, None
    here = os.path.dirname(os.path.abspath(__file__))
    for path in glob.glob(os.path.join(here, "BENCH_serve_r*.json")):
        m = re.search(r"BENCH_serve_r(\d+)\.json$", path)
        if not m:
            continue
        try:
            with open(path) as f:
                rec = json.load(f)
            parsed = rec.get("parsed") or rec   # raw result files too
            val = parsed.get("value")
        except Exception:
            continue
        if val is not None and int(m.group(1)) > best_round:
            best_round, best = int(m.group(1)), parsed
    return best


def compare_serve_records(cur: dict, prev: dict, tolerance: float = 0.25):
    """Serving regression check: tokens/s (headline value) is
    better-higher; TTFT/TPOT p99 latencies are better-lower.  Returns
    human-readable regression strings (empty = within tolerance).  The
    default tolerance is wider than training's — serving latency on a
    shared CI host is noisier than a dedicated chip's step time."""
    regressions = []
    pv, cv = prev.get("value"), cur.get("value")
    if pv and cv is not None and cv < float(pv) * (1.0 - tolerance):
        regressions.append(
            f"tokens_per_s {cv:.2f} < prev {float(pv):.2f} - "
            f"{tolerance:.0%} tolerance (ratio {cv / float(pv):.3f})")
    pd = prev.get("detail") or {}
    cd = cur.get("detail") or {}
    for key in ("ttft_p99_s", "tpot_p99_s"):
        pl, cl = pd.get(key), cd.get(key)
        if pl and cl and float(cl) > float(pl) * (1.0 + tolerance):
            regressions.append(
                f"{key} {float(cl):.4f} > prev {float(pl):.4f} + "
                f"{tolerance:.0%} tolerance")
    # replica cold-start (both artifacts must carry the section)
    pw = (pd.get("cold_start") or {}).get("warmup_wall_s")
    cw = (cd.get("cold_start") or {}).get("warmup_wall_s")
    if pw and cw and float(cw) > float(pw) * (2.0 + tolerance):
        regressions.append(
            f"cold_start.warmup_wall_s {float(cw):.4f} > prev "
            f"{float(pw):.4f} x (2 + {tolerance:.0%})")
    # SLO attainment (better-higher fractions; guarded once both
    # artifacts carry the section AND judged against the same target)
    ps, cs = pd.get("slo_attainment") or {}, cd.get("slo_attainment") or {}
    for kind in ("ttft", "tpot"):
        pa, ca = ps.get(kind), cs.get(kind)
        same_target = ps.get(f"{kind}_target_s") == cs.get(
            f"{kind}_target_s")
        if pa and ca is not None and same_target and \
                float(ca) < float(pa) * (1.0 - tolerance):
            regressions.append(
                f"slo_attainment.{kind} {float(ca):.3f} < prev "
                f"{float(pa):.3f} - {tolerance:.0%} tolerance")
    # quantized serving (guarded once both artifacts ran the same
    # quant modes): the capacity ratio must not shrink and the parity
    # gate's token-match rate is better-higher — quantization can
    # never silently rot quality between rounds
    pq, cq = pd.get("quant") or {}, cd.get("quant") or {}
    if pq and cq and pq.get("weights") == cq.get("weights") and \
            pq.get("kv") == cq.get("kv"):
        pr, cr = pq.get("kv_blocks_ratio"), cq.get("kv_blocks_ratio")
        if pr and cr is not None and float(cr) < float(pr):
            regressions.append(
                f"quant.kv_blocks_ratio {float(cr):.2f} < prev "
                f"{float(pr):.2f}")
        pm, cm = pq.get("token_match_rate"), cq.get("token_match_rate")
        if pm and cm is not None and \
                float(cm) < float(pm) * (1.0 - tolerance):
            regressions.append(
                f"quant.token_match_rate {float(cm):.4f} < prev "
                f"{float(pm):.4f} - {tolerance:.0%} tolerance")
    # fleet serving (router speedup over the in-process single-engine
    # baseline is better-higher; guarded once both artifacts ran
    # --fleet with the same replica count)
    pf, cf = pd.get("fleet") or {}, cd.get("fleet") or {}
    if pf.get("speedup") and cf.get("speedup") is not None and \
            pf.get("replicas") == cf.get("replicas"):
        if float(cf["speedup"]) < float(pf["speedup"]) \
                * (1.0 - tolerance):
            regressions.append(
                f"fleet.speedup {float(cf['speedup']):.3f} < prev "
                f"{float(pf['speedup']):.3f} - {tolerance:.0%} "
                "tolerance")
    # session survivability (guarded once both artifacts ran
    # --sessions): the resident-sessions-over-HBM-capacity ratio is
    # better-higher and must not shrink beyond tolerance, and resumed
    # sessions must stay token-identical — parking can never trade
    # capacity for wrong tokens
    psess, csess = pd.get("sessions") or {}, cd.get("sessions") or {}
    if psess and csess:
        pr = psess.get("sessions_resident_ratio")
        cr = csess.get("sessions_resident_ratio")
        if pr and cr is not None and \
                float(cr) < float(pr) * (1.0 - tolerance):
            regressions.append(
                f"sessions.sessions_resident_ratio {float(cr):.2f} < "
                f"prev {float(pr):.2f} - {tolerance:.0%} tolerance")
        if csess.get("token_identity") is False:
            regressions.append(
                "sessions.token_identity is False: a resumed session "
                "decoded different tokens")
        if csess.get("recompute_fallback_identity") is False:
            regressions.append(
                "sessions.recompute_fallback_identity is False: the "
                "tier-miss recompute path decoded different tokens")
    # tail attribution (guarded once both artifacts carry the
    # forensics section): the dominant overhead cause flipping between
    # rounds, or the cold-resume share of request overhead growing past
    # tolerance, means the serving tail changed shape — not just got
    # uniformly slower — and deserves a named regression
    pt, ct = pd.get("tail_attribution") or {}, \
        cd.get("tail_attribution") or {}
    if pt and ct:
        pdom, cdom = pt.get("dominant_cause"), ct.get("dominant_cause")
        if pdom and cdom and pdom != cdom and cdom != "none":
            regressions.append(
                f"tail_attribution.dominant_cause flipped "
                f"{pdom} -> {cdom}")
        pcold = pt.get("cold_resume_share")
        ccold = ct.get("cold_resume_share")
        if ccold is not None and \
                float(ccold) > float(pcold or 0.0) + tolerance:
            regressions.append(
                f"tail_attribution.cold_resume_share "
                f"{float(ccold):.3f} > prev {float(pcold or 0.0):.3f} "
                f"+ {tolerance:.2f}")
    regressions += _compare_calibration(cur, prev, tolerance)
    return regressions


def _prev_recovery_record():
    """Parsed payload of the latest BENCH_recovery_r*.json — the
    fast-recovery MTTR trajectory (``--recovery-drill`` emits them)."""
    best_round, best = -1, None
    here = os.path.dirname(os.path.abspath(__file__))
    for path in glob.glob(os.path.join(here, "BENCH_recovery_r*.json")):
        m = re.search(r"BENCH_recovery_r(\d+)\.json$", path)
        if not m:
            continue
        try:
            with open(path) as f:
                rec = json.load(f)
            parsed = rec.get("parsed") or rec
            val = parsed.get("value")
        except Exception:
            continue
        if val is not None and int(m.group(1)) > best_round:
            best_round, best = int(m.group(1)), parsed
    return best


def _next_recovery_round(here: str) -> int:
    rounds = [int(m.group(1)) for p in
              glob.glob(os.path.join(here, "BENCH_recovery_r*.json"))
              if (m := re.search(r"BENCH_recovery_r(\d+)\.json$", p))]
    return max(rounds, default=0) + 1


def compare_records(cur: dict, prev: dict, tolerance: float = 0.05):
    """Regression check of a fresh result against a previous BENCH
    payload.  Returns a list of human-readable regression strings
    (empty = within tolerance).  Headline value is better-higher;
    step_time_s is better-lower."""
    regressions = []
    pv = prev.get("value")
    cv = cur.get("value")
    if pv and cv is not None and cv < float(pv) * (1.0 - tolerance):
        regressions.append(
            f"value {cv:.4f} < prev {float(pv):.4f} - {tolerance:.0%} "
            f"tolerance (ratio {cv / float(pv):.3f})")
    pt = (prev.get("detail") or {}).get("step_time_s")
    ct = (cur.get("detail") or {}).get("step_time_s")
    if pt and ct and float(ct) > float(pt) * (1.0 + tolerance):
        regressions.append(
            f"step_time_s {float(ct):.4f} > prev {float(pt):.4f} + "
            f"{tolerance:.0%} tolerance")
    # training goodput (better-higher; only once both artifacts carry it)
    pg = ((prev.get("detail") or {}).get("goodput") or {}).get("value")
    cg = ((cur.get("detail") or {}).get("goodput") or {}).get("value")
    if pg and cg is not None and float(cg) < float(pg) * (1.0 - tolerance):
        regressions.append(
            f"goodput {float(cg):.4f} < prev {float(pg):.4f} - "
            f"{tolerance:.0%} tolerance")
    # cold-start trajectory (only once both artifacts carry the section;
    # compile wall time on a shared host is noisy, so the bar is a 2x+
    # blowup past tolerance rather than drift)
    pc = (prev.get("detail") or {}).get("cold_start") or {}
    cc = (cur.get("detail") or {}).get("cold_start") or {}
    pt, ct = pc.get("total_s"), cc.get("total_s")
    if pt and ct and float(ct) > float(pt) * (2.0 + tolerance):
        regressions.append(
            f"cold_start.total_s {float(ct):.4f} > prev {float(pt):.4f} "
            f"x (2 + {tolerance:.0%})")
    # fast-recovery MTTR (lower-is-better; guarded once both artifacts
    # carry the section) — the trajectory guards time-to-recover like
    # any perf number
    pr = (prev.get("detail") or {}).get("recovery") or {}
    cr = (cur.get("detail") or {}).get("recovery") or {}
    pm, cm = pr.get("mttr_s"), cr.get("mttr_s")
    if pm and cm and float(cm) > float(pm) * (1.0 + tolerance):
        regressions.append(
            f"recovery.mttr_s {float(cm):.4f} > prev {float(pm):.4f} + "
            f"{tolerance:.0%} tolerance")
    regressions += _compare_calibration(cur, prev, tolerance)
    return regressions


def _compare_calibration(cur: dict, prev: dict, tolerance: float):
    """Calibration-health trajectory (guarded: only once BOTH artifacts
    carry an enabled ``detail.calibration`` section): ledger coverage is
    better-higher, mean |residual-1| better-lower.  Residuals on a
    shared CPU host are noisy, so the residual bar is a 2x+ blowup past
    tolerance (the cold-start convention), while coverage — a counting
    ratio — uses the plain tolerance."""
    regressions = []
    pc = (prev.get("detail") or {}).get("calibration") or {}
    cc = (cur.get("detail") or {}).get("calibration") or {}
    if not (pc.get("enabled") and cc.get("enabled")):
        return regressions
    pv, cv = pc.get("coverage"), cc.get("coverage")
    if pv and cv is not None and \
            float(cv) < float(pv) * (1.0 - tolerance):
        regressions.append(
            f"calibration.coverage {float(cv):.3f} < prev "
            f"{float(pv):.3f} - {tolerance:.0%} tolerance")
    pv, cv = pc.get("mean_abs_residual"), cc.get("mean_abs_residual")
    if pv and cv and float(cv) > float(pv) * (2.0 + tolerance):
        regressions.append(
            f"calibration.mean_abs_residual {float(cv):.3f} > prev "
            f"{float(pv):.3f} x (2 + {tolerance:.0%})")
    return regressions


def _prev_named_record(prefix):
    """Parsed payload of the newest ``{prefix}_rNN.json`` artifact — the
    generic trajectory lookup the MoE / long-context variants share."""
    best_round, best = -1, None
    here = os.path.dirname(os.path.abspath(__file__))
    for path in glob.glob(os.path.join(here, f"{prefix}_r*.json")):
        m = re.search(rf"{prefix}_r(\d+)\.json$", path)
        if not m:
            continue
        try:
            with open(path) as f:
                rec = json.load(f)
            parsed = rec.get("parsed") or rec
            val = parsed.get("value")
        except Exception:
            continue
        if val is not None and int(m.group(1)) > best_round:
            best_round, best = int(m.group(1)), parsed
    return best


def _next_named_round(here: str, prefix: str) -> int:
    rounds = [int(m.group(1)) for p in
              glob.glob(os.path.join(here, f"{prefix}_r*.json"))
              if (m := re.search(rf"{prefix}_r(\d+)\.json$", p))]
    return max(rounds, default=0) + 1


def _emit_named(args, result: dict, schema: str, prefix: str) -> None:
    if not args.emit:
        return
    here = os.path.dirname(os.path.abspath(__file__))
    path_out = args.emit
    if path_out == "auto":
        path_out = os.path.join(
            here, f"{prefix}_r{_next_named_round(here, prefix):02d}.json")
    with open(path_out, "w") as f:
        json.dump({"schema": schema, "parsed": result}, f, indent=1)
    print(f"wrote {path_out}", file=sys.stderr)


def _metric_series(name):
    from paddle_tpu.observability import default_registry
    m = default_registry().get(name)
    return {"/".join(k) or "all": c.value() for k, c in m.series()} \
        if m is not None else {}


def compare_moe_records(cur: dict, prev: dict, tolerance: float = 0.05):
    """MoE trajectory check: the base value/step-time/calibration clauses
    plus the grouped-kernel cost-model byte ratio (better-LOWER — the
    kernel's whole claim is that the [G, C, h] hidden intermediate never
    touches HBM) and knob-off parity, which must never rot."""
    regressions = compare_records(cur, prev, tolerance)
    pg = (prev.get("detail") or {}).get("grouped_kernel") or {}
    cg = (cur.get("detail") or {}).get("grouped_kernel") or {}
    pr, cr = pg.get("bytes_ratio"), cg.get("bytes_ratio")
    if pr and cr and float(cr) > float(pr) * (1.0 + tolerance):
        regressions.append(
            f"grouped_kernel.bytes_ratio {float(cr):.3f} > prev "
            f"{float(pr):.3f} + {tolerance:.0%} tolerance")
    cp = (cur.get("detail") or {}).get("knob_off_parity") or {}
    if cp and not cp.get("ok", True):
        regressions.append(
            f"knob_off_parity rel_diff {cp.get('rel_diff')} exceeded bar")
    return regressions


def compare_longctx_records(cur: dict, prev: dict,
                            tolerance: float = 0.05):
    """Long-context trajectory check: base clauses plus the ring-vs-
    single-device parity error, judged against an ABSOLUTE bar (the
    oracle is exact math, not a noisy timing, so drift is never ok)."""
    regressions = compare_records(cur, prev, tolerance)
    cp = (cur.get("detail") or {}).get("parity") or {}
    bar = cp.get("bar", 2e-5)
    ce = cp.get("max_abs_err")
    if ce is not None and float(ce) > float(bar):
        regressions.append(
            f"parity.max_abs_err {float(ce):.2e} > {float(bar):.0e} bar")
    return regressions


def _moe_bench(args):
    """MoE workload bench (ISSUE 18): full train step (fwd+bwd+AdamW) of
    a MoE decoder with the grouped expert-matmul Pallas kernel ON,
    emitting the ``moe_mfu`` trajectory line (activated-FLOPs MFU, the
    standard MoE accounting — idle experts do no math).

    The detail payload carries the acceptance evidence next to the
    headline: the cost-model HBM-byte ratio of the grouped kernel vs the
    dense-einsum dispatch at the sweep shape (< 0.5 means the [G, C, h]
    hidden intermediate never round-trips HBM), knob-off loss parity
    (``PADDLE_TPU_GROUPED_MOE=0`` must reproduce the reference
    numerics), and the per-trace implementation-path counters.  The
    measured step feeds the calibration ledger like the dense bench."""
    import jax
    import jax.numpy as jnp

    import paddle_tpu as pp
    from paddle_tpu import analysis
    from paddle_tpu.jit import TrainStep
    from paddle_tpu.models import MoEConfig, MoEForCausalLM
    from paddle_tpu.ops.pallas import autotune as at
    from paddle_tpu.ops.pallas import grouped_matmul as gm

    dev = jax.devices()[0]
    on_tpu = dev.platform == "tpu"
    mode = os.environ.get("PT_MOE_DISPATCH", "einsum")
    if on_tpu:
        # DeepSeekMoE-family dims scaled to one 16G chip (the
        # moe_train_bench "large" config, grouped-kernel path on)
        cfg = MoEConfig(
            vocab_size=32000, hidden_size=2048, intermediate_size=5632,
            moe_intermediate_size=1408, num_hidden_layers=4,
            num_attention_heads=16, num_key_value_heads=16,
            num_experts=16, num_experts_per_tok=2, num_shared_experts=1,
            first_k_dense_replace=1, max_position_embeddings=2048,
            capacity_factor=1.25, dispatch_mode=mode, dtype="bfloat16")
        batch, seq, iters, warmup = 4, 2048, 8, 2
    else:  # CI/CPU smoke — interpret-mode pallas
        cfg = MoEConfig.tiny(dispatch_mode=mode)
        batch, seq, iters, warmup = 2, 64, 2, 1
    batch = int(os.environ.get("PT_MOE_BATCH", batch))

    rng = np.random.default_rng(0)
    ids = rng.integers(0, cfg.vocab_size, (batch, seq + 1))
    batch_dict = {"input_ids": ids[:, :-1], "labels": ids[:, 1:]}

    def build_step(grouped: bool):
        os.environ["PADDLE_TPU_GROUPED_MOE"] = "1" if grouped else "0"
        pp.seed(0)
        model = MoEForCausalLM(cfg)
        opt = pp.optimizer.AdamW(learning_rate=1e-4,
                                 parameters=model.parameters(),
                                 multi_precision=True)
        return TrainStep(model, opt)

    knob_prev = os.environ.get("PADDLE_TPU_GROUPED_MOE")
    try:
        # knob-off reference first: same seed, same batch, one step —
        # the grouped path must reproduce this loss
        step_off = build_step(False)
        loss_off = float(step_off(batch_dict))
        del step_off

        step = build_step(True)
        loss_on = float(step(batch_dict))  # warmup step 1 + parity probe
        for _ in range(warmup - 1):
            step(batch_dict)
        jax.block_until_ready(step.params)
        t0 = time.perf_counter()
        for _ in range(iters):
            loss = step(batch_dict)
        jax.block_until_ready(step.params)
        dt = (time.perf_counter() - t0) / iters
    finally:
        if knob_prev is None:
            os.environ.pop("PADDLE_TPU_GROUPED_MOE", None)
        else:
            os.environ["PADDLE_TPU_GROUPED_MOE"] = knob_prev

    rel_diff = abs(loss_on - loss_off) / max(abs(loss_off), 1e-9)
    parity_ok = rel_diff <= 5e-3

    n_params = sum(int(np.prod(a.shape)) for a in step.params.values())
    expert_params = sum(int(np.prod(a.shape))
                        for name, a in step.params.items()
                        if ".experts." in name)
    idle = int(expert_params
               * (cfg.num_experts - cfg.num_experts_per_tok)
               / cfg.num_experts)
    activated = n_params - idle
    tokens = batch * seq
    flops_per_token = 6 * activated + \
        12 * cfg.num_hidden_layers * seq * cfg.hidden_size
    mfu = flops_per_token * tokens / dt / _peak_flops(dev)

    # grouped-kernel acceptance: cost-model HBM bytes at the sweep shape
    # vs the dense-einsum pair — trace-level analysis, no execution
    g, c, d, h, dtp = at.SWEEP_SHAPES["grouped_matmul"][0]
    jdt = jnp.bfloat16 if dtp == "bfloat16" else jnp.float32
    xs = [jnp.zeros(s, jdt) for s in
          ((g, c, d), (g, d, h), (g, h), (g, h, d), (g, d))]

    def _cost(fn):
        rep = analysis.check(fn, *xs, passes=["cost-model"])
        return rep.extras["cost"]

    cgr = _cost(lambda *a: gm.grouped_expert_ffn(*a))
    cdn = _cost(lambda *a: gm.grouped_expert_ffn_reference(*a))
    bytes_ratio = cgr.total_bytes / max(cdn.total_bytes, 1)

    # calibration-ledger feed: the measured MoE step lands in the
    # corpus with its roofline prediction, same as the dense bench
    from paddle_tpu.observability import calibration
    if calibration.enabled():
        from paddle_tpu.observability.device_profiler import \
            detect_roofline
        peak_r, _bw = detect_roofline()
        pred_s = flops_per_token * tokens / peak_r if peak_r else 0.0
        calibration.ledger().record(
            "moe_step", (batch, seq), measured_s=dt,
            predicted_s=pred_s, provenance="bench")
    calibration_detail = calibration.bench_detail()

    prev = _prev_named_record("BENCH_moe")
    result = {
        "metric": "moe_mfu",
        "value": round(mfu, 8),  # CPU smoke values are ~1e-6 of peak
        "unit": "fraction_of_peak_activated_flops",
        "vs_prev": round(mfu / float(prev["value"]), 4)
        if prev and prev.get("value") else None,
        "detail": {
            "tokens_per_sec_per_chip": round(tokens / dt, 1),
            "step_time_s": round(dt, 4),
            "params_total": n_params,
            "params_activated": activated,
            "dispatch_mode": mode,
            "experts": cfg.num_experts,
            "top_k": cfg.num_experts_per_tok,
            "batch": batch, "seq": seq,
            "device": getattr(dev, "device_kind", dev.platform),
            "final_loss": float(loss),
            "grouped_kernel": {
                "enabled": True,
                "bytes": int(cgr.total_bytes),
                "dense_bytes": int(cdn.total_bytes),
                "bytes_ratio": round(float(bytes_ratio), 4),
                "shape": {"g": g, "c": c, "d": d, "h": h, "dtype": dtp},
                "paths": _metric_series(
                    "paddle_tpu_grouped_moe_path_total"),
            },
            "knob_off_parity": {
                "loss_grouped": loss_on,
                "loss_reference": loss_off,
                "rel_diff": float(rel_diff),
                "ok": bool(parity_ok),
            },
            "calibration": calibration_detail,
        },
    }
    print(json.dumps(result))
    _emit_named(args, result, "bench_moe", "BENCH_moe")

    rc = 0
    if args.compare:
        if prev is None:
            print(json.dumps({"bench_compare": {
                "ok": True, "note": "no previous BENCH_moe artifact"}}),
                file=sys.stderr)
        else:
            tol = 0.05 if args.tolerance is None else args.tolerance
            regressions = compare_moe_records(result, prev, tol)
            print(json.dumps({"bench_compare": {
                "ok": not regressions, "tolerance": tol,
                "prev_value": prev.get("value"),
                "regressions": regressions}}), file=sys.stderr)
            rc = 1 if regressions else rc
    if bytes_ratio >= 0.5:
        print(f"moe bench: grouped-kernel bytes ratio "
              f"{bytes_ratio:.3f} >= 0.5x dense acceptance bar",
              file=sys.stderr)
        rc = 1
    if not parity_ok:
        print(f"moe bench: knob-off parity FAILED "
              f"(rel_diff {rel_diff:.2e})", file=sys.stderr)
        rc = 1
    return rc


def _longctx_bench(args):
    """Long-context bench (ISSUE 18): flash-backed ring attention on an
    ``sp`` mesh, emitting the ``longctx_mfu`` trajectory line (attention
    FLOPs utilisation of the fwd+bwd step at O(seq/sp) per-device
    memory).  Off-TPU the mesh is the 8-way virtual CPU host platform —
    the same program the multichip dryrun compiles — with pallas in
    interpret mode.  The detail payload carries the single-device flash
    parity error (absolute bar: the oracle is exact math), the striped
    causal-balance variant's parity, and the per-device memory story;
    the measured step feeds the calibration ledger."""
    if os.environ.get("JAX_PLATFORMS", "").lower() == "cpu" and \
            "xla_force_host_platform_device_count" not in \
            os.environ.get("XLA_FLAGS", ""):
        # CPU smoke: the sp mesh needs virtual host devices, set before
        # jax is imported
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "") +
            " --xla_force_host_platform_device_count=8").strip()
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh

    import paddle_tpu.distributed as dist
    from paddle_tpu.nn.functional.attention import _sdpa_reference

    dev = jax.devices()[0]
    on_tpu = dev.platform == "tpu"
    sp = int(os.environ.get("PT_LONGCTX_SP", "4"))
    if on_tpu:
        b, s, h, d = 1, 32768, 8, 128
        iters, warmup = 5, 2
    else:  # CI/CPU smoke — interpret-mode flash per hop
        b, s, h, d = 1, 512, 4, 32
        iters, warmup = 2, 1
    s = int(os.environ.get("PT_LONGCTX_SEQ", s))
    sp = min(sp, len(jax.devices()))
    mesh = Mesh(np.array(jax.devices()[:sp]), ("sp",))

    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (b, s, h, d), jnp.float32) * 0.5
    k = jax.random.normal(ks[1], (b, s, h, d), jnp.float32) * 0.5
    v = jax.random.normal(ks[2], (b, s, h, d), jnp.float32) * 0.5

    ring = dist.make_ring_attention(mesh, causal=True, impl="flash")
    out = jax.jit(ring)(q, k, v)
    want = _sdpa_reference(q, k, v, is_causal=True)
    max_err = float(jnp.max(jnp.abs(out - want)))
    parity_bar = 2e-5  # fp32 operands
    parity_ok = max_err <= parity_bar

    # striped causal-balance variant: operands pre-striped rank-major,
    # unstriped output must match the same oracle
    def _stripe(x):
        return jnp.concatenate([x[:, r::sp] for r in range(sp)], axis=1)

    def _unstripe(y):
        t = y.reshape(b, sp, s // sp, *y.shape[2:])
        return jnp.swapaxes(t, 1, 2).reshape(y.shape)

    striped = dist.make_striped_ring_attention(mesh, causal=True)
    out_s = _unstripe(jax.jit(striped)(_stripe(q), _stripe(k), _stripe(v)))
    striped_err = float(jnp.max(jnp.abs(out_s - want)))

    # timed: fwd+bwd through the flash-hop custom VJP — the training
    # cost the MFU headline measures
    loss_fn = jax.jit(jax.value_and_grad(
        lambda q, k, v: (ring(q, k, v) ** 2).mean(), argnums=(0, 1, 2)))
    for _ in range(warmup):
        loss_fn(q, k, v)
    jax.block_until_ready(loss_fn(q, k, v)[0])
    t0 = time.perf_counter()
    for _ in range(iters):
        val, grads = loss_fn(q, k, v)
    jax.block_until_ready(grads[0])
    dt = (time.perf_counter() - t0) / iters

    # attention FLOPs: fwd = 4*b*h*s^2*d (QK^T + PV), bwd = 2x fwd
    # (dQ/dK/dV + recompute), halved for causal
    flops = 12 * b * h * s * s * d * 0.5
    mfu = flops / dt / _peak_flops(dev)

    from paddle_tpu.distributed.sharding import overlap_enabled
    from paddle_tpu.observability import calibration
    if calibration.enabled():
        from paddle_tpu.observability.device_profiler import \
            detect_roofline
        peak_r, _bw = detect_roofline()
        calibration.ledger().record(
            "longctx_step", (b, s, sp), measured_s=dt,
            predicted_s=flops / peak_r if peak_r else 0.0,
            provenance="bench")
    calibration_detail = calibration.bench_detail()

    # per-device memory story: resident kv vs the dense score matrix
    kv_bytes_per_dev = 2 * b * (s // sp) * h * d * 4
    dense_scores_bytes = b * h * s * s * 4

    prev = _prev_named_record("BENCH_longctx")
    result = {
        "metric": "longctx_mfu",
        "value": round(mfu, 8),  # CPU smoke values are ~1e-6 of peak
        "unit": "fraction_of_peak",
        "vs_prev": round(mfu / float(prev["value"]), 4)
        if prev and prev.get("value") else None,
        "detail": {
            "tokens_per_sec": round(b * s / dt, 1),
            "step_time_s": round(dt, 4),
            "batch": b, "seq": s, "heads": h, "head_dim": d,
            "sp": sp, "impl": "flash", "causal": True,
            "seq_per_device": s // sp,
            "kv_bytes_per_device": kv_bytes_per_dev,
            "dense_scores_bytes": dense_scores_bytes,
            "collective_overlap": bool(overlap_enabled()),
            "device": getattr(dev, "device_kind", dev.platform),
            "final_loss": float(val),
            "parity": {
                "max_abs_err": max_err,
                "striped_max_abs_err": striped_err,
                "bar": parity_bar,
                "ok": bool(parity_ok),
            },
            "calibration": calibration_detail,
        },
    }
    print(json.dumps(result))
    _emit_named(args, result, "bench_longctx", "BENCH_longctx")

    rc = 0
    if args.compare:
        if prev is None:
            print(json.dumps({"bench_compare": {
                "ok": True,
                "note": "no previous BENCH_longctx artifact"}}),
                file=sys.stderr)
        else:
            tol = 0.05 if args.tolerance is None else args.tolerance
            regressions = compare_longctx_records(result, prev, tol)
            print(json.dumps({"bench_compare": {
                "ok": not regressions, "tolerance": tol,
                "prev_value": prev.get("value"),
                "regressions": regressions}}), file=sys.stderr)
            rc = 1 if regressions else rc
    if not parity_ok:
        print(f"longctx bench: ring-vs-flash parity FAILED "
              f"(max_abs_err {max_err:.2e} > {parity_bar:.0e})",
              file=sys.stderr)
        rc = 1
    return rc


def _recovery_drill(args):
    """MTTR drill (ISSUE 14): kill a training rank mid-run under the
    chaos registry, recover it twice — from a peer's in-memory snapshot
    and from the disk checkpoint — in the same artifact, and prove the
    post-recovery loss trajectory is bitwise identical to the
    uninterrupted run.  Both paths resume on a pre-warmed step (the
    relaunch/compile cost is common and measured by the cold-start
    artifact), so ``mttr_s`` isolates the restore path itself:
    detect -> state restored -> first resumed step retired."""
    import jax

    import paddle_tpu as pp
    from paddle_tpu import robustness
    from paddle_tpu.distributed.checkpoint import AutoCheckpoint
    from paddle_tpu.distributed.elastic import free_port
    from paddle_tpu.distributed.tcp_store import TCPStore
    from paddle_tpu.jit import TrainStep
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.robustness import recovery as rec

    drill_t0 = time.perf_counter()
    # big enough that restore cost is real (tens of MB of state), small
    # enough for a CI box
    cfg = LlamaConfig.tiny(vocab_size=512, hidden_size=256,
                           intermediate_size=512, num_hidden_layers=4)
    # kill late enough that the disk side holds its full keep=3
    # candidate set — restore_latest digest-validates every candidate,
    # which is the real production restore cost
    steps_total, kill_step, snap_interval = 15, 10, 3
    bsz, seq = 2, 64

    def batch_for(i):
        r = np.random.default_rng(1000 + i)
        ids = r.integers(0, cfg.vocab_size, (bsz, seq + 1))
        return {"input_ids": ids[:, :-1], "labels": ids[:, 1:]}

    def build_step():
        pp.seed(0)
        model = LlamaForCausalLM(cfg)
        opt = pp.optimizer.AdamW(learning_rate=1e-4,
                                 parameters=model.parameters())
        return TrainStep(model, opt)

    import tempfile
    ckpt_dir = tempfile.mkdtemp(prefix="pt_recovery_drill_")
    store = TCPStore("127.0.0.1", free_port(), is_master=True)
    snap = rec.PeerSnapshotter(store, rank=0, world_size=2,
                               interval_steps=snap_interval)
    ckpt = AutoCheckpoint(ckpt_dir, keep=3,
                          save_interval_steps=snap_interval)

    # the kill rides the chaos registry like every other drill: the
    # spec's nth counts loop iterations, so the fault fires AT kill_step
    robustness.inject("recovery.rank_kill", nth=kill_step, times=1)

    # reference run: doubles as the victim's timeline — snapshots and
    # checkpoints stop at the kill (a dead rank ships nothing), but the
    # loop runs to the end to record the uninterrupted loss trajectory
    # the recovered run must bitwise-match
    victim = build_step()
    losses_ref = {}
    killed_at = None
    pending = None
    for i in range(1, steps_total + 1):
        loss = victim(batch_for(i))
        losses_ref[i] = np.asarray(loss).tobytes()
        if killed_at is None:
            state = victim.state_dict()
            snap.maybe_snapshot(i, state)
            pending = ckpt.maybe_save(
                i, rec.flatten_for_checkpoint(state)) or pending
        if killed_at is None and robustness.fault_fires(
                "recovery.rank_kill", step=i):
            killed_at = i
    assert killed_at == kill_step, "chaos kill did not fire"
    if pending is not None:
        pending.wait()   # the step-6 disk save must be durable; the
        # async-save-racing-a-kill hazard has its own chaos test

    # the replacement rank: pre-built and pre-warmed (one throwaway
    # step compiles the executable), then restored into — twice
    template = build_step()
    jax.block_until_ready(template(batch_for(1)))

    # MTTR here = detect -> restored state INSTALLED on device (the
    # rank can train again); the first resumed step is ordinary
    # training cost, identical on both paths, timed separately.  Each
    # path runs 3x (min) — standard practice for sub-second timings on
    # a shared host.

    def drop_page_cache(path):
        # a replacement rank boots with a COLD page cache — warm
        # re-reads of files this very process just wrote would flatter
        # the disk path (fsync first: fadvise only drops clean pages)
        for root, _dirs, files in os.walk(path):
            for f in files:
                try:
                    fd = os.open(os.path.join(root, f), os.O_RDONLY)
                    os.fsync(fd)
                    os.posix_fadvise(fd, 0, 0, os.POSIX_FADV_DONTNEED)
                    os.close(fd)
                except OSError:
                    pass

    # disk-restore path: newest VALID checkpoint (digest-validated walk
    # over every candidate step dir — the real production restore cost)
    disk_restore_w, mttr_disk_w = [], []
    for _ in range(3):
        drop_page_cache(ckpt_dir)
        t0 = time.perf_counter()
        step_d, flat_d = ckpt.restore_latest()
        state_d = rec.unflatten_from_checkpoint(flat_d)
        disk_restore_w.append(time.perf_counter() - t0)
        template.set_state_dict(state_d)
        jax.block_until_ready(template.params)
        mttr_disk_w.append(time.perf_counter() - t0)
    t0 = time.perf_counter()
    jax.block_until_ready(template(batch_for(step_d + 1)))
    resume_step_disk_s = time.perf_counter() - t0
    disk_restore_s, mttr_disk = min(disk_restore_w), min(mttr_disk_w)

    # peer-restore path: RAM fetch from the ring buddy's mailbox —
    # resident by construction, which is the point of peer replication
    peer_restore_w, mttr_peer_w = [], []
    for _ in range(3):
        t0 = time.perf_counter()
        step_p, state_p, path = rec.resume_train_state(
            store, rank=0, auto_ckpt=ckpt)
        peer_restore_w.append(time.perf_counter() - t0)
        template.set_state_dict(state_p)
        jax.block_until_ready(template.params)
        mttr_peer_w.append(time.perf_counter() - t0)
    t0 = time.perf_counter()
    loss = template(batch_for(step_p + 1))
    jax.block_until_ready(template.params)
    resume_step_s = time.perf_counter() - t0
    peer_restore_s, mttr_peer = min(peer_restore_w), min(mttr_peer_w)
    staleness = killed_at - step_p

    # post-recovery trajectory: bitwise vs the uninterrupted run
    losses_rec = {step_p + 1: np.asarray(loss).tobytes()}
    for i in range(step_p + 2, steps_total + 1):
        losses_rec[i] = np.asarray(template(batch_for(i))).tobytes()
    bitwise = all(losses_rec[i] == losses_ref[i]
                  for i in range(step_p + 1, steps_total + 1))

    # SDC sentinel drill: three simulated DP replicas digest the same
    # params; an armed bit-flip corrupts replica 1's view — it must be
    # detected, blamed via deterministic replay, and quarantined
    true_params = template.params
    sentinels = [rec.SDCSentinel(store, rank=r, dp_peers=[0, 1, 2],
                                 host=f"drill-h{r}", timeout=1.0)
                 for r in range(3)]
    sentinels[0].publish(100, true_params)
    robustness.inject("train.sdc_flip", times=1)
    sentinels[1].publish(100, true_params)
    robustness.clear_faults("train.sdc_flip")
    sentinels[2].publish(100, true_params)
    verdict = sentinels[0].verify(
        100, replay=lambda: rec.params_digest(true_params))
    sdc = {
        "detected": not verdict["ok"],
        "blamed": verdict["blamed"],
        "blamed_correct": verdict["blamed"] == [1],
        "replay_confirmed": verdict["replayed"],
        "quarantined": verdict["quarantined"],
    }
    robustness.clear_faults("recovery.rank_kill")

    from paddle_tpu.observability import goodput as _goodput
    ledger = _goodput.compute_goodput(
        wall_s=time.perf_counter() - drill_t0)
    store.close()
    import shutil
    shutil.rmtree(ckpt_dir, ignore_errors=True)

    n_params = sum(int(np.prod(a.shape))
                   for a in template.params.values())
    speedup = mttr_disk / mttr_peer if mttr_peer > 0 else float("inf")
    result = {
        "metric": "recovery_restore_speedup",
        "value": round(speedup, 2),
        "unit": "x_vs_disk_restore",
        "vs_baseline": round(speedup / 3.0, 4),   # acceptance bar: 3x
        "detail": {"recovery": {
            "mttr_s": round(mttr_peer, 4),
            "mttr_disk_s": round(mttr_disk, 4),
            "restore_path": path,
            "restore_s": round(peer_restore_s, 4),
            "disk_restore_s": round(disk_restore_s, 4),
            "resume_step_s": round(resume_step_s, 4),
            "resume_step_disk_s": round(resume_step_disk_s, 4),
            "snapshot_staleness_steps": staleness,
            "snapshot_interval_steps": snap_interval,
            "snapshot_bytes": int(snap._metrics["snapshot_bytes"]
                                  .value()),
            "kill_step": killed_at,
            "restored_step": step_p,
            "steps": steps_total,
            "replayed_steps": steps_total - step_p,
            "trajectory_bitwise_match": bool(bitwise),
            "goodput": {
                "value": round(ledger["goodput"], 4),
                "productive_s": round(ledger["productive_s"], 4),
                "wall_s": round(ledger["wall_s"], 4),
            },
            "sdc": sdc,
            "params": n_params,
        }},
    }
    print(json.dumps(result))

    if args.emit:
        here = os.path.dirname(os.path.abspath(__file__))
        path_out = args.emit
        if path_out == "auto":
            path_out = os.path.join(
                here,
                f"BENCH_recovery_r{_next_recovery_round(here):02d}.json")
        with open(path_out, "w") as f:
            json.dump({"schema": "bench_recovery", "parsed": result}, f,
                      indent=1)
        print(f"wrote {path_out}", file=sys.stderr)

    rc = 0
    if args.compare:
        prev = _prev_recovery_record()
        if prev is None:
            print(json.dumps({"bench_compare": {
                "ok": True, "note": "no previous BENCH_recovery "
                                    "artifact"}}), file=sys.stderr)
        else:
            # restore timing on a shared CI host is noisy — the default
            # recovery tolerance is wide; the hard floors below still
            # gate correctness absolutely
            tol = 0.5 if args.tolerance is None else args.tolerance
            regressions = compare_records(result, prev, tol)
            print(json.dumps({"bench_compare": {
                "ok": not regressions, "tolerance": tol,
                "prev_value": prev.get("value"),
                "regressions": regressions}}), file=sys.stderr)
            rc = 1 if regressions else rc
    if not bitwise:
        print("recovery drill: post-recovery trajectory DIVERGED from "
              "the uninterrupted run", file=sys.stderr)
        rc = 1
    if not (sdc["detected"] and sdc["blamed_correct"]):
        print("recovery drill: SDC bit-flip not detected/blamed "
              f"correctly ({sdc})", file=sys.stderr)
        rc = 1
    return rc


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--compare", action="store_true",
                    help="flag regressions vs the newest BENCH_r*.json "
                         "(exit 1 beyond --tolerance)")
    ap.add_argument("--tolerance", type=float, default=None,
                    help="relative regression tolerance for --compare "
                         "(default 0.05; 0.25 for --compare-serve)")
    ap.add_argument("--no-device-profile", action="store_true",
                    help="skip the roofline-gap segment profiling pass")
    ap.add_argument("--compare-serve", metavar="RESULT_JSON",
                    help="instead of running the training bench, "
                         "regression-check a bench_serve.py result file "
                         "against the newest BENCH_serve_r*.json "
                         "(TTFT/TPOT p99 + tokens/s, exit 1 beyond "
                         "--tolerance)")
    ap.add_argument("--recovery-drill", action="store_true",
                    help="instead of the training bench, run the MTTR "
                         "drill: chaos-kill a rank mid-run, recover "
                         "from a peer in-memory snapshot AND the disk "
                         "checkpoint, verify the bitwise loss "
                         "trajectory + SDC sentinel blame (exit 1 on "
                         "any failure)")
    ap.add_argument("--moe", action="store_true",
                    help="instead of the dense training bench, run the "
                         "MoE workload bench (grouped expert-matmul "
                         "kernel on) and emit the moe_mfu line; "
                         "--compare checks the newest BENCH_moe_r*.json")
    ap.add_argument("--longctx", action="store_true",
                    help="instead of the dense training bench, run the "
                         "long-context ring-attention bench and emit "
                         "the longctx_mfu line; --compare checks the "
                         "newest BENCH_longctx_r*.json")
    ap.add_argument("--emit", metavar="PATH", nargs="?", const="auto",
                    help="with --recovery-drill/--moe/--longctx: write "
                         "the artifact (auto = next "
                         "BENCH_{recovery,moe,longctx}_rNN.json beside "
                         "this script)")
    args = ap.parse_args(argv)

    # before the first compile: everything a later run can reuse lives
    # under one root that can be placed from outside
    from paddle_tpu import compile_cache
    compile_cache.enable_persistent_cache()

    if args.recovery_drill:
        return _recovery_drill(args)
    if args.moe:
        return _moe_bench(args)
    if args.longctx:
        return _longctx_bench(args)

    if args.compare_serve:
        with open(args.compare_serve) as f:
            rec = json.load(f)
        cur = rec.get("parsed") or rec
        prev = _prev_serve_record()
        if prev is None:
            print(json.dumps({"bench_compare": {
                "ok": True, "note": "no previous BENCH_serve artifact"}}),
                file=sys.stderr)
            return 0
        tol = 0.25 if args.tolerance is None else args.tolerance
        regressions = compare_serve_records(cur, prev, tol)
        print(json.dumps({"bench_compare": {
            "ok": not regressions, "tolerance": tol,
            "prev_value": prev.get("value"),
            "regressions": regressions}}), file=sys.stderr)
        return 1 if regressions else 0

    import jax

    import paddle_tpu as pp
    from paddle_tpu.jit import TrainStep
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM

    bench_t0 = time.perf_counter()
    dev = jax.devices()[0]
    on_tpu = dev.platform == "tpu"

    if on_tpu:
        # Llama-3-8B-proportioned, scaled to fit one 16G-HBM chip with the
        # full AdamW training state (bf16 params + f32 master + f32 m/v
        # ≈ 14 bytes/param → ~810M params ≈ 11.3G + activations)
        cfg = LlamaConfig(
            vocab_size=32000, hidden_size=2048, intermediate_size=7168,
            num_hidden_layers=8, num_attention_heads=16,
            num_key_value_heads=8, max_position_embeddings=4096,
            rope_theta=500000.0, dtype="bfloat16")
        # measured on v5e (this model): b4/s2048/no-remat + fused
        # chunked lm-head CE = 0.52 MFU —
        # the shipped default (longest pretraining context that fits with
        # full AdamW state).  Sweep: full remat 0.39 (recompute tax);
        # b5 0.49 (non-pow2 tiling); b2/s4096 0.42; b8/s1024 0.58 (short
        # context inflates MFU — not representative); b8/s2048 OOM even
        # with dots-saveable remat.
        batch, seq, iters, warmup = 4, 2048, 10, 3
    else:  # CI/CPU smoke
        cfg = LlamaConfig.tiny()
        batch, seq, iters, warmup = 4, 64, 3, 1
    batch = int(os.environ.get("PT_BENCH_BATCH", batch))
    seq = int(os.environ.get("PT_BENCH_SEQ", seq))
    remat = os.environ.get("PT_BENCH_REMAT", "0") == "1"
    remat_policy = os.environ.get("PT_BENCH_REMAT_POLICY") or None
    accum = int(os.environ.get("PT_BENCH_ACCUM", "1"))
    profile_segments = not args.no_device_profile and \
        os.environ.get("PT_BENCH_PROFILE", "1") != "0"

    model = LlamaForCausalLM(cfg)
    opt = pp.optimizer.AdamW(learning_rate=1e-4,
                             parameters=model.parameters(),
                             multi_precision=True)
    step = TrainStep(model, opt, remat=on_tpu and remat,
                     remat_policy=remat_policy, accum_steps=accum)

    n_params = sum(int(np.prod(a.shape)) for a in step.params.values())
    rng = np.random.default_rng(0)
    ids = rng.integers(0, cfg.vocab_size, (batch, seq + 1))
    batch_dict = {"input_ids": ids[:, :-1], "labels": ids[:, 1:]}

    # explicit AOT compile first: the measured run dispatches through the
    # compiled executable (no first-step compile spike inside timing) and
    # lower/compile wall time + XLA's flops/bytes/peak-memory become part
    # of the artifact.  With PADDLE_TPU_COMPILE_CACHE=1 this consults the
    # persistent executable cache — a warm cache turns trace+compile into
    # a deserialize-and-load, which is the cold-start story the
    # `cold_start` detail section below records.
    compile_info = step.compile(batch_dict)

    # device prefetch: H2D for batch N+1 rides behind step N instead of
    # serializing ahead of it (paddle_tpu.io.device_prefetch)
    from paddle_tpu.io import device_prefetch

    def batches(n):
        for _ in range(n):
            yield batch_dict

    first_step_s = None
    for b in device_prefetch(batches(warmup), depth=2):
        t0 = time.perf_counter()
        step(b)
        if first_step_s is None:
            import jax as _jax
            _jax.block_until_ready(step.params)
            first_step_s = time.perf_counter() - t0
    jax.block_until_ready(step.params)
    # min-of-windows timing: the fastest window is taken as the program's
    # speed
    windows = []
    for _ in range(3):
        prefetched = device_prefetch(batches(iters), depth=2)
        next_batches = iter(prefetched)
        first = next(next_batches)  # H2D outside the timed window
        t0 = time.perf_counter()
        loss = step(first)
        for b in next_batches:
            loss = step(b)
        jax.block_until_ready(step.params)
        windows.append((time.perf_counter() - t0) / iters)
        prefetched.close()
    dt = min(windows)  # headline; mean reported alongside in detail

    tokens = batch * seq
    # fwd+bwd FLOPs: 6N per token + attention 12*L*s*d per token
    flops_per_token = 6 * n_params + \
        12 * cfg.num_hidden_layers * seq * cfg.hidden_size
    mfu = flops_per_token * tokens / dt / _peak_flops(dev)
    tok_per_sec = tokens / dt

    # kernel-path attribution: which implementations this run compiled,
    # so BENCH_r*.json trajectories can attribute wins to paths
    from paddle_tpu.observability import default_registry
    from paddle_tpu.distributed.sharding import overlap_enabled
    from paddle_tpu.ops.pallas.cross_entropy import fused_ce_enabled
    from paddle_tpu.ops.pallas.fused_block import (fused_block_enabled,
                                                   fused_block_tier)

    def _series(name):
        m = default_registry().get(name)
        return {"/".join(k) or "all": c.value() for k, c in m.series()} \
            if m is not None else {}

    paths = {
        "fused_ce_enabled": bool(fused_ce_enabled()),
        "fused_ce_calls": _series("paddle_tpu_fused_ce_calls_total"),
        # which block segments this run compiled fused vs reference, and
        # whether tuned block sizes came from the persistent cache —
        # BENCH trajectories can attribute wins to the exact code path
        "fused_block_enabled": bool(fused_block_enabled()),
        "fused_block_tier": fused_block_tier(),
        "fused_block_traces": _series("paddle_tpu_fused_block_path_total"),
        "autotune_cache": _series("paddle_tpu_autotune_cache_total"),
        # compute/collective overlap (ISSUE 15): whether the knob was on
        # and which paths actually traced overlap-expressed collectives
        "collective_overlap": bool(overlap_enabled()),
        "overlap_traces": _series("paddle_tpu_collective_overlap_total"),
        "accum_steps": accum,
        "device_prefetch": True,
    }

    # device-time breakdown: where the step's MFU gap actually sits —
    # the ranked attribution rows are the fusion target list (ROADMAP 2)
    from paddle_tpu.observability.device_profiler import (
        DeviceProfiler, device_memory_monitor, llama_step_segments)
    device_profile = None
    if profile_segments:
        try:
            prof = DeviceProfiler()
            for seg in llama_step_segments(model, batch_dict):
                prof.add(seg)
            result = prof.profile(reps=2, warmup=1,
                                  parent_span="train.step")
            device_profile = {
                "segments": result.to_dicts(top=8),
                "peak_flops": result.peak_flops,
                "hbm_bw": result.hbm_bw,
            }
        except Exception as e:   # attribution must never sink the bench
            device_profile = {"error": f"{type(e).__name__}: {e}"}
    live_watermark = device_memory_monitor().watermark

    # cold-start ledger (ROADMAP 5): how long from process start to a
    # runnable step — trace, compile-or-load (cache hit → deserialize
    # time), first real step — plus the compile-cache counters that say
    # WHICH path this run took.  --compare guards it once two artifacts
    # carry the section.
    from paddle_tpu import compile_cache
    cache_series = _series("paddle_tpu_compile_cache_total")
    cold_start = {
        "trace_s": round(compile_info.lower_s, 4),
        "compile_or_load_s": round(compile_info.compile_s, 4),
        "first_step_s": round(first_step_s or 0.0, 4),
        "total_s": round(compile_info.lower_s + compile_info.compile_s
                         + (first_step_s or 0.0), 4),
        "cache_hit": bool(compile_info.cached),
        "cache_enabled": compile_cache.enabled(),
        "cache": {
            "hit": sum(v for k, v in cache_series.items()
                       if k.endswith("/hit")),
            "miss": sum(v for k, v in cache_series.items()
                        if k.endswith("/miss")),
            "deserialize_error": sum(
                v for k, v in cache_series.items()
                if k.endswith("/deserialize_error")),
        },
    }

    # measurement ledger (ROADMAP 5): the whole measured train step
    # lands in the calibration corpus with its roofline prediction —
    # the record a fresh planner process calibrates against — and the
    # detail.calibration section summarizes residual health for
    # --compare (coverage better-higher, |residual| better-lower).
    # The profiler segments above already fed their own rows.
    from paddle_tpu.observability import calibration
    if calibration.enabled():
        peak = bw = None
        if profile_segments:
            try:
                peak, bw = prof.peak_flops, prof.hbm_bw
            except Exception:
                peak = bw = None
        if not peak or not bw:
            from paddle_tpu.observability.device_profiler import \
                detect_roofline
            peak, bw = detect_roofline()
        step_pred_s = max(
            compile_info.stats.flops / peak if peak else 0.0,
            compile_info.stats.bytes_accessed / bw if bw else 0.0)
        calibration.ledger().record(
            "train_step", (batch, seq), measured_s=dt,
            predicted_s=step_pred_s, provenance="bench")
    calibration_detail = calibration.bench_detail()

    # goodput ledger (fleet observability): productive step seconds over
    # the bench's own wall clock, with the lost-time attribution — the
    # field --compare guards alongside MFU once two artifacts carry it
    from paddle_tpu.observability import goodput as _goodput
    ledger = _goodput.compute_goodput(
        wall_s=time.perf_counter() - bench_t0)
    goodput_detail = {
        "value": round(ledger["goodput"], 4),
        "productive_s": round(ledger["productive_s"], 4),
        "wall_s": round(ledger["wall_s"], 4),
        "lost": {k: round(v, 4) for k, v in ledger["lost"].items()},
    }

    prev = _prev_value()
    result = {
        "metric": "llama_pretrain_mfu",
        "value": round(mfu, 4),
        "unit": "fraction_of_peak",
        "vs_baseline": round(mfu / 0.40, 4),
        "vs_prev": round(mfu / prev, 4) if prev else None,
        "detail": {
            "tokens_per_sec_per_chip": round(tok_per_sec, 1),
            "step_time_s": round(dt, 4),
            "step_time_mean_s": round(sum(windows) / len(windows), 4),
            "params": n_params,
            "batch": batch, "seq": seq,
            "device": getattr(dev, "device_kind", dev.platform),
            "final_loss": float(loss),
            "paths": paths,
            "compile": {
                "lower_s": round(compile_info.lower_s, 4),
                "compile_s": round(compile_info.compile_s, 4),
                "flops": compile_info.stats.flops,
                "bytes_accessed": compile_info.stats.bytes_accessed,
                "peak_hbm_bytes": compile_info.stats.peak_bytes,
            },
            "peak_hbm_bytes": compile_info.stats.peak_bytes,
            "device_live_bytes_watermark": live_watermark,
            "device_profile": device_profile,
            "cold_start": cold_start,
            "goodput": goodput_detail,
            "calibration": calibration_detail,
        },
    }
    print(json.dumps(result))

    if args.compare:
        prev_rec = _prev_record()
        if prev_rec is None:
            print(json.dumps({"bench_compare": {
                "ok": True, "note": "no previous BENCH artifact"}}),
                file=sys.stderr)
            return 0
        tol = 0.05 if args.tolerance is None else args.tolerance
        regressions = compare_records(result, prev_rec, tol)
        print(json.dumps({"bench_compare": {
            "ok": not regressions,
            "tolerance": tol,
            "prev_value": prev_rec.get("value"),
            "regressions": regressions}}), file=sys.stderr)
        if regressions:
            return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
