"""Serving benchmark: Poisson arrivals over the continuous-batching engine.

Serving joins the benchmark trajectory (training has BENCH_r*.json since
r02; serving had nothing).  Prints ONE JSON line:

    {"metric": "serving_tokens_per_s", "value", "unit", "detail": {...}}

with TTFT/TPOT p50/p99 under Poisson load, prefix-cache hit counters,
paged-block utilization, and speculative-decode accept counters in the
detail payload.  ``--emit`` writes a ``BENCH_serve_r*.json`` artifact so
``bench.py --compare-serve`` (or ``bench_serve.py --compare``) can guard
the trajectory the way training's ``--compare`` does.

The workload models the fleet case the paged KV cache exists for: every
request shares a system-prompt prefix (``--shared-prefix``) and appends
a short unique suffix, so the prefix prefills once and later requests
reuse its blocks (watch ``prefix_hit_tokens``).  ``--check-equivalence``
replays the workload one request at a time through the plain engine
(bf16, no prefix cache, no speculation, no quantization — the engine
tier-1 holds to ``generate()``) and asserts token-for-token greedy
identity: prefix reuse, speculation and routing must be pure
memory/scheduling optimizations.
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


def _percentiles(xs, ps=(50, 99)):
    if not xs:
        return {f"p{p}": None for p in ps}
    return {f"p{p}": float(np.percentile(xs, p)) for p in ps}


def _series(name):
    from paddle_tpu.observability import default_registry
    m = default_registry().get(name)
    return {"/".join(k) or "all": c.value() for k, c in m.series()} \
        if m is not None else {}


def _next_serve_round(here):
    rounds = [int(m.group(1)) for p in
              glob.glob(os.path.join(here, "BENCH_serve_r*.json"))
              if (m := re.search(r"BENCH_serve_r(\d+)\.json$", p))]
    return max(rounds, default=0) + 1


def _build_engine(model, args, quant_weights="0", quant_kv="0",
                  prefix_cache=True):
    from paddle_tpu.inference.serving import ContinuousBatchingEngine
    return ContinuousBatchingEngine(
        model, slots=args.slots, max_len=args.max_len,
        prefill_buckets=(args.max_len // 2,),
        steps_per_sync=args.steps_per_sync if not args.spec else 1,
        kv_block_size=args.block_size,
        prefill_chunk=args.chunk, prefix_cache=prefix_cache,
        spec_decode=args.spec,
        quant_weights=quant_weights, quant_kv=quant_kv)


def _build_router(model, args, quant_weights="0", quant_kv="0"):
    """The fleet under test: dedicated prefill replica(s) feeding a
    decode tier that runs DEEP step fusion (--decode-sync) — legal only
    because disaggregation means prefill never interleaves there.  The
    host-dispatch amortization is the measured fleet win; --fleet-mixed
    builds a homogeneous fleet instead (routing/spill only).
    --decode-slots sizes the decode tier's slot pool independently of
    the prefill tier (decode holds sequences for their whole decode
    phase; prefill slots turn over per prompt)."""
    from paddle_tpu.inference.router import ServingRouter
    ek = dict(slots=args.slots, max_len=args.max_len,
              prefill_buckets=(args.max_len // 2,),
              steps_per_sync=1,
              kv_block_size=args.block_size, prefill_chunk=args.chunk,
              quant_weights=quant_weights, quant_kv=quant_kv)
    dk = dict(steps_per_sync=args.decode_sync if not args.spec else 1,
              spec_decode=args.spec)
    if args.decode_slots:
        dk["slots"] = args.decode_slots
    prefill = 0 if args.fleet_mixed else max(1, args.prefill_replicas)
    return ServingRouter(
        model, replicas=args.fleet, prefill_replicas=prefill,
        engine_kwargs=ek, decode_kwargs=dk,
        warm_on_spawn=False)   # bench warms explicitly, outside timing


def _run_stats(eng, prompts, arrivals, args):
    """Drive one workload and fold the per-request timings."""
    results, rids, t0, t1 = _run_workload(eng, prompts, arrivals,
                                          args.max_new)
    ttfts, tpots, total_tokens = [], [], 0
    reused_tokens = 0.0
    accept_rates = []
    route_s, handoff_s = [], []
    timings = []
    for rid in rids:
        st = eng.request_status(rid)
        out = results.get(rid, [])
        total_tokens += len(out)
        t = st.timings if st is not None else {}
        timings.append(t)
        if t.get("ttft_s"):
            ttfts.append(t["ttft_s"])
        if t.get("decode_s") and len(out) > 1:
            tpots.append(t["decode_s"] / (len(out) - 1))
        reused_tokens += t.get("prefix_tokens_reused", 0.0)
        if t.get("route_s"):
            route_s.append(t["route_s"])
        if t.get("handoff_s"):
            handoff_s.append(t["handoff_s"])
        if args.spec:
            accept_rates.append(t.get("speculative_accept_rate", 0.0))
    wall = t1 - t0
    return {"results": results, "rids": rids, "wall": wall,
            "tokens": total_tokens,
            "tok_s": total_tokens / wall if wall > 0 else 0.0,
            "ttfts": ttfts, "tpots": tpots,
            "reused_tokens": reused_tokens,
            "accept_rates": accept_rates,
            "route_s": route_s, "handoff_s": handoff_s,
            "timings": timings}


def _workload(args, vocab):
    """(prompts, arrival_offsets): shared system prefix + per-request
    tails, Poisson inter-arrival gaps at --rps.

    ``--workload random`` (default): uniform-random unique suffixes —
    the adversarial case for speculative decoding (history n-grams
    predict nothing; accept rate ~0 at short horizons).
    ``--workload text``: repeated-phrase tails modeling natural-language
    redundancy (boilerplate, extraction, code) — the n-gram proposer's
    home turf, so ``--spec`` shows a non-zero accept rate the artifact
    records."""
    rng = np.random.default_rng(args.seed)
    shared = rng.integers(0, vocab, (args.shared_prefix,))
    prompts = []
    for _ in range(args.requests):
        if args.workload == "text":
            phrase = rng.integers(0, vocab,
                                  (int(rng.integers(4, 9)),))
            reps = max(2, -(-args.suffix_max // len(phrase)))
            tail = np.tile(phrase, reps)[:max(args.suffix_max, 8)]
        else:
            tail = rng.integers(0, vocab,
                                (int(rng.integers(2,
                                                  args.suffix_max + 1)),))
        prompts.append(np.concatenate([shared, tail]).astype(np.int32))
    gaps = rng.exponential(1.0 / args.rps, size=args.requests)
    arrivals = np.cumsum(gaps)
    arrivals[0] = 0.0
    return prompts, arrivals


def _session_drill(model, args, vocab, qw_mode="0", qkv_mode="0"):
    """Session-survivability drill (ISSUE 19): far more live sessions
    than the HBM pool holds, parked through the KV tier manager (host
    RAM + peer store) and resumed token-identically.

    A deliberately tiny paged pool (sized for ``slots`` concurrent
    sessions) serves ``--sessions`` logical sessions: each decodes a
    couple of tokens, parks (KV spilled to the tier), and later
    resumes (KV promoted back into fresh blocks).  The
    ``sessions_resident`` trajectory counts parked+active sessions
    after each park; its peak over the pool's HBM-equivalent session
    capacity is the survivability headline
    (``sessions_resident_ratio``).  A no-parking reference engine
    proves every resumed session's greedy tokens are identical, and
    one extra session resumes through an injected ``kv_tier.fetch``
    fault to prove the recompute fallback is token-identical too."""
    from paddle_tpu.inference.kv_tier import KVTierManager
    from paddle_tpu.inference.serving import ContinuousBatchingEngine
    from paddle_tpu.observability.fleet import LocalStore
    from paddle_tpu.robustness import clear_faults, inject

    n = args.sessions
    rng = np.random.default_rng(args.seed + 101)
    Lp, max_new, bs, slots = 24, 8, 8, 2
    prompts = [rng.integers(0, vocab, (Lp,)).astype(np.int32)
               for _ in range(n + 1)]          # +1 fault-drill session
    bps = -(-(Lp + max_new) // bs)             # blocks per session
    num_blocks = 1 + slots * bps + 2           # ~slots sessions fit
    kw = dict(slots=slots, max_len=64, prefill_buckets=(32,),
              kv_block_size=bs, prefill_chunk=16,
              num_kv_blocks=num_blocks,
              quant_weights=qw_mode, quant_kv=qkv_mode)
    tier = KVTierManager(store=LocalStore())
    eng = ContinuousBatchingEngine(model, kv_tier=tier, **kw)

    # reference: identical engine, nothing ever parked (sessions run
    # one at a time so the tiny pool suffices) — the identity oracle
    ref_eng = ContinuousBatchingEngine(model, **kw)
    ref = []
    for p in prompts:
        r = ref_eng.add_request(p, max_new_tokens=max_new)
        ref.append(ref_eng.run()[r][1])
    ref_eng.close()

    def _out_len(rid):
        for req in eng._active:
            if req is not None and req.rid == rid:
                return len(req.out)
        return -1

    t0 = time.perf_counter()
    trajectory, parked = [], []
    # phase 1 — admit, decode >=2 tokens, park: the resident session
    # set grows far past what the pool could ever hold
    for i in range(n):
        rid = eng.add_request(prompts[i], max_new_tokens=max_new)
        while _out_len(rid) < 2:
            eng.step()
        key = eng.park(rid)
        assert key is not None, f"park failed for session {i}"
        parked.append(rid)
        trajectory.append(
            len(eng.parked_rids())
            + sum(1 for q in eng._active if q is not None))
    resident_peak = max(trajectory) if trajectory else 0
    # phase 2 — resume everything (tier promote) and decode to the end
    for rid in parked:
        eng.resume(rid)
    done = eng.run()
    resume_s, parked_s = [], []
    identity = True
    for i, rid in enumerate(parked):
        if list(done[rid][1]) != list(ref[i]):
            identity = False
            print(f"SESSION MISMATCH {i}: parked={list(done[rid][1])} "
                  f"ref={list(ref[i])}", file=sys.stderr)
        st = eng.request_status(rid)
        t = st.timings if st is not None else {}
        resume_s.append(t.get("resume_s", 0.0))
        parked_s.append(t.get("parked_s", 0.0))
    # phase 3 — one session resumes through a dropped tier fetch: the
    # recompute fallback must regenerate the same tokens, never hang
    rid = eng.add_request(prompts[n], max_new_tokens=max_new)
    while _out_len(rid) < 2:
        eng.step()
    eng.park(rid)
    inject("kv_tier.fetch", times=1)
    try:
        eng.resume(rid)
        fb = eng.run()[rid][1]
    finally:
        clear_faults()
    recompute_ok = list(fb) == list(ref[n])
    if not recompute_ok:
        print(f"RECOMPUTE-FALLBACK MISMATCH: {list(fb)} != "
              f"{list(ref[n])}", file=sys.stderr)
    hbm_eq = max(1, (num_blocks - 1) // bps)
    detail = {
        "sessions": n,
        "slots": slots,
        "kv_blocks_total": num_blocks - 1,
        "blocks_per_session": bps,
        "hbm_equivalent_sessions": hbm_eq,
        "resident_peak": resident_peak,
        "sessions_resident_ratio": round(resident_peak / hbm_eq, 2),
        "resident_trajectory": trajectory,
        "drill_wall_s": round(time.perf_counter() - t0, 4),
        "cold_resume": {
            "resume_p50_s": _percentiles(resume_s, ps=(50,))["p50"],
            "resume_p99_s": _percentiles(resume_s, ps=(99,))["p99"],
            "parked_p50_s": _percentiles(parked_s, ps=(50,))["p50"],
        },
        "token_identity": bool(identity),
        "recompute_fallback_identity": bool(recompute_ok),
        "parks": _series("paddle_tpu_serving_session_parks_total"),
        "resumes": _series("paddle_tpu_serving_session_resumes_total"),
        "tier_fetch": _series("paddle_tpu_kv_tier_fetch_total"),
        "tier_spills": _series("paddle_tpu_kv_tier_spills_total"),
        "tier": tier.stats(),
    }
    eng.close()
    return detail


def _run_workload(eng, prompts, arrivals, max_new):
    """Drive the engine under the arrival schedule (wall clock).
    Returns (results {rid: tokens}, rids, t_start, t_end)."""
    from paddle_tpu.robustness import QueueFullError
    results = {}
    rids = [None] * len(prompts)
    waiting = list(range(len(prompts)))
    t0 = time.perf_counter()
    while waiting or eng.pending:
        now = time.perf_counter() - t0
        while waiting and arrivals[waiting[0]] <= now:
            i = waiting[0]
            try:
                rids[i] = eng.add_request(prompts[i],
                                          max_new_tokens=max_new)
                waiting.pop(0)
            except QueueFullError:
                break   # shed: retry on a later loop pass
        if eng.pending:
            eng.step()
            for rid, _p, out in eng.finished():
                results[rid] = out
        elif waiting:
            time.sleep(max(0.0, arrivals[waiting[0]] - now))
    t1 = time.perf_counter()
    return results, rids, t0, t1


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--rps", type=float, default=20.0,
                    help="Poisson arrival rate")
    ap.add_argument("--max-new", type=int, default=12)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--shared-prefix", type=int, default=24,
                    help="system-prompt tokens shared by every request")
    ap.add_argument("--suffix-max", type=int, default=12)
    ap.add_argument("--block-size", type=int, default=8)
    ap.add_argument("--chunk", type=int, default=16,
                    help="chunked-prefill width")
    ap.add_argument("--spec", type=int, default=0,
                    help="n-gram speculative draft length")
    ap.add_argument("--steps-per-sync", type=int, default=1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--workload", choices=("random", "text"),
                    default="random",
                    help="suffix style: 'text' = repeated-phrase tails "
                         "(speculative decoding shows real accept "
                         "rates there)")
    ap.add_argument("--quant-weights", default=None,
                    choices=("int8", "fp8"),
                    help="weight-only quantized engine (default: "
                         "PADDLE_TPU_QUANT_WEIGHTS)")
    ap.add_argument("--quant-kv", default=None, choices=("int8",),
                    help="int8 paged-KV pools (default: "
                         "PADDLE_TPU_QUANT_KV)")
    ap.add_argument("--parity-floor", type=float, default=0.98,
                    help="--check-equivalence under quantization: "
                         "minimum greedy token-match rate vs the bf16 "
                         "engine (hard gate)")
    ap.add_argument("--logit-tol", type=float, default=0.10,
                    help="max relative logit error vs bf16 the parity "
                         "gate tolerates")
    ap.add_argument("--check-equivalence", action="store_true",
                    help="replay through the plain engine (bf16, no "
                         "prefix cache, no speculation) and assert "
                         "greedy outputs are identical")
    ap.add_argument("--emit", metavar="PATH",
                    help="write the artifact ('auto' → next "
                         "BENCH_serve_rNN.json beside this script)")
    ap.add_argument("--compare", action="store_true",
                    help="regression-check vs the newest "
                         "BENCH_serve_r*.json (exit 1 beyond tolerance)")
    ap.add_argument("--tolerance", type=float, default=0.25)
    from paddle_tpu.inference.router import fleet_serve_replicas
    ap.add_argument("--fleet", type=int,
                    default=fleet_serve_replicas(0),
                    help="route the workload through a ServingRouter "
                         "over N replicas (default PADDLE_TPU_FLEET_"
                         "SERVE; 0 = single engine).  The single-engine "
                         "baseline runs first in the same process so "
                         "detail.fleet carries the measured speedup")
    ap.add_argument("--prefill-replicas", type=int, default=1,
                    help="dedicated prefill replicas in the fleet")
    ap.add_argument("--fleet-mixed", action="store_true",
                    help="homogeneous mixed fleet (no disaggregation)")
    ap.add_argument("--decode-sync", type=int, default=4,
                    help="decode-tier steps_per_sync under "
                         "disaggregation")
    ap.add_argument("--sessions", type=int, default=0,
                    help="run the session-survivability drill: park N "
                         "sessions through the KV tier (host+peer), "
                         "resume them token-identically, and record "
                         "the sessions_resident trajectory in "
                         "detail.sessions")
    ap.add_argument("--decode-slots", type=int, default=0,
                    help="decode-tier slot pool size (0 = same as "
                         "--slots; decode holds sequences far longer "
                         "than prefill, so an asymmetric fleet sizes "
                         "them independently)")
    args = ap.parse_args(argv)
    if args.fleet and args.fleet < 2:
        ap.error("--fleet needs >= 2 replicas")

    import jax

    # before the first compile: everything a later run can reuse lives
    # under one root that can be placed from outside
    from paddle_tpu import compile_cache
    compile_cache.enable_persistent_cache()

    import paddle_tpu as pp
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM

    # quant knobs resolve ONCE here, then ride explicitly into every
    # engine build — the bf16 equivalence baseline must not re-read env
    from paddle_tpu.inference.kv_cache import quant_kv_mode
    from paddle_tpu.quantization.serving import quant_weights_mode
    qw_mode = quant_weights_mode(args.quant_weights)
    qkv_mode = quant_kv_mode(args.quant_kv)
    dev = jax.devices()[0]
    pp.seed(args.seed)
    if dev.platform == "tpu":
        # serving-proportioned model that decodes comfortably on one chip
        cfg = LlamaConfig(
            vocab_size=32000, hidden_size=1024, intermediate_size=3584,
            num_hidden_layers=8, num_attention_heads=16,
            num_key_value_heads=8,
            max_position_embeddings=max(2 * args.max_len, 2048),
            rope_theta=500000.0, dtype="bfloat16")
    else:  # CI/CPU smoke
        cfg = LlamaConfig.tiny(
            max_position_embeddings=max(args.max_len, 128))
    model = LlamaForCausalLM(cfg)

    prompts, arrivals = _workload(args, cfg.vocab_size)
    eng = _build_engine(model, args,
                        quant_weights=qw_mode or "0",
                        quant_kv=qkv_mode or "0")
    # explicit AOT warmup outside the timed window: compiles (or, with
    # PADDLE_TPU_COMPILE_CACHE=1, deserialize-and-loads) every serving
    # executable up front — the replica cold-start cost is a measured
    # number, not a first-request latency spike
    t_warm0 = time.perf_counter()
    warm_stats = eng.aot_warmup()
    warmup_s = time.perf_counter() - t_warm0
    from paddle_tpu.observability.device_profiler import compile_records
    warm_recs = [r for r in compile_records()
                 if r.target in warm_stats]
    # one throwaway request flushes any remaining lazy init
    w = eng.add_request(prompts[0][: max(2, len(prompts[0]) // 2)],
                        max_new_tokens=2)
    eng.run()
    st_warm = eng.request_status(w)
    first_token_s = (st_warm.timings.get("ttft_s")
                     if st_warm is not None else None)

    base = _run_stats(eng, prompts, arrivals, args)

    fleet_detail = None
    if args.fleet:
        # the fleet under test: same workload, fresh arrival clock; the
        # run above is the in-process single-engine baseline the
        # speedup/TTFT-ratio acceptance numbers divide by
        router = _build_router(model, args,
                               quant_weights=qw_mode or "0",
                               quant_kv=qkv_mode or "0")
        for rep in router._replicas.values():
            stats = rep.engine.aot_warmup()
            warm_stats.update(stats)
        w = router.add_request(
            prompts[0][: max(2, len(prompts[0]) // 2)],
            max_new_tokens=2)
        router.run()
        fleet = _run_stats(router, prompts, arrivals, args)
        serving = fleet
        serving_eng = router
        base_ttft99 = _percentiles(base["ttfts"])["p99"]
        fl_ttft99 = _percentiles(fleet["ttfts"])["p99"]
        fleet_detail = {
            "replicas": args.fleet,
            "prefill_replicas": (0 if args.fleet_mixed
                                 else max(1, args.prefill_replicas)),
            "decode_steps_per_sync": (args.decode_sync if not args.spec
                                      else 1),
            "baseline_tokens_per_s": round(base["tok_s"], 2),
            "speedup": round(fleet["tok_s"] / base["tok_s"], 4)
            if base["tok_s"] else None,
            "baseline_ttft_p99_s": base_ttft99,
            "ttft_p99_ratio": round(fl_ttft99 / base_ttft99, 4)
            if base_ttft99 and fl_ttft99 else None,
            "baseline_tpot_p99_s": _percentiles(base["tpots"])["p99"],
            "route_p50_s": _percentiles(fleet["route_s"],
                                        ps=(50,))["p50"],
            "handoff_p50_s": _percentiles(fleet["handoff_s"],
                                          ps=(50,))["p50"],
            "handoffs": _series("paddle_tpu_router_handoffs_total"),
            "dispatch": _series("paddle_tpu_router_affinity_total"),
            "requeues": _series("paddle_tpu_router_requeues_total"),
            "replica_deaths": _series(
                "paddle_tpu_router_replica_deaths_total"),
            "handoff_bytes": _series(
                "paddle_tpu_router_handoff_bytes_total"),
        }
    else:
        serving = base
        serving_eng = eng

    sessions_detail = None
    if args.sessions:
        sessions_detail = _session_drill(model, args, cfg.vocab_size,
                                         qw_mode or "0",
                                         qkv_mode or "0")
        print("sessions_resident trajectory (parked+active): "
              + " ".join(str(v) for v in
                         sessions_detail["resident_trajectory"]),
              file=sys.stderr)
        print(f"sessions_resident "
              f"peak={sessions_detail['resident_peak']} "
              f"hbm_equivalent="
              f"{sessions_detail['hbm_equivalent_sessions']} "
              f"ratio={sessions_detail['sessions_resident_ratio']} "
              f"token_identity={sessions_detail['token_identity']} "
              f"recompute_fallback="
              f"{sessions_detail['recompute_fallback_identity']}",
              file=sys.stderr)

    results, rids = serving["results"], serving["rids"]
    reused_tokens = serving["reused_tokens"]
    accept_rates = serving["accept_rates"]
    wall = serving["wall"]
    total_tokens = serving["tokens"]
    tok_s = serving["tok_s"]
    ttfts, tpots = serving["ttfts"], serving["tpots"]
    ttft = _percentiles(ttfts)
    tpot = _percentiles(tpots)

    # SLO attainment from the per-request timings (same targets the
    # engine's paddle_tpu_serving_slo_total counters judge against) —
    # the serving twin of training's goodput, guarded by --compare
    from paddle_tpu.observability.goodput import slo_targets
    targets = slo_targets()
    slo = {"ttft_target_s": targets["ttft"],
           "tpot_target_s": targets["tpot"],
           "ttft": (sum(1 for v in ttfts if v <= targets["ttft"])
                    / len(ttfts) if ttfts and targets["ttft"] > 0
                    else None),
           "tpot": (sum(1 for v in tpots if v <= targets["tpot"])
                    / len(tpots) if tpots and targets["tpot"] > 0
                    else None)}

    detail = {
        "requests": args.requests,
        "completed": len(results),
        "rps": args.rps,
        "wall_s": round(wall, 4),
        "generated_tokens": total_tokens,
        "ttft_p50_s": ttft["p50"], "ttft_p99_s": ttft["p99"],
        "tpot_p50_s": tpot["p50"], "tpot_p99_s": tpot["p99"],
        "spec_decode": args.spec,
        "steps_per_sync": args.steps_per_sync,
        "workload": args.workload,
        "shared_prefix": args.shared_prefix,
        "device": getattr(dev, "device_kind", dev.platform),
        "prefix_hit_tokens": reused_tokens,
        "slo_attainment": slo,
        "prefix_cache": _series("paddle_tpu_serving_prefix_cache_total"),
        "spec_tokens": _series("paddle_tpu_serving_spec_tokens_total"),
        "spec_accept_rate_mean": (float(np.mean(accept_rates))
                                  if accept_rates else None),
    }
    # per-cause tail attribution (ISSUE 20): fold every request's
    # timings through the forensics cause decomposition so --compare
    # can flag a dominant-cause flip or a cold-resume share regression
    from paddle_tpu.observability import forensics
    detail["tail_attribution"] = forensics.summarize_attributions(
        [forensics.attribute(t) for t in serving["timings"]])
    if fleet_detail is not None:
        detail["fleet"] = fleet_detail
    if sessions_detail is not None:
        detail["sessions"] = sessions_detail
    # replica cold-start ledger (ROADMAP 5): wall time to acquire every
    # serving executable (trace+compile live, or deserialize on a
    # compile-cache hit), TTFT of the first request after warmup, and
    # the cache counters that say which path this boot took
    from paddle_tpu import compile_cache
    cache_series = _series("paddle_tpu_compile_cache_total")
    detail["cold_start"] = {
        "trace_s": round(sum(r.lower_s for r in warm_recs), 4),
        "compile_or_load_s": round(
            sum(r.compile_s for r in warm_recs), 4),
        "warmup_wall_s": round(warmup_s, 4),
        "first_token_s": (round(first_token_s, 4)
                          if first_token_s else None),
        "executables": len(warm_stats),
        "cache_hits": sum(1 for r in warm_recs if r.cached),
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
    # measurement ledger (PADDLE_TPU_CALIBRATION=1): serving's decode
    # latency joins the corpus (provenance bench_serve; no model
    # prediction, so it contributes measurement coverage, not a
    # residual) and the artifact carries the same calibration-health
    # section bench.py does, guarded identically by --compare
    from paddle_tpu.observability import calibration
    if calibration.enabled() and tpot["p50"]:
        calibration.ledger().record(
            "serve_decode", (args.slots, args.max_len),
            measured_s=float(tpot["p50"]), provenance="bench_serve")
    detail["calibration"] = calibration.bench_detail()
    detail["kv_blocks_total"] = eng._num_blocks - 1
    detail["kv_blocks_peak_used"] = eng._blocks_used_peak
    detail["kv_block_utilization"] = round(
        eng._blocks_used_peak / max(1, eng._num_blocks - 1), 4)
    detail["kv_events"] = {
        "evictions": _series("paddle_tpu_serving_kv_evictions_total"),
        "cow": _series("paddle_tpu_serving_kv_cow_copies_total"),
        "alloc_failures": _series(
            "paddle_tpu_serving_kv_alloc_failures_total"),
    }
    if qw_mode or qkv_mode:
        # the quantized-serving capacity/accuracy ledger: blocks ratio
        # is the tentpole's measured capacity claim (int8 pools hold
        # itemsize-ratio more blocks at the SAME payload HBM bytes);
        # token_match_rate / max_logit_err land here when
        # --check-equivalence runs the parity gate below
        base_blocks = args.slots * (-(-args.max_len // args.block_size))
        detail["quant"] = {
            "weights": qw_mode,
            "kv": qkv_mode,
            "kv_blocks_ratio": round((eng._num_blocks - 1)
                                     / base_blocks, 4),
            "kv_pool_bytes": eng._pool.nbytes,
            "quant_paths": _series(
                "paddle_tpu_quant_kernel_path_total"),
            "token_match_rate": None,
            "max_logit_err": None,
        }
    result = {
        "metric": "serving_tokens_per_s",
        "value": round(tok_s, 2),
        "unit": "tokens/s",
        "detail": detail,
    }

    if args.check_equivalence:
        # replay sequentially through the plain bf16 engine (no prefix
        # cache, no speculation).  Unquantized: prefix-reusing /
        # speculative / routed greedy decode must be token-for-
        # token IDENTICAL.  Quantized: the accuracy-parity gate — the
        # greedy token-match rate must clear --parity-floor and the
        # weight-quant logit error must stay under --logit-tol, so
        # quantization can never silently rot quality.  Engines close
        # first: the weight conversion is refcounted on the model and
        # the baseline must see the original bf16 weights.
        serving_eng.close()
        if serving_eng is not eng:
            eng.close()
        base_eng = _build_engine(model, argparse.Namespace(
            **{**vars(args), "spec": 0}), prefix_cache=False)
        quant = bool(qw_mode or qkv_mode)
        mismatches = 0
        matched = total = 0
        for i, rid in enumerate(rids):
            b = base_eng.add_request(prompts[i],
                                     max_new_tokens=args.max_new)
            got = base_eng.run()[b][1]
            ours = results.get(rid) or []
            # greedy token-match counts up to and including the FIRST
            # divergence per request: past it the two engines decode
            # different contexts, so positionwise comparison would
            # charge one flipped argmax as a fully-wrong tail.  This is
            # P(token survives quantization | identical context) — the
            # spec-decode-literature greedy-equivalence metric.
            lcp = 0
            while lcp < min(len(got), len(ours)) and \
                    got[lcp] == ours[lcp]:
                lcp += 1
            diverged = lcp < max(len(got), len(ours))
            matched += lcp
            total += lcp + (1 if diverged else 0)
            if got != ours:
                mismatches += 1
                if not quant:
                    print(f"EQUIVALENCE MISMATCH req {i}: served="
                          f"{ours} baseline={got}", file=sys.stderr)
        match_rate = matched / total if total else 0.0
        result["detail"]["equivalence"] = {
            "checked": len(rids), "mismatches": mismatches,
            "token_match_rate": round(match_rate, 4)}
        if args.shared_prefix >= 2 * args.block_size and \
                reused_tokens < 1:
            print("EQUIVALENCE: expected >=1 prefix-cache hit on the "
                  "shared-prompt workload, saw none", file=sys.stderr)
            mismatches += 1
        if quant:
            q = result["detail"]["quant"]
            q["token_match_rate"] = round(match_rate, 4)
            failed = match_rate < args.parity_floor
            if qw_mode:
                from paddle_tpu.quantization.serving import \
                    parity_report
                rep = parity_report(model, qw_mode,
                                    prompts[0][None, :])
                q["max_logit_err"] = round(rep["max_logit_err"], 6)
                q["rel_logit_err"] = round(rep["rel_logit_err"], 6)
                if rep["rel_logit_err"] > args.logit_tol:
                    failed = True
                    print(f"PARITY: rel logit error "
                          f"{rep['rel_logit_err']:.4f} exceeds "
                          f"--logit-tol {args.logit_tol}",
                          file=sys.stderr)
            if failed or match_rate < args.parity_floor:
                print(f"PARITY GATE FAILED: token_match_rate="
                      f"{match_rate:.4f} (floor {args.parity_floor})",
                      file=sys.stderr)
                print(json.dumps(result))
                return 1
            print(f"parity ok: {len(rids)} requests, token_match_rate="
                  f"{match_rate:.4f} >= {args.parity_floor}, "
                  f"logit_err={q.get('rel_logit_err')}",
                  file=sys.stderr)
        elif mismatches:
            print(json.dumps(result))
            return 1
        else:
            print(f"equivalence ok: {len(rids)} requests, served == "
                  f"baseline, prefix_hit_tokens={reused_tokens}",
                  file=sys.stderr)

    print(json.dumps(result))

    if args.emit:
        here = os.path.dirname(os.path.abspath(__file__))
        path = args.emit
        if path == "auto":
            path = os.path.join(
                here, f"BENCH_serve_r{_next_serve_round(here):02d}.json")
        with open(path, "w") as f:
            json.dump({"schema": "bench_serve", "parsed": result}, f,
                      indent=1)
        print(f"wrote {path}", file=sys.stderr)

    if args.compare:
        import bench as _bench
        prev = _bench._prev_serve_record()
        if prev is None:
            print(json.dumps({"bench_compare": {
                "ok": True, "note": "no previous BENCH_serve artifact"}}),
                file=sys.stderr)
            return 0
        regressions = _bench.compare_serve_records(result, prev,
                                                   args.tolerance)
        print(json.dumps({"bench_compare": {
            "ok": not regressions, "tolerance": args.tolerance,
            "prev_value": prev.get("value"),
            "regressions": regressions}}), file=sys.stderr)
        if regressions:
            return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
