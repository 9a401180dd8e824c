"""Serving cells: ContinuousBatchingEngine(paged_kv=True) under an open
loop.  Requests are offered when they are due whether or not earlier ones
have finished; every time is taken from when a request was *due*.

The window offers requests for ``--seconds`` seconds and then drains those
in flight for at most ``DRAIN_LIMIT_S``; a request that failed or did not
finish misses, and its times are counted at that limit.  Once the window
has closed, a seeded sample of the finished requests (the longest in it)
goes through the plain reference.
"""

from __future__ import annotations

import time

import numpy as np

from perf import common, flops, trace_reduce
from perf.reference import served

DRAIN_LIMIT_S = 30.0
TRACE_SECONDS, TRACE_SETTLE_S = 6.0, 2.0   # the window's last seconds are
# traced; the profiler starts TRACE_SETTLE_S earlier (starting it stalls
# the host) and stops after the drain (stopping it stalls for seconds)
WARM_PROMPTS = (300, 40)                  # two chunks and one; 4 tokens each


def build(cell, seed, device):
    """Model with the seed's weights and the engine over it.  The traffic
    file's ``system.engine`` is passed to the engine as it stands; every
    other argument is the program's default."""
    import jax
    from paddle_tpu.inference.serving import ContinuousBatchingEngine

    cfg = cell["config"]
    model = common.arch_of(cfg).build(cfg, seed, device)
    with jax.default_device(device):
        engine = dict(cell["traffic"]["system"]["engine"])
        if "prefill_chunk" in engine:
            engine.setdefault("prefill_buckets", (engine["prefill_chunk"],))
        eng = ContinuousBatchingEngine(model, **engine)
    return eng


def drive(eng, reqs, seconds, trace_at=None):
    """The open loop.  Returns per-request records, the window's start on
    the host clock, per-step gauge samples and the profiler's bounds."""
    import jax
    ann = jax.profiler.TraceAnnotation
    recs = [dict(r, rid=None, offered=None, tokens=None) for r in reqs]
    by_rid, nxt, blocks = {}, 0, []
    prof, prof_cm = {}, None
    w0 = time.perf_counter()
    while True:
        now = time.perf_counter() - w0
        if trace_at and prof_cm is None and \
                now >= trace_at[0] - TRACE_SETTLE_S:
            prof_cm = common.profiler_window(True)
            prof = prof_cm.__enter__()
        if trace_at and now >= trace_at[0] and "begin" not in prof:
            prof["begin"] = now
            with ann(trace_reduce.WINDOW_BEGIN):
                pass
        if trace_at and now >= trace_at[1] and "end" not in prof:
            prof["end"] = now
            with ann(trace_reduce.WINDOW_END):
                pass
        with ann("bench.offer"):
            while nxt < len(recs) and recs[nxt]["due_s"] <= now:
                r = recs[nxt]
                r["rid"] = eng.add_request(r["prompt"],
                                           max_new_tokens=r["max_new"])
                r["offered"] = time.perf_counter() - w0
                by_rid[r["rid"]] = r
                nxt += 1
        if nxt == len(recs) and not eng.pending:
            break
        if now >= seconds + DRAIN_LIMIT_S:
            break
        if eng.pending:
            with ann("bench.engine_step"):
                eng.step()
            with ann("bench.collect"):
                for rid, _p, out in eng.finished():
                    by_rid[rid]["tokens"] = out
                blocks.append((common.total(
                    "paddle_tpu_serving_kv_blocks_used"), common.total(
                    "paddle_tpu_serving_kv_blocks_free")))
        else:
            time.sleep(min(0.002, max(0.0, recs[nxt]["due_s"] - now)))
    if prof_cm:
        now = time.perf_counter() - w0
        prof.setdefault("begin", min(now, trace_at[0]))
        prof.setdefault("end", now)
        prof_cm.__exit__(None, None, None)
    for r in recs:
        st = eng.request_status(r["rid"]) if r["rid"] is not None else None
        r["status"] = str(st) if st is not None else "unfinished"
        t = getattr(st, "timings", None) or {}
        for k in ("admitted", "first_token", "retired"):
            r[k] = (t[k] - w0) if t.get(k) else None
        r["ok"] = r["status"] == "ok" and r["tokens"] is not None and \
            len(r["tokens"]) == r["max_new"]
    return recs, w0, blocks, prof


def summarise(recs, seconds):
    """End-to-end numbers over *all* requests offered; a miss counts at
    the drain limit."""
    miss = DRAIN_LIMIT_S * 1e3
    ttft, tpot, done_tokens = [], [], 0
    for r in recs:
        if not r["ok"]:
            ttft.append(miss)
            tpot.append(miss)
            continue
        ttft.append((r["first_token"] - r["due_s"]) * 1e3)
        tpot.append((r["retired"] - r["first_token"]) * 1e3
                    / max(1, len(r["tokens"]) - 1))
        if r["retired"] <= seconds:
            done_tokens += len(r["prompt"]) + len(r["tokens"])
    return {"ttft_ms": np.array(ttft), "tpot_ms": np.array(tpot),
            "serve_tokens_per_s": done_tokens / seconds}


def sample_rows(recs, seed, count):
    """The longest finished request and ``count - 1`` others by the seed."""
    ok = [r for r in recs if r["ok"]]
    if not ok:
        return []
    ok.sort(key=lambda r: -(len(r["prompt"]) + len(r["tokens"])))
    rng = np.random.default_rng([int(seed), 11])
    rest = rng.permutation(len(ok) - 1)[:count - 1] + 1
    return [(ok[i]["prompt"], np.asarray(ok[i]["tokens"], np.int32))
            for i in [0, *sorted(rest.tolist())]]


def live_kv_tokens(recs, lo, hi, since="first_token", block=1, n=400):
    """At ``n`` instants of [lo, hi]: the tokens whose keys and values the
    requests hold, counted from ``since`` (a request grows linearly from
    its first token to its retirement), in whole blocks of ``block``."""
    ts = np.linspace(lo, hi, n)
    live = np.zeros(n)
    for r in recs:
        if not r["ok"] or r["retired"] <= r["first_token"]:
            continue
        inside = (ts >= r[since]) & (ts <= r["retired"])
        frac = np.clip((ts - r["first_token"])
                       / (r["retired"] - r["first_token"]), 0, 1)
        held = len(r["prompt"]) + frac * len(r["tokens"])
        live += inside * np.ceil(held / block) * block
    return live


def live_rows(recs, lo, hi, n=400):
    """At the same instants: the requests between their first token and
    their retirement, the rows a decode step carries."""
    ts = np.linspace(lo, hi, n)
    live = np.zeros(n)
    for r in recs:
        if r["ok"] and r["retired"] > r["first_token"]:
            live += (ts >= r["first_token"]) & (ts <= r["retired"])
    return live


def run(bench, cell, args, t_start, control=None):
    device = common.require_device(cell["chips"])[0]
    from paddle_tpu import compile_cache
    compile_cache.enable_persistent_cache()
    missed = common.watch_cache_misses()

    cfg, traffic = cell["config"], cell["traffic"]
    params, limits = traffic["params"], traffic["limits"]
    gen = common.load_generator(traffic)
    reqs = gen.requests(params, cfg, args.seed, args.seconds)
    common.say(f"cell {cell['name']}: {cell['config_entry']['name']} depth "
               f"{cfg['num_hidden_layers']}, "
               f"{flops.total_params(cfg) / 1e9:.2f} B parameters, engine "
               f"{traffic['system']['engine']}, {len(reqs)} requests in "
               f"{args.seconds:g} s, {device.device_kind}, seed {args.seed}")

    eng = build(cell, args.seed, device)
    stats = eng.aot_warmup()
    common.say(f"aot_warmup: {sorted(stats)}")
    rng = np.random.default_rng([int(args.seed), 3])
    for n in WARM_PROMPTS:
        eng.add_request(rng.integers(0, cfg["vocab_size"], n,
                                     dtype=np.int32), max_new_tokens=4)
    eng.run()
    cache0 = compile_cache.persistent_cache_counts()
    setup_s = time.perf_counter() - t_start
    common.say(f"programs that missed the persistent cache: {missed}")

    trace_at = None
    if args.trace:
        trace_at = (max(0.0, args.seconds - TRACE_SECONDS), args.seconds)
    recs, w0, blocks, prof = drive(eng, reqs, args.seconds, trace_at)
    cache1 = compile_cache.persistent_cache_counts()
    if cache1["misses"] != cache0["misses"]:
        raise AssertionError(f"a program compiled inside the window: "
                             f"{cache0} -> {cache1}")
    res = summarise(recs, args.seconds)
    failed = sum(not r["ok"] for r in recs)
    late = np.array([r["offered"] - r["due_s"] for r in recs
                     if r["offered"] is not None]) * 1e3
    pct = lambda a, q: float(np.percentile(a, q))
    e2e = {"ttft_p95_ms": pct(res["ttft_ms"], 95),
           "tpot_p95_ms": pct(res["tpot_ms"], 95),
           "serve_tokens_per_s": res["serve_tokens_per_s"],
           "setup_s": setup_s}
    common.say("stats " + " ".join(
        f"{k}_{n}={f(res[k + '_ms']):.3f}" for k in ("ttft", "tpot")
        for n, f in (("mean", np.mean), ("p50", lambda a: pct(a, 50)),
                     ("p90", lambda a: pct(a, 90)),
                     ("p99", lambda a: pct(a, 99)))))
    common.say(f"window: {len(recs)} offered, {failed} failed or unfinished;"
               f" ttft p50 {pct(res['ttft_ms'], 50):.1f} ms, tpot p50 "
               f"{pct(res['tpot_ms'], 50):.2f} ms; generator lateness p50 "
               f"{pct(late, 50):.2f} ms, max {late.max():.2f} ms; statuses "
               f"{sorted({r['status'] for r in recs})}")

    # correct: the served tokens of a seeded sample against the reference
    check = common.Check()
    rows = sample_rows(recs, args.seed, traffic["check"]["requests"])
    t = time.perf_counter()
    pad = (-(-(params["prompt"]["max"] + params["output"]["max"]) // 128)
           * 128, params["output"]["max"])
    lg = served.served_logits(cfg, args.seed, rows, *pad) if rows else None
    if lg is not None:
        g = served.gaps(lg, rows)
        common.say(f"reference: {len(rows)} requests, {g.size} served "
                   f"tokens in {time.perf_counter() - t:.1f} s; "
                   f"{int((g == 0).sum())} of them are the reference's own "
                   f"best")
        check.add("served_gap_max", g.max(), limits["served_gap_max"],
                  "widest (best logit - served token's logit) / max|logit|")
        check.add("served_gap_mean", g.mean(), limits["served_gap_mean"],
                  "mean of the same over the served tokens")
    check.add("failed_share", failed / len(recs), limits["failed_share"],
              "requests that failed or did not finish")
    if control and lg is not None:
        low = served.served_logits(cfg, args.seed, rows, *pad,
                                   precision=control)
        g = served.gaps(lg, rows, tokens=[a.argmax(-1) for a in low])
        for name, v in (("served_gap_max", g.max()),
                        ("served_gap_mean", g.mean())):
            common.say(f"control[{control}] {name}: {v:.6g} (limit "
                       f"{limits[name]:g}) "
                       f"{'FAILS' if v > limits[name] else 'passes'}")

    layer, device_extra, breakdown = {}, None, None
    if args.trace:
        tr = trace_reduce.load(prof["path"])
        obs = {"cell": cell, "devices": [device], "requests": recs,
               "blocks": blocks, "trace": tr,
               "cache_misses": cache0["misses"],
               "untraced_until": trace_at[0] - TRACE_SETTLE_S,
               "live_kv_tokens": float(live_kv_tokens(
                   recs, prof["begin"], prof["end"]).mean()),
               "live_rows": float(live_rows(
                   recs, prof["begin"], prof["end"]).mean()),
               "held_kv_tokens_peak": float(live_kv_tokens(
                   recs, 0.0, args.seconds, "admitted",
                   traffic["system"]["engine"]["kv_block_size"]).max()),
               "programs": {"decode": eng._decode_compiled,
                            "prefill_chunk": eng._prefill_chunk_compiled},
               "peaks": flops.peaks(device.device_kind)}
        layer = common.read_layer_metrics(bench, cell, obs)
        device_extra, breakdown = trace_reduce.device_summary(tr)
    common.emit(bench, cell, trace=args.trace, correct=check.ok,
                attempted=len(recs), failed=failed, end_to_end=e2e,
                layer=layer,
                device=common.device_info([device], device_extra),
                breakdown=breakdown, check=check)
    return 0
