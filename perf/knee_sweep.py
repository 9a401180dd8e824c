"""Find a serving cell's knee once, on the chip: the highest offered rate
the engine sustains without a growing backlog.

  python3 perf/knee_sweep.py --workload serve-chat --rates 3,5,6,7,8,10

One engine, the cell's own mix at each rate in turn (its own seed each),
``--seconds`` of offers and the drain.  A rate is sustained when the
requests still in flight at the end of the window are no more than at its
middle plus what one second offers (the backlog is not growing) and none
failed.  The table goes to stdout and to chiprun_out/knee_sweep.json; the
benchmark PR that runs it writes the table and 0.8 x the knee into the
cell's traffic file.  The benchmark's own runs never search for a rate.
"""

import sys
import time

T_START = time.perf_counter()

import run as _run   # noqa: E402  (perf/run.py: puts the root on sys.path)


def main(argv=None) -> int:
    import argparse
    import json
    import os
    import numpy as np
    from perf import common
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=25.0)
    args = ap.parse_args(argv)
    bench = common.load_json(os.path.join(_run.ROOT, "BENCHMARK.json"))
    cell = common.resolve_cell(bench, args.workload, _run.ROOT)
    common.use_cache_dir()
    from perf.kinds import serve
    device = common.require_device(cell["chips"])[0]
    from paddle_tpu import compile_cache
    compile_cache.enable_persistent_cache()
    cfg, traffic = cell["config"], cell["traffic"]
    gen = common.load_generator(traffic)
    eng = serve.build(cell, args.seed, device)
    eng.aot_warmup()
    rng = np.random.default_rng(0)
    for n in serve.WARM_PROMPTS:
        eng.add_request(rng.integers(0, cfg["vocab_size"], n,
                                     dtype=np.int32), max_new_tokens=4)
    eng.run()
    common.say(f"set-up {time.perf_counter() - T_START:.1f} s")
    table = []
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        params = dict(traffic["params"], rate_per_s=rate)
        reqs = gen.requests(params, cfg, args.seed + i, args.seconds)
        recs, _, _, _ = serve.drive(eng, reqs, args.seconds)
        res = serve.summarise(recs, args.seconds)
        flying = lambda t: sum(
            r["due_s"] <= t and (r["retired"] is None or r["retired"] > t)
            for r in recs)
        mid, end = flying(args.seconds / 2), flying(args.seconds)
        failed = sum(not r["ok"] for r in recs)
        pct = lambda a, q: float(np.percentile(a, q))
        row = {"rate_per_s": rate, "offered": len(recs), "failed": failed,
               "in_flight_mid": mid, "in_flight_end": end,
               "sustained": failed == 0 and end <= mid + rate,
               "serve_tokens_per_s": res["serve_tokens_per_s"],
               "ttft_p50_ms": pct(res["ttft_ms"], 50),
               "ttft_p95_ms": pct(res["ttft_ms"], 95),
               "tpot_p50_ms": pct(res["tpot_ms"], 50),
               "tpot_p95_ms": pct(res["tpot_ms"], 95),
               "drain_s": max((r["retired"] or 0) for r in recs)
               - args.seconds}
        table.append(row)
        common.say("sweep " + json.dumps(row))
    os.makedirs(os.path.join(common.ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(common.ROOT, "chiprun_out", "knee_sweep.json"),
              "w") as f:
        json.dump({"device": device.device_kind, "seconds": args.seconds,
                   "table": table}, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
