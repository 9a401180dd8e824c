"""chip_smoke.py — the quickest proof that paddle_tpu still starts on the chip.

One process, through the classes a user imports:

  python chip_smoke.py            one TPU chip: *train* (TrainStep + AdamW at
                                  the shape bench.py uses on a chip) and *serve*
                                  (ContinuousBatchingEngine over
                                  Llama-3-8B at its published widths, depth cut)
  python chip_smoke.py --chips 4  four chips: the sharded TrainStep (fsdp 2 x
                                  tp 2) at Llama-3-8B widths and the un-sharded
                                  forward it is compared with — nothing else

Every phase checks its own output against a reference computed another way
and fails loudly.  The script refuses to run a phase unless jax's first
device is a TPU: no CPU fallback, no interpret mode.  Only a run in which
every phase passed on a TPU prints the result line, last:

  {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

``--rehearse`` runs the same code at a tiny size on whatever backend jax
has (the CPU rehearsals of the on-chip-measurement guide).  It never prints
a result line and always exits non-zero.  Times printed here are smoke
observations, not benchmark numbers.
"""

from __future__ import annotations

import argparse
import contextlib
import gc
import json
import os
import re
import sys
import time
import traceback

import numpy as np

PATH_COUNTERS = (
    "paddle_tpu_fused_block_path_total",
    "paddle_tpu_fused_ce_calls_total",
    "paddle_tpu_paged_attention_path_total",
    "paddle_tpu_kernel_mesh_route_total",
    "paddle_tpu_autotune_cache_total",
)
# a greedy token may differ from the reference argmax only where the
# reference itself is a bf16 tie: its top logit leads the engine's token
# by at most this fraction of the largest |logit| (8 bf16 ulps)
TIE_REL_TOL = 2.0 ** -5
LOSS_REL_TOL = 1e-2      # step-0 loss vs the reference forward, bf16


class SmokeFailure(AssertionError):
    """A phase's check did not hold."""


def say(phase, msg):
    print(f"[{phase}] {msg}", flush=True)


def check(cond, msg):
    if not cond:
        raise SmokeFailure(msg)


# -- sizes -------------------------------------------------------------------

def sizes(tiny: bool):
    """Model configurations and shapes per phase.  Full size: published
    Llama-3-8B widths for serve and the sharded trainer (depth cut only),
    bench.py's on-chip shape for the one-chip trainer."""
    from paddle_tpu.models import LlamaConfig
    if tiny:
        cfg = LlamaConfig.tiny(max_position_embeddings=256,
                               dtype="bfloat16")
        return {
            "train": dict(cfg=cfg, batch=4, seq=32, steps=3, lr=1e-3),
            "serve": dict(cfg=cfg, why="tiny rehearsal", slots=2,
                          max_len=128, chunk=16, block=8,
                          prompts=(20, 48, 9, 33), new_tokens=6,
                          ref_len=128),
            "sharded": dict(cfg=cfg, batch=4, seq=32, steps=3, lr=1e-3),
        }
    import dataclasses
    l3 = LlamaConfig.llama3_8b()
    return {
        # bench.py's on-chip configuration (bench.py: `if on_tpu:`)
        "train": dict(
            cfg=LlamaConfig(
                vocab_size=32000, hidden_size=2048, intermediate_size=7168,
                num_hidden_layers=8, num_attention_heads=16,
                num_key_value_heads=8, max_position_embeddings=4096,
                rope_theta=500000.0, dtype="bfloat16"),
            batch=4, seq=2048, steps=4, lr=1e-4),
        "serve": dict(
            cfg=dataclasses.replace(l3, num_hidden_layers=8),
            why="8 of 32 layers: 2.8 B parameters, 5.6 GB in bf16 (11.2 GB "
                "while the fp32 initialiser's arrays are cast), beside the "
                "KV pool on one 16 GB chip; 32 layers are 16 GB of weights "
                "alone",
            slots=4, max_len=2048, chunk=256, block=16,
            prompts=(128, 384, 1024, 200, 640, 96), new_tokens=24,
            ref_len=1152),
        "sharded": dict(
            cfg=dataclasses.replace(l3, num_hidden_layers=2),
            batch=4, seq=2048, steps=3, lr=1e-4),
    }


# -- what every phase prints -------------------------------------------------

def series(name):
    from paddle_tpu.observability import default_registry
    m = default_registry().get(name)
    return {"/".join(k) or "all": c.value() for k, c in m.series()} \
        if m is not None else {}


def counters():
    return {n: series(n) for n in PATH_COUNTERS}


def print_paths(phase, before):
    """The routing decisions this phase's traces took (counter deltas)."""
    for name, now in counters().items():
        delta = {k: v - before[name].get(k, 0) for k, v in now.items()
                 if v != before[name].get(k, 0)}
        say(phase, f"paths {name} {json.dumps(delta, sort_keys=True)}")


def mem(device):
    st = device.memory_stats() or {}
    return {k: st[k] for k in ("bytes_in_use", "peak_bytes_in_use")
            if k in st}


def print_mem(phase, devices):
    for d in devices:
        say(phase, f"memory {d} {json.dumps(mem(d))}")


def check_kernels(phase, program, compiled, routed_on, on_tpu):
    """A kernel the gates routed on must be in the compiled program (the
    kernels carry no names in the HLO yet: they are counted)."""
    n = len(re.findall(r'custom_call_target="tpu_custom_call"',
                       compiled.as_text()))
    say(phase, f"program {program}: {n} tpu_custom_call(s); gates routed "
               f"on: {sorted(routed_on) or 'none'}")
    if on_tpu and routed_on:
        check(n > 0, f"{program}: the gates routed {sorted(routed_on)} to "
                     f"Pallas but the compiled program holds no "
                     f"tpu_custom_call")


def routed_on(before):
    """Kernels whose gate chose the Pallas path since ``before``."""
    on = set()
    for name, now in counters().items():
        for k, v in now.items():
            if v == before[name].get(k, 0):
                continue
            if name.endswith("fused_block_path_total") and \
                    k.endswith("/fused"):
                on.add(k.split("/")[0])
            elif name.endswith("fused_ce_calls_total") and k == "fused":
                on.add("fused_ce")
            elif name.endswith("paged_attention_path_total") and \
                    k == "pallas":
                on.add("paged_decode")
            elif (name.endswith("autotune_cache_total") or
                  name.endswith("kernel_mesh_route_total")) and \
                    k.startswith("flash/"):
                on.add("flash")
    return on


@contextlib.contextmanager
def gates_off():
    """The XLA path for everything but flash, through the switches the
    gates already have."""
    keys = ("PADDLE_TPU_FUSED_BLOCK", "PADDLE_TPU_FUSED_CE")
    old = {k: os.environ.get(k) for k in keys}
    os.environ.update({k: "0" for k in keys})
    try:
        yield
    finally:
        for k, v in old.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


def lm_batch(cfg, batch, seq, seed):
    ids = np.random.default_rng(seed).integers(
        0, cfg.vocab_size, (batch, seq + 1)).astype(np.int32)
    return {"input_ids": ids[:, :-1], "labels": ids[:, 1:]}


def reference_loss(model, batch):
    """model.loss() outside TrainStep, gates off, on the model's own
    (un-sharded) weights."""
    import jax
    from paddle_tpu.core.dispatch import unwrap
    from paddle_tpu.core.functional import functional_call, params_of
    with gates_off():
        fn = jax.jit(lambda p, i, l: unwrap(
            functional_call(model, p, i, l, method="loss")))
        return float(fn(params_of(model), batch["input_ids"],
                        batch["labels"]))


def run_steps(phase, step, batch, steps, ref):
    import jax
    losses = []
    for i in range(steps):
        t0 = time.perf_counter()
        losses.append(float(step(batch)))
        jax.block_until_ready(step.params)
        say(phase, f"step {i}: loss {losses[-1]:.4f} "
                   f"({time.perf_counter() - t0:.2f} s, smoke observation)")
    check(all(np.isfinite(losses)), f"non-finite loss: {losses}")
    check(losses[-1] < losses[0],
          f"loss did not fall on a repeated batch: {losses}")
    rel = abs(losses[0] - ref) / abs(ref)
    say(phase, f"step-0 loss {losses[0]:.4f} vs reference {ref:.4f} "
               f"(rel diff {rel:.2e}, tolerance {LOSS_REL_TOL})")
    check(rel <= LOSS_REL_TOL,
          f"step-0 loss {losses[0]} differs from the reference {ref}")
    return losses


def release(phase, devices, *, on_tpu):
    """Drop what the phase held and show the device let go of it."""
    import jax
    gc.collect()
    jax.clear_caches()
    gc.collect()
    if on_tpu:
        print_mem(phase + ":released", devices)


# -- phases ------------------------------------------------------------------

def train_phase(sz, seed, on_tpu):
    """TrainStep(model, AdamW(multi_precision=True)) for a few steps."""
    import jax
    import paddle_tpu as pp
    from paddle_tpu.jit import TrainStep
    from paddle_tpu.models import LlamaForCausalLM

    ph, cfg = "train", sz["cfg"]
    dev = jax.devices()[0]
    say(ph, f"config d{cfg.hidden_size} ffn{cfg.intermediate_size} "
            f"L{cfg.num_hidden_layers} {cfg.num_attention_heads}Q/"
            f"{cfg.num_key_value_heads}KV·{cfg.head_dim} v{cfg.vocab_size} "
            f"{cfg.dtype}, b{sz['batch']}·s{sz['seq']} — bench.py's on-chip "
            f"shape; Llama-3-8B widths do not fit one chip with AdamW state "
            f"(embedding + head alone are 14.7 GB): the full-width trainer "
            f"is --chips 4")
    pp.seed(seed)
    model = LlamaForCausalLM(cfg)
    batch = lm_batch(cfg, sz["batch"], sz["seq"], seed)
    ref = reference_loss(model, batch)
    opt = pp.optimizer.AdamW(learning_rate=sz["lr"],
                             parameters=model.parameters(),
                             multi_precision=True)
    before = counters()
    step = TrainStep(model, opt)
    info = step.compile(batch)
    say(ph, f"compile lower {info.lower_s:.1f} s + xla {info.compile_s:.1f} s"
            f" (cache hit: {info.cached})")
    print_paths(ph, before)
    check_kernels(ph, "TrainStep", step._compiled, routed_on(before), on_tpu)
    run_steps(ph, step, batch, sz["steps"], ref)
    print_mem(ph, [dev])


def serve_phase(sz, seed, on_tpu):
    """ContinuousBatchingEngine: warm-up, more requests
    than slots, greedy tokens against the plain full-context forward."""
    import jax
    import jax.numpy as jnp
    import paddle_tpu as pp
    from paddle_tpu.core.dispatch import unwrap
    from paddle_tpu.core.functional import functional_call, params_of
    from paddle_tpu.inference.serving import ContinuousBatchingEngine
    from paddle_tpu.models import LlamaForCausalLM

    ph, cfg = "serve", sz["cfg"]
    dev = jax.devices()[0]
    say(ph, f"config d{cfg.hidden_size} ffn{cfg.intermediate_size} "
            f"{cfg.num_attention_heads}Q/{cfg.num_key_value_heads}KV·"
            f"{cfg.head_dim} v{cfg.vocab_size} {cfg.dtype}, depth "
            f"{cfg.num_hidden_layers} — {sz['why']}")
    pp.seed(seed)
    model = LlamaForCausalLM(cfg)
    n_new, ref_len = sz["new_tokens"], sz["ref_len"]
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(0, cfg.vocab_size, (n,)).astype(np.int32)
               for n in sz["prompts"]]

    before = counters()
    # the first step exception raises: a compile error must not retire
    # its batch as "error" and let run() return as if it had served
    eng = ContinuousBatchingEngine(
        model, slots=sz["slots"], max_len=sz["max_len"],
        kv_block_size=sz["block"], prefill_buckets=(sz["chunk"],),
        prefill_chunk=sz["chunk"], max_consecutive_errors=1)
    errors0 = series("paddle_tpu_serving_engine_errors_total").get("all", 0)
    t0 = time.perf_counter()
    stats = eng.aot_warmup()
    say(ph, f"aot_warmup {sorted(stats)} in {time.perf_counter() - t0:.1f} s")
    print_paths(ph, before)
    on = routed_on(before)
    check_kernels(ph, "serving.decode", eng._decode_compiled,
                  on & {"paged_decode"}, on_tpu)
    check_kernels(ph, "serving.prefill_chunk", eng._prefill_chunk_compiled,
                  on - {"paged_decode"}, on_tpu)

    t0 = time.perf_counter()
    rids = [eng.add_request(p, max_new_tokens=n_new) for p in prompts]
    out = eng.run()
    say(ph, f"{len(prompts)} requests over {sz['slots']} slots "
            f"(prompts {list(sz['prompts'])}, {n_new} new tokens each, "
            f"prefill chunk {sz['chunk']}) in {time.perf_counter() - t0:.1f}"
            f" s (smoke observation)")
    errors = series("paddle_tpu_serving_engine_errors_total").get("all", 0) \
        - errors0
    statuses = [str(eng.request_status(r)) for r in rids]
    say(ph, f"statuses {statuses}, engine_errors {errors}")
    check(errors == 0, f"engine_errors counter rose by {errors}")
    check(all(s == "ok" for s in statuses), f"request statuses {statuses}")
    check(all(len(out[r][1]) == n_new for r in rids),
          f"token counts {[len(out[r][1]) for r in rids]} != {n_new}")

    # reference: teacher-forced argmax of the plain forward on the same
    # weights — no engine, no paged cache, fused-block gate off; one
    # padded length, so one compile (causal: the pad cannot reach back)
    def ref_rows(params, ids, start, toks):
        """Per generated position: the reference argmax, how far its
        logit leads the engine's token's, and the largest |logit|."""
        logits = unwrap(functional_call(model, params, ids))[0]
        rows = jax.lax.dynamic_slice_in_dim(logits, start, n_new, 0) \
            .astype(jnp.float32)
        got = jnp.take_along_axis(rows, toks[:, None], axis=1)[:, 0]
        return rows.argmax(-1), rows.max(-1) - got, jnp.abs(rows).max(-1)

    with gates_off():
        ref_fn = jax.jit(ref_rows)
        params = params_of(model)
        exact = total = 0
        for r, p in zip(rids, prompts):
            toks = np.asarray(out[r][1], np.int32)
            ids = np.zeros((1, ref_len), np.int32)
            ids[0, :len(p)] = p
            ids[0, len(p):len(p) + n_new] = toks
            top, gap, scale = (np.asarray(x) for x in
                               ref_fn(params, ids, len(p) - 1, toks))
            tol = TIE_REL_TOL * scale
            bad = np.nonzero(gap > tol)[0]
            exact += int((top == toks).sum())
            total += n_new
            check(bad.size == 0,
                  f"request {r} (prompt {len(p)}): token(s) at {bad.tolist()}"
                  f" trail the reference argmax by {gap[bad].tolist()} > "
                  f"{tol[bad].tolist()}")
    say(ph, f"greedy tokens vs plain forward: {exact}/{total} equal the "
            f"reference argmax; every other one is a bf16 tie (reference "
            f"top logit leads by <= {TIE_REL_TOL} x max|logit|)")
    check(exact >= total // 2,
          f"only {exact}/{total} tokens equal the reference argmax")
    print_mem(ph, [dev])
    eng.close()


def sharded_train_phase(sz, seed, devices, on_tpu):
    """TrainStep over Mesh(fsdp 2 x tp 2) against the un-sharded forward."""
    import jax
    from jax.sharding import Mesh, PartitionSpec as P
    import paddle_tpu as pp
    from paddle_tpu.jit import TrainStep
    from paddle_tpu.models import LlamaForCausalLM

    ph, cfg = "sharded", sz["cfg"]
    check(len(devices) == 4, f"need 4 devices, got {len(devices)}")
    mesh = Mesh(np.array(devices).reshape(2, 2), ("fsdp", "tp"))
    say(ph, f"config d{cfg.hidden_size} ffn{cfg.intermediate_size} "
            f"L{cfg.num_hidden_layers} {cfg.num_attention_heads}Q/"
            f"{cfg.num_key_value_heads}KV·{cfg.head_dim} v{cfg.vocab_size} "
            f"{cfg.dtype}, b{sz['batch']}·s{sz['seq']}, mesh fsdp 2 x tp 2 "
            f"over {[str(d) for d in devices]}")
    pp.seed(seed)
    with jax.default_device(devices[0]):
        model = LlamaForCausalLM(cfg)
        batch = lm_batch(cfg, sz["batch"], sz["seq"], seed)
        ref = reference_loss(model, batch)
        opt = pp.optimizer.AdamW(learning_rate=sz["lr"],
                                 parameters=model.parameters(),
                                 multi_precision=True)
        rules = LlamaForCausalLM.partition_specs(cfg, tp_axis="tp",
                                                 fsdp_axis="fsdp")
        specs = {n: LlamaForCausalLM.spec_for(n, rules)
                 for n in model.state_dict(keep_vars=True)}
        gc.collect()
        held = [mem(d).get("bytes_in_use", 0) for d in devices]
        before = counters()
        step = TrainStep(model, opt, mesh=mesh, param_specs=specs,
                         batch_spec=P("fsdp"))
    jax.block_until_ready((step.params, step.opt_state))

    # the spread: every parameter and every optimizer-state array of its
    # shape lives on all four devices, and no chip holds the whole state
    want = set(devices)
    state_bytes = 0
    for n, a in step.params.items():
        check(a.sharding.device_set == want,
              f"param {n} lives on {a.sharding.device_set}")
        state_bytes += a.nbytes
        for leaf in jax.tree.leaves(step.opt_state[n]):
            if getattr(leaf, "shape", None) == a.shape:
                check(leaf.sharding.device_set == want,
                      f"optimizer state of {n} lives on "
                      f"{leaf.sharding.device_set}")
                state_bytes += leaf.nbytes
    n_params = sum(int(np.prod(a.shape)) for a in step.params.values())
    say(ph, f"{n_params / 1e9:.2f} B parameters, {state_bytes / 1e9:.1f} GB "
            f"of training state, every array on all of {len(want)} devices")
    gc.collect()
    for d, h in zip(devices, held):
        used = mem(d).get("bytes_in_use")
        if used is None:
            continue    # the CPU backend reports no memory statistics
        share = (used - h) / state_bytes
        say(ph, f"{d}: {used / 1e9:.2f} GB in use, {(used - h) / 1e9:.2f} GB "
                f"of it new = {share:.2f} of the state")
        check(0.2 <= share <= 0.35,
              f"{d} holds {share:.2f} of the training state, not a quarter")

    info = step.compile(batch)
    say(ph, f"compile lower {info.lower_s:.1f} s + xla {info.compile_s:.1f} s"
            f" (cache hit: {info.cached})")
    print_paths(ph, before)
    check_kernels(ph, "TrainStep[fsdp2xtp2]", step._compiled,
                  routed_on(before), on_tpu)
    text = step._compiled.as_text()
    say(ph, "collectives " + json.dumps({
        op: len(re.findall(rf"\b{op}(?:-start)?\(", text))
        for op in ("all-gather", "all-reduce", "reduce-scatter",
                   "all-to-all", "collective-permute")}))
    run_steps(ph, step, batch, sz["steps"], ref)
    print_mem(ph, devices)


# -- driver ------------------------------------------------------------------

def autotune_blocks():
    """Block sizes in use on this backend, and where each came from: the
    shipped seed (benchmarks/autotune_tpu_v5.json) or a sweep on the chip
    (this run's, or an earlier one's kept under the cache root)."""
    from paddle_tpu.ops.pallas import autotune as at
    seed = at._parse(at.seed_path()) or {}
    tag = "@" + at.backend_tag()
    return {k: f"{v} ({'seed' if seed.get(k) == v else 'swept'})"
            for k, v in sorted(at.cached_entries().items())
            if k.endswith(tag)}


def native_libraries():
    """paddle_tpu's own shared objects mapped into this process."""
    try:
        with open("/proc/self/maps") as f:
            return sorted({ln.split("/")[-1].strip() for ln in f
                           if "libpt_" in ln})
    except OSError:
        return []


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: the sharded trainer and its comparison only")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny size on whatever backend jax has; never a "
                         "result line, always a non-zero exit")
    args = ap.parse_args(argv)

    import jax
    from paddle_tpu import compile_cache

    dev = jax.devices()[0]
    on_tpu = dev.platform == "tpu"
    n_dev = len(jax.devices())
    print(f"chip_smoke: jax {jax.__version__}, platform {dev.platform!r}, "
          f"kind {dev.device_kind!r}, {n_dev} device(s)", flush=True)
    if not args.rehearse:
        if not on_tpu:
            print(f"chip_smoke: refusing to run: jax's first device is on "
                  f"platform {dev.platform!r}, not 'tpu' (no CPU fallback, "
                  f"no interpret mode; --rehearse for the tiny CPU "
                  f"rehearsal)", file=sys.stderr)
            return 2
        if n_dev < args.chips:
            print(f"chip_smoke: refusing to run: --chips {args.chips} but "
                  f"jax reports {n_dev} device(s)", file=sys.stderr)
            return 2
    root = compile_cache.enable_persistent_cache()
    print(f"chip_smoke: compile cache at {root} "
          f"(JAX_COMPILATION_CACHE_DIR "
          f"{'set' if os.environ.get('JAX_COMPILATION_CACHE_DIR') else 'unset'}"
          f")", flush=True)

    sz = sizes(tiny=args.rehearse)
    devices = jax.devices()[:args.chips]
    if args.chips == 4:
        phases = [("sharded", lambda: sharded_train_phase(
            sz["sharded"], args.seed, devices, on_tpu))]
    else:
        # train first: the engine's pull gauges keep the serving model
        # alive in the metrics registry after close()
        phases = [("train", lambda: train_phase(sz["train"], args.seed,
                                                on_tpu)),
                  ("serve", lambda: serve_phase(sz["serve"], args.seed,
                                                on_tpu))]
    failed = []
    for name, run in phases:
        t0 = time.perf_counter()
        try:
            run()
            say(name, f"PASSED in {time.perf_counter() - t0:.0f} s")
        except Exception:
            traceback.print_exc()
            sys.stderr.flush()
            say(name, "FAILED")
            failed.append(name)
        release(name, devices, on_tpu=on_tpu)

    print(f"chip_smoke: persistent compile cache "
          f"{json.dumps(compile_cache.persistent_cache_counts())}, "
          f"executable cache "
          f"{json.dumps(series('paddle_tpu_compile_cache_total'))}",
          flush=True)
    print(f"chip_smoke: autotune blocks {json.dumps(autotune_blocks())}",
          flush=True)
    print(f"chip_smoke: native libraries loaded: "
          f"{native_libraries() or 'none'}", flush=True)
    if failed:
        print(f"chip_smoke: phases that did not pass: {failed}", flush=True)
        return 1
    if args.rehearse or not on_tpu:
        print("chip_smoke: rehearsal complete — not a chip run, no result",
              flush=True)
        return 3
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": n_dev}}),
        flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
