"""Training cells: TrainStep(model, AdamW(multi_precision=True)), on one
chip or over a mesh, fed a fresh seeded batch every step.

One object — the compiled step with its state — is built in set-up, driven
from the seed through its first steps by the window's own call and feed,
checked on those steps against the plain reference, and handed to the
window.
"""

from __future__ import annotations

import time

import numpy as np

from perf import common, flops, trace_reduce, weights
from perf.reference import train_steps

TRACE_SECONDS = 4.0     # of the window, from its start


def build(cell, seed, devices):
    """The program under test: model with the seed's weights, optimizer,
    TrainStep.  Everything not named in the traffic file's ``system`` is
    the program's default."""
    import jax
    import paddle_tpu as pp
    from paddle_tpu.jit import TrainStep

    cfg, system = cell["config"], cell["traffic"]["system"]
    hp = system["adamw"]
    arch = common.arch_of(cfg)
    model = arch.build(cfg, seed, devices[0])
    kw = {}
    with jax.default_device(devices[0]):
        opt = pp.optimizer.AdamW(
            learning_rate=hp["lr"], beta1=hp["beta1"], beta2=hp["beta2"],
            epsilon=hp["eps"], weight_decay=hp["weight_decay"],
            parameters=model.parameters(), multi_precision=True)
        if system.get("mesh"):
            from jax.sharding import Mesh, PartitionSpec as P
            axes = system["mesh"]
            mesh = Mesh(np.array(devices).reshape(list(axes.values())),
                        tuple(axes))
            kw = dict(mesh=mesh, batch_spec=P(system["batch_axis"]),
                      param_specs=arch.partition_specs(
                          model, tp_axis="tp", fsdp_axis="fsdp"))
        step = TrainStep(model, opt, **kw)
    return step


def program_norms(step, cfg, seed, hp):
    """From the step's own state: the first gradient as the optimizer got
    it (Adam's first moment after one step is (1 - beta1) g), and how far
    the float32 master weights are from the seed's."""
    import jax
    import jax.numpy as jnp
    spec = weights.leaves(cfg)

    def grads(state):
        return {n: jnp.sqrt(jnp.sum(jnp.square(state[n]["moment1"])))
                / (1 - hp["beta1"]) for n, _, _ in spec}

    def deltas(key, state, params):
        out = {}
        for i, (n, shape, init) in enumerate(spec):
            now = state[n].get("_master", params[n]).astype(jnp.float32)
            p0 = weights._leaf(key, i, shape, init, params[n].dtype)
            out[n] = jnp.sqrt(jnp.sum(jnp.square(
                now - p0.astype(jnp.float32))))
        return out

    to_float = lambda t: {n: float(v) for n, v in t.items()}
    return (lambda: to_float(jax.jit(grads)(step.opt_state)),
            lambda: to_float(jax.jit(deltas)(
                weights.base_key(seed), step.opt_state, step.params)))


def run(bench, cell, args, t_start, control=None):
    devices = common.require_device(cell["chips"])
    import jax
    from paddle_tpu import compile_cache
    compile_cache.enable_persistent_cache()
    missed = common.watch_cache_misses()

    cfg, traffic = cell["config"], cell["traffic"]
    params, system = traffic["params"], traffic["system"]
    hp = {k: system["adamw"][k]
          for k in ("lr", "beta1", "beta2", "eps", "weight_decay")}
    limits = traffic["limits"]
    gen = common.load_generator(traffic)
    feed = lambda i: gen.batch(params, cfg, args.seed, i)
    tokens_per_step = params["batch"] * params["seq"]
    common.say(f"cell {cell['name']}: {cell['config_entry']['name']} depth "
               f"{cfg['num_hidden_layers']}, "
               f"{flops.total_params(cfg) / 1e6:.0f} M parameters, batch "
               f"{params['batch']} x {params['seq']}, {len(devices)} x "
               f"{devices[0].device_kind}, seed {args.seed}")

    # the reference first: nothing of the program is on the device yet,
    # and its time is not set-up
    t = time.perf_counter()
    ref = train_steps.follow(
        cfg, args.seed, [feed(i) for i in range(train_steps.STEPS)], hp,
        sharding=_leaf_sharding(devices))
    if control:
        # the reference again, computed in the precision below the
        # configuration's: every number beside the limit it must break
        low = train_steps.follow(
            cfg, args.seed, [feed(i) for i in range(train_steps.STEPS)], hp,
            precision=control, sharding=_leaf_sharding(devices))
        for i in range(train_steps.STEPS):
            _control(control, "loss_rel", limits, abs(
                low["loss"][i] - ref["loss"][i]) / abs(ref["loss"][i]))
        for k in ("grad_norm", "delta_norm"):
            gap, _, mean = train_steps.worst_leaf_gap(low[k], ref[k])
            _control(control, k + "_worst_leaf", limits, gap)
            if k == "grad_norm":
                _control(control, k + "_mean_leaf", limits, mean)
    if control and not args.seconds:
        return 0        # the control's readings need no program
    jax.clear_caches()
    ref_s = time.perf_counter() - t
    common.say(f"reference: {train_steps.STEPS} steps in {ref_s:.1f} s "
               f"(not counted in setup_s), losses {ref['loss']}; device "
               f"memory peak so far "
               f"{common.device_info(devices)['memory_peak_bytes'] / 1e9:.2f}"
               f" GB")

    step = build(cell, args.seed, devices)
    info = step.compile(feed(0))
    common.say(f"compile: lower {info.lower_s:.1f} s + xla "
               f"{info.compile_s:.1f} s (executable cache hit: "
               f"{info.cached})")
    grad_norms, delta_norms = program_norms(step, cfg, args.seed, hp)

    def one_step(i):
        with jax.profiler.TraceAnnotation("bench.batch_prep"):
            batch = feed(i)
        with jax.profiler.TraceAnnotation("bench.train_step"):
            loss = step(batch)
            jax.block_until_ready(step.params)
        return loss

    got = {"loss": []}
    for i in range(train_steps.STEPS + 1):
        got["loss"].append(float(one_step(i)))
        if i == 0:
            got["grad_norm"] = grad_norms()
        if i == train_steps.STEPS - 1:
            got["delta_norm"] = delta_norms()
    misses0 = compile_cache.persistent_cache_counts()
    recompiles0 = common.total("paddle_tpu_train_recompiles_total")
    setup_s = time.perf_counter() - t_start - ref_s
    common.say(f"programs that missed the persistent cache: {missed}")

    # the window
    done = train_steps.STEPS + 1
    stamps = []
    prof = {}
    w0 = time.perf_counter()
    if args.trace:
        with common.profiler_window(True) as prof:
            while time.perf_counter() - w0 < min(TRACE_SECONDS,
                                                 args.seconds):
                one_step(done + len(stamps))
                stamps.append(time.perf_counter())
                if len(stamps) == 1:    # starting the profiler stalled it
                    with jax.profiler.TraceAnnotation(
                            trace_reduce.WINDOW_BEGIN):
                        pass
    while time.perf_counter() - w0 < args.seconds:
        one_step(done + len(stamps))
        stamps.append(time.perf_counter())
    elapsed = stamps[-1] - w0
    rate = len(stamps) * tokens_per_step / elapsed / len(devices)
    steps = np.diff([w0] + stamps)
    misses1 = compile_cache.persistent_cache_counts()
    common.say(f"window: {len(stamps)} steps in {elapsed:.3f} s; step "
               f"median {np.median(steps) * 1e3:.1f} ms, min "
               f"{steps.min() * 1e3:.1f}, max {steps.max() * 1e3:.1f}")
    if misses1["misses"] != misses0["misses"]:
        raise AssertionError(f"a program compiled inside the window: "
                             f"{misses0} -> {misses1}")

    check = common.Check()
    for i in range(train_steps.STEPS):
        rel = abs(got["loss"][i] - ref["loss"][i]) / abs(ref["loss"][i])
        what = (f"program {got['loss'][i]:.6f} vs reference "
                f"{ref['loss'][i]:.6f}")
        if i == 0:
            check.add("loss_step1_rel", rel, limits["loss_rel"], what)
        else:
            # no control and no fault reads a later step's loss far enough
            # above the sound runs (PERF.md section 2): it could only
            # fail them, so it is printed and not compared
            common.say(f"loss_step{i + 1}_rel: {rel:.6g} (not compared) — "
                       f"{what}")
    gap, leaf, mean = train_steps.worst_leaf_gap(got["grad_norm"],
                                                 ref["grad_norm"])
    check.add("grad_norm_worst_leaf", gap, limits["grad_norm_worst_leaf"],
              f"first gradient, worst at {leaf}")
    check.add("grad_norm_mean_leaf", mean, limits["grad_norm_mean_leaf"],
              "first gradient, mean over the leaves")
    gap, leaf, _ = train_steps.worst_leaf_gap(got["delta_norm"],
                                              ref["delta_norm"])
    check.add("delta_norm_worst_leaf", gap, limits["delta_norm_worst_leaf"],
              f"parameters' change after {train_steps.STEPS} steps, worst "
              f"at {leaf}")

    obs = {"cell": cell, "devices": devices, "step_s": steps,
           "window_s": elapsed, "tokens_per_s_per_chip": rate,
           "tokens_per_step": tokens_per_step,
           "recompiles": common.total("paddle_tpu_train_recompiles_total")
           - recompiles0,
           "cache_misses": misses0["misses"],
           "programs": {"train": step._compiled},
           "trace": trace_reduce.load(prof["path"]) if args.trace else None,
           "peaks": flops.peaks(devices[0].device_kind)}
    layer, device_extra, breakdown = {}, None, None
    if args.trace:
        layer = common.read_layer_metrics(bench, cell, obs)
        device_extra, breakdown = trace_reduce.device_summary(obs["trace"])
    common.emit(bench, cell, trace=args.trace, correct=check.ok,
                attempted=len(stamps), failed=0,
                end_to_end={"train_tokens_per_s_per_chip": rate,
                            "setup_s": setup_s},
                layer=layer,
                device=common.device_info(devices, device_extra),
                breakdown=breakdown, check=check)
    return 0


def _control(control, name, limits, value):
    common.say(f"control[{control}] {name}: {value:.6g} (limit "
               f"{limits[name]:g}) "
               f"{'FAILS' if value > limits[name] else 'passes'}")


def _leaf_sharding(devices):
    """On several chips the reference's float32 leaves are split over
    them along their first axis (they would not fit one chip)."""
    if len(devices) == 1:
        return None
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    mesh = Mesh(np.array(devices), ("x",))
    return lambda shape: NamedSharding(mesh, P("x"))
