"""TrainStep — the compiled training step.

TPU-native replacement for the reference's static-graph path: where Paddle
builds a ProgramDesc and runs it on InterpreterCore
(python/paddle/fluid/executor.py:1241 → new_executor/interpretercore.cc:188),
here the whole train step (forward + backward + optimizer update) is ONE
jitted pure function over (params, opt_state, batch) pytrees.  XLA is the
interpreter, scheduler, and memory planner.

Supports single-chip jit and sharded pjit: pass `mesh` + `param_specs` and
every pytree is placed with NamedSharding; XLA/GSPMD inserts the collectives
(grad psum for DP, mp allreduce for TP, …).
"""

from __future__ import annotations

import time
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp

from paddle_tpu.core.functional import functional_call, params_of, \
    trainable_mask

__all__ = ["TrainStep", "CompiledStepBase"]


def _resolve_plan(shardings, mesh, param_specs, batch_spec):
    """Expand a ``shardings=`` argument — an AutoShardPlan or a plain
    ``{name → PartitionSpec}`` dict — into (mesh, param_specs,
    batch_spec), keeping any explicitly-passed value."""
    if hasattr(shardings, "param_specs"):        # AutoShardPlan duck type
        if getattr(shardings, "is_pipeline", False):
            raise ValueError(
                "autoshard plan has pp>1 — a pipeline layout targets "
                "distributed.PipelineTrainStep, not TrainStep")
        mesh = mesh if mesh is not None else shardings.jax_mesh()
        param_specs = param_specs if param_specs is not None \
            else dict(shardings.param_specs)
        batch_spec = batch_spec if batch_spec is not None \
            else shardings.batch_spec
        return mesh, param_specs, batch_spec
    if isinstance(shardings, dict):
        if mesh is None:
            for sh in shardings.values():
                m = getattr(sh, "mesh", None)
                if m is not None:
                    mesh = m
                    break
        specs = {n: getattr(sh, "spec", sh) for n, sh in shardings.items()}
        return mesh, (param_specs if param_specs is not None else specs), \
            batch_spec
    raise TypeError(f"shardings= expects an AutoShardPlan or a dict, "
                    f"got {type(shardings).__name__}")


@jax.custom_vjp
def _ordered_after(x, token):
    """``x`` pinned to issue after ``token`` via optimization_barrier —
    the link of the collective-overlap prefetch chain.  The barrier is a
    forward scheduling constraint only, so the VJP passes the cotangent
    straight through (the backward's gather/reduce-scatter schedule is
    XLA's to pick)."""
    return jax.lax.optimization_barrier((x, token))[0]


def _ordered_after_fwd(x, token):
    return _ordered_after(x, token), token


def _ordered_after_bwd(token, g):
    return g, jax.tree.map(jnp.zeros_like, token)


_ordered_after.defvjp(_ordered_after_fwd, _ordered_after_bwd)


def _train_metrics():
    """Lazily created instruments on the default registry (shared by
    every TrainStep in the process — that is what an operator scrapes)."""
    from paddle_tpu.observability import default_registry
    reg = default_registry()
    return {
        "step": reg.histogram(
            "paddle_tpu_train_step_seconds",
            "wall time of one compiled train step (fwd+bwd+update)"),
        "steps": reg.counter("paddle_tpu_train_steps_total",
                             "train steps executed"),
        "tokens": reg.counter("paddle_tpu_train_tokens_total",
                              "tokens consumed by train steps"),
        "tps": reg.gauge("paddle_tpu_train_tokens_per_second",
                         "tokens/s of the most recent train step"),
        "loss": reg.gauge("paddle_tpu_train_loss",
                          "loss of the most recent train step"),
        "gnorm": reg.gauge("paddle_tpu_train_grad_norm",
                           "global gradient norm of the most recent "
                           "train step"),
        "recompiles": reg.counter(
            "paddle_tpu_train_recompiles_total",
            "novel call signatures after the first — each one is a "
            "silent retrace + XLA compile"),
        "accum": reg.histogram(
            "paddle_tpu_train_accum_microbatches",
            "microbatches accumulated per optimizer update",
            buckets=(1, 2, 4, 8, 16, 32, 64)),
        "skipped": reg.counter(
            "paddle_tpu_train_step_skipped_total",
            "optimizer updates skipped by the non-finite step-guard "
            "(params and optimizer state left unchanged)",
            labelnames=("reason",)),
        "mfu": reg.gauge(
            "paddle_tpu_train_mfu",
            "measured model-FLOPs utilisation of the most recent step "
            "(XLA executable FLOPs / step time / device peak; set once "
            "TrainStep.compile() has introspected the executable)"),
        # goodput accounting (fleet observability tentpole): wall time
        # of APPLIED updates vs. time burned on guard-discarded ones —
        # observability.goodput turns these into the goodput gauge
        "productive": reg.counter(
            "paddle_tpu_train_productive_seconds_total",
            "step wall seconds whose optimizer update was applied "
            "(the goodput numerator)"),
        "skipped_s": reg.counter(
            "paddle_tpu_train_skipped_seconds_total",
            "step wall seconds whose update the non-finite step-guard "
            "discarded (lost time, debited from goodput)"),
        "ema": reg.gauge(
            "paddle_tpu_train_step_ema_seconds",
            "EMA of step wall time — host-labeled after fleet "
            "federation, the series the straggler SLO rule compares "
            "against the fleet median"),
    }


class CompiledStepBase:
    """Shared plumbing for compiled training steps (``TrainStep`` and
    ``distributed.PipelineTrainStep``): sharded placement of params and
    optimizer state, the donated-jit call protocol, lr/scheduler wiring,
    and the checkpoint state_dict round-trip.  Subclasses build
    ``self._jitted`` with signature
    ``(params, opt_state, step_count, *step_args, lr) ->
    (loss, params, opt_state, step_count)`` — the loss slot may be any
    pytree the subclass's caller unpacks (TrainStep returns
    ``(loss, grad_norm, skip_code)`` there for the telemetry gauges and
    the non-finite step-guard)."""

    def _init_step_state(self, optimizer, params, param_sh=None):
        """Place params on their shardings and derive optimizer state
        (each state leaf shaped like its param inherits the sharding;
        every other leaf, and the step counter, is replicated over the
        mesh — nothing is left on the default device alone)."""
        self.optimizer = optimizer
        self._param_sh = param_sh
        # copy defensively: the step donates its buffers to XLA, and
        # device_put may ALIAS the caller's array when the sharding already
        # matches — donation would silently delete the caller's copy
        if param_sh is not None:
            params = {n: jax.device_put(jnp.copy(jnp.asarray(a)),
                                        param_sh[n])
                      for n, a in params.items()}
        else:
            params = {n: jnp.copy(jnp.asarray(a))
                      for n, a in params.items()}
        self.params = params
        self.opt_state = optimizer.init_state_pytree(params)
        self.step_count = jnp.zeros((), jnp.int32)
        if param_sh:
            self.opt_state = {n: self._place_state(n, st)
                              for n, st in self.opt_state.items()}
            self.step_count = jax.device_put(self.step_count,
                                             self._replicated())

    def _replicated(self):
        from jax.sharding import NamedSharding, PartitionSpec as P
        return NamedSharding(next(iter(self._param_sh.values())).mesh, P())

    def _place_state(self, name, state):
        """One param's optimizer state on the mesh: leaves of the param's
        shape share its sharding, the rest are replicated."""
        shape = tuple(self.params[name].shape)
        return jax.tree.map(
            lambda a: jax.device_put(
                jnp.asarray(a), self._param_sh[name]
                if jnp.shape(a) == shape else self._replicated()), state)

    def _state_out_shardings(self):
        """``out_shardings`` that hand params, optimizer state and step
        counter back exactly as they went in, so the layout is a fixed
        point of the step (XLA would otherwise pick its own for the
        outputs, and an AOT-compiled step then rejects its own result).
        None without a mesh."""
        if not self._param_sh:
            return None
        sh_of = lambda tree: jax.tree.map(lambda a: a.sharding, tree)
        return (None, sh_of(self.params), sh_of(self.opt_state),
                self.step_count.sharding)

    def _dispatch_fn(self, *step_args):
        """The callable that executes this step — subclasses may return
        an AOT-compiled executable when the call signature matches it
        (TrainStep.compile)."""
        return self._jitted

    def _run_jitted(self, *step_args):
        lr = jnp.asarray(self.optimizer.get_lr(), jnp.float32)
        fn = self._dispatch_fn(*step_args)
        loss, self.params, self.opt_state, self.step_count = fn(
            self.params, self.opt_state, self.step_count, *step_args, lr)
        if self.optimizer._lr_scheduler is not None:
            self.optimizer._lr_scheduler.step()
        return loss

    # checkpointing ----------------------------------------------------------
    def state_dict(self):
        import numpy as np
        out = {"params": jax.tree.map(np.asarray, self.params),
               "opt_state": jax.tree.map(np.asarray, self.opt_state),
               "step": int(self.step_count)}
        # the dropout RNG chain rides along (when the subclass keeps
        # one) so a restored run's loss trajectory is bitwise identical
        # to the uninterrupted run — the property the peer-recovery
        # MTTR drill (bench --recovery-drill) asserts
        key = getattr(self, "_key", None)
        if key is not None:
            out["rng_key"] = np.asarray(key)
        if self.optimizer._lr_scheduler is not None:
            out["lr_scheduler"] = self.optimizer._lr_scheduler.state_dict()
        return out

    def set_state_dict(self, state):
        import numpy as np
        self.step_count = jnp.asarray(state["step"], jnp.int32)
        if self._param_sh:
            put = lambda n, a: jax.device_put(jnp.asarray(a),
                                              self._param_sh[n])
            put_st = self._place_state
            self.step_count = jax.device_put(self.step_count,
                                             self._replicated())
        else:
            put = lambda n, a: jnp.asarray(a)
            put_st = lambda n, st: jax.tree.map(jnp.asarray, st)
        self.params = {n: put(n, a) for n, a in state["params"].items()}
        self.opt_state = {n: put_st(n, st)
                          for n, st in state["opt_state"].items()}
        if "rng_key" in state and hasattr(self, "_key"):
            self._key = jnp.asarray(np.asarray(state["rng_key"]),
                                    jnp.uint32)
        if "lr_scheduler" in state and \
                self.optimizer._lr_scheduler is not None:
            self.optimizer._lr_scheduler.set_state_dict(state["lr_scheduler"])


def _has_lm_loss(model) -> bool:
    """True when model.loss has the LM contract loss(input_ids, labels)
    — duck-typing on a bare attribute would misroute models whose loss
    takes a different signature (e.g. DiT's (x, t, y, noise))."""
    fn = getattr(model, "loss", None)
    if fn is None or not callable(fn):
        return False
    import inspect
    try:
        params = [p for p in inspect.signature(fn).parameters.values()
                  if p.kind in (p.POSITIONAL_ONLY,
                                p.POSITIONAL_OR_KEYWORD)]
    except (TypeError, ValueError):
        return False
    required = [p for p in params if p.default is p.empty]
    return len(required) == 2


def _loss_of(model, loss_fn, params, batch, rngs):
    """batch: dict with 'input_ids'/'labels' (LM) or (x, y) tuple routed to
    loss_fn(model_out, y).  A model exposing .loss(input_ids, labels)
    owns its objective (e.g. Llama's fused chunked lm-head+CE)."""
    if loss_fn is None:
        if _has_lm_loss(model):
            loss = functional_call(
                model, params, batch["input_ids"], batch["labels"],
                rngs=rngs, method="loss")
            return loss._data if hasattr(loss, "_data") else loss
        from paddle_tpu.nn.functional import cross_entropy
        out = functional_call(model, params, batch["input_ids"], rngs=rngs)
        logits = out._data if hasattr(out, "_data") else out
        v = logits.shape[-1]
        loss = cross_entropy(logits.reshape((-1, v)),
                             batch["labels"].reshape((-1,)))
        return loss._data if hasattr(loss, "_data") else loss
    x, y = batch
    out = functional_call(model, params, x, rngs=rngs)
    loss = loss_fn(out, y)
    return loss._data if hasattr(loss, "_data") else loss


class TrainStep(CompiledStepBase):
    """Compile model+optimizer into one donated, jitted update.

    step = TrainStep(model, opt)          # or loss_fn=, mesh=, param_specs=
    loss = step({"input_ids": ids, "labels": labels})
    step.sync_to_model()                  # write params back into the Layer
    """

    def __init__(self, model, optimizer, loss_fn: Optional[Callable] = None,
                 mesh=None, param_specs: Optional[Dict[str, Any]] = None,
                 batch_spec=None, compute_dtype=None, seed: int = 0,
                 remat: bool = False, remat_policy: Optional[str] = None,
                 analyze: Optional[str] = None, accum_steps: int = 1,
                 guard_nonfinite: Optional[bool] = None,
                 max_consecutive_skips: Optional[int] = None,
                 shardings=None, collective_overlap: Optional[bool] = None,
                 overlap_axis: str = "fsdp", sdc_sentinel=None,
                 sdc_check_interval: Optional[int] = None):
        # shardings=: an autoshard plan (analysis.autoshard.AutoShardPlan
        # — carries mesh shape, per-param specs and the batch spec in one
        # object) expands into the mesh/param_specs/batch_spec triple
        if shardings is not None:
            mesh, param_specs, batch_spec = _resolve_plan(
                shardings, mesh, param_specs, batch_spec)
        self.model = model
        self.loss_fn = loss_fn
        self.mesh = mesh
        # anomaly step-guard (robustness tentpole): a jitted all-finite
        # check on (loss, grad-norm); a NaN/Inf step SKIPS the optimizer
        # update — params, opt state and step_count come back bitwise
        # unchanged — instead of poisoning every weight.  Default ON
        # (PADDLE_TPU_STEP_GUARD=0 or guard_nonfinite=False disables);
        # after max_consecutive_skips straight skips the guard dumps the
        # flight recorder and raises NonFiniteStepError — a persistent
        # divergence must page someone, not spin forever.
        import os as _os
        if guard_nonfinite is None:
            guard_nonfinite = _os.environ.get(
                "PADDLE_TPU_STEP_GUARD", "1") != "0"
        self._guard_nonfinite = bool(guard_nonfinite)
        if max_consecutive_skips is None:
            max_consecutive_skips = int(_os.environ.get(
                "PADDLE_TPU_MAX_SKIP_STEPS", "25"))
        if max_consecutive_skips < 1:
            raise ValueError("max_consecutive_skips must be >= 1, got "
                             f"{max_consecutive_skips}")
        self._max_skips = max_consecutive_skips
        self._skip_streak = 0
        # microbatch gradient accumulation: the batch's leading axis is
        # split into accum_steps slices scanned sequentially with an fp32
        # grad carry — activation memory is per-MICROBATCH, so effective
        # batch grows without HBM blowup; equivalent to the full batch up
        # to accumulation order
        if int(accum_steps) < 1:
            raise ValueError(f"accum_steps must be >= 1, got {accum_steps}")
        self._accum_steps = int(accum_steps)
        # opt-in whole-step program analysis ("warn" prints findings on
        # the first step, "strict" raises on ERROR); default follows the
        # PADDLE_TPU_ANALYZE env var (paddle_tpu.analysis.analysis_mode)
        self._analyze_mode = analyze
        self._analyzed = False
        # (no copy here: _init_step_state copies every leaf before the
        # donated jit, which is what protects the Layer's own Parameters)
        params = params_of(model, dtype=compute_dtype)
        self._mask = trainable_mask(model)
        self._key = jax.random.PRNGKey(seed)
        self._remat = remat
        # named XLA remat policies (SURVEY hard-part: trade FLOPs for HBM);
        # 'dots' saves matmul outputs and recomputes elementwise — near
        # no-remat throughput at a fraction of the activation memory
        self._remat_policy_name = remat_policy
        if remat_policy is None:
            self._remat_policy = None
        else:
            from jax.ad_checkpoint import checkpoint_policies as cp
            policies = {
                "dots": cp.checkpoint_dots,
                "dots_no_batch": cp.checkpoint_dots_with_no_batch_dims,
                "nothing": cp.nothing_saveable,
                "everything": cp.everything_saveable,
            }
            if remat_policy not in policies:
                raise ValueError(
                    f"unknown remat_policy {remat_policy!r}; "
                    f"choose from {sorted(policies)}")
            self._remat_policy = policies[remat_policy]

        if mesh is not None and param_specs is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P

            def sanitize(spec):
                # model partition rules name every axis they know about
                # (dp/fsdp/tp/ep); drop the ones absent from this mesh so a
                # ('dp','ep') mesh accepts Llama-style tp rules unchanged
                axes = set(mesh.axis_names)

                def keep(e):
                    if e is None or (not isinstance(e, tuple) and e in axes):
                        return e
                    if isinstance(e, tuple):
                        kept = tuple(a for a in e if a in axes)
                        return kept if kept else None
                    return None
                return P(*(keep(e) for e in spec))

            to_sh = lambda spec: NamedSharding(mesh, sanitize(spec))
            param_sh = {n: to_sh(param_specs.get(n, P())) for n in params}
            self._batch_sh = to_sh(batch_spec) if batch_spec is not None \
                else None
        else:
            param_sh = self._batch_sh = None
        # mesh axes the batch dim is sharded over (() = replicated)
        lead = self._batch_sh.spec[0] if self._batch_sh is not None \
            and len(self._batch_sh.spec) else None
        self._batch_axes = () if lead is None else \
            (lead,) if isinstance(lead, str) else tuple(lead)

        # compute/collective overlap (ISSUE 15): express the per-layer
        # FSDP weight all-gathers as an explicit, layer-ordered prefetch
        # chain (issue order decoupled from consumers) so XLA's async
        # scheduler hides them under the previous layer's compute.
        # Knob-gated (PADDLE_TPU_COLLECTIVE_OVERLAP / collective_overlap=)
        # and default off = exact previous jaxpr; only arms when a mesh
        # axis actually shards weights on ``overlap_axis``.
        from paddle_tpu.distributed.sharding import (gathered_spec,
                                                     overlap_enabled,
                                                     prefetch_groups,
                                                     spec_mentions_axis)
        if collective_overlap is None:
            collective_overlap = overlap_enabled()
        self._overlap_axis = overlap_axis
        self._collective_overlap = False
        self._overlap_groups = None
        self._gathered_sh = None
        if collective_overlap and mesh is not None and \
                param_sh is not None and overlap_axis in mesh.axis_names:
            from jax.sharding import NamedSharding
            gathered = {
                n: NamedSharding(mesh, gathered_spec(sh.spec, overlap_axis))
                for n, sh in param_sh.items()
                if spec_mentions_axis(sh.spec, overlap_axis)}
            if gathered:
                self._gathered_sh = gathered
                self._overlap_groups = prefetch_groups(sorted(gathered))
                self._collective_overlap = True

        # optional SDC sentinel hook (robustness.recovery.SDCSentinel):
        # publish/verify the params digest across DP peers every
        # ``sdc_check_interval`` applied steps — the TrainStep-driven
        # form of the PR-14 loop-driven sentinel
        self._sdc_sentinel = sdc_sentinel
        if sdc_check_interval is None:
            sdc_check_interval = getattr(sdc_sentinel, "interval", 1) \
                if sdc_sentinel is not None else 0
        if sdc_sentinel is not None and int(sdc_check_interval) < 1:
            raise ValueError("sdc_check_interval must be >= 1, got "
                             f"{sdc_check_interval}")
        self._sdc_interval = int(sdc_check_interval or 0)
        self.last_sdc_verdict = None

        self._init_step_state(optimizer, params, param_sh)
        self._jitted = jax.jit(self._step_impl, donate_argnums=(0, 1, 2),
                               out_shardings=self._state_out_shardings())
        # AOT path (device-profiler tentpole): compile(batch) stores the
        # explicit lower().compile() executable here; calls whose batch
        # signature matches dispatch through it (no retrace hazard, and
        # the executable's cost/memory analysis feeds the MFU gauge)
        self._compiled = None
        self._compiled_sig = None
        self._exe_flops = None
        self._peak_flops = None
        self._cache_probed = False
        # per-step HBM watermark sampling (leak detection rides on it);
        # PADDLE_TPU_DEVICE_WATERMARK=0 disables, _WATERMARK_INTERVAL
        # thins it (the sweep is O(live arrays))
        self._memmon = None
        self._watermark_every = max(1, int(_os.environ.get(
            "PADDLE_TPU_WATERMARK_INTERVAL", "1")))
        if _os.environ.get("PADDLE_TPU_DEVICE_WATERMARK", "1") != "0":
            from paddle_tpu.observability.device_profiler import \
                device_memory_monitor
            self._memmon = device_memory_monitor()

        # always-on telemetry (observability tentpole): metric writes are
        # dict lookups + float adds; the loss / grad-norm gauges hold the
        # DEVICE scalar and only float() when an exporter scrapes, so the
        # hot path never blocks on the device
        self._metrics = _train_metrics()
        from paddle_tpu.observability import flight_recorder
        from paddle_tpu.observability.tracing import tracer
        self._recorder = flight_recorder()
        self._tracer = tracer()
        from paddle_tpu.analysis.recompile import SignatureMonitor
        self._signature_monitor = SignatureMonitor(
            name=f"TrainStep({type(model).__name__})")
        self._host_steps = 0
        self._step_ema: Optional[float] = None

    def _overlap_prefetch(self, params):
        """Issue every ZeRO-3 weight all-gather as an explicit,
        layer-ordered chain: ``with_sharding_constraint`` to the
        axis-free layout forces GSPMD to materialize the gather here —
        decoupled from the layer that consumes it — and the
        ``optimization_barrier`` chain pins issue order layer i → i+1,
        so the scheduler streams the gathers as a prefetch queue it can
        hide under earlier layers' compute instead of paying each one
        just-in-time at its consumer."""
        from paddle_tpu.distributed.sharding import overlap_path_counter
        overlap_path_counter().labels(path="fsdp_prefetch").inc()
        out = dict(params)
        token = None
        for group in self._overlap_groups:
            nxt = None
            for n in group:
                p = jax.lax.with_sharding_constraint(
                    params[n], self._gathered_sh[n])
                if token is not None:
                    p = _ordered_after(p, token)
                if nxt is None:
                    nxt = p
                out[n] = p
            token = nxt if nxt is not None else token
        return out

    def _step_impl(self, *step_args):
        # the trace declares the step's mesh: Pallas kernels meet it as
        # ops/pallas/mesh.py decides (XLA cannot partition them)
        from paddle_tpu.ops.pallas.mesh import step_mesh
        with step_mesh(self.mesh, self._batch_axes):
            return self._step_body(*step_args)

    def _step_body(self, params, opt_state, step_count, batch, key, lr):
        model = self.model

        def loss_of_trainable(train_params, frozen_params, mb, k):
            full = dict(frozen_params)
            full.update(train_params)
            if self._collective_overlap:
                full = self._overlap_prefetch(full)
            f = lambda p: _loss_of(model, self.loss_fn, p, mb,
                                   {"dropout": k})
            if self._remat:
                f = jax.checkpoint(f, policy=self._remat_policy)
            return f(full)

        train_p = {n: v for n, v in params.items() if self._mask.get(n)}
        frozen_p = {n: v for n, v in params.items() if not self._mask.get(n)}
        n_acc = self._accum_steps
        if n_acc == 1:
            loss, grads = jax.value_and_grad(loss_of_trainable)(
                train_p, frozen_p, batch, key)
        else:
            # scan over microbatches: loss/grads are the mean over slices
            # (each slice weights equally, matching the full-batch mean
            # for equal-size microbatches); the fp32 carry is donated
            # buffer-reuse inside the scan, so peak memory holds ONE
            # microbatch's activations + one fp32 grad copy
            micro = jax.tree.map(
                lambda a: a.reshape((n_acc, a.shape[0] // n_acc)
                                    + a.shape[1:]), batch)
            keys = jax.random.split(key, n_acc)
            inv = 1.0 / n_acc

            def one_micro(carry, xs):
                loss_acc, g_acc = carry
                mb, k = xs
                l, g = jax.value_and_grad(loss_of_trainable)(
                    train_p, frozen_p, mb, k)
                g_acc = jax.tree.map(
                    lambda a, b: a + b.astype(jnp.float32) * inv, g_acc, g)
                return (loss_acc + l.astype(jnp.float32) * inv, g_acc), None

            g0 = jax.tree.map(lambda a: jnp.zeros(a.shape, jnp.float32),
                              train_p)
            (loss, grads), _ = jax.lax.scan(
                one_micro, (jnp.zeros((), jnp.float32), g0), (micro, keys))
        # everything after the backward pass carries one name in a
        # device trace: the grad norm, the update, the guard's select
        with jax.named_scope("optimizer"):
            return self._update(params, opt_state, step_count, train_p,
                                frozen_p, loss, grads, lr)

    def _update(self, params, opt_state, step_count, train_p, frozen_p,
                loss, grads, lr):
        # global grad norm for the telemetry gauge: one vdot per leaf —
        # noise next to the backward pass it rides on
        gnorm = jnp.sqrt(sum(
            (jnp.vdot(g, g).real for g in jax.tree.leaves(grads)),
            start=jnp.zeros((), jnp.float32)))
        step_count = step_count + 1
        new_train, new_state = self.optimizer.apply_gradients(
            train_p, grads,
            {n: opt_state[n] for n in train_p}, step_count, lr=lr)
        new_params = dict(frozen_p)
        new_params.update(new_train)
        new_opt_state = dict(opt_state)
        new_opt_state.update(new_state)
        # non-finite step-guard: skip_code 0 = applied, 1 = non-finite
        # loss, 2 = finite loss but non-finite grad norm (a single
        # NaN/Inf anywhere in the grads poisons the norm, so one scalar
        # check covers every leaf).  On skip, a jnp.where per leaf keeps
        # the OLD params/opt state/step_count — the anomalous update is
        # fully discarded on device; no host round-trip decides anything.
        if self._guard_nonfinite:
            skip_code = jnp.where(
                jnp.isfinite(loss),
                jnp.where(jnp.isfinite(gnorm), 0, 2), 1).astype(jnp.int32)
            keep = skip_code == 0

            def sel(new, old):
                return jax.tree.map(
                    lambda a, b: jnp.where(keep, a, b), new, old)

            new_params = sel(new_params, params)
            new_opt_state = sel(new_opt_state, opt_state)
            step_count = jnp.where(keep, step_count, step_count - 1)
        else:
            skip_code = jnp.zeros((), jnp.int32)
        return (loss, gnorm, skip_code), new_params, new_opt_state, \
            step_count

    def _place_batch(self, batch):
        """Device placement shared by the call path and compile():
        sharded device_put under a mesh, plain asarray otherwise
        (device-prefetched batches are already resident — no-op)."""
        if self._batch_sh is not None:
            return jax.tree.map(
                lambda a: jax.device_put(jnp.asarray(a), self._batch_sh),
                batch)
        return jax.tree.map(jnp.asarray, batch)

    def _cache_extra(self) -> str:
        """Compile-cache key discriminators the call-argument avals
        can't see: closed-over step config plus the model config that
        bakes constants (rope tables, eps) into the trace."""
        from paddle_tpu import compile_cache
        lf = getattr(self.loss_fn, "__name__", repr(self.loss_fn)) \
            if self.loss_fn is not None else ""
        return (f"model={compile_cache.model_config_tag(self.model)}"
                f"|opt={type(self.optimizer).__name__}"
                f"|loss={lf}|accum={self._accum_steps}"
                f"|remat={int(self._remat)}:{self._remat_policy_name}"
                f"|guard={int(self._guard_nonfinite)}"
                f"|ovl={int(self._collective_overlap)}")

    def compile(self, batch):
        """AOT-compile the step for this batch signature with full
        compile observability: ``train.compile`` span (with
        ``compile.lower`` / ``compile.xla`` children), the per-target
        compile counter, and the executable's measured FLOPs / HBM
        bytes / peak memory exposed as ``paddle_tpu_xla_*`` gauges.
        With ``PADDLE_TPU_COMPILE_CACHE=1`` the persistent executable
        cache is consulted first: a hit deserialize-and-loads under a
        ``compile.cache_hit`` span instead of lower→compile, and a
        live compile's executable is stored for the next boot.
        Subsequent calls whose batch matches dispatch through the
        compiled executable (no retrace), and the step starts setting
        the ``paddle_tpu_train_mfu`` gauge.  Returns the
        :class:`~paddle_tpu.observability.device_profiler.CompileInfo`.
        """
        from paddle_tpu import compile_cache
        from paddle_tpu.observability.device_profiler import signature_of
        batch = self._place_batch(batch)
        lr = jnp.asarray(self.optimizer.get_lr(), jnp.float32)
        target = f"TrainStep({type(self.model).__name__})"
        with self._tracer.span("train.compile", target=target):
            compiled, info, _hit = compile_cache.aot_compile_cached(
                self._jitted, self.params, self.opt_state,
                self.step_count, batch, self._key, lr, target=target,
                mesh=self.mesh, shardings=self._param_sh,
                extra=self._cache_extra())
        self._compiled = compiled
        self._compiled_sig = signature_of(batch)
        self._exe_flops = info.stats.flops or None
        return info

    def _probe_compile_cache(self, batch):
        """Transparent cold-start adoption: the FIRST plain call checks
        the persistent cache for this exact step signature — a restarted
        worker that never calls compile() still boots without an XLA
        compile when the cache is warm.  Misses leave the jit path
        untouched; failures never escape (a stale cache must not break
        a boot)."""
        self._cache_probed = True
        try:
            from paddle_tpu import compile_cache
            if not compile_cache.enabled():
                return
            from paddle_tpu.observability.device_profiler import \
                signature_of
            lr = jnp.asarray(self.optimizer.get_lr(), jnp.float32)
            target = f"TrainStep({type(self.model).__name__})"
            compiled, info, hit = compile_cache.aot_compile_cached(
                self._jitted, self.params, self.opt_state,
                self.step_count, batch, self._key, lr, target=target,
                mesh=self.mesh, shardings=self._param_sh,
                extra=self._cache_extra(), cache_only=True)
            if hit:
                self._compiled = compiled
                self._compiled_sig = signature_of(batch)
                self._exe_flops = info.stats.flops or None
        except Exception:
            pass

    def _dispatch_fn(self, *step_args):
        if self._compiled is not None:
            from paddle_tpu.observability.device_profiler import \
                signature_of
            if signature_of(step_args[0]) == self._compiled_sig:
                return self._compiled
        return self._jitted

    def __call__(self, batch):
        # step span: children cover h2d placement, the compiled dispatch
        # and the step-guard's device sync — a slow step names its slow
        # phase in the trace, and in a device trace (tracing.py)
        with self._tracer.span("train.step", step=self._host_steps,
                               accum=self._accum_steps):
            return self._call_traced(batch)

    def _call_traced(self, batch):
        # chaos: poison this batch's float leaves with NaN — the
        # injectable twin of a corrupt record / bad-loss microbatch,
        # which the step-guard must absorb (int-only LM batches have no
        # poisonable leaf; use a float-input model to drill this path)
        from paddle_tpu.robustness import fault_fires
        if fault_fires("train.nonfinite_batch", step=self._host_steps):
            batch = jax.tree.map(
                lambda a: a * jnp.nan
                if jnp.issubdtype(jnp.asarray(a).dtype, jnp.floating)
                else a, batch)
        with self._tracer.span("train.h2d"):
            batch = self._place_batch(batch)
        if self._compiled is None and not self._cache_probed:
            self._probe_compile_cache(batch)
        if self._accum_steps > 1:
            for leaf in jax.tree.leaves(batch):
                if getattr(leaf, "ndim", 0) and \
                        leaf.shape[0] % self._accum_steps:
                    raise ValueError(
                        f"batch leading dim {leaf.shape[0]} not divisible "
                        f"by accum_steps={self._accum_steps}")
        if not self._analyzed:
            self._maybe_analyze(batch)
        # recompile telemetry: a novel signature after the first call IS
        # a retrace (jax.jit keys its executable cache the same way)
        novel = self._signature_monitor.record((batch,))
        if novel and self._signature_monitor.calls > 1:
            self._metrics["recompiles"].inc()
            self._recorder.record(
                "train.recompile",
                target=self._signature_monitor.name,
                distinct_signatures=len(self._signature_monitor.records))
        self._key, sub = jax.random.split(self._key)
        t0 = time.perf_counter()
        # chaos: per-host step delay INSIDE the timed region — the
        # injectable straggler whose inflated step EMA the fleet
        # straggler rule must catch (delay via
        # PADDLE_TPU_STRAGGLER_DELAY_S, default 50ms)
        if fault_fires("train.straggler_delay", step=self._host_steps):
            import os as _os
            time.sleep(float(_os.environ.get(
                "PADDLE_TPU_STRAGGLER_DELAY_S", "0.05")))
        with self._recorder.instrumented("train.step",
                                         step=self._host_steps):
            # with accum_steps > 1 the microbatch scan runs on the
            # device as ONE program inside this dispatch
            with self._tracer.span("train.dispatch",
                                   microbatches=self._accum_steps):
                loss, gnorm, skip_code = self._run_jitted(batch, sub)
        dt = time.perf_counter() - t0
        self._host_steps += 1
        m = self._metrics
        m["step"].observe(dt)
        m["steps"].inc()
        m["accum"].observe(self._accum_steps)
        m["loss"].set(loss)     # device scalar, resolved at scrape
        m["gnorm"].set(gnorm)
        self._step_ema = dt if self._step_ema is None \
            else 0.8 * self._step_ema + 0.2 * dt
        m["ema"].set(self._step_ema)
        if self._guard_nonfinite:
            # the int() sync IS the guard's cost; the span makes it
            # visible instead of smearing into "step overhead"
            with self._tracer.span("train.guard"):
                code = int(skip_code)
                # goodput split BEFORE _account_skip may raise: a
                # discarded update is lost time, not productive time
                m["productive" if code == 0 else "skipped_s"].inc(dt)
                self._account_skip(code)
        else:
            m["productive"].inc(dt)
        tokens = self._batch_tokens(batch)
        if tokens:
            m["tokens"].inc(tokens)
            if dt > 0:
                m["tps"].set(tokens / dt)
        # measured MFU: the AOT executable's XLA-counted FLOPs over this
        # step's wall time — the drift gauge the mfu_drift SLO rule
        # watches (only armed once compile(batch) introspected the step)
        if self._exe_flops and dt > 0:
            if self._peak_flops is None:
                from paddle_tpu.observability.device_profiler import \
                    detect_roofline
                self._peak_flops = detect_roofline()[0]
            m["mfu"].set(self._exe_flops / dt / self._peak_flops)
        if self._memmon is not None and \
                (self._host_steps % self._watermark_every) == 0:
            self._memmon.sample(step=self._host_steps)
        # SDC sentinel cadence: publish this rank's params digest and
        # judge it against the DP peers' (bounded wait = the sentinel's
        # timeout).  Mismatch handling (metrics, flight-recorder dump,
        # blame, quarantine) lives in the sentinel itself.
        if self._sdc_sentinel is not None and \
                self._host_steps % self._sdc_interval == 0:
            self._sdc_sentinel.publish(self._host_steps, self.params)
            self.last_sdc_verdict = self._sdc_sentinel.verify(
                self._host_steps)
        return loss

    def _account_skip(self, code: int):
        """Host side of the step-guard: metric + flight-recorder entry
        per skipped step, escape hatch after K consecutive skips.  The
        ``int(skip_code)`` in __call__ is the guard's one cost — it
        synchronizes on the step (the price of knowing in time)."""
        if code == 0:
            self._skip_streak = 0
            return
        reason = "nonfinite_loss" if code == 1 else "nonfinite_grad"
        self._skip_streak += 1
        self._metrics["skipped"].labels(reason=reason).inc()
        self._recorder.record("train.step_skipped", reason=reason,
                              step=self._host_steps - 1,
                              streak=self._skip_streak)
        if self._skip_streak >= self._max_skips:
            from paddle_tpu.robustness import NonFiniteStepError
            self._recorder.dump(
                reason=f"step-guard: {self._skip_streak} consecutive "
                       f"non-finite steps ({reason})")
            raise NonFiniteStepError(
                f"{self._skip_streak} consecutive optimizer updates "
                f"skipped (last reason: {reason}) — persistent "
                "divergence, not a transient bad microbatch; params are "
                "unchanged since the last finite step")

    @staticmethod
    def _batch_tokens(batch) -> int:
        """Token count for throughput metrics: LM batches count
        input_ids elements, (x, y) batches count examples."""
        if isinstance(batch, dict) and "input_ids" in batch:
            ids = batch["input_ids"]
            return int(ids.size) if hasattr(ids, "size") else 0
        leaves = jax.tree.leaves(batch)
        if leaves and getattr(leaves[0], "ndim", 0):
            return int(leaves[0].shape[0])
        return 0

    def _maybe_analyze(self, batch):
        self._analyzed = True
        from paddle_tpu.analysis import analysis_mode
        mode = self._analyze_mode if self._analyze_mode is not None \
            else analysis_mode()
        if not mode:
            return
        import sys
        report = self.analyze(batch, strict=(mode == "strict"))
        if len(report):
            print(report.format(), file=sys.stderr)

    def analyze(self, batch, strict: bool = False, passes=None,
                options=None):
        """Run the ``paddle_tpu.analysis`` pass pipeline over the whole
        compiled step (fwd+bwd+update) with this step's parameter
        shardings.  Abstract — no step executes."""
        import paddle_tpu.analysis as _analysis
        return _analysis.check(self, batch, strict=strict, passes=passes,
                               options=options)

    def sync_to_model(self):
        state = self.model.state_dict(keep_vars=True)
        for n, arr in self.params.items():
            state[n]._set_data(arr.astype(state[n]._data.dtype))
