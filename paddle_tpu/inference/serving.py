"""Continuous-batching serving engine over the compiled KV-cache step.

Reference role: the AnalysisPredictor serving loop
(inference/api/analysis_predictor.cc) + the fused_multi_transformer
decode path — rebuilt TPU-style: ONE compiled per-token decode step over
a fixed pool of batch slots, plus ONE compiled prefill executable that
serves every chunk of every prompt.  New requests join as running
sequences finish (slot reuse); every slot decodes at its own position.

The KV cache is paged (``inference/kv_cache.py``): fixed-size token
blocks with a refcounted free list, a prefix trie so requests sharing a
system prompt map to the same physical blocks (prefill once,
copy-on-write on divergence), and a block-table attention path (Pallas
kernel where eligible, a gather elsewhere — the CPU runs that one).  On
top of it: **chunked prefill** (a prompt advances one
``prefill_chunk``-sized piece per engine step, interleaved with decode
so in-flight TTFT/TPOT don't stall; the last chunk is right-padded, and
causality makes the padding invisible — pad positions sit to the RIGHT
of every real token, the first generated token reads the logits at the
TRUE last prompt position, and decode then overwrites the pad rows) and
**n-gram speculative decoding** (``spec_decode=k`` drafts from the
request's own history and verifies all drafts in ONE batched forward;
greedy-equivalence guaranteed — accepted tokens are exactly what
step-by-step argmax would emit).  Greedy outputs are token-for-token
those of ``generation.generate``, the reference the tests hold the
engine to.

Weight-only int8: ``int8_weights=True`` stores every 2-D matmul weight
as int8 with a per-output-channel fp32 scale and dequantizes INSIDE the
compiled step (XLA fuses the convert+scale into the matmul prologue), so
decode — a bandwidth-bound workload — reads half the bytes.
"""

from __future__ import annotations

import time
from collections import deque
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp

__all__ = ["ContinuousBatchingEngine", "RequestStatus",
           "quantize_weights_int8"]

# decode-token latency lives in the sub-ms..s decade; TTFT includes a
# possible compile, so it keeps the wide default upper range
_TOKEN_BUCKETS = (0.0001, 0.00025, 0.0005, 0.001, 0.0025, 0.005, 0.01,
                  0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5)


def _serving_metrics():
    """Process-wide serving instruments (observability tentpole)."""
    from paddle_tpu.observability import DEFAULT_BUCKETS, default_registry
    reg = default_registry()
    return {
        "requests": reg.counter("paddle_tpu_serving_requests_total",
                                "requests enqueued"),
        "admissions": reg.counter("paddle_tpu_serving_admissions_total",
                                  "requests admitted into a slot"),
        "retirements": reg.counter(
            "paddle_tpu_serving_retirements_total",
            "requests retired (eos or budget exhausted)"),
        "tokens": reg.counter("paddle_tpu_serving_tokens_total",
                              "tokens generated (prefill first token + "
                              "decode)"),
        "pad_tokens": reg.counter(
            "paddle_tpu_serving_prefill_pad_tokens_total",
            "prompt positions wasted on padding a prompt's last chunk"),
        "ttft": reg.histogram(
            "paddle_tpu_serving_ttft_seconds",
            "time from enqueue to first generated token",
            buckets=DEFAULT_BUCKETS),
        "decode": reg.histogram(
            "paddle_tpu_serving_decode_token_seconds",
            "per-token decode latency (chunk wall time / tokens in "
            "chunk)", buckets=_TOKEN_BUCKETS),
        "itl": reg.histogram(
            "paddle_tpu_serving_inter_token_seconds",
            "per-token gap a client saw: time between two emissions of "
            "one request / tokens in the later one, observed per token "
            "at retirement (a prefill chunk that lands between two of a "
            "request's tokens shows here, not in the decode latency)",
            buckets=_TOKEN_BUCKETS),
        "timeouts": reg.counter(
            "paddle_tpu_serving_timeouts_total",
            "requests retired with status=timeout (deadline expired "
            "while queued or decoding)"),
        "rejections": reg.counter(
            "paddle_tpu_serving_rejections_total",
            "requests rejected at admission", labelnames=("reason",)),
        "engine_errors": reg.counter(
            "paddle_tpu_serving_engine_errors_total",
            "engine-step exceptions recovered by failing the in-flight "
            "batch (the engine itself survives)"),
        # SLO-attainment feed (fleet observability tentpole): one
        # hit/miss verdict per retirement against the TTFT/TPOT targets
        # (PADDLE_TPU_SLO_TTFT_TARGET / _TPOT_TARGET seconds);
        # observability.goodput folds these into the
        # paddle_tpu_slo_attainment{kind} gauge
        "slo": reg.counter(
            "paddle_tpu_serving_slo_total",
            "retired requests judged against the serving latency "
            "targets", labelnames=("kind", "result")),
        "prefix_lookups": reg.counter(
            "paddle_tpu_serving_prefix_cache_total",
            "prefix-cache lookups at admission",
            labelnames=("result",)),
        "prefix_tokens": reg.counter(
            "paddle_tpu_serving_prefix_tokens_reused_total",
            "prompt tokens whose prefill was skipped because their "
            "blocks were already in the prefix cache"),
        "evictions": reg.counter(
            "paddle_tpu_serving_kv_evictions_total",
            "prefix-cache blocks evicted under allocator pressure"),
        "cow": reg.counter(
            "paddle_tpu_serving_kv_cow_copies_total",
            "copy-on-write block copies (a shared block was written)"),
        "alloc_failures": reg.counter(
            "paddle_tpu_serving_kv_alloc_failures_total",
            "admissions deferred because the block pool was exhausted "
            "(load shed back into the bounded queue)"),
        "deferred": reg.counter(
            "paddle_tpu_serving_admissions_deferred_total",
            "admission attempts deferred, by the block group that "
            "lacked the blocks (full: the layers that see the whole "
            "context; window: the sliding-window layers' rings)",
            labelnames=("group",)),
        "chunks": reg.counter(
            "paddle_tpu_serving_prefill_chunks_total",
            "chunked-prefill dispatches"),
        "spec": reg.counter(
            "paddle_tpu_serving_spec_tokens_total",
            "speculative-decoding draft tokens",
            labelnames=("kind",)),
        "decode_dispatches": reg.counter(
            "paddle_tpu_serving_decode_dispatches_total",
            "batched decode dispatches (the fused decode, the speculative "
            "verify) by whether an earlier one's output was still unread "
            "on the host when this one was issued: 'overlapped' went out "
            "while the host had yet to read its predecessor, 'waited' "
            "after everything before it was read", labelnames=("kind",)),
        "dispatches": reg.counter(
            "paddle_tpu_serving_dispatches_total",
            "programs handed to the device (kind: decode, prefill_chunk, "
            "spec_verify) by what the device held when each was issued: "
            "'fed' something of this engine's was still running or "
            "queued, so the device goes straight on; 'drained' the "
            "newest output was already complete, so the device is idle "
            "until this program starts", labelnames=("kind", "device")),
        "parks": reg.counter(
            "paddle_tpu_serving_session_parks_total",
            "sessions demoted out of HBM (slot freed, KV spilled to "
            "the tier manager)", labelnames=("kind",)),
        "resumes": reg.counter(
            "paddle_tpu_serving_session_resumes_total",
            "parked-session resumes by path: 'promote' re-imported the "
            "tier payload, 'recompute' re-prefilled after a tier miss",
            labelnames=("path",)),
    }


def _ngram_propose(history: np.ndarray, k: int, max_n: int = 3):
    """Draft up to `k` tokens by matching the tail n-gram of the
    request's own history (prompt + generated) against its most recent
    earlier occurrence — 'prompt lookup' decoding: free drafts that pay
    off on extractive/repetitive spans, and the verify step guarantees
    they never change the output.  Returns int32 drafts (possibly fewer
    than k) or None.  The linear scan is fine at serving history
    lengths; a production proposer would keep an n-gram index."""
    L = len(history)
    for n in range(min(max_n, L - 1), 0, -1):
        pat = history[L - n:]
        for i in range(L - n - 1, -1, -1):
            if np.array_equal(history[i:i + n], pat):
                cont = history[i + n:i + n + k]
                if len(cont):
                    return np.asarray(cont, np.int32)
    return None


def quantize_weights_int8(params: Dict[str, jnp.ndarray],
                          min_size: int = 1 << 16):
    """Split params into (passthrough, {name: (w8, scale)}) — every
    float 2-D weight with >= min_size elements becomes symmetric
    per-output-channel int8 (the weight-only quantization serving
    engines use; reference quantization/ptq int8 path)."""
    keep, quant = {}, {}
    for name, a in params.items():
        if (a.ndim == 2 and jnp.issubdtype(a.dtype, jnp.floating)
                and a.size >= min_size):
            scale = (jnp.max(jnp.abs(a.astype(jnp.float32)), axis=0,
                             keepdims=True) / 127.0).astype(jnp.float32)
            w8 = jnp.clip(jnp.round(a.astype(jnp.float32)
                                    / jnp.maximum(scale, 1e-12)),
                          -127, 127).astype(jnp.int8)
            quant[name] = (w8, scale)
        else:
            keep[name] = a
    return keep, quant


def _dequant(keep, quant, dtype):
    out = dict(keep)
    for name, (w8, scale) in quant.items():
        out[name] = (w8.astype(jnp.float32) * scale).astype(dtype)
    return out


@dataclass
class _Request:
    rid: int
    prompt: np.ndarray              # [Lp] int32
    max_new_tokens: int
    out: List[int] = field(default_factory=list)
    enqueued_at: float = 0.0        # perf_counter at add_request (TTFT)
    deadline: Optional[float] = None  # perf_counter; None = no deadline
    span: Any = None                # root trace span (admission→retire)
    admitted_at: float = 0.0        # perf_counter at slot admission
    first_token_at: float = 0.0     # perf_counter when prefill emitted
    retired_at: float = 0.0         # perf_counter at retirement
    # (perf_counter, tokens) of every emission: one stamp per engine
    # step that gave this request tokens, shared by the whole batch
    token_stamps: List[Tuple[float, int]] = field(default_factory=list)
    prefix_reused: int = 0          # prompt tokens served from the
    #                                 prefix cache
    spec_proposed: int = 0          # speculative drafts proposed
    spec_accepted: int = 0          # speculative drafts accepted
    # fleet routing (ServingRouter): "full" is a normal request;
    # "prefill_only" retires after its first token with the prompt KV
    # parked for export; "resume" skips prefill, importing that KV
    mode: str = "full"
    handoff: Optional[dict] = None  # resume payload (blocks + first tok)
    router_t0: Optional[float] = None  # router enqueue (end-to-end TTFT)
    route_s: float = 0.0            # router queue -> slot admission
    handoff_s: float = 0.0          # prefill->decode block transfer
    # session survivability (KV tier): park/resume lifecycle stamps
    parked_at: float = 0.0          # perf_counter at park (0 = not parked)
    parked_s: float = 0.0           # cumulative wall time spent parked
    resume_at: float = 0.0          # perf_counter at resume() call
    resume_s: float = 0.0           # cumulative resume->decoding latency
    auto_parked: bool = False       # parked by the scheduler, not caller
    # recompute fallback bookkeeping: the client-visible prompt and
    # token budget before the prompt was extended with generated tokens
    orig_prompt: Optional[np.ndarray] = None
    orig_max_new: int = 0


@dataclass
class _Dispatch:
    """A batched dispatch whose output the host has not read yet."""
    out: Any                        # device: tokens [B, S] (, counts)
    rows: List[Tuple[int, _Request]]   # (slot, request) it advances
    steps: int                      # positions it writes from each row
    t0: float                       # perf_counter when it was issued
    seq: int                        # its number among the engine's programs


class RequestStatus(str):
    """Terminal request status that IS the plain status string
    (``"ok"`` / ``"timeout"`` / ``"error"`` — every existing ``==``
    comparison keeps working) but additionally carries the request's
    lifecycle timing fields and trace id, so a client staring at its
    own timeout can tell queued-too-long from decoded-too-slowly
    without server logs.  ``token_times`` is the request's emissions,
    ``[(perf_counter, tokens), ...]`` — the first is the first token,
    the gaps between them are what the client waited between tokens
    (``timings`` keeps its float-only :data:`TIMING_KEYS` schema)."""

    def __new__(cls, status: str, timings: Optional[Dict[str, float]]
                = None, trace_id: Optional[str] = None,
                token_times: Sequence[Tuple[float, int]] = ()):
        obj = super().__new__(cls, status)
        obj.timings = dict(timings or {})
        obj.trace_id = trace_id
        obj.token_times = list(token_times)
        return obj


#: Canonical ``RequestStatus.timings`` schema.  Every retirement
#: carries EVERY key — absolute perf_counter stamps read 0.0 for a
#: phase never reached and derived durations read 0.0 when not
#: applicable — so TTFT/TPOT decomposition (forensics ``attribute``)
#: and clients need no feature detection and no per-layer
#: ``setdefault`` patches.  New timing fields MUST be added here; the
#: schema regression test (tests/test_forensics.py) fails otherwise.
TIMING_KEYS = (
    "enqueued", "admitted", "first_token", "retired",
    "queue_s", "ttft_s", "prefill_s", "decode_s", "total_s",
    "generated", "prefix_tokens_reused", "speculative_accept_rate",
    "route_s", "handoff_s", "parked_s", "resume_s",
)

#: Keys layered on by the router's fleet-level retirement — the only
#: permitted extras beyond :data:`TIMING_KEYS`.
ROUTER_TIMING_KEYS = ("router_enqueued", "attempts")

#: Re-emit a starving request's "defer" decision every this many
#: deferred admission attempts.  Each deferred step also records a
#: kv_alloc_exhausted event (plus fault.injected under chaos), so the
#: period must satisfy period x churn-per-step < ring capacity (256 x
#: 2 = 512 < 1024 default) for the latest defer to survive eviction.
DEFER_EMIT_EVERY = 256


def _request_timings(req: "_Request") -> Dict[str, float]:
    """Lifecycle stamps (perf_counter; 0.0 = phase never reached) plus
    the derived durations clients actually reason about.  Always
    returns exactly the :data:`TIMING_KEYS` schema."""
    t = {"enqueued": req.enqueued_at, "admitted": req.admitted_at,
         "first_token": req.first_token_at, "retired": req.retired_at}
    if req.admitted_at and req.enqueued_at:
        t["queue_s"] = req.admitted_at - req.enqueued_at
    # routed requests measure TTFT from the ROUTER's enqueue stamp —
    # the client-visible origin; the engine-local stamp stays the
    # origin for direct requests
    origin = req.router_t0 or req.enqueued_at
    if req.first_token_at and origin and req.first_token_at >= origin:
        t["ttft_s"] = req.first_token_at - origin
    if req.first_token_at and req.admitted_at \
            and req.first_token_at >= req.admitted_at:
        # absent for "resume" requests: their first token predates this
        # engine's admission (it happened on the prefill replica)
        t["prefill_s"] = req.first_token_at - req.admitted_at
    if req.retired_at and req.first_token_at:
        # parked wall time is not decode time; the clamp also keeps a
        # stale first_token stamp (resumed sessions) from going negative
        t["decode_s"] = max(
            0.0, req.retired_at - req.first_token_at - req.parked_s)
    if req.retired_at and req.enqueued_at:
        t["total_s"] = req.retired_at - req.enqueued_at
    # how much prefill the prefix cache skipped, and how much of the
    # decode came from accepted speculative drafts
    t["prefix_tokens_reused"] = float(req.prefix_reused)
    t["speculative_accept_rate"] = (
        req.spec_accepted / req.spec_proposed if req.spec_proposed
        else 0.0)
    # fleet routing evidence (router queue -> slot admission, and the
    # prefill->decode block transfer) — 0.0 for unrouted requests, but
    # ALWAYS present so TTFT decomposition needs no feature detection
    t["route_s"] = float(req.route_s)
    t["handoff_s"] = float(req.handoff_s)
    # session survivability evidence: wall time spent parked out of HBM
    # and the resume->decoding latency (tier promote or recompute) —
    # 0.0 for never-parked requests, but always present
    t["parked_s"] = float(req.parked_s)
    t["resume_s"] = float(req.resume_s)
    t["generated"] = float(len(req.out))
    for key in TIMING_KEYS:
        t.setdefault(key, 0.0)
    return t


class ContinuousBatchingEngine:
    """Decode over ``slots`` concurrent sequences with slot reuse —
    greedy by default, or sampled (``do_sample=True`` with
    temperature / top-k / nucleus, the generation module's sampler).

    add_request() enqueues; step() admits a queued request into a free
    slot (reserving its KV blocks), advances one admitted prompt by one
    prefill chunk, or advances every decoding slot by ``steps_per_sync``
    tokens (single compiled decode step).  finished() yields completed
    (rid, prompt, tokens) triples.

    A model with sliding-window layers (``model.attention_windows()``)
    gets a second block group for them — ``num_window_blocks`` ids, an
    allocator and a table of their own, a ring of blocks a request
    (kv_cache.py, "Two block groups") — and, like a model with per-slot
    state, none of what moves blocks by id.

    ``paged_kv`` and ``prefill_buckets`` are kept for the benchmark's
    traffic file and harness, which still pass them (ROADMAP D2a):
    ``paged_kv`` takes ``None`` / ``True`` and nothing reads it;
    ``prefill_buckets``' largest entry is the chunk width when
    ``prefill_chunk`` is not given.
    """

    def __init__(self, model, slots: int = 8, max_len: int = 1024,
                 prefill_buckets: Sequence[int] = (32, 64, 128, 256),
                 eos_token_id: Optional[int] = None,
                 int8_weights: bool = False,
                 steps_per_sync: int = 1,
                 do_sample: bool = False, temperature: float = 1.0,
                 top_k: int = 0, top_p: float = 1.0, seed: int = 0,
                 analyze: Optional[str] = None,
                 max_queue: Optional[int] = None,
                 request_timeout_s: Optional[float] = None,
                 max_consecutive_errors: int = 3,
                 paged_kv: Optional[bool] = None,
                 kv_block_size: int = 16,
                 num_kv_blocks: Optional[int] = None,
                 num_window_blocks: Optional[int] = None,
                 prefill_chunk: Optional[int] = None,
                 prefix_cache: bool = True,
                 spec_decode: int = 0,
                 spec_ngram: int = 3,
                 role: str = "mixed",
                 quant_weights: Optional[str] = None,
                 quant_kv: Optional[str] = None,
                 kv_tier=None,
                 auto_park_s: Optional[float] = None):
        from paddle_tpu.core.functional import functional_call, params_of
        from paddle_tpu.generation import GenerationConfig as _GC

        self.model = model
        self.slots = slots
        self.max_len = max_len
        self.eos = eos_token_id
        # decode steps fused into ONE device program per host interaction
        # (lax.scan): amortizes host/dispatch latency K-fold — the thing
        # that matters when the host sits far from the chip.  Sequences
        # finishing mid-chunk over-generate < K tokens (truncated by the
        # host; the wasted rows are unreachable for successors, see step())
        self.steps_per_sync = max(1, int(steps_per_sync))
        if paged_kv is not None and not paged_kv:
            raise ValueError(
                "paged_kv=False: the slot-contiguous engine is gone; the "
                "paged engine is the only one (drop the argument)")
        from paddle_tpu.inference.kv_cache import quant_kv_mode
        # quantized paged-KV (PADDLE_TPU_QUANT_KV=int8 / quant_kv=):
        # int8 pools + per-block scales — the pool holds itemsize-ratio
        # MORE blocks at the same payload HBM bytes (2x for bf16, 4x
        # for fp32), which is the capacity claim BENCH_serve records
        self.kv_quant = quant_kv_mode(quant_kv)
        self.spec_tokens = max(0, int(spec_decode))
        self._spec_ngram = max(1, int(spec_ngram))
        if self.spec_tokens and do_sample:
            raise ValueError(
                "n-gram speculative decoding is greedy-only "
                "(accepted tokens must equal step-by-step argmax); "
                "do_sample=True is incompatible")
        # sampling config shared by prefill + decode (the generation
        # module's _sample: temperature / top-k / nucleus; greedy when
        # do_sample=False).  One key stream serves the whole pool —
        # jax.random.categorical draws rows independently
        self._gen_cfg = _GC(do_sample=do_sample, temperature=temperature,
                            top_k=top_k, top_p=top_p)
        self._key = jax.random.PRNGKey(seed)
        self._do_sample = do_sample
        table = getattr(model.config, "max_position_embeddings", None)
        if table is not None and max_len > table:
            # the per-row RoPE gather CLAMPS out-of-range positions
            # (silent wrong rotations) — reject up front where the scalar
            # path would have raised at trace time
            raise ValueError(
                f"max_len {max_len} exceeds the model's RoPE table "
                f"(max_position_embeddings={table})")
        largest = max(prefill_buckets)
        if largest >= max_len:
            raise ValueError(
                f"largest prefill bucket {largest} must be < max_len "
                f"{max_len} (it is the default chunk width)")
        # weight-only quantized serving (quantization.serving tentpole):
        # PADDLE_TPU_QUANT_WEIGHTS=int8|fp8 (or quant_weights=) converts
        # the model's large Linears to QuantedLinear IN PLACE (refcounted
        # — a fleet shares one conversion; close() restores).  Unset
        # keeps the exact previous engine, jaxpr-identical.
        from paddle_tpu.quantization.serving import quant_weights_mode
        self.quant_mode = quant_weights_mode(quant_weights)
        self._quant_converted = False
        if self.quant_mode:
            if int8_weights:
                raise ValueError(
                    "int8_weights (the legacy param-dict path) and "
                    "quant_weights= are mutually exclusive")
            from paddle_tpu.quantization.serving import \
                quantize_for_serving
            info = quantize_for_serving(model, self.quant_mode)
            self._quant_converted = True
            self._quant_layers = info["layers"]
        params = params_of(model)
        self._dtype = next(
            (a.dtype for a in params.values()
             if jnp.issubdtype(a.dtype, jnp.floating)),
            next(iter(params.values())).dtype)
        if int8_weights:
            self._keep, self._quant = quantize_weights_int8(params)
        else:
            self._keep, self._quant = params, {}
        self.int8 = int8_weights

        cfgm = model.config
        from paddle_tpu.inference.kv_cache import (BlockAllocator,
                                                   PagedKVPool,
                                                   PrefixCache)
        self._block_size = int(kv_block_size)
        if self._block_size < 1:
            raise ValueError(f"kv_block_size must be >= 1, got "
                             f"{kv_block_size}")
        self._max_blocks = -(-max_len // self._block_size)
        # default pool: every slot can hold a worst-case sequence,
        # plus the reserved scratch block; prefix sharing then turns
        # the saved blocks into prefix-cache headroom.  An int8-
        # quantized pool multiplies the block count by the compute
        # dtype's itemsize — SAME payload HBM bytes, itemsize-ratio
        # more blocks (the extra blocks become prefix-cache and
        # concurrency headroom)
        if num_kv_blocks:
            self._num_blocks = int(num_kv_blocks)
        else:
            ratio = jnp.dtype(self._dtype).itemsize \
                if self.kv_quant else 1
            self._num_blocks = 1 + ratio * slots * self._max_blocks
        self._allocator = BlockAllocator(self._num_blocks)
        # two kinds of state (kv_cache.py): block pools for the layers
        # that attend, per-slot recurrent state for the layers that
        # recur (a model says which through ``config.layer_types`` and
        # ``slot_state_shapes()``; without them every layer attends).
        # Slot state is not paged: what shares, exports or tiers KV
        # blocks would serve such a model wrong tokens, so each is
        # refused here or where it is asked for, and the prefix cache
        # is simply off.  Apart from that a model may say how many of
        # its layers route over experts (``routed_expert_layers()``):
        # their counts come back beside a decode step's tokens.  Either
        # statement gets the model a ``StepInfo`` after its caches
        # A model whose attention caches one latent row a token
        # (``config.latent_row``, its width) gets a latent pool: one array
        # a layer and no V pool, behind the same block ids — the programs
        # below carry its empty ``vpools`` as they carry an unquantised
        # pool's empty scale lists
        kinds = ["attention" if k.endswith("attention") else k
                 for k in (getattr(cfgm, "layer_types", None)
                           or ["attention"] * cfgm.num_hidden_layers)]
        latent_row = int(getattr(cfgm, "latent_row", 0) or 0)
        # a third statement: which attention layers see a sliding window
        # (``attention_windows()``: positions a layer, 0 for none).  Their
        # keys and values live in a block group of their own, a ring a
        # request (kv_cache.py, "Two block groups"); what moves blocks by
        # id knows one group, so it is refused as for slot state
        windows = [int(w or 0) for w in model.attention_windows()] \
            if hasattr(model, "attention_windows") else []
        self._window = max(windows, default=0)
        win_layers = tuple(i for i, w in enumerate(windows) if w)
        self._state = None
        shapes = model.slot_state_shapes() \
            if hasattr(model, "slot_state_shapes") else []
        self._expert_layers = int(model.routed_expert_layers()) \
            if hasattr(model, "routed_expert_layers") else 0
        self._step_info = bool(shapes) or self._expert_layers > 0
        self._blocks_only = None        # why block-moving features are off
        if shapes:
            self._blocks_only = "keeps per-slot recurrent state"
        elif self._window:
            self._blocks_only = "keeps a second block group for its " \
                "sliding-window layers"
        if self._blocks_only:
            for what, asked in (("spec_decode", self.spec_tokens),
                                ("kv_tier", kv_tier is not None),
                                (f"role={role!r}", role != "mixed"),
                                ("quant_kv", self.kv_quant
                                 and self._window)):
                if asked:
                    raise ValueError(
                        f"{what}: {type(model).__name__} "
                        f"{self._blocks_only}, which {what} would neither "
                        f"carry nor roll back (the full group's KV blocks "
                        f"only)")
            prefix_cache = False
        if shapes:
            from paddle_tpu.inference.kv_cache import SlotStatePool
            self._state = SlotStatePool(slots, shapes, self._dtype)
        self._prefix = PrefixCache(self._block_size, self._allocator) \
            if prefix_cache else None
        heads, width = (1, latent_row) if latent_row else \
            (cfgm.num_key_value_heads, cfgm.head_dim)
        self._chunk = int(prefill_chunk) if prefill_chunk else largest
        if not 1 <= self._chunk < max_len:
            raise ValueError(f"prefill_chunk must be in [1, "
                             f"max_len), got {prefill_chunk}")
        # the window group: a request's ring covers the window and the
        # longest write of one dispatch, plus the block both may share;
        # default ids: a whole ring a slot beside the scratch block
        self._ring = min(self._max_blocks, -(-(
            self._window + max(self._chunk, self.steps_per_sync))
            // self._block_size) + 1) if self._window else 0
        self._num_window_blocks = 0
        if self._window:
            self._num_window_blocks = int(num_window_blocks) \
                if num_window_blocks else 1 + slots * self._ring
        elif num_window_blocks:
            raise ValueError(
                f"num_window_blocks={num_window_blocks}: "
                f"{type(model).__name__} has no sliding-window layer")
        self._allocator_w = BlockAllocator(self._num_window_blocks) \
            if self._window else None
        kw = {"window_layers": win_layers,
              "window_blocks": self._num_window_blocks} \
            if self._window else {}
        self._pool = PagedKVPool(
            kinds.count("attention"), self._num_blocks, self._block_size,
            heads, width, self._dtype, quant=self.kv_quant,
            latent=bool(latent_row), **kw)
        # per-slot block table rows; 0 = reserved scratch block (a group
        # has its own: ``_bt_w`` / ``_seq_w`` are the window group's)
        self._bt = np.zeros((slots, self._max_blocks), np.int32)
        self._seq: List[Optional[object]] = [None] * slots
        self._bt_w = np.zeros((slots, self._max_blocks), np.int32) \
            if self._window else None
        self._seq_w: List[Optional[object]] = [None] * slots
        self._blocks_used_peak_w = 0
        self._prefilling: Dict[int, int] = {}  # slot -> next pos
        self._interleave_decode = False
        self._blocks_used_peak = 0
        # session survivability (kv_tier.py): demoted sessions live in
        # the tier manager; _parked maps rid -> (request, tier key) for
        # sessions this engine still owns the resume of
        self._kv_tier = kv_tier
        self._auto_park_s = auto_park_s
        if auto_park_s is not None and kv_tier is None:
            raise ValueError("auto_park_s requires kv_tier=")
        self._parked: Dict[int, tuple] = {}
        if self._kv_tier is not None and self._prefix is not None:
            # demote-before-free: cold prefix blocks spill to the host
            # tier instead of vanishing; admission promotes them back
            self._prefix.on_evict = self._demote_prefix_node
        # prefill-only requests park their prompt blocks here at
        # retirement (rid -> (request, SequenceBlocks, first_token));
        # the router exports/discards them (prefill/decode handoff)
        self._handoff_ready: Dict[int, tuple] = {}
        self._pos = np.zeros((slots,), np.int32)       # next write row
        self._active: List[Optional[_Request]] = [None] * slots
        self._budget = np.zeros((slots,), np.int32)    # tokens remaining
        self._last_tok = np.zeros((slots,), np.int32)
        # a decode step is dispatched first and collected afterwards: the
        # one batched dispatch whose output the host has yet to read, the
        # decode program's last output column (it stays on the device and
        # is the next dispatch's input), and when the host last read one
        self._inflight: Optional[_Dispatch] = None
        self._dev_toks = jnp.zeros((slots,), jnp.int32)
        self._collected_at = 0.0
        # every program handed to the device is numbered, and the newest
        # one's output says whether the device still has work (one
        # stream, programs in order: that output complete, nothing left)
        self._dispatch_seq = 0
        self._newest_out = None
        self._step_span = None
        self._queue: deque = deque()
        self._done: deque = deque()
        self._next_rid = 0
        # backpressure + fault containment (robustness tentpole):
        # * bounded admission queue — at capacity add_request REJECTS
        #   (QueueFullError) instead of growing; a serving tier must shed
        #   load at the edge, not queue into OOM
        # * per-request deadlines — expired requests (queued OR decoding)
        #   are retired with status "timeout"; a stuck slot frees itself
        # * engine-step exception recovery — a step() exception fails the
        #   in-flight batch (status "error", caches rebuilt) but the
        #   engine keeps serving; `max_consecutive_errors` straight
        #   failures re-raise (the fault is persistent, not transient)
        if max_queue is not None and max_queue < 1:
            raise ValueError(f"max_queue must be >= 1, got {max_queue}")
        self._max_queue = max_queue
        self._default_timeout = request_timeout_s
        self._status: Dict[int, str] = {}
        self._error_streak = 0
        self._max_consecutive_errors = max(1, int(max_consecutive_errors))

        # telemetry: counters/histograms are shared process-wide; the
        # occupancy gauges are pull-style (read at scrape, zero cost in
        # the serving loop)
        self._metrics = _serving_metrics()
        # latency targets snapshotted once per engine (env-tunable); a
        # target <= 0 disables that kind's hit/miss counting
        from paddle_tpu.observability.goodput import slo_targets
        self._slo_targets = slo_targets()
        from paddle_tpu.observability import default_registry, \
            flight_recorder
        from paddle_tpu.observability.tracing import tracer
        from paddle_tpu.observability.forensics import emit_decision
        self._recorder = flight_recorder()
        self._tracer = tracer()
        # scheduler decision provenance (forensics): ring-only, no wire
        self._emit_decision = emit_decision
        # rid -> deferred admission attempts this wait. The defer
        # decision re-emits every _DEFER_EMIT_EVERY attempts: one
        # starving request must not flood the bounded ring with an
        # event per step, but each deferred step also records
        # kv_alloc_exhausted (+ fault.injected when rigged), so a
        # single emission would be evicted by its own wait's churn —
        # the period keeps the latest defer inside the ring window.
        self._defer_attempts: Dict[int, int] = {}
        reg = default_registry()
        reg.gauge("paddle_tpu_serving_queue_depth",
                  "requests waiting for a slot").set_function(
            lambda q=self._queue: len(q))
        reg.gauge("paddle_tpu_serving_active_slots",
                  "slots currently decoding").set_function(
            lambda a=self._active: sum(r is not None for r in a))
        reg.gauge("paddle_tpu_serving_slots",
                  "slot pool size").set(slots)
        # fleet role marker (disaggregated serving): one-replica-per-
        # process fleets publish this through the metrics publisher and
        # the fleet table renders it as the replica's role column
        if role not in ("mixed", "prefill", "decode"):
            raise ValueError(f"role must be mixed|prefill|decode, got "
                             f"{role!r}")
        self.role = role
        reg.gauge("paddle_tpu_serving_replica_role",
                  "serving role this engine plays in a disaggregated "
                  "fleet (value 1 marks the active role)",
                  labelnames=("role",)).labels(role=role).set(1.0)
        # read through the engine, not a bound allocator: _recover
        # rebuilds the allocator/prefix objects on error containment
        reg.gauge("paddle_tpu_serving_kv_blocks_free",
                  "paged KV blocks on the free list").set_function(
            lambda e=self: e._allocator.free_blocks)
        reg.gauge("paddle_tpu_serving_kv_blocks_used",
                  "paged KV blocks held by sequences or the prefix "
                  "cache").set_function(
            lambda e=self: e._allocator.used_blocks)
        # the full group's peak, and the window group's gauges (the
        # registry holds one set of labels a name, so a second group has
        # names of its own); zeros without a window group
        reg.gauge("paddle_tpu_serving_kv_blocks_used_peak",
                  "most paged KV blocks (the full group's) held at one "
                  "time since the engine was built").set_function(
            lambda e=self: e._blocks_used_peak)
        for what, help_, fn in (
                ("free", "window-group KV blocks on the free list",
                 lambda a: a.free_blocks),
                ("used", "window-group KV blocks held by requests' rings",
                 lambda a: a.used_blocks)):
            reg.gauge(f"paddle_tpu_serving_kv_window_blocks_{what}",
                      help_).set_function(
                lambda e=self, fn=fn: fn(e._allocator_w)
                if e._allocator_w is not None else 0)
        reg.gauge("paddle_tpu_serving_kv_window_blocks_used_peak",
                  "most window-group KV blocks held at one time since "
                  "the engine was built").set_function(
            lambda e=self: e._blocks_used_peak_w)
        reg.gauge("paddle_tpu_serving_prefix_cache_blocks",
                  "blocks registered in the prefix trie"
                  ).set_function(
            lambda e=self: len(e._prefix)
            if e._prefix is not None else 0)
        reg.gauge("paddle_tpu_serving_kv_pool_bytes",
                  "device bytes held by the paged KV pools "
                  "(K/V payload + quant scale arrays)"
                  ).set_function(lambda e=self: e._pool.nbytes)
        reg.gauge("paddle_tpu_serving_state_bytes",
                  "device bytes held by per-slot recurrent state "
                  "(convolution tails + SSM states of every slot)"
                  ).set_function(
            lambda e=self: e._state.nbytes if e._state else 0)
        reg.gauge("paddle_tpu_serving_state_slots_used",
                  "slots whose recurrent state belongs to an admitted "
                  "request").set_function(
            lambda e=self: sum(r is not None for r in e._active)
            if e._state else 0)
        if self._expert_layers:
            # what a decode step's expert layers count (StepInfo), beside
            # its tokens: made only for a model that has them
            self._moe_counters = (
                reg.counter(
                    "paddle_tpu_moe_experts_touched",
                    "held experts that at least one row chose, summed "
                    "over expert layers and decode steps (stat=sum) "
                    "beside the number of those layer-steps "
                    "(stat=layer_steps): their ratio is the mean a "
                    "layer a step", labelnames=("stat",)),
                reg.counter(
                    "paddle_tpu_moe_local_picks_total",
                    "token-expert picks of decode steps that landed on "
                    "an expert held here"),
                reg.counter(
                    "paddle_tpu_moe_picks_total",
                    "token-expert picks decode steps made over the "
                    "router's whole width"))
        reg.gauge("paddle_tpu_serving_sessions_parked",
                  "sessions demoted to the KV tier and awaiting "
                  "resume on this engine").set_function(
            lambda e=self: len(e._parked))

        # serving traces must see eval-mode (dropout off); remembered so
        # close() / context exit can hand the model back for training
        self._was_training = getattr(model, "training", False)
        if self._was_training:
            model.eval()

        import functools as _ft

        from paddle_tpu.core.dispatch import unwrap
        from paddle_tpu.generation import _sample
        from paddle_tpu.inference.kv_cache import PagedCache, StepInfo
        dtype = self._dtype
        gen_cfg = self._gen_cfg
        K = self.steps_per_sync

        # kscales/vscales are EMPTY lists on an unquantized pool:
        # they contribute no jaxpr inputs, so the knob-off programs
        # are identical to the pre-quantization engine
        # ``state`` / ``info``: a model with slot state gets its
        # recurrent layers' SlotStates between the paged caches, in
        # layer order; it, and a model with routed expert layers, gets
        # a StepInfo after them; for any other model both are empty
        # and the programs are what they were
        # ``bt`` is the block table, or for a model with a window group
        # the pair (full group's, window group's): a layer's cache gets
        # its group's
        def fwd_paged(ps, ids, kpools, vpools, kscales, vscales,
                      bt, pos, state=(), info=None):
            if kscales:
                cc = [PagedCache(kk, vv, bt, ks, vs)
                      for kk, vv, ks, vs in zip(kpools, vpools,
                                                kscales, vscales)]
            elif win_layers:
                cc = [PagedCache(kk, vv, bt[1 if j in win_layers else 0])
                      for j, (kk, vv) in enumerate(zip(kpools, vpools))]
            else:
                cc = [PagedCache(kk, vv, bt) for kk, vv in zip(
                    kpools, vpools or [None] * len(kpools))]
            if info is not None:
                paged, recur = iter(cc), iter(state)
                cc = [next(paged if k == "attention" else recur)
                      for k in kinds] + [info]
            logits, new_caches = functional_call(model, ps, ids,
                                                 None, cc, pos)
            raw = unwrap(logits).astype(jnp.float32)
            counts = None
            if info is not None:
                if experts:
                    counts = new_caches[len(kinds)].moe_counts
                state = [c for c, k in zip(new_caches, kinds)
                         if k != "attention"]
                new_caches = [c for c, k in zip(new_caches, kinds)
                              if k == "attention"]
            return raw, ([unwrap(c.k) for c in new_caches],
                         [unwrap(c.v) for c in new_caches]
                         if vpools else [],
                         [unwrap(c.k_scale) for c in new_caches]
                         if kscales else [],
                         [unwrap(c.v_scale) for c in new_caches]
                         if kscales else []), state, counts

        # chunked prefill: ONE executable serves every chunk of
        # every prompt (B=1, fixed width C, per-row [1] position
        # vector so padded tails clamp safely in the RoPE gather).
        # Non-final chunks ignore the sampled token; the final
        # chunk's sample at the true last prompt position is the
        # request's first generated token.
        # A model that takes a StepInfo also gives its slot state
        # (donated like the pools; none without recurrent layers), the
        # chunk's slot and how many of the chunk's positions are
        # tokens, and one with slot state gets the state back.
        stateful = self._state is not None
        step_info, experts = self._step_info, self._expert_layers > 0

        @_ft.partial(jax.jit, donate_argnums=(3, 4, 5, 6)
                     + ((11,) if stateful else ()))
        def prefill_chunk(keep, quant, ids, kpools, vpools, kscales,
                          vscales, bt_row, start, last_idx, key,
                          state=(), slot=None, n=None):
            ps = _dequant(keep, quant, dtype)
            info = StepInfo(n, slot) if step_info else None
            logits, pools, state, _ = fwd_paged(
                ps, ids, kpools, vpools, kscales, vscales, bt_row,
                start, state, info)
            first = _sample(logits[0, last_idx][None], gen_cfg,
                            key)[0]
            if stateful:
                return first.astype(jnp.int32), pools, state
            return first.astype(jnp.int32), pools

        # ``toks`` is this program's own last output column, which never
        # left the device; a row whose token the host made (a prompt's
        # last chunk, a resume) takes it from ``host_toks`` instead
        def decode_paged(keep, quant, kpools, vpools, kscales,
                         vscales, bt, toks, host_toks, from_host, pos,
                         active, key, state=()):
            ps = _dequant(keep, quant, dtype)
            info = StepInfo(active.astype(jnp.int32)) if step_info \
                else None
            toks = jnp.where(from_host, host_toks, toks)

            def one(carry, _):
                (kpools, vpools, kscales, vscales, toks, pos, key,
                 state, counts) = carry
                logits, (kpools, vpools, kscales, vscales), state, n = \
                    fwd_paged(ps, toks[:, None], kpools, vpools,
                              kscales, vscales, bt, pos, state, info)
                if experts:
                    counts = counts + n
                key, sub = jax.random.split(key)
                nxt = _sample(logits[:, -1], gen_cfg,
                              sub).astype(jnp.int32)
                # inactive rows: host pins pos=0 and zeroes their
                # block-table row, so the write lands in the
                # reserved scratch block
                nxt = jnp.where(active, nxt, toks)
                pos = jnp.where(active, pos + 1, pos)
                return (kpools, vpools, kscales, vscales, nxt, pos,
                        key, state, counts), nxt

            counts = jnp.zeros((3,), jnp.int32) if experts else None
            (kpools, vpools, kscales, vscales, last, _, _, state,
             counts), seq = jax.lax.scan(
                    one, (kpools, vpools, kscales, vscales, toks,
                          pos, key, state, counts), None, length=K)
            out = jnp.swapaxes(seq, 0, 1)
            if experts:         # the expert layers' counts come back
                out = (out, counts)     # beside the tokens, in one copy
            return (out, kpools, vpools, kscales, vscales, last) \
                + ((state,) if stateful else ())

        # speculative verify: ONE batched forward over
        # [last_token, draft_1..draft_k] per row; argmax at every
        # position is exactly what step-by-step greedy would emit,
        # so the host can accept the longest matching draft prefix
        # plus one bonus token with zero output drift
        def spec_verify(keep, quant, kpools, vpools, kscales,
                        vscales, bt, toks, pos, active):
            ps = _dequant(keep, quant, dtype)
            logits, (kpools, vpools, kscales, vscales), _, _ = fwd_paged(
                ps, toks, kpools, vpools, kscales, vscales, bt, pos)
            return (jnp.argmax(logits, axis=-1).astype(jnp.int32),
                    kpools, vpools, kscales, vscales)

        self._prefill_chunk_fn = prefill_chunk
        # raw (unjitted) decode kept for program analysis
        self._decode_paged_raw = decode_paged
        self._decode_paged = jax.jit(
            decode_paged, donate_argnums=(2, 3, 4, 5)
            + ((13,) if stateful else ()))
        self._spec_verify = jax.jit(spec_verify,
                                    donate_argnums=(2, 3, 4, 5))
        # AOT executables from aot_warmup(); dispatch prefers them (no
        # first-request compile spike)
        self._decode_compiled = None
        self._prefill_chunk_compiled = None
        self._spec_verify_compiled = None

        from paddle_tpu.analysis import analysis_mode
        mode = analyze if analyze is not None else analysis_mode()
        if mode:
            import sys
            report = self.analyze(strict=(mode == "strict"))
            if len(report):
                print(report.format(), file=sys.stderr)

    def _cache_extra(self) -> str:
        """Compile-cache key discriminators invisible to call-argument
        avals: closed-over sampling config, chunking, and the model
        config whose constants (rope tables, eps) are baked into the
        traced programs."""
        from paddle_tpu import compile_cache
        gc = self._gen_cfg
        return (f"model={compile_cache.model_config_tag(self.model)}"
                f"|gc={gc.do_sample}:{gc.temperature}:{gc.top_k}"
                f":{gc.top_p}|K={self.steps_per_sync}"
                f"|int8={int(self.int8)}"
                f"|spec={self.spec_tokens}"
                f"|qw={self.quant_mode or '-'}"
                f"|qkv={self.kv_quant or '-'}")

    def aot_warmup(self, cache_only: bool = False):
        """Explicitly compile the serving executables up front — the
        decode step, the chunked-prefill program and, with
        ``spec_decode``, the spec-verify program — with full compile
        observability (``compile.lower``/``compile.xla`` spans,
        ``paddle_tpu_compile_total{target}`` counters, per-executable
        FLOPs / HBM bytes / peak-memory gauges).  With
        ``PADDLE_TPU_COMPILE_CACHE=1`` every executable
        is served from (or stored into) the persistent compile cache:
        a warm replica boots to first token with ZERO XLA compiles.
        ``cache_only=True`` adopts cached executables but never pays a
        live compile — the ``_recover`` re-warm path.  The engine then
        dispatches through the compiled objects: no first-request
        compile spike, a shape drift raises instead of silently
        recompiling, and a restarting replica's warmup cost is a
        measured number (ROADMAP item 5's cold-start budget).  Returns
        ``{target: ExecutableStats}`` of every executable acquired."""
        from paddle_tpu import compile_cache
        stats = {}
        extra = self._cache_extra()

        def warm(fn, *args, target):
            compiled, info, _hit = compile_cache.aot_compile_cached(
                fn, *args, target=target, extra=extra,
                cache_only=cache_only)
            if compiled is not None:
                stats[target] = info.stats
            return compiled

        toks = jnp.zeros((self.slots,), jnp.int32)
        pos = jnp.zeros((self.slots,), jnp.int32)
        active = jnp.ones((self.slots,), jnp.bool_)
        kpools, vpools, kscales, vscales, bt = self._paged_dummies()
        c = warm(self._decode_paged, self._keep, self._quant, kpools,
                 vpools, kscales, vscales, bt, toks, toks, active, pos,
                 active, self._key, *self._state_dummies(),
                 target="serving.decode")
        if c is not None:
            self._decode_compiled = c
        kpools, vpools, kscales, vscales, bt = self._paged_dummies()
        ids = jnp.zeros((1, self._chunk), jnp.int32)
        target = f"serving.prefill_chunk[{self._chunk}]"
        c = warm(self._prefill_chunk_fn, self._keep, self._quant, ids,
                 kpools, vpools, kscales, vscales,
                 jax.tree.map(lambda t: t[:1], bt),
                 jnp.zeros((1,), jnp.int32),
                 jnp.asarray(0, jnp.int32), self._key,
                 *self._state_dummies(chunk=True), target=target)
        if c is not None:
            self._prefill_chunk_compiled = c
        if self.spec_tokens:
            kpools, vpools, kscales, vscales, bt = self._paged_dummies()
            toksS = jnp.zeros((self.slots, self.spec_tokens + 1),
                              jnp.int32)
            c = warm(self._spec_verify, self._keep, self._quant, kpools,
                     vpools, kscales, vscales, bt, toksS, pos, active,
                     target="serving.spec_verify")
            if c is not None:
                self._spec_verify_compiled = c
        # handoff transfer executables (prefill/decode disaggregation):
        # one pow-2-bucketed gather/scatter pair per size, compiled now
        # so a fleet's first KV handoff doesn't pay an XLA compile
        self._pool.warm_transfer(self._max_blocks)
        if self._state is not None and not cache_only:
            self._state.reset_slot(0)   # admission's reset, compiled now
        return stats

    def _paged_dummies(self):
        """Zero-filled pool/table/state avals for AOT compile + lint."""
        kpools = [jnp.zeros_like(p) for p in self._pool.kpools]
        vpools = [jnp.zeros_like(p) for p in self._pool.vpools]
        kscales = [jnp.zeros_like(p) for p in self._pool.kscales]
        vscales = [jnp.zeros_like(p) for p in self._pool.vscales]
        bt = jnp.zeros((self.slots, self._max_blocks), jnp.int32)
        if self._window:
            bt = (bt, jnp.zeros_like(bt))
        return kpools, vpools, kscales, vscales, bt

    def _state_dummies(self, chunk: bool = False):
        """The arguments a model that takes a StepInfo adds to a
        program, to compile against: zero-filled slot state (none
        without recurrent layers) and, for the prefill chunk, a slot
        and a token count.  Empty for any other model."""
        if not self._step_info:
            return ()
        state = (self._state.zeros_like() if self._state else [],)
        if chunk:
            state += (jnp.asarray(0, jnp.int32),
                      jnp.ones((1,), jnp.int32))
        return state

    def analyze(self, strict: bool = False, passes=None, options=None):
        """Lint the compiled decode step (the hot serving path) with the
        ``paddle_tpu.analysis`` pipeline.  Abstract — nothing executes;
        call any time (the engine build hook uses ``analyze="warn"`` /
        ``"strict"`` ctor opt-in or PADDLE_TPU_ANALYZE)."""
        import paddle_tpu.analysis as _analysis
        toks = jnp.zeros((self.slots,), jnp.int32)
        pos = jnp.zeros((self.slots,), jnp.int32)
        active = jnp.ones((self.slots,), jnp.bool_)
        kpools, vpools, kscales, vscales, bt = self._paged_dummies()
        return _analysis.check(
            self._decode_paged_raw, self._keep, self._quant, kpools,
            vpools, kscales, vscales, bt, toks, toks, active, pos, active,
            self._key, *self._state_dummies(), strict=strict,
            passes=passes, options=options)

    def _next_key(self):
        """Advance the sampling stream — greedy mode skips the split
        (the key is dead in _sample there; no per-step dispatch)."""
        if not self._do_sample:
            return self._key
        self._key, sub = jax.random.split(self._key)
        return sub

    # -- public API ----------------------------------------------------------
    def add_request(self, prompt_ids, max_new_tokens: int = 64,
                    timeout_s: Optional[float] = None, *,
                    prefill_only: bool = False,
                    handoff: Optional[Dict] = None,
                    router_enqueued_at: Optional[float] = None,
                    span_parent=None) -> int:
        """Enqueue a prompt.  `timeout_s` (or the engine-wide
        ``request_timeout_s`` default) is a wall-clock deadline from NOW:
        a request still queued or decoding past it is retired with
        status "timeout".  Raises :class:`QueueFullError` when the
        bounded admission queue is at capacity.

        Fleet-router hooks:
        ``prefill_only=True`` retires the request right after its first
        token with status ``"prefilled"`` and parks the prompt's KV
        blocks for :meth:`export_handoff`; ``handoff=payload`` is the
        receiving side — the request skips prefill entirely, importing
        the exported blocks at admission.  ``router_enqueued_at``
        re-anchors TTFT at the router's clock and ``span_parent`` nests
        the request span under the router's (the cross-hop trace)."""
        p = np.asarray(prompt_ids, np.int32).reshape(-1)
        if len(p) == 0:
            raise ValueError("empty prompt: the first token is sampled at "
                             "the last prompt position, and there is none")
        if max_new_tokens < 1:
            raise ValueError(f"max_new_tokens must be >= 1 (the prefill "
                             f"already emits one token); got "
                             f"{max_new_tokens}")
        if prefill_only and handoff is not None:
            raise ValueError("prefill_only and handoff are the two ends "
                             "of one transfer; a request can't be both")
        if prefill_only or handoff is not None:
            self._refuse_with_slot_state("a prefill / decode handoff")
        if handoff is not None and \
                int(handoff.get("block_size", self._block_size)) \
                != self._block_size:
            raise ValueError(
                f"handoff block_size {handoff.get('block_size')} != "
                f"engine kv_block_size {self._block_size}")
        if self._max_queue is not None and \
                len(self._queue) >= self._max_queue:
            from paddle_tpu.robustness import QueueFullError
            self._metrics["rejections"].labels(reason="queue_full").inc()
            self._recorder.record("serving.reject", reason="queue_full",
                                  queue_depth=len(self._queue))
            raise QueueFullError(
                f"admission queue at capacity ({self._max_queue}); "
                "retry with backoff or scale out")
        # strict bound: row max_len-1 is the inactive-slot scratch row and
        # must stay unreachable; chunked decode over-writes up to the next
        # steps_per_sync boundary, so budget in whole chunks
        K = self.steps_per_sync
        if prefill_only:
            # prefill writes rows 0..Lp-1 only; the first token is
            # sampled, never cached here — the decode replica writes it
            span = 0
            if len(p) > self.max_len - 1:
                raise ValueError(
                    f"prompt {len(p)} exceeds max_len-1 = "
                    f"{self.max_len - 1} (last row is reserved)")
        elif self.spec_tokens:
            # spec verify writes up to spec_tokens draft rows past the
            # accepted position; budget that headroom up front
            span = max_new_tokens + self.spec_tokens
            if len(p) + span > self.max_len - 1:
                raise ValueError(
                    f"prompt {len(p)} + max_new {max_new_tokens} + "
                    f"spec_decode={self.spec_tokens} draft headroom "
                    f"exceeds max_len-1 = {self.max_len - 1}")
        else:
            span = -(-max_new_tokens // K) * K
            if len(p) + span > self.max_len - 1:
                raise ValueError(
                    f"prompt {len(p)} + max_new {max_new_tokens} "
                    f"(rounded to {span} by steps_per_sync={K}) "
                    f"exceeds max_len-1 = {self.max_len - 1} (last row "
                    "is reserved)")
        # a request the EMPTY pool couldn't hold would starve in the
        # queue forever — reject at submission, like the max_len bound
        # (transient exhaustion, by contrast, defers admission and
        # resolves as running slots retire)
        worst = -(-(len(p) + span) // self._block_size)
        if worst > self._num_blocks - 1:
            raise ValueError(
                f"prompt {len(p)} + generation span {span} needs "
                f"{worst} KV blocks but the pool holds "
                f"{self._num_blocks - 1}; raise num_kv_blocks")
        if self._window and \
                min(worst, self._ring) > self._num_window_blocks - 1:
            raise ValueError(
                f"prompt {len(p)} + generation span {span} needs a ring "
                f"of {min(worst, self._ring)} window-group KV blocks but "
                f"the group holds {self._num_window_blocks - 1}; raise "
                f"num_window_blocks")
        rid = self._next_rid
        self._next_rid += 1
        timeout = timeout_s if timeout_s is not None \
            else self._default_timeout
        now = time.perf_counter()
        req = _Request(
            rid, p, max_new_tokens, enqueued_at=now,
            deadline=(now + timeout) if timeout is not None else None,
            mode=("prefill_only" if prefill_only
                  else "resume" if handoff is not None else "full"),
            handoff=handoff, router_t0=router_enqueued_at)
        # per-request root span, open until retirement.  The engine loop
        # may run on another thread; the span rides the request object —
        # explicit propagation, no thread-local assumptions.  A routed
        # request parents under the router's span (the cross-hop trace).
        if span_parent is not None:
            req.span = self._tracer.start_span(
                "serving.request", parent=span_parent, rid=rid,
                prompt_len=len(p), max_new_tokens=max_new_tokens,
                mode=req.mode)
        else:
            req.span = self._tracer.start_span(
                "serving.request", rid=rid, prompt_len=len(p),
                max_new_tokens=max_new_tokens)
        self._queue.append(req)
        self._metrics["requests"].inc()
        ev = dict(rid=rid, prompt_len=len(p),
                  max_new_tokens=max_new_tokens,
                  queue_depth=len(self._queue))
        if req.span.trace_id is not None:
            ev["trace_id"] = req.span.trace_id
        self._recorder.record("serving.enqueue", **ev)
        return rid

    def finished(self):
        while self._done:
            yield self._done.popleft()

    @property
    def pending(self) -> int:
        # AUTO-parked sessions count: the scheduler owes them a resume,
        # so run() must keep stepping.  Caller-parked sessions don't —
        # they are dormant until the caller's resume().
        # A dispatch the host has not read counts too: its tokens are
        # not in ``finished()`` until a step (or ``run``) collects them.
        return len(self._queue) + sum(r is not None for r in self._active) \
            + sum(1 for req, _k in self._parked.values()
                  if req.auto_parked) + (self._inflight is not None)

    # -- scheduling ----------------------------------------------------------
    def _admit(self, slot: int, req: _Request) -> bool:
        """Reserve blocks for `slot` (prefix-cache hits arrive as shared
        refs — those tokens never re-prefill) and mark it prefilling.
        Returns False on allocator exhaustion: the request stays queued
        and admission pressure backs up into the bounded queue, where
        add_request already sheds load (QueueFullError)."""
        from paddle_tpu.inference.kv_cache import SequenceBlocks
        from paddle_tpu.robustness import fault_fires
        if req.handoff is not None:
            return self._admit_resume(slot, req)
        bs = self._block_size
        Lp = len(req.prompt)
        if req.mode == "prefill_only":
            gen_span = 0             # this replica never decodes it
        elif self.spec_tokens:
            gen_span = req.max_new_tokens + self.spec_tokens
        else:
            K = self.steps_per_sync
            gen_span = -(-req.max_new_tokens // K) * K
        total = Lp + gen_span        # every position this slot may write
        reuse_bids: List[int] = []
        m = self._metrics
        if self._prefix is not None:
            matched = self._prefix.match(req.prompt)
            if self._kv_tier is not None:
                # promotion fused into admission: extend the matched
                # chain block-by-block from the tier (host RAM / peer)
                # — a demoted prefix re-enters HBM exactly like a
                # handoff import, never via re-prefill
                matched = self._promote_prefix_tail(req.prompt, matched)
            # only FULL blocks strictly before the last prompt token are
            # adopted: the final token always re-forwards (its logits
            # seed generation) and must land in a private block — shared
            # blocks are never written, so COW stays off the hot path
            reuse_bids = matched[:(Lp - 1) // bs]
            m["prefix_lookups"].labels(
                result="hit" if reuse_bids else "miss").inc()
        need = -(-total // bs) - len(reuse_bids)
        # the window group's part: a ring, or every block of a request
        # shorter than one; both groups have the blocks or neither is
        # touched (a request never waits holding half)
        ring = min(-(-total // bs), self._ring)
        exhausted = fault_fires("serving.kv_alloc", slot=slot,
                                rid=req.rid, need=need)
        if not exhausted and self._allocator.free_blocks < need and \
                self._prefix is not None:
            m["evictions"].inc(
                self._prefix.evict(need - self._allocator.free_blocks))
        short = [g for g, lacks in (
            ("full", exhausted or self._allocator.free_blocks < need),
            ("window", ring and self._allocator_w.free_blocks < ring))
            if lacks]
        if short:
            m["alloc_failures"].inc()
            for g in short:
                m["deferred"].labels(group=g).inc()
            self._recorder.record(
                "serving.kv_alloc_exhausted", rid=req.rid, need=need,
                free=self._allocator.free_blocks,
                injected=bool(exhausted))
            n = self._defer_attempts.get(req.rid, 0) + 1
            self._defer_attempts[req.rid] = n
            if n % DEFER_EMIT_EVERY == 1:
                self._emit_decision(
                    "admit", rid=req.rid, chosen="defer",
                    reason="kv_alloc_exhausted", need=need,
                    free=self._allocator.free_blocks,
                    injected=bool(exhausted), attempts=n)
            return False
        seq = SequenceBlocks(self._allocator, bs)
        seq.adopt_shared(reuse_bids)
        seq.ensure_capacity(total)   # free count checked above
        self._seq[slot] = seq
        self._bt[slot, :] = 0
        self._bt[slot, :len(seq.bids)] = seq.bids
        if ring:
            # the ring, named over and over along the logical table:
            # writes go through it like any table, and a block comes
            # round again only when all it holds is out of every later
            # query's window
            seq_w = SequenceBlocks(self._allocator_w, bs)
            seq_w.ensure_capacity(ring * bs)
            self._seq_w[slot] = seq_w
            self._bt_w[slot, :] = 0
            self._bt_w[slot, :len(seq.bids)] = np.asarray(
                seq_w.bids, np.int32)[np.arange(len(seq.bids)) % ring]
            self._blocks_used_peak_w = max(self._blocks_used_peak_w,
                                           self._allocator_w.used_blocks)
        if self._state is not None:
            # the slot's last request left its recurrence behind
            self._state.reset_slot(slot)
        reused = len(reuse_bids) * bs
        req.prefix_reused = reused
        # a recompute-resumed session keeps its ORIGINAL admission
        # stamp (like _admit_resume): queue_s/prefill_s describe the
        # first life; the re-admission wait + replay is resume_s
        req.admitted_at = req.admitted_at or time.perf_counter()
        if req.router_t0 is not None and not req.parked_s:
            # once a session has been parked, admission latency is
            # resume latency (resume_s), not routing latency
            req.route_s = req.admitted_at - req.router_t0
        if reused:
            m["prefix_tokens"].inc(reused)
        m["admissions"].inc()
        self._active[slot] = req
        self._prefilling[slot] = reused   # next prompt pos to prefill
        self._blocks_used_peak = max(self._blocks_used_peak,
                                     self._allocator.used_blocks)
        self._recorder.record("serving.admit", rid=req.rid, slot=slot,
                              prompt_len=Lp, prefix_reused=reused,
                              blocks=len(seq.bids))
        self._defer_attempts.pop(req.rid, None)
        self._emit_decision("admit", rid=req.rid, chosen="slot",
                            slot=slot, prefix_reused=reused,
                            blocks=len(seq.bids))
        return True

    def _admit_resume(self, slot: int, req: _Request) -> bool:
        """Admit a handed-off request: allocate blocks for the full
        span, IMPORT the prefill replica's exported prompt KV (skipping
        any leading blocks this replica's prefix cache already holds),
        and enter decode directly — the handoff is a copy, never a
        recompute.  Returns False on allocator exhaustion, exactly like
        :meth:`_admit` (the request stays queued)."""
        from paddle_tpu.inference.kv_cache import SequenceBlocks
        from paddle_tpu.robustness import fault_fires
        h = req.handoff
        bs = self._block_size
        Lp = len(req.prompt)
        # session payloads (park/resume, replica migration) carry the
        # whole decode state: KV rows 0..pos-1, the generated tokens so
        # far, and the next decode input — the remaining budget is what
        # the payload hasn't emitted yet
        session = bool(h.get("session"))
        if session:
            out_prev = [int(t) for t in
                        np.asarray(h["tokens_out"]).reshape(-1)]
            covered = int(h["pos"])
            remaining = req.max_new_tokens - len(out_prev)
        else:
            out_prev = [int(h["first_token"])]
            covered = Lp
            remaining = req.max_new_tokens - 1
        # span sizing mirrors fresh admission with the emitted prefix
        # already paid for: entry budget + the step the entry token took
        if self.spec_tokens:
            gen_span = max(0, remaining) + 1 + self.spec_tokens
        else:
            K = self.steps_per_sync
            gen_span = -(-max(1, remaining + 1) // K) * K
        total = covered + gen_span
        m = self._metrics
        reuse_bids: List[int] = []
        if self._prefix is not None:
            matched = self._prefix.match(req.prompt)
            reuse_bids = matched[:(Lp - 1) // bs]
            m["prefix_lookups"].labels(
                result="hit" if reuse_bids else "miss").inc()
        need = -(-total // bs) - len(reuse_bids)
        exhausted = fault_fires("serving.kv_alloc", slot=slot,
                                rid=req.rid, need=need)
        if not exhausted and self._allocator.free_blocks < need and \
                self._prefix is not None:
            m["evictions"].inc(
                self._prefix.evict(need - self._allocator.free_blocks))
        if exhausted or self._allocator.free_blocks < need:
            m["alloc_failures"].inc()
            self._recorder.record(
                "serving.kv_alloc_exhausted", rid=req.rid, need=need,
                free=self._allocator.free_blocks,
                injected=bool(exhausted))
            n = self._defer_attempts.get(req.rid, 0) + 1
            self._defer_attempts[req.rid] = n
            if n % DEFER_EMIT_EVERY == 1:
                self._emit_decision(
                    "admit", rid=req.rid, chosen="defer",
                    reason="kv_alloc_exhausted", resume=True, need=need,
                    free=self._allocator.free_blocks,
                    injected=bool(exhausted), attempts=n)
            return False
        seq = SequenceBlocks(self._allocator, bs)
        seq.adopt_shared(reuse_bids)
        seq.ensure_capacity(total)
        nprompt = -(-covered // bs)  # blocks the payload covers
        t0 = time.perf_counter()
        if nprompt > len(reuse_bids):
            self._pool.import_blocks(
                h["kv"], seq.bids[len(reuse_bids):nprompt],
                src_start=len(reuse_bids))
        req.handoff_s = float(h.get("transfer_s", 0.0)) \
            + (time.perf_counter() - t0)
        req.route_s = req.route_s or float(h.get("route_s", 0.0))
        self._seq[slot] = seq
        self._bt[slot, :] = 0
        self._bt[slot, :len(seq.bids)] = seq.bids
        reused = len(reuse_bids) * bs
        req.prefix_reused = reused
        if reused:
            m["prefix_tokens"].inc(reused)
        if self._prefix is not None:
            # the imported prompt blocks are as shareable as locally
            # prefilled ones: register them so later affine requests
            # (or handoffs) skip even the copy
            self._prefix.register(req.prompt, seq.bids, limit_tokens=Lp)
        now = time.perf_counter()
        req.admitted_at = req.admitted_at or now
        m["admissions"].inc()
        # the first token was produced (and counted: tokens counter,
        # TTFT observation, slo ttft verdict) on the ORIGINATING
        # replica/session — only the lifecycle stamps carry over, and a
        # resumed session keeps its original anchor (no TTFT re-anchor)
        if not req.first_token_at:
            req.first_token_at = float(h.get("first_token_at") or now)
        req.out = list(out_prev)
        if not req.token_stamps:
            # emitted elsewhere (the prefill replica, a migrated
            # session): one stamp at the first token's time
            req.token_stamps.append((req.first_token_at, len(out_prev)))
        if req.resume_at:
            req.resume_s += now - req.resume_at
            req.resume_at = 0.0
        if session:
            m["resumes"].labels(path="promote").inc()
            last = int(h["last_token"])
        else:
            last = out_prev[-1]
        self._active[slot] = req
        self._pos[slot] = covered
        self._budget[slot] = remaining
        self._last_tok[slot] = last
        self._blocks_used_peak = max(self._blocks_used_peak,
                                     self._allocator.used_blocks)
        self._recorder.record("serving.admit", rid=req.rid, slot=slot,
                              prompt_len=Lp, resume=True,
                              session=session, pos=covered,
                              prefix_reused=reused,
                              handoff_s=round(req.handoff_s, 6),
                              blocks=len(seq.bids))
        self._defer_attempts.pop(req.rid, None)
        self._emit_decision("admit", rid=req.rid, chosen="slot",
                            slot=slot, resume=True, session=session,
                            pos=covered,
                            handoff_s=round(req.handoff_s, 6))
        if (self.eos is not None and last == self.eos) \
                or self._budget[slot] <= 0:
            self._retire(slot)
        return True

    def _refuse_with_slot_state(self, what: str):
        """Park, resume and handoff move the full group's KV blocks and
        nothing else: a model with per-slot recurrent state would come
        back with a zeroed recurrence, one with sliding-window layers
        with empty rings, and serve wrong tokens."""
        if self._blocks_only:
            raise ValueError(
                f"{what}: {type(self.model).__name__} "
                f"{self._blocks_only}, which {what} does not carry (the "
                f"full group's KV blocks only)")

    def export_handoff(self, rid: int) -> Dict:
        """Package a ``"prefilled"`` request's prompt KV for transfer:
        the exported blocks, the sampled first token, and the lifecycle
        stamps the decode replica's timings need.  Releases the parked
        blocks (the prefix trie keeps its own refs on the prompt's full
        blocks, so affine repeats still hit).  The payload feeds
        ``add_request(handoff=...)`` directly, or
        :func:`~paddle_tpu.inference.kv_cache.serialize_handoff` for a
        byte transport."""
        self._refuse_with_slot_state("export_handoff")
        self._collect()
        req, seq, first = self._handoff_ready.pop(rid)
        bs = self._block_size
        Lp = len(req.prompt)
        nblocks = -(-Lp // bs)
        payload = {
            "prompt": np.asarray(req.prompt, np.int32),
            "tokens": int(Lp),
            "first_token": int(first),
            "block_size": int(bs),
            "first_token_at": float(req.first_token_at),
            "route_s": float(req.route_s),
            "kv": self._pool.export_blocks(seq.bids[:nblocks]),
        }
        seq.release()
        return payload

    def discard_handoff(self, rid: int):
        """Drop a parked handoff (transfer failed / replica drained);
        tolerates an already-exported or unknown rid."""
        ent = self._handoff_ready.pop(rid, None)
        if ent is not None:
            ent[1].release()

    # ------------------------------------------------- session tiering
    def _session_payload(self, slot: int, req: _Request) -> Dict:
        """Snapshot an active decoding slot as a resumable session
        payload: KV rows 0..pos-1 plus the host-side decode state.  Pure
        read — the slot keeps running (checkpoint) or is freed right
        after (park)."""
        bs = self._block_size
        pos = int(self._pos[slot])
        nkv = -(-pos // bs)
        seq = self._seq[slot]
        return {
            "session": True,
            "prompt": np.asarray(req.prompt, np.int32),
            "tokens_out": np.asarray(req.out, np.int32),
            "pos": int(pos),
            "last_token": int(self._last_tok[slot]),
            "block_size": int(bs),
            "first_token_at": float(req.first_token_at),
            "route_s": float(req.route_s),
            "kv": self._pool.export_blocks(seq.bids[:nkv]),
        }

    def park(self, rid: int, key: Optional[str] = None,
             detach: bool = False, _auto: bool = False) -> Optional[str]:
        """Demote an actively decoding session out of HBM: its KV spills
        to the tier manager, the slot (and its blocks) free, and
        :meth:`resume` later promotes it back — token-identical, the
        greedy chain continues from the parked position.  Returns the
        tier key, or None when the rid is not parkable (unknown,
        queued, or mid-prefill).  ``detach=True`` hands resume ownership
        to the caller (the router): the engine forgets the request
        entirely."""
        self._refuse_with_slot_state("park")
        if self._kv_tier is None:
            raise ValueError("park() requires a kv_tier= manager "
                             "attached")
        self._collect()     # the session's tokens and write head, as read
        slot = next((i for i, r in enumerate(self._active)
                     if r is not None and r.rid == rid), None)
        if slot is None or slot in self._prefilling:
            return None
        req = self._active[slot]
        key = key or f"rid{rid}"
        # spill BEFORE the free — demotion, not deletion.  An injected
        # kv_tier.spill fault degrades to a drop: resume then misses the
        # tier and falls back to recompute (never a hang, never wrong
        # tokens — the replayed greedy chain is the same chain)
        self._kv_tier.spill(key, self._session_payload(slot, req),
                            kind="session")
        seq = self._seq[slot]
        self._active[slot] = None
        self._seq[slot] = None
        self._bt[slot, :] = 0
        seq.release()
        req.parked_at = time.perf_counter()
        req.auto_parked = _auto
        self._metrics["parks"].labels(
            kind="auto" if _auto else "manual").inc()
        self._recorder.record("serving.park", rid=rid, slot=slot,
                              key=key, auto=_auto,
                              tokens_out=len(req.out))
        if not _auto:
            # the auto-park decision (victim + rejected candidates'
            # headroom) is emitted by _maybe_auto_park
            self._emit_decision("park", rid=rid, chosen="park",
                                auto=False, key=key,
                                tokens_out=len(req.out))
        if not detach:
            self._parked[rid] = (req, key)
        return key

    def resume(self, rid: int) -> int:
        """Re-enqueue a parked session.  Tier hit → the payload rides
        the resume-admission import (a promotion, like a handoff).
        Tier miss (spill faulted, fetch faulted, entry lost) → the
        recompute fallback: the prompt is extended with the tokens
        already emitted and re-prefilled; greedy argmax regenerates the
        same chain, so the final output is token-identical either way."""
        self._refuse_with_slot_state("resume")
        self._collect()
        ent = self._parked.pop(rid, None)
        if ent is None:
            raise KeyError(f"rid {rid} is not parked on this engine")
        req, key = ent
        now = time.perf_counter()
        if req.parked_at:
            req.parked_s += now - req.parked_at
            req.parked_at = 0.0
        req.resume_at = now
        payload = self._kv_tier.fetch(key) \
            if self._kv_tier is not None else None
        self._kv_tier.discard(key)
        if payload is not None and payload.get("kv") is not None:
            req.handoff = payload
            req.mode = "resume"
        else:
            self._prepare_recompute(req)
        self._queue.append(req)
        path = "promote" if req.handoff is not None else "recompute"
        self._recorder.record("serving.resume", rid=rid, key=key,
                              path=path)
        self._emit_decision("resume", rid=rid, chosen=path, path=path,
                            key=key, parked_s=round(req.parked_s, 6))
        return rid

    def _prepare_recompute(self, req: _Request):
        """Tier-miss fallback: fold the already-emitted tokens into the
        prompt so a fresh (chunked, prefix-cache-assisted) prefill
        rebuilds the KV.  The re-prefill's sampled token is the token
        the session last emitted — greedy argmax over the identical
        context — so it is re-appended and the output stream is
        unchanged."""
        base = req.orig_prompt if req.orig_prompt is not None \
            else req.prompt
        if not req.orig_max_new:
            req.orig_max_new = req.max_new_tokens
        req.orig_prompt = base
        g = len(req.out)   # >= 1: parked sessions are post-first-token
        req.prompt = np.concatenate(
            [base, np.asarray(req.out[:-1], np.int32)]).astype(np.int32)
        req.out = req.out[:g - 1]
        # the re-prefill regenerates token g-1 as its sampled first
        # token, so the budget regains exactly that one step
        req.max_new_tokens = req.orig_max_new - (g - 1)
        req.handoff = None
        req.mode = "full"
        self._metrics["resumes"].labels(path="recompute").inc()

    def checkpoint_sessions(self, key_of=None) -> int:
        """Spill every actively decoding session's current KV + state to
        the tier WITHOUT disturbing it — the peer-tier replica that
        makes replica death survivable (the router fetches these for
        its survivors).  ``key_of(rid)`` maps engine rids to fleet-wide
        tier keys; None skips a session.  Returns sessions shipped."""
        if self._kv_tier is None:
            return 0
        self._collect()     # a payload holds what the host has read
        shipped = 0
        for slot, req in enumerate(self._active):
            if req is None or slot in self._prefilling or not req.out:
                continue
            key = key_of(req.rid) if key_of is not None else \
                f"rid{req.rid}"
            if key is None:
                continue
            if self._kv_tier.spill(key, self._session_payload(slot, req),
                                   kind="session"):
                shipped += 1
        return shipped

    def parked_rids(self):
        """Rids of sessions this engine parked and still owns."""
        return list(self._parked.keys())

    def _maybe_auto_park(self):
        """Deadline-aware auto-park: when every slot is busy and work is
        queued, the active session with the MOST deadline headroom (>=
        auto_park_s; no deadline = infinitely patient) yields its slot;
        when slots are free and the queue is empty, the oldest
        auto-parked session comes back.  Strictly work-conserving:
        each park admits a queued request, each drain resumes one."""
        free = any(r is None for r in self._active)
        if free and not self._queue and self._parked:
            for rid, (req, _key) in list(self._parked.items()):
                if req.auto_parked:
                    self.resume(rid)
                    return
            return
        if not self._queue or free:
            return
        now = time.perf_counter()
        best, best_h = None, float(self._auto_park_s)
        cands = []
        for i, r in enumerate(self._active):
            if r is None or i in self._prefilling or not r.out:
                continue
            h = (r.deadline - now) if r.deadline is not None \
                else float("inf")
            cands.append({"rid": r.rid,
                          "headroom_s": round(h, 4)
                          if h != float("inf") else None})
            if h >= best_h:
                best, best_h = r.rid, h
        if best is not None:
            self._emit_decision(
                "park", rid=best, auto=True,
                chosen={"rid": best,
                        "headroom_s": round(best_h, 4)
                        if best_h != float("inf") else None},
                alternatives=[c for c in cands if c["rid"] != best],
                queue_depth=len(self._queue))
            self.park(best, _auto=True)

    def _demote_prefix_node(self, node):
        """PrefixCache.on_evict hook: spill the victim block to the
        tier under its chain key before the allocator frees it."""
        from paddle_tpu.inference.kv_tier import prefix_block_key
        tokens = self._prefix.node_tokens(node)
        payload = {
            "prefix": True,
            "block_size": int(self._block_size),
            "kv": self._pool.export_blocks([node.bid]),
        }
        self._kv_tier.spill(prefix_block_key(tokens), payload,
                            kind="prefix")

    def _promote_prefix_tail(self, prompt, matched: List[int]
                             ) -> List[int]:
        """Extend a prefix-cache match with blocks promoted from the KV
        tier: fetch chain keys block-by-block past the in-HBM match,
        import each hit into a fresh block, and hand it to the trie —
        after this the admission path sees the promoted blocks as
        ordinary prefix-cache hits."""
        from paddle_tpu.inference.kv_tier import prefix_block_key
        bs = self._block_size
        nfull = (len(prompt) - 1) // bs  # blocks usable for reuse
        bids = list(matched)
        while len(bids) < nfull:
            upto = (len(bids) + 1) * bs
            payload = self._kv_tier.fetch(
                prefix_block_key(prompt[:upto]))
            if payload is None or payload.get("kv") is None:
                break
            bid = self._allocator.alloc()
            if bid is None:
                break
            try:
                self._pool.import_blocks(payload["kv"], [bid])
            except Exception:  # noqa: BLE001 — geometry/dtype mismatch
                self._allocator.free(bid)
                break
            new = self._prefix.register(
                np.asarray(prompt[:upto], np.int32), bids + [bid],
                limit_tokens=upto)
            # the trie holds its own ref on a newly inserted block;
            # drop ours either way (new == 0 returns it to the pool)
            self._allocator.free(bid)
            if not new:
                break
            bids.append(bid)
        return bids

    def _prefill_chunk_step(self, slot: int):
        """Advance `slot`'s prefill by one fixed-width chunk.  The final
        chunk samples the request's first token at the true last prompt
        position and registers the prompt's full blocks in the prefix
        trie (so the NEXT request with this prompt prefix skips them)."""
        from paddle_tpu.observability.tracing import host_annotation
        tr = self._tracer
        req = self._active[slot]
        with tr.span("serving.build"):
            start = self._prefilling[slot]
            Lp = len(req.prompt)
            C = self._chunk
            n = min(C, Lp - start)
            ids = np.zeros((1, C), np.int32)
            ids[0, :n] = req.prompt[start:start + n]
            final = (start + n) == Lp
            last_idx = (Lp - 1 - start) if final else 0
            sub = self._next_key()
        prefill = self._prefill_chunk_compiled or self._prefill_chunk_fn
        pool, state = self._pool, self._state
        with tr.span("serving.prefill", parent=req.span,
                     rid=req.rid, chunk_start=start, tokens=n):
            seq = self._count_dispatch("prefill_chunk")
            with tr.span("serving.dispatch", seq=seq, kind="prefill_chunk"):
                got = prefill(
                    self._keep, self._quant, jnp.asarray(ids),
                    pool.kpools, pool.vpools, pool.kscales, pool.vscales,
                    jax.tree.map(jnp.asarray,
                                 self._tables(slice(slot, slot + 1))),
                    jnp.asarray([start], jnp.int32),
                    jnp.asarray(last_idx, jnp.int32), sub,
                    *(() if not self._step_info else (
                        state.layers if state else [],
                        jnp.asarray(slot, jnp.int32),
                        jnp.asarray([n], jnp.int32))))
                first, (pool.kpools, pool.vpools, pool.kscales,
                        pool.vscales) = got[:2]
                if state is not None:
                    state.layers = got[2]
                self._newest_out = first
            # the context this chunk attends, on the profiler's host
            # plane just after its dispatch (an atomic check while no
            # profiler is capturing): chunks execute in dispatch order,
            # so a reader pairs the n-th of these from the trace's end
            # with the n-th prefill-chunk execution from its end
            with host_annotation("serving.prefill_context", start=start,
                                 tokens=n):
                pass
            # the decode step before this chunk is read while the device
            # runs the chunk: its tokens are not held back by a prompt
            self._collect()
            if final:
                # a chunk that is not the last leaves nothing to wait
                # for: the device runs it while the host goes on
                with tr.span("serving.sync", seq=seq):
                    first = int(first)
        with tr.span("serving.emit"):
            self._emit_chunk(slot, req, first, start, n, final)

    def _emit_chunk(self, slot, req, first, start, n, final):
        Lp, C, m = len(req.prompt), self._chunk, self._metrics
        self._prefilling[slot] = start + n
        m["chunks"].inc()
        if C > n:
            m["pad_tokens"].inc(C - n)
        if not final:
            return
        del self._prefilling[slot]
        if self._prefix is not None:
            # generated tokens are per-request noise — register only the
            # prompt's full blocks (the trie takes its own ref on each)
            self._prefix.register(req.prompt, self._seq[slot].bids,
                                  limit_tokens=Lp)
        now = time.perf_counter()
        if not req.first_token_at:
            # a recompute-resumed session keeps its ORIGINAL first-token
            # stamp: the client saw that token long ago, TTFT must not
            # re-anchor on the replay
            req.first_token_at = now
            origin = req.router_t0 or req.enqueued_at
            if origin:
                m["ttft"].observe(now - origin)
        if req.resume_at:
            # recompute fallback finished its re-prefill: the session
            # is decoding again — that replay wall time is resume_s
            # (the replayed token keeps the stamp of its first emission)
            req.resume_s += now - req.resume_at
            req.resume_at = 0.0
        else:
            req.token_stamps.append((now, 1))
        req.out.append(first)
        m["tokens"].inc()
        if req.mode == "prefill_only":
            # park the prompt blocks for the router's KV transfer: the
            # slot frees NOW (the prefill tier keeps admitting) but the
            # blocks stay referenced until export_handoff/discard_handoff
            seq = self._seq[slot]
            self._seq[slot] = None
            self._handoff_ready[req.rid] = (req, seq, first)
            self._retire(slot, status="prefilled")
            return
        self._pos[slot] = Lp
        self._budget[slot] = req.max_new_tokens - 1
        self._last_tok[slot] = first
        if (self.eos is not None and first == self.eos) \
                or self._budget[slot] <= 0:
            self._retire(slot)

    def _ensure_writable_span(self, slots_: List[int], pos, span: int):
        """COW guard before a dispatch that writes `span` positions from
        each slot's write head `pos`: any still-shared block in the span
        is copied to a private one (device block copy) and the block
        table is repointed.  Steady state is a no-op — the engine
        allocates private decode blocks at admission."""
        bs = self._block_size
        for i in slots_:
            seq = self._seq[i]
            first = int(pos[i]) // bs
            last = min((int(pos[i]) + span - 1) // bs,
                       len(seq.bids) - 1)
            for idx in range(first, last + 1):
                if seq.ensure_writable(idx,
                                       self._pool.copy_block) is not None:
                    self._metrics["cow"].inc()
                    self._bt[i, idx] = seq.bids[idx]

    def _tables(self, rows):
        """The block table a program is handed, on the host — for a
        model with a window group the pair (full, window); the caller
        uploads it (``jax.tree.map(jnp.asarray, .)``).  ``rows``: a slice
        of slots (a prefill chunk's one), or which slots are active in a
        batched dispatch (the others' rows are zeroed)."""
        def pick(bt):
            if isinstance(rows, slice):
                return bt[rows]
            return np.where(rows[:, None], bt, 0)
        if self._bt_w is None:
            return pick(self._bt)
        return pick(self._bt), pick(self._bt_w)

    def _ahead(self):
        """Per slot, the positions the outstanding dispatch writes that
        the host has not read yet: its ``steps`` for a row still held by
        the request it was dispatched for, 0 for every other.  What a
        dispatch needs of such a row it knows without the tokens: the
        write head and the budget move by this much, whatever they are."""
        ahead = np.zeros((self.slots,), np.int32)
        d = self._inflight
        if d is not None:
            for i, req in d.rows:
                if self._active[i] is req:
                    ahead[i] = d.steps
        return ahead

    def _phase(self, name: str, **attrs):
        """A span of the collect: a child of the step under way also
        where the caller stands inside a request's span (a prompt's last
        chunk collects between its dispatch and its own read)."""
        tr = self._tracer
        return tr.span(name, parent=self._step_span or tr.current_span(),
                       root_eligible=False, **attrs)

    def _count_dispatch(self, kind: str) -> int:
        """Number the program about to be handed to the device (the
        ``seq`` of its ``serving.dispatch`` span and of the
        ``serving.sync`` that reads it) and count it by whether the
        device had run dry: programs run in order on one stream, so the
        newest output being complete means nothing of this engine's is
        left there.  Non-blocking, and it needs no profiler."""
        self._dispatch_seq += 1
        newest = self._newest_out
        fed = newest is not None and not newest.is_ready()
        self._metrics["dispatches"].labels(
            kind=kind, device="fed" if fed else "drained").inc()
        return self._dispatch_seq

    def _dispatch_batched(self, kind: str, program, decoding: List[int],
                          toks, span: int, *more):
        """Upload and call ONE batched decode-shaped program (``kind``:
        the fused ``decode``, the speculative ``spec_verify``) and start
        its output's copy to the host; nothing here waits for the
        device.  ``toks`` are its token arguments, ``span`` the
        positions it writes from each decoding slot's write head,
        ``more`` what it takes after ``active`` (the fused decode: the
        sampling key and, for a model that takes a StepInfo, its slot
        state).  Returns the dispatch, to be read by :meth:`_read`, and
        what the program returned after its pools.  The only place the
        engine hands its pools and its slot state to a batched program
        and takes them back."""
        tr = self._tracer
        with tr.span("serving.build"):
            active = np.zeros((self.slots,), bool)
            active[decoding] = True
            pos = self._pos + self._ahead()
            self._ensure_writable_span(decoding, pos, span)
            pos = np.where(active, pos, 0).astype(np.int32)
            # non-decoding rows (free OR mid-prefill) get a zeroed
            # block-table row: their masked write lands in the scratch
            # block, not in a real sequence's (possibly shared) block 0
            bt = self._tables(active)
        self._metrics["decode_dispatches"].labels(
            kind="waited" if self._inflight is None else "overlapped").inc()
        t0 = time.perf_counter()
        pool, state = self._pool, self._state
        seq = self._count_dispatch(kind)
        with self._recorder.instrumented("serving.decode"), \
                tr.span("serving.dispatch", seq=seq, kind=kind):
            got = program(
                self._keep, self._quant, pool.kpools, pool.vpools,
                pool.kscales, pool.vscales,
                jax.tree.map(jnp.asarray, bt),
                *(jnp.asarray(t) for t in toks),
                jnp.asarray(pos), jnp.asarray(active), *more)
            (out, pool.kpools, pool.vpools, pool.kscales,
             pool.vscales) = got[:5]
            rest = list(got[5:])
            if state is not None:
                state.layers = rest.pop()
            leaves = jax.tree_util.tree_leaves(out)
            for leaf in leaves:
                leaf.copy_to_host_async()
            self._newest_out = leaves[0]
        return _Dispatch(out, [(i, self._active[i]) for i in decoding],
                         span, t0, seq), rest

    def _read(self, d: _Dispatch):
        """The tokens of dispatch ``d`` on the host (blocks until the
        device has run it).  An expert model's counts come in the same
        copy and are counted here, once a dispatch, in dispatch order."""
        with self._recorder.instrumented("serving.decode"), \
                self._phase("serving.sync", seq=d.seq):
            out = jax.device_get(d.out)
        if isinstance(out, tuple):
            out, counts = out
            self._count_experts(counts, d.steps)
        return out

    def _collect(self):
        """Read the outstanding decode dispatch, if there is one, and
        move the host's state by it: ``out``, the write heads, budgets,
        last tokens and stamps of its rows, and the retirements.  A row
        whose request left the slot since the dispatch (an ``eos`` the
        host learned a step late) ran one wasted row-step: its tokens
        are dropped.  Every method that reads or moves a slot's tokens,
        blocks or state from outside a step calls this first."""
        d, self._inflight = self._inflight, None
        if d is None:
            return
        toks = self._read(d)                            # toks: [B, K]
        with self._phase("serving.emit"):
            live = [i for i, req in d.rows if self._active[i] is req]
            self._emit_decoded(live, [toks[i] for i in live],
                               max(d.t0, self._collected_at),
                               toks.shape[1])

    def _count_experts(self, counts, steps: int):
        """The expert layers' counts of one decode dispatch (summed over
        its ``steps`` steps and the model's expert layers) into the
        registry and, while a profiler session is capturing, onto the
        host plane once its copy has reached the host."""
        from paddle_tpu.observability.tracing import host_annotation
        touched, local, picks = self._moe_counters
        layer_steps = steps * self._expert_layers
        touched.labels(stat="sum").inc(int(counts[0]))
        touched.labels(stat="layer_steps").inc(layer_steps)
        local.inc(int(counts[1]))
        picks.inc(int(counts[2]))
        with host_annotation("serving.moe_counts", touched=int(counts[0]),
                             layer_steps=layer_steps):
            pass

    def _decode_step(self, decoding: List[int]):
        """One fused K-step decode over every decoding slot, issued
        before the one before it is read: a row that dispatch advanced
        goes on from the device's own tokens, any other row from the
        token the host holds for it."""
        from_host = self._ahead() == 0
        if self._window:
            lens = (self._pos + self._ahead())[decoding].astype(np.int64) + 1
        d, (self._dev_toks,) = self._dispatch_batched(
            "decode", self._decode_compiled or self._decode_paged, decoding,
            (self._dev_toks, self._last_tok.copy(), from_host),
            self.steps_per_sync, self._next_key(),
            *(() if not self._step_info else (
                self._state.layers if self._state else [],)))
        if self._window:
            # what this dispatch's first step attends, on the profiler's
            # host plane just after it (as ``serving.prefill_context``):
            # the rows, their keys in a layer that sees everything and in
            # one that sees a window
            from paddle_tpu.observability.tracing import host_annotation
            with host_annotation(
                    "serving.kv_live", rows=len(decoding),
                    tokens=int(lens.sum()),
                    window_tokens=int(np.minimum(lens,
                                                 self._window).sum())):
                pass
        self._collect()
        self._inflight = d

    def _emit_decoded(self, slots_: List[int], rows, t0: float,
                      per_slot: Optional[int] = None):
        """The token loop after a decode dispatch is read: ``rows[n]``
        are the tokens slot ``slots_[n]`` got, in order.  ONE clock
        reading, taken now that the host has them, stamps the whole
        batch's emission; a request that reaches eos or its budget
        retires here.  The decode-latency histogram gets the wall time
        since ``t0`` over the tokens a slot hauled: ``per_slot``, or
        the mean over the slots when they differ."""
        now = self._collected_at = time.perf_counter()
        emitted = 0
        for i, row in zip(slots_, rows):
            req = self._active[i]
            n = 0
            done = False
            for t in row:
                t = int(t)
                req.out.append(t)
                n += 1
                self._pos[i] += 1
                self._budget[i] -= 1
                self._last_tok[i] = t
                if (self.eos is not None and t == self.eos) \
                        or self._budget[i] <= 0:
                    # mid-chunk finish: the device generated (and
                    # cached) the rest of the chunk; those rows are
                    # unreachable for any successor
                    done = True
                    break
            req.token_stamps.append((now, n))
            emitted += n
            if done:
                self._retire(i)
        if emitted:
            m = self._metrics
            m["tokens"].inc(emitted)
            # one host interaction covers every active slot in
            # parallel: a slot's token costs wall time / its haul
            m["decode"].observe(
                (now - t0) / (per_slot or emitted / len(slots_)))

    def _spec_decode_step(self, decoding: List[int]):
        """n-gram speculative decode: draft from each request's own
        history, verify every row's [last, d1..dk] in ONE batched
        forward, accept the longest draft prefix matching the argmax
        chain plus one bonus token.  Greedy-equivalent by construction:
        position j's argmax is conditioned only on tokens the chain has
        already validated."""
        k = self.spec_tokens
        S = k + 1
        # the drafts come from the history the host holds and the next
        # ones from what this verify accepts: nothing can be left unread
        # before the build, and this dispatch is read in its own step
        self._collect()
        tr = self._tracer
        with tr.span("serving.build"):
            toks = np.zeros((self.slots, S), np.int32)
            proposed = np.zeros((self.slots,), np.int64)
            for i in decoding:
                req = self._active[i]
                toks[i, 0] = self._last_tok[i]
                hist = np.concatenate([req.prompt,
                                       np.asarray(req.out, np.int32)])
                draft = _ngram_propose(hist, k, self._spec_ngram)
                if draft is not None:
                    n = len(draft)
                    toks[i, 1:1 + n] = draft
                    toks[i, 1 + n:] = draft[-1]  # static-shape pad; unused
                    proposed[i] = n
        d, _ = self._dispatch_batched(
            "spec_verify", self._spec_verify_compiled or self._spec_verify,
            decoding,
            (toks,), S)
        greedy = self._read(d)                          # greedy: [B, S]
        with tr.span("serving.emit"):
            m = self._metrics
            rows = []
            for i in decoding:
                req = self._active[i]
                n = int(proposed[i])
                a = 0
                while a < n and greedy[i, a] == toks[i, a + 1]:
                    a += 1
                # a accepted drafts + the bonus token the verify
                # computed at the last validated position (rejected
                # rows' KV is stale but masked — the write head rolls
                # back over it)
                rows.append([*toks[i, 1:1 + a], greedy[i, a]])
                req.spec_proposed += n
                req.spec_accepted += a
                if n:
                    m["spec"].labels(kind="proposed").inc(n)
                    if a:
                        m["spec"].labels(kind="accepted").inc(a)
            self._emit_decoded(decoding, rows, d.t0)

    def _schedule_head(self, step_span):
        """What an engine step starts with, inside the caller's
        ``serving.schedule`` span: the fault point and the step span's
        counts.  Returns the number of active slots."""
        from paddle_tpu.robustness import fault_point
        n_active = sum(r is not None for r in self._active)
        fault_point("serving.engine_step", active=n_active,
                    queued=len(self._queue))
        step_span.set_attribute("active", n_active)
        step_span.set_attribute("queued", len(self._queue))
        return n_active

    def _step_inner(self, step_span) -> bool:
        tr = self._tracer
        with tr.span("serving.schedule"):
            self._expire()
            self._schedule_head(step_span)
            if self._auto_park_s is not None:
                # deadline-aware session scheduling: park the most
                # patient active session when queued work is
                # slot-starved; bring auto-parked sessions back once
                # the queue drains
                self._maybe_auto_park()
            free = [i for i, r in enumerate(self._active) if r is None]
            # a failed admission leaves the slots as they are, so who
            # decodes is settled here, in the schedule's own span; a row
            # whose budget ends in the dispatch still unread is left out
            # (an eos is learned one collect late: that row runs on)
            left = self._budget - self._ahead()
            decoding = [i for i, r in enumerate(self._active)
                        if r is not None and i not in self._prefilling
                        and left[i] > 0]
            step_span.set_attribute("decoding", len(decoding))
        if free and self._queue:
            # blocks are handed out with nothing unread: what a retired
            # row's last dispatch wrote, it wrote before they are reused
            self._collect()
            req = self._queue[0]
            with tr.span("serving.admit", rid=req.rid) as sp:
                admitted = self._admit(free[0], req)
                # no blocks: not admitted, or admitted and retired at once
                seq = self._seq[free[0]] if admitted else None
                sp.set_attribute("prefix_tokens_reused", req.prefix_reused)
                sp.set_attribute("blocks",
                                 len(seq.bids) if seq is not None else 0)
            if admitted:
                self._queue.popleft()
                step_span.set_attribute("ran", "admit")
                return True
            # allocator dry: the request stays queued (add_request
            # already rejected anything the empty pool couldn't hold, so
            # retiring slots / evicting cached prefixes will free enough
            # blocks eventually; deadlines still bound the wait)
            decoding = [i for i in decoding     # the collect retired some
                        if self._active[i] is not None]
        if all(r is None for r in self._active):
            self._collect()     # at most a wasted row-step is left
            return bool(self._queue)
        # chunked prefill interleaves with decode: alternate dispatches
        # so a kilotoken prompt can't stall in-flight requests' TPOT,
        # and an idle decode pool can't starve TTFT
        do_chunk = bool(self._prefilling) and (
            not decoding or self._interleave_decode)
        self._interleave_decode = not self._interleave_decode
        if do_chunk:
            step_span.set_attribute("ran", "prefill_chunk")
            # the oldest admission first (the dict keeps admission order;
            # a chunk's update leaves a key in place): by slot number a
            # later arrival that lands in a lower, just-freed slot would
            # overtake a prompt already under way, and which slot is
            # free when it arrives is a matter of milliseconds
            self._prefill_chunk_step(next(iter(self._prefilling)))
            return True
        if not decoding:
            self._collect()     # every row's budget ends in it
            return True
        if self.spec_tokens:
            step_span.set_attribute("ran", "spec")
            self._spec_decode_step(decoding)
        else:
            step_span.set_attribute("ran", "decode")
            self._decode_step(decoding)
        return True

    def _retire(self, slot: int, status: str = "ok"):
        req = self._active[slot]
        self._active[slot] = None
        self._prefilling.pop(slot, None)
        seq = self._seq[slot]
        if seq is not None:
            seq.release()   # shared prefix blocks stay in the trie
        self._seq[slot] = None
        self._bt[slot, :] = 0
        if self._seq_w[slot] is not None:
            self._seq_w[slot].release()     # the window group's ring
            self._seq_w[slot] = None
            self._bt_w[slot, :] = 0
        self._finish(req, slot=slot, status=status)

    def _finish(self, req: _Request, slot: Optional[int] = None,
                status: str = "ok"):
        req.retired_at = time.perf_counter()
        trace_id = req.span.trace_id if req.span is not None else None
        timings = _request_timings(req)
        self._status[req.rid] = RequestStatus(
            status, timings=timings, trace_id=trace_id,
            token_times=req.token_stamps)
        # the gap a client saw before each token after its first
        itl = self._metrics["itl"]
        for (t_prev, _), (t, n) in zip(req.token_stamps,
                                       req.token_stamps[1:]):
            for _ in range(n):
                itl.observe((t - t_prev) / n)
        while len(self._status) > 8192:   # bounded, like everything else
            self._status.pop(next(iter(self._status)))
        # a recompute-resumed session folded generated tokens into its
        # prompt; the client-visible prompt is the original
        prompt = req.orig_prompt if req.orig_prompt is not None \
            else req.prompt
        self._done.append((req.rid, prompt, list(req.out)))
        self._metrics["retirements"].inc()
        self._count_slo(req)
        ev = dict(rid=req.rid, slot=slot, generated=len(req.out),
                  status=status)
        if trace_id is not None:
            ev["trace_id"] = trace_id
        self._recorder.record("serving.retire", **ev)
        # the retirement decision carries the full canonical timings —
        # this is what lets explain()/tail_report() attribute latency
        # from a federated (cross-process) event stream alone.  Routed
        # requests are marked so the router's fleet-level retirement
        # stays authoritative (no double counting in tail windows).
        self._emit_decision(
            "retire", rid=req.rid, chosen=status, status=status,
            source="engine", routed=req.router_t0 is not None,
            generated=len(req.out), timings=timings)
        if req.router_t0 is None:
            # routed requests: the router's retirement (merged fleet
            # timings) feeds the overage counter instead
            from paddle_tpu.observability.forensics import \
                observe_retirement
            observe_retirement(timings, targets=self._slo_targets)
        if req.span is not None:
            req.span.set_attribute("status", status)
            req.span.set_attribute("generated", len(req.out))
            req.span.set_attribute("token_stamps",
                                   list(req.token_stamps))
            req.span.end(end_time=req.retired_at)

    def _count_slo(self, req: _Request):
        """SLO verdicts from the request's own lifecycle stamps: TTFT is
        judged for every retirement (a request that never produced a
        first token — queue timeout, engine error — MISSED by
        definition); TPOT only once there are >= 2 output tokens to
        average over."""
        ttft_target = self._slo_targets.get("ttft", 0.0)
        # a resumed (handed-off) request's TTFT verdict was already
        # counted by the prefill replica at its "prefilled" retirement
        if ttft_target > 0 and req.mode != "resume":
            origin = req.router_t0 or req.enqueued_at
            ttft = (req.first_token_at - origin
                    if req.first_token_at and origin else None)
            hit = ttft is not None and ttft <= ttft_target
            self._metrics["slo"].labels(
                kind="ttft", result="hit" if hit else "miss").inc()
        tpot_target = self._slo_targets.get("tpot", 0.0)
        if tpot_target > 0 and len(req.out) > 1 and \
                req.first_token_at and req.retired_at:
            tpot = (req.retired_at - req.first_token_at) \
                / (len(req.out) - 1)
            self._metrics["slo"].labels(
                kind="tpot",
                result="hit" if tpot <= tpot_target else "miss").inc()

    def request_status(self, rid: int) -> Optional[str]:
        """Terminal status of a finished request: "ok" (eos/budget),
        "timeout" (deadline expired), "error" (engine-step failure);
        None while still queued/decoding.  The returned value compares
        equal to those plain strings but is a :class:`RequestStatus`
        whose ``.timings`` carries the lifecycle stamps
        (enqueued/admitted/first_token/retired + queue_s/ttft_s/
        prefill_s/decode_s/total_s, sourced from the request's trace
        span bookkeeping) and whose ``.trace_id`` joins it to the
        exported trace — a timed-out client can self-diagnose where its
        deadline went."""
        return self._status.get(rid)

    def _expire(self):
        """Retire every request whose deadline has passed — stuck SLOTS
        free themselves (the other slots keep decoding), and queued
        requests stop waiting for a slot that isn't coming."""
        now = time.perf_counter()
        late = [slot for slot, req in enumerate(self._active)
                if req is not None and req.deadline is not None
                and now > req.deadline]
        if late:
            self._collect()     # a slot is freed with nothing unread
        for slot in late:
            req = self._active[slot]
            if req is not None:     # the collect may have retired it
                self._metrics["timeouts"].inc()
                self._recorder.record("serving.timeout", rid=req.rid,
                                      slot=slot, generated=len(req.out))
                self._emit_decision("expire", rid=req.rid,
                                    chosen="timeout", where="slot")
                self._retire(slot, status="timeout")
        if self._queue:
            keep = deque()
            for req in self._queue:
                if req.deadline is not None and now > req.deadline:
                    self._metrics["timeouts"].inc()
                    self._recorder.record("serving.timeout", rid=req.rid,
                                          slot=None, generated=0)
                    self._emit_decision("expire", rid=req.rid,
                                        chosen="timeout",
                                        where="queue")
                    self._finish(req, status="timeout")
                else:
                    keep.append(req)
            self._queue.clear()
            self._queue.extend(keep)
        # parked sessions keep their deadline: one that expires in the
        # tier retires as "timeout" and its payload is dropped
        for rid, (req, key) in list(self._parked.items()):
            if req.deadline is not None and now > req.deadline:
                del self._parked[rid]
                if req.parked_at:
                    req.parked_s += now - req.parked_at
                    req.parked_at = 0.0
                if self._kv_tier is not None:
                    self._kv_tier.discard(key)
                self._metrics["timeouts"].inc()
                self._recorder.record("serving.timeout", rid=rid,
                                      slot=None, parked=True,
                                      generated=len(req.out))
                self._emit_decision("expire", rid=rid,
                                    chosen="timeout", where="parked")
                self._finish(req, status="timeout")

    def _recover(self, exc: BaseException):
        """Engine-step exception containment: fail the in-flight batch
        (every active slot retires with status "error"), rebuild the KV
        caches (the failed donated call may have consumed them), keep
        the queue — the engine stays alive for the next request.  After
        ``max_consecutive_errors`` straight failures the exception
        re-raises: that is a persistent fault, not a transient one."""
        self._error_streak += 1
        self._metrics["engine_errors"].inc()
        try:        # what an earlier dispatch finished is served; one the
            self._collect()     # fault took with it goes with the batch
        except Exception:  # noqa: BLE001
            pass
        self._recorder.record("serving.engine_error",
                              error=type(exc).__name__,
                              message=str(exc)[:200],
                              streak=self._error_streak)
        for slot, req in enumerate(self._active):
            if req is not None:
                self._retire(slot, status="error")
        # the failed donated call may have consumed the pools; the
        # host bookkeeping may be mid-flight — rebuild both from
        # scratch (the prefix cache is warm state, safe to drop)
        from paddle_tpu.inference.kv_cache import (BlockAllocator,
                                                   PrefixCache)
        self._allocator = BlockAllocator(self._num_blocks)
        if self._prefix is not None:
            self._prefix = PrefixCache(self._block_size,
                                       self._allocator)
            if self._kv_tier is not None:
                self._prefix.on_evict = self._demote_prefix_node
        self._pool.reset()
        if self._state is not None:
            self._state.reset()
        self._bt[:] = 0
        self._seq = [None] * self.slots
        if self._window:
            self._allocator_w = BlockAllocator(self._num_window_blocks)
            self._bt_w[:] = 0
            self._seq_w = [None] * self.slots
        self._prefilling.clear()
        # parked handoffs reference the replaced allocator/pool —
        # they are gone with it (the router's transfer will fail
        # and fall back to a fresh prefill elsewhere)
        self._handoff_ready.clear()
        self._pos[:] = 0
        self._budget[:] = 0
        self._last_tok[:] = 0
        self._dev_toks = jnp.zeros((self.slots,), jnp.int32)
        self._newest_out = None     # the failed call's may never complete
        # restart-after-fault cold start: consult the persistent compile
        # cache so a recovering engine that never warmed (or a future
        # where recovery rebuilds executables) gets its programs back
        # without paying a live compile — cache_only means a cold cache
        # is a no-op and recovery stays cheap.  Never allowed to fail
        # the recovery itself.
        try:
            from paddle_tpu import compile_cache
            if compile_cache.enabled():
                self.aot_warmup(cache_only=True)
        except Exception:
            pass
        if self._error_streak >= self._max_consecutive_errors:
            raise exc

    def step(self) -> bool:
        """One scheduling step.  Returns False when nothing is left.
        Engine-step exceptions fail the in-flight batch without killing
        the engine (see :meth:`_recover`)."""
        tr = self._tracer
        # one span per step, whatever the batch: its children are the
        # phases the device waits for the host in (schedule, admit,
        # build, dispatch, sync, emit), each opened where the work is
        with tr.span("serving.step", root_eligible=False) as sp:
            # what the step ran is known when it ends: set, not opened
            # with, so the profiler's event carries no placeholder
            sp.set_attribute("ran", "none")
            self._step_span = sp
            try:
                try:
                    out = self._step_inner(sp)
                except Exception as e:  # KeyboardInterrupt etc. propagate
                    self._recover(e)
                    return bool(self._queue) or \
                        any(r is not None for r in self._active)
                self._error_streak = 0
                return out
            finally:
                self._step_span = None

    def run(self):
        """Drain queue + slots; returns {rid: (prompt, tokens)}."""
        while self.pending:
            self.step()
        return {rid: (p, out) for rid, p, out in self.finished()}

    def close(self):
        """Hand the model back: restores train mode if the engine
        flipped it at construction, and drops this engine's weight-
        quantization reference (the original Linears come back when the
        last engine holding the conversion closes).  A dispatch still
        unread is collected first: ``finished()`` then holds it."""
        self._collect()
        if self._quant_converted:
            from paddle_tpu.quantization.serving import \
                restore_from_serving
            restore_from_serving(self.model)
            self._quant_converted = False
        if self._was_training:
            self.model.train()
            self._was_training = False

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False
