"""Kernel block-size autotuner — persistent, versioned cache + offline sweep.

Reference parity: ``phi/kernels/autotune/auto_tune_base.h`` +
``cache_base.h`` — the reference times kernel variants at first
invocation and caches the winner per shape key.  TPU-native version:
candidates are Pallas block-size configurations; each is compiled and
timed ONCE on the real chip at first use of a shape (also when the op
is hit inside a ``jit`` trace — the measurement runs on concrete side
inputs in a thread of its own, outside the trace), and the winner
persists to a
versioned on-disk JSON cache so later processes skip the sweep
entirely.

Two ways entries get into the cache:

* **lazy** — first use of a shape on-chip measures candidates and
  persists the winner (the original behaviour);
* **offline sweep** — ``python -m paddle_tpu.ops.pallas.autotune
  --sweep`` enumerates the candidate grid for every kernel (flash
  attention, fused CE, fused rmsnorm+QKV, fused MLP) over the bench
  shapes, TVM-style (PAPERS.md), and writes the winners in one go.
  ``--dry-run`` skips timing (heuristic winners) but exercises the full
  persistence round-trip — the CI gate for machines without a chip.
  The checked-in ``benchmarks/autotune_tpu_v5.json`` is loaded as a
  read-only seed layer so cold starts and fresh clones get tuned sizes
  without ever re-timing.

Cache format (schema ``version`` bumps invalidate silently — old or
corrupt/truncated files fall back to heuristic defaults, never raise)::

    {"version": 2,
     "entries": {"<op>|<shape-key>@<backend>": [block, sizes, ...]}}

Keys carry the dtype AND the backend (``tpu:<device_kind>`` vs
``cpu-interpret``), so a CPU test run can never poison the TPU entry
for the same shape.

Env knobs:
  PADDLE_TPU_AUTOTUNE=0           disable (use the heuristic default)
  PADDLE_TPU_AUTOTUNE_CACHE=path  cache file (default autotune.json
                                  under compile_cache.cache_root())
  PADDLE_TPU_AUTOTUNE_SEED=path   shipped seed cache override ("0"
                                  disables the seed layer)
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from typing import Callable, Dict, Sequence, Tuple

__all__ = ["autotune", "flash_block_sizes", "ce_block_sizes",
           "qkv_block_sizes", "mlp_block_sizes", "quant_block_sizes",
           "decoder_block_sizes", "cache_path", "seed_path",
           "backend_tag", "cached_entries", "clear_cache", "reload",
           "CACHE_VERSION", "main"]

CACHE_VERSION = 2

_mem_cache: Dict[str, object] = {}
_loaded = False


# -- persistence -------------------------------------------------------------

def cache_path() -> str:
    env = os.environ.get("PADDLE_TPU_AUTOTUNE_CACHE")
    if env:
        return env
    from paddle_tpu.compile_cache import cache_root
    return os.path.join(cache_root(), "autotune.json")


def seed_path() -> str:
    """The checked-in cache shipped with the repo (read-only base
    layer); "" disables."""
    env = os.environ.get("PADDLE_TPU_AUTOTUNE_SEED")
    if env is not None:
        return "" if env in ("0", "") else env
    here = os.path.dirname(os.path.abspath(__file__))
    return os.path.join(here, "..", "..", "..", "benchmarks",
                        "autotune_tpu_v5.json")


def _parse(path: str):
    """Entries of a cache file, or None when the file is missing,
    truncated, corrupt or of a different schema version — silent
    invalidation, the caller falls back to heuristics/benching."""
    try:
        with open(path) as f:
            raw = json.load(f)
    except Exception:
        return None
    if not isinstance(raw, dict) or raw.get("version") != CACHE_VERSION:
        return None
    entries = raw.get("entries")
    return entries if isinstance(entries, dict) else None


def _load():
    global _loaded
    if _loaded:
        return
    _loaded = True
    sp = seed_path()
    if sp:
        seed = _parse(sp)
        if seed:
            _mem_cache.update(seed)
    user = _parse(cache_path())
    if user:
        _mem_cache.update(user)         # user cache overrides the seed


def _save(path: str = None):
    path = path or cache_path()
    try:
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        # merge-then-atomic-replace: concurrent processes benching
        # different shapes must not clobber each other or expose a
        # half-written file to readers
        merged = dict(_parse(path) or {})
        merged.update(_mem_cache)
        tmp = f"{path}.tmp.{os.getpid()}"
        with open(tmp, "w") as f:
            json.dump({"version": CACHE_VERSION, "entries": merged},
                      f, indent=0, sort_keys=True)
        os.replace(tmp, path)
    except OSError as e:
        # read-only fs: the in-memory cache still works, but the next
        # process sweeps again — say so
        print(f"autotune: cache write to {path} failed ({e}); winners "
              f"stay in memory only", file=sys.stderr)


def clear_cache():
    global _loaded
    _mem_cache.clear()
    _loaded = True
    try:
        os.remove(cache_path())
    except OSError:
        pass


def reload():
    """Forget the in-memory state so the next lookup re-reads the cache
    file(s) — for tests that swap PADDLE_TPU_AUTOTUNE_CACHE."""
    global _loaded
    _mem_cache.clear()
    _loaded = False


def cached_entries() -> Dict[str, object]:
    _load()
    return dict(_mem_cache)


# -- keys --------------------------------------------------------------------

def backend_tag(interpret: bool = None) -> str:
    """The backend component of every cache key: a TPU entry is keyed by
    the device kind; anything else (including interpret-mode kernels on
    a TPU host) is ``cpu-interpret`` — disjoint namespaces, so CPU test
    runs can never poison a chip's tuned entry."""
    try:
        import jax
        dev = jax.devices()[0]
        if not interpret and dev.platform == "tpu":
            return f"tpu:{getattr(dev, 'device_kind', '?')}" \
                .replace(" ", "_")
    except Exception:
        pass
    return "cpu-interpret"


# -- core --------------------------------------------------------------------

def _cache_counter():
    from paddle_tpu.observability import default_registry
    return default_registry().counter(
        "paddle_tpu_autotune_cache_total",
        "autotune persistent-cache lookups by outcome",
        labelnames=("op", "result"))


def enabled() -> bool:
    if os.environ.get("PADDLE_TPU_AUTOTUNE", "1") == "0":
        return False
    # multi-controller runs must compile IDENTICAL programs on every
    # process; per-host timing sweeps could disagree (noise) and deadlock
    # the first collective — use the deterministic default there
    try:
        import jax
        if jax.process_count() > 1:
            return False
    except Exception:
        pass
    return True


def _verify_prune(op: str, shape: tuple, cands: list):
    """Drop candidates the static verifier proves Mosaic-illegal before
    any of them is benchmarked (TVM-style legality-before-search).
    Returns (kept, n_pruned); never empties the set and never raises —
    a broken verifier must not cost a sweep."""
    try:
        from paddle_tpu.analysis.kernel_verify import prune_candidates
        return prune_candidates(op, shape, cands)
    except Exception:   # pragma: no cover - verifier bugs must not bench-fail
        return list(cands), 0


def _outside_trace(fn, *args):
    """``fn(*args)`` outside the caller's jax trace.  The first use of a
    shape is usually inside a ``jit`` trace, and there even concrete
    values are staged out; the trace is thread-local, so a fresh thread
    measures on real arrays."""
    import concurrent.futures
    with concurrent.futures.ThreadPoolExecutor(1) as pool:
        return pool.submit(fn, *args).result()


def autotune(op_name: str, key: str, candidates: Sequence,
             bench: Callable[[object], float], default):
    """Return the cached winner for (op_name, key), measuring once.

    bench(candidate) -> seconds (lower is better); raise to disqualify
    a candidate.  A candidate that fails is counted
    (``paddle_tpu_autotune_cache_total{result="candidate_failed"}``) and
    named on stderr; a sweep in which EVERY candidate fails raises —
    the kernel does not run at this shape, and ``default`` would only
    move the same failure into the caller's compile.  ``default`` is
    what a disabled autotuner returns."""
    full_key = f"{op_name}|{key}"
    _load()
    if full_key in _mem_cache:
        _cache_counter().labels(op=op_name, result="hit").inc()
        got = _mem_cache[full_key]
        return tuple(got) if isinstance(got, list) else got
    if not enabled():
        return default
    _cache_counter().labels(op=op_name, result="miss").inc()

    best, best_t = None, float("inf")
    failed = []
    for c in candidates:
        try:
            t = _outside_trace(bench, c)
        except Exception as e:
            failed.append((c, e))
            _cache_counter().labels(op=op_name,
                                    result="candidate_failed").inc()
            reason = str(e).strip().splitlines()[0] if str(e).strip() else ""
            print(f"autotune {full_key}: candidate {c} failed: "
                  f"{type(e).__name__}: {reason[:300]}", file=sys.stderr)
            continue
        if t < best_t:
            best, best_t = c, t
    if best is None:
        raise RuntimeError(
            f"autotune {full_key}: all {len(failed)} candidates failed "
            f"({[c for c, _ in failed]})") from failed[-1][1]
    _feed_calibration(op_name, key, best_t)
    _mem_cache[full_key] = list(best) if isinstance(best, tuple) else best
    _save()
    return best


def _feed_calibration(op_name: str, key: str, measured_s: float):
    """Measurement-ledger feeder (PADDLE_TPU_CALIBRATION=1): the
    winner's benched seconds land in the calibration ledger under the
    kernel's own content-addressed key — the autotune sweep is one of
    the three measurement sources the calibrated cost model reads."""
    try:
        from paddle_tpu.observability import calibration
        if not calibration.enabled():
            return
        # the autotune key already embeds its backend tag; strip it and
        # let the ledger key carry the process fingerprint instead
        shape_part = key.rsplit("@", 1)[0]
        calibration.ledger().record(
            f"autotune:{op_name}", shape_part, measured_s=measured_s,
            provenance="autotune")
    except Exception:
        pass


def _put(op_name: str, key: str, value):
    """Record a winner without benching (offline sweep writer)."""
    _load()
    _mem_cache[f"{op_name}|{key}"] = \
        list(value) if isinstance(value, tuple) else value


# -- flash attention ---------------------------------------------------------

def _flash_candidates(s: int, d: int, dtype: str) -> list:
    """(block_q, block_k) candidates of the FORWARD, bounded by the VMEM
    working set.  The backward's tiles follow a rule read from the shapes
    (``flash_attention.bwd_tiles``) and are no part of the sweep."""
    blocks = []
    sizes = (128, 256) if s < 4096 else (128, 256, 512)
    for bq in sizes:
        for bk in sizes:
            if bq > s or bk > s or s % bq or s % bk:
                continue
            itemsize = 2 if "bfloat16" in dtype or "float16" in dtype else 4
            vmem = (2 * (bq + 2 * bk) * d * itemsize   # double-buffered io
                    + bq * bk * 4                      # score tile
                    + 2 * bq * d * 4)                  # fp32 accumulators
            if vmem < 10 * (1 << 20):
                blocks.append((bq, bk))
    return blocks or [(min(128, s), min(128, s))]


def flash_key(b, s, h, hk, d, dtype, causal, backend=None, interpret=None):
    return (f"b{b}s{s}h{h}k{hk}d{d}{dtype}c{int(causal)}"
            f"@{backend or backend_tag(interpret)}")


def flash_block_sizes(b: int, s: int, h: int, hk: int, d: int,
                      dtype: str, causal: bool) -> Tuple[int, int]:
    """Measured (block_q, block_k) of the forward for this shape; each
    candidate is timed through one forward + backward, as a step runs
    it."""
    default = (min(128, s), min(128, s))
    cands = _flash_candidates(s, d, dtype)
    cands, _ = _verify_prune("flash", (b, s, h, hk, d, dtype, causal),
                             cands)
    if len(cands) == 1:
        return tuple(cands[0])
    key = flash_key(b, s, h, hk, d, dtype, causal)

    def bench(blocks):
        import jax
        import jax.numpy as jnp
        import numpy as np
        from jax import lax

        from paddle_tpu.ops.pallas.flash_attention import flash_attention

        bq, bk = blocks
        iters = 8
        rng = np.random.default_rng(0)
        dt = jnp.dtype(dtype)
        q = jnp.asarray(rng.standard_normal((b, s, h, d)), dt)
        k = jnp.asarray(rng.standard_normal((b, s, hk, d)), dt)
        v = jnp.asarray(rng.standard_normal((b, s, hk, d)), dt)

        @jax.jit
        def run(q_, k_, v_):
            # iterations loop INSIDE the jit: one dispatch, so per-call
            # host latency cannot bias the sweep
            def loss(args):
                o = flash_attention(*args, causal=causal, block_q=bq,
                                    block_k=bk, autotune=False)
                return jnp.sum(o.astype(jnp.float32) ** 2)

            def body(i, carry):
                g = jax.grad(loss)((q_ * (1 + carry * 1e-12).astype(dt),
                                    k_, v_))
                return carry + sum(
                    jnp.sum(jnp.abs(x).astype(jnp.float32)) for x in g)
            return lax.fori_loop(0, iters, body, 0.0)

        np.asarray(run(q, k, v))                      # compile + warm
        t0 = time.perf_counter()
        np.asarray(run(q, k, v))
        return (time.perf_counter() - t0) / iters

    return tuple(autotune("flash", key, cands, bench, default))


# -- fused cross-entropy -----------------------------------------------------

def _ce_candidates(t: int, v: int, dtype: str) -> list:
    """(block_t, block_v) candidates for the fused cross-entropy: the
    vocab block must divide V; VMEM holds the io block (double-buffered)
    plus one fp32 working copy and the [bt, 1] statistics."""
    itemsize = 2 if "bfloat16" in dtype or "float16" in dtype else 4
    out = []
    for bt in (64, 128, 256):
        if bt > max(t, 8):
            continue
        for bv in (256, 512, 1024, 2048):
            if v % bv:
                continue
            vmem = bt * bv * (2 * itemsize + 4) + 8 * bt * 4
            if vmem < 10 * (1 << 20):
                out.append((bt, bv))
    if not out:
        from paddle_tpu.ops.pallas.cross_entropy import _default_blocks
        out = [_default_blocks(t, v)]
    return out


def ce_key(t, v, dtype, backend=None, interpret=None):
    return f"t{t}v{v}{dtype}@{backend or backend_tag(interpret)}"


def ce_block_sizes(t: int, v: int, dtype: str) -> Tuple[int, int]:
    """Measured (block_t, block_v) for the fused cross-entropy at this
    [tokens, vocab] shape (loss + grad timed together — the backward is
    where the one-hot traffic used to live)."""
    from paddle_tpu.ops.pallas.cross_entropy import _default_blocks
    default = _default_blocks(t, v)
    cands = _ce_candidates(t, v, dtype)
    cands, _ = _verify_prune("fused_ce", (t, v, dtype), cands)
    if len(cands) == 1:
        return tuple(cands[0])
    key = ce_key(t, v, dtype)

    def bench(blocks):
        import jax
        import jax.numpy as jnp
        import numpy as np
        from jax import lax

        from paddle_tpu.ops.pallas.cross_entropy import \
            fused_softmax_cross_entropy

        bt, bv = blocks
        iters = 8
        rng = np.random.default_rng(0)
        dt = jnp.dtype(dtype)
        x = jnp.asarray(rng.standard_normal((t, v)), dt)
        lbl = jnp.asarray(rng.integers(0, v, (t,)), jnp.int32)

        @jax.jit
        def run(x_, lbl_):
            def loss(a):
                return jnp.sum(fused_softmax_cross_entropy(
                    a, lbl_, block_t=bt, block_v=bv, autotune=False))

            def body(i, carry):
                g = jax.grad(loss)(x_ * (1 + carry * 1e-12).astype(dt))
                return carry + jnp.sum(jnp.abs(g).astype(jnp.float32))
            return lax.fori_loop(0, iters, body, 0.0)

        np.asarray(run(x, lbl))                       # compile + warm
        t0 = time.perf_counter()
        np.asarray(run(x, lbl))
        return (time.perf_counter() - t0) / iters

    return tuple(autotune("fused_ce", key, cands, bench, default))


# -- fused rmsnorm + QKV -----------------------------------------------------

def _qkv_candidates(t, d, dq, dk, dv, dtype) -> list:
    from paddle_tpu.ops.pallas.fused_block import _block_candidates
    return _block_candidates("qkv", t, (dq, dk, dv), d, dtype)


def _fused_block_scope() -> str:
    """Part of the two per-segment kernels' keys: the most VMEM their calls
    ask the compiler for.  A winner taller than the compiler's own scope
    holds compiles only under code that asks, so a cache shared with a
    checkout that does not (or asks for less) must not hand it over."""
    from paddle_tpu.ops.pallas.fused_block import _VMEM_LIMIT
    return f"+vmem{_VMEM_LIMIT >> 20}"


def qkv_key(t, d, dq, dk, dv, dtype, backend=None, interpret=None):
    return f"t{t}d{d}q{dq}k{dk}v{dv}{dtype}{_fused_block_scope()}" \
           f"@{backend or backend_tag(interpret)}"


def qkv_block_sizes(t: int, d: int, dq: int, dk: int, dv: int,
                    dtype: str) -> Tuple[int, int]:
    """Measured (block_t, block_o) for the fused rmsnorm+QKV kernel
    (fwd + bwd timed together, matching how training hits it)."""
    from paddle_tpu.ops.pallas.fused_block import _default_qkv_blocks
    default = _default_qkv_blocks(t, d, dq, dk, dv, dtype)
    cands = _qkv_candidates(t, d, dq, dk, dv, dtype)
    cands, _ = _verify_prune("fused_qkv", (t, d, dq, dk, dv, dtype),
                             cands)
    if len(cands) == 1:
        return tuple(cands[0])
    key = qkv_key(t, d, dq, dk, dv, dtype)

    def bench(blocks):
        import jax
        import jax.numpy as jnp
        import numpy as np
        from jax import lax

        from paddle_tpu.ops.pallas.fused_block import fused_rmsnorm_qkv

        bt, bo = blocks
        iters = 8
        rng = np.random.default_rng(0)
        dt = jnp.dtype(dtype)
        x = jnp.asarray(rng.standard_normal((t, d)), dt)
        wn = jnp.ones((d,), dt)
        wq = jnp.asarray(rng.standard_normal((d, dq)) * 0.02, dt)
        wk = jnp.asarray(rng.standard_normal((d, dk)) * 0.02, dt)
        wv = jnp.asarray(rng.standard_normal((d, dv)) * 0.02, dt)

        @jax.jit
        def run(x_, wn_, wq_, wk_, wv_):
            def loss(a):
                q, k, v = fused_rmsnorm_qkv(a, wn_, wq_, wk_, wv_,
                                            block_t=bt, block_o=bo,
                                            autotune=False)
                return sum(jnp.sum(o.astype(jnp.float32) ** 2)
                           for o in (q, k, v))

            def body(i, carry):
                g = jax.grad(loss)(x_ * (1 + carry * 1e-12).astype(dt))
                return carry + jnp.sum(jnp.abs(g).astype(jnp.float32))
            return lax.fori_loop(0, iters, body, 0.0)

        np.asarray(run(x, wn, wq, wk, wv))            # compile + warm
        t0 = time.perf_counter()
        np.asarray(run(x, wn, wq, wk, wv))
        return (time.perf_counter() - t0) / iters

    return tuple(autotune("fused_qkv", key, cands, bench, default))


# -- fused MLP ---------------------------------------------------------------

def _mlp_candidates(t, d, f, dtype) -> list:
    from paddle_tpu.ops.pallas.fused_block import _block_candidates
    return _block_candidates("mlp", t, (f,), d, dtype)


def mlp_key(t, d, f, dtype, backend=None, interpret=None):
    return f"t{t}d{d}f{f}{dtype}{_fused_block_scope()}" \
           f"@{backend or backend_tag(interpret)}"


def mlp_block_sizes(t: int, d: int, f: int, dtype: str) -> Tuple[int, int]:
    """Measured (block_t, block_f) for the fused SwiGLU MLP kernel
    (fwd + bwd timed together)."""
    from paddle_tpu.ops.pallas.fused_block import _default_mlp_blocks
    default = _default_mlp_blocks(t, d, f, dtype)
    cands = _mlp_candidates(t, d, f, dtype)
    cands, _ = _verify_prune("fused_mlp", (t, d, f, dtype), cands)
    if len(cands) == 1:
        return tuple(cands[0])
    key = mlp_key(t, d, f, dtype)

    def bench(blocks):
        import jax
        import jax.numpy as jnp
        import numpy as np
        from jax import lax

        from paddle_tpu.ops.pallas.fused_block import fused_mlp

        bt, bf = blocks
        iters = 8
        rng = np.random.default_rng(0)
        dt = jnp.dtype(dtype)
        x = jnp.asarray(rng.standard_normal((t, d)), dt)
        wg = jnp.asarray(rng.standard_normal((d, f)) * 0.02, dt)
        wu = jnp.asarray(rng.standard_normal((d, f)) * 0.02, dt)
        wd = jnp.asarray(rng.standard_normal((f, d)) * 0.02, dt)

        @jax.jit
        def run(x_, wg_, wu_, wd_):
            def loss(a):
                y = fused_mlp(a, wg_, wu_, wd_, block_t=bt, block_f=bf,
                              autotune=False)
                return jnp.sum(y.astype(jnp.float32) ** 2)

            def body(i, carry):
                g = jax.grad(loss)(x_ * (1 + carry * 1e-12).astype(dt))
                return carry + jnp.sum(jnp.abs(g).astype(jnp.float32))
            return lax.fori_loop(0, iters, body, 0.0)

        np.asarray(run(x, wg, wu, wd))                # compile + warm
        t0 = time.perf_counter()
        np.asarray(run(x, wg, wu, wd))
        return (time.perf_counter() - t0) / iters

    return tuple(autotune("fused_mlp", key, cands, bench, default))


# -- whole-decoder-block megakernel ------------------------------------------

def _decoder_candidates(s, d, dq, dkv, hd, f, dtype) -> list:
    """(block_t, block_o, block_f) candidates for the whole-block
    kernel, bounded by its VMEM working set (the sequence-wide K/V
    scratch is a fixed cost every candidate pays)."""
    from paddle_tpu.ops.pallas.fused_block import (_DECODER_VMEM_BUDGET,
                                                   decoder_vmem_bytes)
    itemsize = 2 if "bfloat16" in dtype or "float16" in dtype else 4
    qmin = 16 if itemsize == 2 else 8
    out = []
    for bo in (128, 256, 512):
        if bo % hd or dq % bo or dkv % bo or d % bo:
            continue
        for bf in (128, 256, 512):
            if f % bf:
                continue
            for bt in (qmin, 32, 64, 128, 256):
                if bt < qmin or s % bt:
                    continue
                if decoder_vmem_bytes(s, d, dq, dkv, hd, f, bt, bo, bf,
                                      dtype) < _DECODER_VMEM_BUDGET:
                    out.append((bt, bo, bf))
    if not out:
        from paddle_tpu.ops.pallas.fused_block import \
            _default_decoder_blocks
        fallback = _default_decoder_blocks(s, d, dq, dkv, hd, f, dtype)
        out = [fallback] if fallback else []
    return sorted(set(out))


def decoder_key(b, s, d, dq, dkv, hd, f, dtype, backend=None,
                interpret=None):
    return (f"b{b}s{s}d{d}q{dq}k{dkv}h{hd}f{f}{dtype}"
            f"@{backend or backend_tag(interpret)}")


def decoder_block_sizes(b, s, d, dq, dkv, hd, f,
                        dtype: str) -> Tuple[int, int, int]:
    """Measured (block_t, block_o, block_f) for the whole-decoder-block
    kernel (fwd + bwd timed together — the backward is the reference
    recompute, so the win being tuned lives in the forward)."""
    from paddle_tpu.ops.pallas.fused_block import _default_decoder_blocks
    default = _default_decoder_blocks(s, d, dq, dkv, hd, f, dtype)
    cands = _decoder_candidates(s, d, dq, dkv, hd, f, dtype)
    cands, _ = _verify_prune(
        "fused_decoder", (b, s, d, dq, dkv, hd, f, dtype), cands)
    if default is None:
        raise ValueError(
            f"no decoder block sizes fit the VMEM budget at s={s} d={d} "
            f"dkv={dkv} f={f}")
    if len(cands) <= 1:
        return tuple(cands[0]) if cands else tuple(default)
    key = decoder_key(b, s, d, dq, dkv, hd, f, dtype)

    def bench(blocks):
        import jax
        import jax.numpy as jnp
        import numpy as np
        from jax import lax

        from paddle_tpu.ops.pallas.fused_block import fused_decoder_block

        bt, bo, bf = blocks
        iters = 4
        rng = np.random.default_rng(0)
        dt = jnp.dtype(dtype)
        nh, nkvh = dq // hd, dkv // hd
        x = jnp.asarray(rng.standard_normal((b, s, d)), dt)
        wn1 = jnp.ones((d,), dt)
        wn2 = jnp.ones((d,), dt)
        wq = jnp.asarray(rng.standard_normal((d, dq)) * 0.02, dt)
        wk = jnp.asarray(rng.standard_normal((d, dkv)) * 0.02, dt)
        wv = jnp.asarray(rng.standard_normal((d, dkv)) * 0.02, dt)
        wo = jnp.asarray(rng.standard_normal((dq, d)) * 0.02, dt)
        wg = jnp.asarray(rng.standard_normal((d, f)) * 0.02, dt)
        wu = jnp.asarray(rng.standard_normal((d, f)) * 0.02, dt)
        wd = jnp.asarray(rng.standard_normal((f, d)) * 0.02, dt)
        from paddle_tpu.nn.functional.attention import rotary_freqs
        cos, sin = rotary_freqs(hd, s)

        @jax.jit
        def run(x_):
            def loss(a):
                y = fused_decoder_block(
                    a, wn1, wq, wk, wv, cos, sin, wo, wn2, wg, wu, wd,
                    num_heads=nh, num_kv_heads=nkvh, block_t=bt,
                    block_o=bo, block_f=bf, autotune=False,
                    use_pallas=True)
                return jnp.sum(y.astype(jnp.float32) ** 2)

            def body(i, carry):
                g = jax.grad(loss)(x_ * (1 + carry * 1e-12).astype(dt))
                return carry + jnp.sum(jnp.abs(g).astype(jnp.float32))
            return lax.fori_loop(0, iters, body, 0.0)

        np.asarray(run(x))                            # compile + warm
        t0 = time.perf_counter()
        np.asarray(run(x))
        return (time.perf_counter() - t0) / iters

    return tuple(autotune("fused_decoder", key, cands, bench, default))


# -- weight-only quantized matmul --------------------------------------------

def _quant_candidates(t, k, n, wdtype, xdtype) -> list:
    """(block_t, block_n) candidates for the weight-only quant matmul:
    K is unblocked, so VMEM holds the x tile, the quantized [k, bn]
    weight tile (1 byte/elem for int8 AND fp8), its up-converted copy,
    the fp32 accumulator tile, and the [1, bn] scale row."""
    x_item = 2 if ("bfloat16" in xdtype or "float16" in xdtype) else 4
    out = []
    for bn in (128, 256, 512):
        if n % bn:
            continue
        for bt in (8, 16, 32, 64, 128, 256, 512):
            if t % bt or bt > t:
                continue
            vmem = (2 * bt * k * x_item          # double-buffered x io
                    + k * bn * (1 + x_item)      # quant block + upcast
                    + bt * bn * (4 + x_item)     # fp32 acc + out tile
                    + bn * 4)
            if vmem < 10 * (1 << 20):
                out.append((bt, bn))
    if not out:
        from paddle_tpu.ops.pallas.quant_matmul import \
            _default_quant_blocks
        out = [_default_quant_blocks(t, n, xdtype)]
    return out


def quant_key(t, k, n, wdtype, xdtype, backend=None, interpret=None):
    return (f"t{t}k{k}n{n}w{wdtype}x{xdtype}"
            f"@{backend or backend_tag(interpret)}")


def quant_block_sizes(t: int, k: int, n: int, wdtype: str,
                      xdtype: str) -> Tuple[int, int]:
    """Measured (block_t, block_n) for the weight-only quantized matmul
    at this [t, k] x [k, n] shape — forward only (serving decode never
    differentiates through it)."""
    from paddle_tpu.ops.pallas.quant_matmul import _default_quant_blocks
    default = _default_quant_blocks(t, n, xdtype)
    cands = _quant_candidates(t, k, n, wdtype, xdtype)
    cands, _ = _verify_prune("quant_matmul", (t, k, n, wdtype, xdtype),
                             cands)
    if len(cands) == 1:
        return tuple(cands[0])
    key = quant_key(t, k, n, wdtype, xdtype)

    def bench(blocks):
        import jax
        import jax.numpy as jnp
        import numpy as np
        from jax import lax

        from paddle_tpu.ops.pallas.quant_matmul import quant_matmul_pallas

        bt, bn = blocks
        iters = 8
        rng = np.random.default_rng(0)
        xdt = jnp.dtype(xdtype)
        wdt = jnp.dtype(wdtype) if "int8" in wdtype else None
        w = rng.standard_normal((k, n)).astype(np.float32)
        scale = jnp.asarray(np.abs(w).max(axis=0) / 127.0, jnp.float32)
        if wdt is not None:
            qw = jnp.asarray(np.clip(np.round(w / np.asarray(scale)),
                                     -127, 127).astype(np.int8))
        else:
            import ml_dtypes
            qw = jnp.asarray((w / np.asarray(scale))
                             .astype(ml_dtypes.float8_e4m3fn))
        x = jnp.asarray(rng.standard_normal((t, k)), xdt)

        @jax.jit
        def run(x_, qw_, s_):
            def body(i, carry):
                o = quant_matmul_pallas(
                    x_ * (1 + carry * 1e-12).astype(xdt), qw_, s_,
                    block_t=bt, block_n=bn, autotune=False)
                return carry + jnp.sum(jnp.abs(o).astype(jnp.float32))
            return lax.fori_loop(0, iters, body, 0.0)

        np.asarray(run(x, qw, scale))                 # compile + warm
        t0 = time.perf_counter()
        np.asarray(run(x, qw, scale))
        return (time.perf_counter() - t0) / iters

    return tuple(autotune("quant_matmul", key, cands, bench, default))


# -- grouped expert-matmul (MoE) ---------------------------------------------

def _grouped_candidates(g, c, d, h, dtype) -> list:
    """(block_c, block_f) candidates for the grouped expert FFN: the
    f (hidden) axis is the sequential dim, so VMEM holds the x/y tiles,
    the fp32 accumulator, and double-buffered [d, bf]/[bf, d] weight
    tiles — the same working set as the fused MLP plus nothing (the
    counts operand is one int32 word per group)."""
    item = 2 if ("bfloat16" in dtype or "float16" in dtype) else 4
    quantum = 16 if item == 2 else 8
    out = []
    for bf in (128, 256, 512):
        if h % bf:
            continue
        for bc in (8, 16, 32, 64, 128, 256, 512):
            if bc % quantum or c % bc or bc > c:
                continue
            vmem = (2 * bc * d * item            # x, double-buffered
                    + bc * d * 4                 # fp32 accumulator
                    + 2 * bc * d * item          # y, double-buffered
                    + 4 * d * bf * item)         # w1 + w2 tiles, 2x
            if vmem < 10 * (1 << 20):
                out.append((bc, bf))
    if not out:
        from paddle_tpu.ops.pallas.grouped_matmul import \
            _default_grouped_blocks
        out = [_default_grouped_blocks(c, d, h, dtype)]
    return out


def grouped_key(g, c, d, h, dtype, backend=None, interpret=None):
    return (f"g{g}c{c}d{d}h{h}x{dtype}"
            f"@{backend or backend_tag(interpret)}")


def grouped_block_sizes(g: int, c: int, d: int, h: int,
                        dtype: str) -> Tuple[int, int]:
    """Measured (block_c, block_f) for the grouped expert FFN at this
    [g, c, d] x stacked [g, d, h] shape.  Benched with full counts
    (worst case: no empty-block skip) so the winner is robust to
    routing balance."""
    from paddle_tpu.ops.pallas.grouped_matmul import _default_grouped_blocks
    default = _default_grouped_blocks(c, d, h, dtype)
    cands = _grouped_candidates(g, c, d, h, dtype)
    cands, _ = _verify_prune("grouped_matmul", (g, c, d, h, dtype), cands)
    if len(cands) == 1:
        return tuple(cands[0])
    key = grouped_key(g, c, d, h, dtype)

    def bench(blocks):
        import jax
        import jax.numpy as jnp
        import numpy as np
        from jax import lax

        from paddle_tpu.ops.pallas.grouped_matmul import \
            grouped_expert_ffn_pallas

        bc, bf = blocks
        iters = 8
        rng = np.random.default_rng(0)
        dt = jnp.dtype(dtype)
        x = jnp.asarray(rng.standard_normal((g, c, d)), dt)
        w1 = jnp.asarray(rng.standard_normal((g, d, h)) * 0.02, dt)
        b1 = jnp.zeros((g, h), dt)
        w2 = jnp.asarray(rng.standard_normal((g, h, d)) * 0.02, dt)
        b2 = jnp.zeros((g, d), dt)
        counts = jnp.full((g,), c, jnp.int32)

        @jax.jit
        def run(x_, w1_, b1_, w2_, b2_, cnt_):
            def body(i, carry):
                o = grouped_expert_ffn_pallas(
                    x_ * (1 + carry * 1e-12).astype(dt), w1_, b1_, w2_,
                    b2_, cnt_, act=jax.nn.gelu, block_c=bc, block_f=bf,
                    interpret=False)
                return carry + jnp.sum(jnp.abs(o).astype(jnp.float32))
            return lax.fori_loop(0, iters, body, 0.0)

        np.asarray(run(x, w1, b1, w2, b2, counts))    # compile + warm
        t0 = time.perf_counter()
        np.asarray(run(x, w1, b1, w2, b2, counts))
        return (time.perf_counter() - t0) / iters

    return tuple(autotune("grouped_matmul", key, cands, bench, default))


# -- offline sweep -----------------------------------------------------------

# the bench llama (bench.py on-TPU config: 810M-param Llama-3 proportions,
# b4/s2048 bf16) plus the short-context variant from the r2 sweep notes
SWEEP_SHAPES = {
    "flash": [
        (4, 2048, 16, 8, 128, "bfloat16", True),
        (8, 1024, 16, 8, 128, "bfloat16", True),
    ],
    "fused_ce": [
        (8192, 32000, "bfloat16"),
    ],
    "fused_qkv": [
        (8192, 2048, 2048, 1024, 1024, "bfloat16"),
        (8192, 4096, 4096, 1024, 1024, "bfloat16"),
    ],
    "fused_mlp": [
        (8192, 2048, 7168, "bfloat16"),
        (8192, 4096, 14336, "bfloat16"),
    ],
    # whole-decoder-block megakernel: the VMEM budget (sequence-wide K/V
    # scratch) bounds it to short/medium contexts — sweep the shapes it
    # actually serves: a short-context training block and a
    # prefill/verify-sized row batch at bench-llama widths
    "fused_decoder": [
        (4, 512, 1024, 1024, 512, 128, 3584, "bfloat16"),
        (8, 128, 2048, 2048, 1024, 128, 7168, "bfloat16"),
    ],
    # weight-only quantized GEMM (serving): the bench_serve llama's
    # prefill-chunk and batched-decode token counts over its projection
    # shapes, int8 and fp8 weight storage
    "quant_matmul": [
        (256, 1024, 3584, "int8", "bfloat16"),
        (256, 1024, 1024, "int8", "bfloat16"),
        (256, 1024, 3584, "float8_e4m3fn", "bfloat16"),
        (16, 1024, 1024, "int8", "bfloat16"),
    ],
    # grouped expert-matmul (MoE): the bench_moe llama's E=8 experts at
    # bench widths — capacity from b4/s2048 top-2 routing at
    # capacity_factor 1.25 (C = 1.25*2*8192/8 = 2560), plus the
    # short-context variant
    "grouped_matmul": [
        (8, 2560, 1024, 3584, "bfloat16"),
        (8, 1280, 1024, 3584, "bfloat16"),
    ],
}


def _sweep_one(op, shape, dry_run, backend):
    """(key, winner, n_candidates, n_pruned) for one (op, shape) sweep
    entry — ``n_pruned`` counts candidates the static verifier rejected
    before any timing (``pruned_invalid`` in the sweep output)."""
    if op == "flash":
        b, s, h, hk, d, dtype, causal = shape
        cands = _flash_candidates(s, d, dtype)
        default = (min(128, s), min(128, s))
        key = flash_key(b, s, h, hk, d, dtype, causal, backend=backend)
        _, npruned = _verify_prune(op, shape, cands)
        if not dry_run:
            return key, flash_block_sizes(b, s, h, hk, d, dtype, causal), \
                len(cands), npruned
    elif op == "fused_ce":
        t, v, dtype = shape
        from paddle_tpu.ops.pallas.cross_entropy import _default_blocks
        cands = _ce_candidates(t, v, dtype)
        default = _default_blocks(t, v)
        key = ce_key(t, v, dtype, backend=backend)
        _, npruned = _verify_prune(op, shape, cands)
        if not dry_run:
            return key, ce_block_sizes(t, v, dtype), len(cands), npruned
    elif op == "fused_qkv":
        t, d, dq, dk, dv, dtype = shape
        from paddle_tpu.ops.pallas.fused_block import _default_qkv_blocks
        cands = _qkv_candidates(t, d, dq, dk, dv, dtype)
        default = _default_qkv_blocks(t, d, dq, dk, dv, dtype)
        key = qkv_key(t, d, dq, dk, dv, dtype, backend=backend)
        _, npruned = _verify_prune(op, shape, cands)
        if not dry_run:
            return key, qkv_block_sizes(t, d, dq, dk, dv, dtype), \
                len(cands), npruned
    elif op == "fused_mlp":
        t, d, f, dtype = shape
        from paddle_tpu.ops.pallas.fused_block import _default_mlp_blocks
        cands = _mlp_candidates(t, d, f, dtype)
        default = _default_mlp_blocks(t, d, f, dtype)
        key = mlp_key(t, d, f, dtype, backend=backend)
        _, npruned = _verify_prune(op, shape, cands)
        if not dry_run:
            return key, mlp_block_sizes(t, d, f, dtype), len(cands), \
                npruned
    elif op == "fused_decoder":
        b, s, d, dq, dkv, hd, f, dtype = shape
        from paddle_tpu.ops.pallas.fused_block import \
            _default_decoder_blocks
        cands = _decoder_candidates(s, d, dq, dkv, hd, f, dtype)
        default = _default_decoder_blocks(s, d, dq, dkv, hd, f, dtype)
        key = decoder_key(b, s, d, dq, dkv, hd, f, dtype, backend=backend)
        _, npruned = _verify_prune(op, shape, cands)
        if not dry_run:
            return key, decoder_block_sizes(b, s, d, dq, dkv, hd, f,
                                            dtype), len(cands), npruned
    elif op == "quant_matmul":
        t, k, n, wdtype, xdtype = shape
        from paddle_tpu.ops.pallas.quant_matmul import \
            _default_quant_blocks
        cands = _quant_candidates(t, k, n, wdtype, xdtype)
        default = _default_quant_blocks(t, n, xdtype)
        key = quant_key(t, k, n, wdtype, xdtype, backend=backend)
        _, npruned = _verify_prune(op, shape, cands)
        if not dry_run:
            return key, quant_block_sizes(t, k, n, wdtype, xdtype), \
                len(cands), npruned
    elif op == "grouped_matmul":
        g, c, d, h, dtype = shape
        from paddle_tpu.ops.pallas.grouped_matmul import \
            _default_grouped_blocks
        cands = _grouped_candidates(g, c, d, h, dtype)
        default = _default_grouped_blocks(c, d, h, dtype)
        key = grouped_key(g, c, d, h, dtype, backend=backend)
        _, npruned = _verify_prune(op, shape, cands)
        if not dry_run:
            return key, grouped_block_sizes(g, c, d, h, dtype), \
                len(cands), npruned
    else:
        raise ValueError(f"unknown sweep op {op!r}")
    # dry run: the heuristic default stands in for the measured winner —
    # exercises key construction + persistence without touching a chip
    _put(op, key, tuple(default))
    return key, tuple(default), len(cands), npruned


def _sweep_candidates(op, shape):
    """The sweep's candidate list for one (op, shape) entry."""
    if op == "flash":
        b, s, h, hk, d, dtype, causal = shape
        return _flash_candidates(s, d, dtype)
    if op == "fused_ce":
        t, v, dtype = shape
        return _ce_candidates(t, v, dtype)
    if op == "fused_qkv":
        t, d, dq, dk, dv, dtype = shape
        return _qkv_candidates(t, d, dq, dk, dv, dtype)
    if op == "fused_mlp":
        t, d, f, dtype = shape
        return _mlp_candidates(t, d, f, dtype)
    if op == "fused_decoder":
        b, s, d, dq, dkv, hd, f, dtype = shape
        return _decoder_candidates(s, d, dq, dkv, hd, f, dtype)
    if op == "quant_matmul":
        t, k, n, wdtype, xdtype = shape
        return _quant_candidates(t, k, n, wdtype, xdtype)
    if op == "grouped_matmul":
        g, c, d, h, dtype = shape
        return _grouped_candidates(g, c, d, h, dtype)
    raise ValueError(f"unknown sweep op {op!r}")


def _verify_only_main(args) -> int:
    """--sweep --verify-only: dry-validate every candidate for every
    sweep shape — zero timings, zero cache writes.  On-chip sweep day
    starts from this report and skips the doomed configs."""
    from paddle_tpu.analysis.kernel_verify import candidate_ok
    ops = sorted(SWEEP_SHAPES) if not args.ops else \
        [o.strip() for o in args.ops.split(",") if o.strip()]
    all_dead = []
    total = pruned = 0
    for op in ops:
        for shape in SWEEP_SHAPES[op]:
            cands = _sweep_candidates(op, shape)
            bad = []
            for c in cands:
                try:
                    ok = candidate_ok(op, shape, c)
                except Exception:
                    ok = True   # match _verify_prune: never lose a config
                if not ok:
                    bad.append(tuple(c))
            total += len(cands)
            pruned += len(bad)
            status = "ALL-PRUNED" if bad and len(bad) == len(cands) \
                else "ok"
            print(f"verify {op} {shape}: {len(cands) - len(bad)}/"
                  f"{len(cands)} valid, pruned_invalid={len(bad)} "
                  f"{('-> ' + status) if status != 'ok' else ''}".rstrip())
            if bad:
                print(f"  pruned: {bad}")
            if bad and len(bad) == len(cands):
                all_dead.append((op, shape))
    print(f"verify-only: {total} candidates checked, {pruned} pruned, "
          f"0 timed")
    if all_dead:
        print(f"FAIL: candidate set(s) 100% pruned (wrongly-strict "
              f"verifier or unservable shape): {all_dead}",
              file=sys.stderr)
        return 1
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m paddle_tpu.ops.pallas.autotune",
        description="Offline TVM-style block-size sweep for the Pallas "
                    "kernels (flash attention, fused CE, fused "
                    "rmsnorm+QKV, fused MLP).")
    ap.add_argument("--sweep", action="store_true",
                    help="enumerate + persist winners for the bench "
                         "shape grid")
    ap.add_argument("--dry-run", action="store_true",
                    help="skip timing: write heuristic winners "
                         "(persistence round-trip without a chip)")
    ap.add_argument("--verify-only", action="store_true",
                    help="statically validate every sweep candidate "
                         "(analysis/kernel_verify) with ZERO timings "
                         "and no cache write; exit 1 if any op/shape "
                         "has its whole candidate set pruned")
    ap.add_argument("--cache", default=None,
                    help="cache file to write (default: "
                         "PADDLE_TPU_AUTOTUNE_CACHE / the cache root)")
    ap.add_argument("--target", default=None,
                    help="backend tag for the written keys (e.g. "
                         "'tpu:TPU_v5_lite'); default: this process's "
                         "backend")
    ap.add_argument("--ops", default=None,
                    help="comma-separated subset of "
                         f"{sorted(SWEEP_SHAPES)}")
    args = ap.parse_args(argv)
    if not args.sweep:
        ap.error("nothing to do (pass --sweep)")
    if args.verify_only:
        return _verify_only_main(args)

    if args.cache:
        os.environ["PADDLE_TPU_AUTOTUNE_CACHE"] = args.cache
        reload()
    backend = args.target or backend_tag()
    ops = sorted(SWEEP_SHAPES) if not args.ops else \
        [o.strip() for o in args.ops.split(",") if o.strip()]

    n = 0
    for op in ops:
        for shape in SWEEP_SHAPES[op]:
            try:
                key, winner, ncand, npruned = _sweep_one(
                    op, shape, args.dry_run, backend)
            except Exception as e:     # a shape too big for this host
                print(f"sweep {op} {shape}: SKIP ({type(e).__name__}: "
                      f"{e})", file=sys.stderr)
                continue
            n += 1
            mode = "dry-run default" if args.dry_run else "measured"
            print(f"sweep {op} {shape} -> {winner}  "
                  f"[{ncand} candidates, pruned_invalid={npruned}, "
                  f"{mode}]")
    _save(args.cache)
    print(f"autotune cache: wrote {n} entries (schema v{CACHE_VERSION}) "
          f"to {args.cache or cache_path()}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
