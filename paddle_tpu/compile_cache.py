"""Persistent AOT-executable cache + model-artifact bundles (ROADMAP 5).

Production fleets restart constantly — elastic drills it, serving
replicas scale up under load — and every restart used to re-trace and
re-compile every executable: TrainStep, decode, the prefill chunk,
the spec-verify forward.  This module makes compiled XLA
executables a *shippable artifact*: ``jax.experimental.
serialize_executable`` bytes in a content-addressed on-disk cache, so a
fresh process deserialize-and-loads in milliseconds instead of paying
trace + XLA compile.

Cache discipline (the autotune-cache v2 rules, applied to binaries):

* **Content-addressed keys** — sha256 over (target, argument signature
  from :func:`~paddle_tpu.observability.device_profiler.signature_of`
  — the same pytree-structure + leaf-aval string ``jax.jit`` keys its
  executable cache on, i.e. the ``compile_records`` key — mesh shape +
  axis names, per-param shardings, jax version, backend/platform
  fingerprint, and an ``extra`` discriminator for config the caller
  closed over).  One entry file per key; no shared index to corrupt.
* **Versioned schema** — every entry embeds ``schema``; an old-schema,
  corrupt, or truncated entry is silently invalidated (treated as a
  miss, unlinked best-effort), never raised.
* **Atomic writes** — entries land via tmp-file + ``os.replace`` so a
  concurrent reader can never observe a half-written executable.
* **Backend fencing** — the backend fingerprint (platform, device kind,
  device count) is in the key AND re-verified at load, so a CPU entry
  can never be served to a TPU process (or vice versa), and a
  wrong-jax-version entry falls through to live compilation.
* **Counters** — ``paddle_tpu_compile_cache_total{target,result}``
  (hit / miss / store / deserialize_error) in the default metrics
  registry; a hit runs under a ``compile.cache_hit`` tracer span.
* **Graceful fall-through** — every cache code path is wrapped: any
  lookup or deserialization failure degrades to live compilation.  A
  stale cache must never be able to break a boot.

On top, :func:`bundle` / :func:`load_bundle` package a *model artifact*:
checkpoint weights (the digested index from ``distributed.checkpoint``)
+ serialized executables + tuned block sizes from the autotune cache —
everything a drained elastic worker or a brand-new serving replica
needs to go from empty disk to first token without a single XLA
compile.

Env knobs:
  PADDLE_TPU_COMPILE_CACHE=1        enable (default off — opt-in)
  PADDLE_TPU_COMPILE_CACHE_DIR=path cache directory (default
                                    <cache_root()>/executables)
  JAX_COMPILATION_CACHE_DIR=path    places :func:`cache_root` — JAX's own
                                    persistent compilation cache plus
                                    this cache, the autotune winners and
                                    the calibration ledger under it —
                                    from outside (default
                                    .paddle_tpu_cache in the checkout)

CLI::

    python -m paddle_tpu.compile_cache stats
    python -m paddle_tpu.compile_cache bundle OUT --checkpoint CKPT
    python -m paddle_tpu.compile_cache load-bundle PATH
    python -m paddle_tpu.compile_cache clear
"""

from __future__ import annotations

import argparse
import hashlib
import json
import os
import pickle
import shutil
import sys
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax

__all__ = ["SCHEMA_VERSION", "enabled", "cache_root",
           "enable_persistent_cache", "persistent_cache_counts",
           "cache_dir", "backend_fingerprint",
           "cache_key", "lookup", "store", "aot_compile_cached",
           "model_config_tag", "cached_entries", "clear_cache",
           "cache_stats", "bundle", "load_bundle", "main"]

SCHEMA_VERSION = 2

# in-memory layer: a process that stored an entry (or already loaded it)
# never re-reads / re-deserializes the file
_mem: Dict[str, Any] = {}


# -- knobs + keys ------------------------------------------------------------

def enabled() -> bool:
    """Opt-in: ``PADDLE_TPU_COMPILE_CACHE=1``.  Default off — loading a
    serialized binary is semantically identical to recompiling, but the
    knob keeps cold-start behaviour explicit."""
    return os.environ.get("PADDLE_TPU_COMPILE_CACHE", "0") == "1"


def cache_root() -> str:
    """The one directory that carries everything a later process can
    reuse: JAX's persistent compilation cache, and under it this
    module's executables, the autotune winners and the calibration
    ledger.  ``JAX_COMPILATION_CACHE_DIR`` where set; otherwise a fixed
    git-ignored directory at the root of the checkout (the path is part
    of JAX's cache key, so it must never move between runs)."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        ".paddle_tpu_cache")


_jax_cache_events = {"requests": 0, "hits": 0, "listening": False}


def _on_jax_event(event: str, **_):
    if event == "/jax/compilation_cache/compile_requests_use_cache":
        _jax_cache_events["requests"] += 1
    elif event == "/jax/compilation_cache/cache_hits":
        _jax_cache_events["hits"] += 1


def persistent_cache_counts() -> Dict[str, int]:
    """Hits and misses of JAX's persistent compilation cache since
    :func:`enable_persistent_cache` (one request per XLA compile)."""
    ev = _jax_cache_events
    return {"hits": ev["hits"], "misses": ev["requests"] - ev["hits"]}


def enable_persistent_cache() -> str:
    """Turn JAX's persistent compilation cache on at :func:`cache_root`
    — call before the first compile.  Where ``JAX_COMPILATION_CACHE_DIR``
    is set JAX already points there and no directory is set here.
    Returns the root."""
    from jax import monitoring
    root = cache_root()
    if not _jax_cache_events["listening"]:
        monitoring.register_event_listener(_on_jax_event)
        _jax_cache_events["listening"] = True
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", root)
    # keep every program, however quick to compile: a cold machine pays
    # for all of them again
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return root


def cache_dir() -> str:
    return os.environ.get("PADDLE_TPU_COMPILE_CACHE_DIR") or \
        os.path.join(cache_root(), "executables")


def backend_fingerprint() -> str:
    """Platform + device kind + device count — the hardware assembly an
    executable was compiled for.  In the key AND re-checked at load:
    disjoint namespaces, so a CPU test run can never poison (or serve)
    a TPU boot."""
    try:
        dev = jax.devices()[0]
        kind = getattr(dev, "device_kind", "?").replace(" ", "_")
        return f"{dev.platform}:{kind}:n{jax.device_count()}"
    except Exception:
        return "unknown:?:n0"


def _mesh_tag(mesh) -> str:
    if mesh is None:
        return "nomesh"
    try:
        return ",".join(f"{a}={s}" for a, s in mesh.shape.items())
    except Exception:
        return repr(mesh)


def _shardings_tag(shardings) -> str:
    if not shardings:
        return "nosharding"
    try:
        items = sorted(shardings.items())
        return ";".join(
            f"{n}:{getattr(sh, 'spec', sh)}" for n, sh in items)
    except Exception:
        return repr(shardings)


def cache_key(target: str, signature: str, mesh=None, shardings=None,
              extra: str = "") -> str:
    """Content address of one executable.  ``signature`` is
    ``signature_of((args, kwargs))`` — the jaxpr-level call signature;
    ``extra`` carries closed-over config the avals can't see (sampling
    params, accumulation steps, optimizer hyperparameters, …)."""
    material = "\x1f".join([
        f"schema{SCHEMA_VERSION}", target, signature,
        _mesh_tag(mesh), _shardings_tag(shardings),
        f"jax{jax.__version__}", backend_fingerprint(), extra])
    return hashlib.sha256(material.encode()).hexdigest()


def _entry_path(key: str, root: Optional[str] = None) -> str:
    return os.path.join(root or cache_dir(), f"{key}.exe")


def model_config_tag(model) -> str:
    """Key discriminator for config a model BAKES into its trace as
    constants (rope tables, norm epsilons, …): the avals of the call
    arguments can't see those, so two models with identical parameter
    shapes but different config must not share an executable."""
    cfg = getattr(model, "config", None)
    if cfg is None:
        return type(model).__name__
    try:
        d = sorted((k, repr(v)) for k, v in vars(cfg).items()
                   if not k.startswith("_"))
        digest = hashlib.sha256(repr(d).encode()).hexdigest()[:16]
    except TypeError:
        digest = hashlib.sha256(repr(cfg).encode()).hexdigest()[:16]
    return f"{type(model).__name__}:{digest}"


# -- telemetry ---------------------------------------------------------------

def _counter():
    from paddle_tpu.observability import default_registry
    return default_registry().counter(
        "paddle_tpu_compile_cache_total",
        "persistent executable-cache lookups/stores by outcome",
        labelnames=("target", "result"))


def _count(target: str, result: str):
    try:
        _counter().labels(target=target, result=result).inc()
    except Exception:
        pass


# -- entry io ----------------------------------------------------------------

def _read_entry(path: str) -> Optional[dict]:
    """Parse + validate one entry file.  None on missing / truncated /
    corrupt / old-schema / wrong-jax-version / wrong-backend — silent
    invalidation (stale files are unlinked best-effort), never raises."""
    try:
        with open(path, "rb") as f:
            entry = pickle.load(f)
    except FileNotFoundError:
        return None
    except Exception:
        _unlink_quiet(path)
        return None
    if not isinstance(entry, dict) \
            or entry.get("schema") != SCHEMA_VERSION \
            or entry.get("jax_version") != jax.__version__ \
            or entry.get("backend") != backend_fingerprint():
        _unlink_quiet(path)
        return None
    if not isinstance(entry.get("payload"), bytes):
        _unlink_quiet(path)
        return None
    return entry


def _unlink_quiet(path: str):
    try:
        os.remove(path)
    except OSError:
        pass


def _write_entry(path: str, entry: dict) -> bool:
    try:
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        tmp = f"{path}.tmp.{os.getpid()}"
        with open(tmp, "wb") as f:
            pickle.dump(entry, f)
        os.replace(tmp, path)
        return True
    except Exception:
        return False   # read-only fs: the in-memory layer still works


def lookup(key: str, target: str = "fn", root: Optional[str] = None):
    """Deserialize-and-load the cached executable for ``key``, or None.
    The load runs under a ``compile.cache_hit`` span; a payload that no
    longer deserializes counts ``deserialize_error`` and falls through
    (the stale entry is removed so the next boot doesn't retry it)."""
    if key in _mem:
        _count(target, "hit")
        return _mem[key]
    path = _entry_path(key, root)
    entry = _read_entry(path)
    if entry is None:
        _count(target, "miss")
        return None
    try:
        from jax.experimental import serialize_executable as se

        from paddle_tpu.observability.tracing import tracer
        with tracer().span("compile.cache_hit", target=target,
                           key=key[:12]):
            t0 = time.perf_counter()
            by_id = {d.id: d for d in jax.devices()}
            compiled = se.deserialize_and_load(
                entry["payload"], entry["in_tree"], entry["out_tree"],
                execution_devices=[by_id[i] for i in entry["device_ids"]])
            load_s = time.perf_counter() - t0
    except Exception:
        _count(target, "deserialize_error")
        _unlink_quiet(path)
        return None
    _mem[key] = compiled
    _count(target, "hit")
    _record_hit(target, entry, load_s)
    return compiled


def _record_hit(target: str, entry: dict, load_s: float):
    """A cache hit joins the compile log (so ``compile_records`` shows
    the boot's executables) WITHOUT touching paddle_tpu_compile_total —
    that counter means 'explicit XLA compiles', and the whole point of
    a hit is that none happened."""
    try:
        from paddle_tpu.observability.device_profiler import (
            CompileInfo, ExecutableStats, record_compile_info)
        st = ExecutableStats(**(entry.get("stats") or {}))
        record_compile_info(CompileInfo(
            target=target, signature=entry.get("signature", ""),
            lower_s=0.0, compile_s=load_s, stats=st, cached=True))
    except Exception:
        pass
    try:
        from paddle_tpu.observability.recorder import flight_recorder
        flight_recorder().record("compile.cache_hit", target=target,
                                 load_s=round(load_s, 4))
    except Exception:
        pass


def _device_ids(compiled) -> List[int]:
    """Ids of the devices ``compiled`` executes on, in assignment order
    — a load must name them, or jax loads the executable for every
    local device."""
    shardings = jax.tree.leaves((compiled.input_shardings,
                                 compiled.output_shardings))
    if not shardings:
        return [jax.devices()[0].id]
    return [d.id for d in shardings[0]._device_assignment]


def store(key: str, compiled, target: str = "fn", signature: str = "",
          stats: Optional[dict] = None, root: Optional[str] = None) -> bool:
    """Serialize ``compiled`` into the cache.  Unserializable
    executables (backends without PjRt executable serialization) and io
    failures degrade to False — the live executable keeps working."""
    try:
        from jax.experimental import serialize_executable as se
        payload, in_tree, out_tree = se.serialize(compiled)
    except Exception:
        return False
    entry = {
        "schema": SCHEMA_VERSION,
        "jax_version": jax.__version__,
        "backend": backend_fingerprint(),
        "target": target,
        "signature": signature,
        "stats": stats or {},
        "payload": payload,
        "device_ids": _device_ids(compiled),
        "in_tree": in_tree,
        "out_tree": out_tree,
        "created": time.time(),
    }
    ok = _write_entry(_entry_path(key, root), entry)
    if ok:
        _mem[key] = compiled
        _count(target, "store")
    return ok


def aot_compile_cached(fn: Callable, *args, target: str = "fn",
                       mesh=None, shardings=None, extra: str = "",
                       registry=None, cache_only: bool = False,
                       **kwargs):
    """:func:`~paddle_tpu.observability.device_profiler.aot_compile`
    with the persistent cache in front.

    Hit → deserialize-and-load (no trace, no XLA compile, no
    ``paddle_tpu_compile_total`` bump) under a ``compile.cache_hit``
    span.  Miss → live ``lower().compile()`` with full compile
    observability, then stored.  Returns ``(compiled, CompileInfo,
    hit)``; with ``cache_only=True`` a miss returns ``(None, None,
    False)`` instead of compiling (the _recover re-warm path: consult
    the cache, never pay a compile inside fault recovery)."""
    from paddle_tpu.observability.device_profiler import (
        CompileInfo, ExecutableStats, aot_compile, compiled_stats,
        signature_of)

    if not enabled():
        if cache_only:
            return None, None, False
        compiled, info = aot_compile(fn, *args, target=target,
                                     registry=registry, **kwargs)
        return compiled, info, False

    signature = signature_of((args, kwargs))
    key = cache_key(target, signature, mesh=mesh, shardings=shardings,
                    extra=extra)
    t0 = time.perf_counter()
    compiled = lookup(key, target=target)
    if compiled is not None:
        st = compiled_stats(compiled)
        # compile_s carries the deserialize-and-load wall time: the
        # cold-start ledger's 'compile_or_load' column on the hit path
        info = CompileInfo(target=target, signature=signature,
                           lower_s=0.0,
                           compile_s=time.perf_counter() - t0,
                           stats=st, cached=True)
        return compiled, info, True
    if cache_only:
        return None, None, False
    compiled, info = aot_compile(fn, *args, target=target,
                                 registry=registry, **kwargs)
    store(key, compiled, target=target, signature=signature,
          stats=_stats_dict(info.stats))
    return compiled, info, False


def _stats_dict(stats) -> dict:
    import dataclasses
    try:
        return dataclasses.asdict(stats)
    except Exception:
        return {}


# -- inventory ---------------------------------------------------------------

def cached_entries(root: Optional[str] = None) -> List[dict]:
    """Metadata rows (no payload) of every VALID entry in the cache —
    invalid files are skipped (and invalidated) exactly as a lookup
    would."""
    root = root or cache_dir()
    rows = []
    try:
        names = sorted(os.listdir(root))
    except OSError:
        return rows
    for name in names:
        if not name.endswith(".exe"):
            continue
        entry = _read_entry(os.path.join(root, name))
        if entry is None:
            continue
        rows.append({"key": name[:-4], "target": entry["target"],
                     "signature": entry.get("signature", "")[:80],
                     "bytes": len(entry["payload"]),
                     "created": entry.get("created", 0.0)})
    return rows


def clear_cache(root: Optional[str] = None):
    root = root or cache_dir()
    _mem.clear()
    try:
        for name in os.listdir(root):
            if name.endswith(".exe") or ".exe.tmp." in name:
                _unlink_quiet(os.path.join(root, name))
    except OSError:
        pass


def reset_memory():
    """Forget in-process loaded executables (tests that swap
    PADDLE_TPU_COMPILE_CACHE_DIR)."""
    _mem.clear()


def cache_stats(root: Optional[str] = None) -> dict:
    rows = cached_entries(root)
    return {"entries": len(rows),
            "bytes": sum(r["bytes"] for r in rows),
            "targets": sorted({r["target"] for r in rows})}


# -- model-artifact bundle ---------------------------------------------------

BUNDLE_SCHEMA = 1


def bundle(out_dir: str, *, state_dict: Optional[Dict[str, Any]] = None,
           checkpoint_dir: Optional[str] = None,
           targets: Optional[List[str]] = None,
           cache_root: Optional[str] = None,
           note: str = "") -> dict:
    """Package a versioned model artifact: weights + executables +
    tuned block sizes, so a new replica boots from empty disk to first
    token with zero XLA compiles.

    * weights: either ``state_dict`` (saved here via the checksummed
      ``distributed.checkpoint`` writer) or an existing
      ``checkpoint_dir`` (copied, digests and all);
    * executables: every valid compile-cache entry (optionally filtered
      to ``targets``);
    * autotune: the merged block-size entries visible to this process
      (seed layer + user cache), written in the v2 schema.

    Returns the manifest dict (also written as ``MANIFEST.json``)."""
    os.makedirs(out_dir, exist_ok=True)
    manifest: dict = {"schema": BUNDLE_SCHEMA,
                      "jax_version": jax.__version__,
                      "backend": backend_fingerprint(),
                      "created": time.time(), "note": note}

    # weights --------------------------------------------------------------
    ckpt_out = os.path.join(out_dir, "checkpoint")
    if state_dict is not None:
        from paddle_tpu.distributed.checkpoint import save_state_dict
        save_state_dict(state_dict, ckpt_out)
        manifest["checkpoint"] = "checkpoint"
    elif checkpoint_dir is not None:
        if os.path.abspath(checkpoint_dir) != os.path.abspath(ckpt_out):
            if os.path.isdir(ckpt_out):
                shutil.rmtree(ckpt_out)
            shutil.copytree(checkpoint_dir, ckpt_out)
        manifest["checkpoint"] = "checkpoint"
    else:
        manifest["checkpoint"] = None

    # executables ----------------------------------------------------------
    exe_dir = os.path.join(out_dir, "executables")
    os.makedirs(exe_dir, exist_ok=True)
    copied = []
    root = cache_root or cache_dir()
    for row in cached_entries(root):
        if targets is not None and row["target"] not in targets:
            continue
        src = _entry_path(row["key"], root)
        try:
            shutil.copy2(src, os.path.join(exe_dir, f"{row['key']}.exe"))
            copied.append({"key": row["key"], "target": row["target"],
                           "bytes": row["bytes"]})
        except OSError:
            continue
    manifest["executables"] = copied

    # tuned block sizes ----------------------------------------------------
    try:
        from paddle_tpu.ops.pallas import autotune as at
        entries = at.cached_entries()
        with open(os.path.join(out_dir, "autotune.json"), "w") as f:
            json.dump({"version": at.CACHE_VERSION, "entries": entries},
                      f, indent=0, sort_keys=True)
        manifest["autotune_entries"] = len(entries)
    except Exception:
        manifest["autotune_entries"] = 0

    with open(os.path.join(out_dir, "MANIFEST.json"), "w") as f:
        json.dump(manifest, f, indent=1, sort_keys=True)
    return manifest


def load_bundle(path: str, *, cache_root: Optional[str] = None,
                install_autotune: bool = True,
                restore_weights: bool = True) -> dict:
    """Unpack a model artifact onto this machine:

    * executables are installed into the active compile cache (invalid
      / wrong-backend entries are skipped silently — a bundle built on
      another fleet must not poison this one);
    * autotune entries merge into the persistent block-size cache;
    * weights are restored (``{name: np.ndarray}``) from the bundled
      checkpoint when present.

    Returns ``{"manifest", "installed", "skipped", "autotune_entries",
    "state_dict"}``.  Raises ValueError on a missing/old-schema
    manifest — loading a bundle is an explicit operation, unlike the
    silent per-entry invalidation."""
    man_path = os.path.join(path, "MANIFEST.json")
    try:
        with open(man_path) as f:
            manifest = json.load(f)
    except Exception as e:
        raise ValueError(f"not a model bundle (no readable MANIFEST.json "
                         f"at {path}): {e}")
    if manifest.get("schema") != BUNDLE_SCHEMA:
        raise ValueError(f"bundle schema {manifest.get('schema')!r} != "
                         f"supported {BUNDLE_SCHEMA}")

    root = cache_root or cache_dir()
    installed, skipped = [], 0
    exe_dir = os.path.join(path, "executables")
    if os.path.isdir(exe_dir):
        for name in sorted(os.listdir(exe_dir)):
            if not name.endswith(".exe"):
                continue
            entry = _read_entry(os.path.join(exe_dir, name))
            if entry is None:         # wrong backend/jax/schema: skip
                skipped += 1
                continue
            if _write_entry(_entry_path(name[:-4], root), entry):
                installed.append(entry["target"])
            else:
                skipped += 1

    n_autotune = 0
    if install_autotune:
        try:
            from paddle_tpu.ops.pallas import autotune as at
            loaded = at._parse(os.path.join(path, "autotune.json"))
            if loaded:
                at._load()
                at._mem_cache.update(loaded)
                at._save()
                n_autotune = len(loaded)
        except Exception:
            n_autotune = 0

    state = None
    if restore_weights and manifest.get("checkpoint"):
        try:
            from paddle_tpu.distributed.checkpoint import load_state_dict
            state = load_state_dict(
                os.path.join(path, manifest["checkpoint"]))
        except Exception:
            state = None

    try:
        from paddle_tpu.observability.recorder import flight_recorder
        flight_recorder().record("compile_cache.load_bundle", path=path,
                                 installed=len(installed),
                                 skipped=skipped,
                                 autotune=n_autotune)
    except Exception:
        pass
    return {"manifest": manifest, "installed": installed,
            "skipped": skipped, "autotune_entries": n_autotune,
            "state_dict": state}


# -- CLI ---------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m paddle_tpu.compile_cache",
        description="Persistent AOT executable cache + model-artifact "
                    "bundles (second-scale cold start).")
    sub = ap.add_subparsers(dest="cmd", required=True)
    sub.add_parser("stats", help="list valid cache entries")
    sub.add_parser("clear", help="remove every cache entry")
    b = sub.add_parser("bundle", help="package weights + executables + "
                                      "tuned block sizes")
    b.add_argument("out", help="bundle directory to write")
    b.add_argument("--checkpoint", default=None,
                   help="existing distributed.checkpoint dir to include")
    b.add_argument("--targets", default=None,
                   help="comma-separated executable targets to include "
                        "(default: all)")
    b.add_argument("--note", default="", help="free-form manifest note")
    lb = sub.add_parser("load-bundle", help="install a bundle onto this "
                                            "machine")
    lb.add_argument("path")
    lb.add_argument("--no-autotune", action="store_true")
    args = ap.parse_args(argv)

    if args.cmd == "stats":
        st = cache_stats()
        print(json.dumps({"dir": cache_dir(), **st,
                          "enabled": enabled()}, indent=1))
        for row in cached_entries():
            print(f"  {row['key'][:12]}  {row['bytes']:>10d}B  "
                  f"{row['target']}")
        return 0
    if args.cmd == "clear":
        n = len(cached_entries())
        clear_cache()
        print(f"cleared {n} entries from {cache_dir()}")
        return 0
    if args.cmd == "bundle":
        targets = [t.strip() for t in args.targets.split(",")] \
            if args.targets else None
        man = bundle(args.out, checkpoint_dir=args.checkpoint,
                     targets=targets, note=args.note)
        print(f"bundle {args.out}: {len(man['executables'])} "
              f"executables, {man['autotune_entries']} autotune "
              f"entries, checkpoint={man['checkpoint']}")
        return 0
    if args.cmd == "load-bundle":
        out = load_bundle(args.path,
                          install_autotune=not args.no_autotune)
        print(f"installed {len(out['installed'])} executables "
              f"({out['skipped']} skipped), {out['autotune_entries']} "
              f"autotune entries, weights="
              f"{'yes' if out['state_dict'] is not None else 'no'}")
        return 0
    return 1


if __name__ == "__main__":
    sys.exit(main())
